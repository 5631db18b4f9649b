"""The comparisons that decide ``correct``."""
import math

import numpy as np

from chipbench import verify


def _rec(**kw):
    base = {"cell": 0, "policy": "GreedyP */OPT=MIN", "max_stretch": 12.5,
            "n_pmtn": 3, "trace_fingerprint": "ab12", "wall_s": 1.0,
            "sim_wall_s": 0.9, "backend": "jax"}
    base.update(kw)
    return base


def test_record_gap():
    assert verify.record_gap([_rec()], [_rec(wall_s=3.0, backend=None)]) == 0
    gap = verify.record_gap([_rec(max_stretch=12.5 * (1 + 1e-12))], [_rec()])
    assert 0.5e-12 < gap < 2e-12
    assert verify.record_gap([_rec(n_pmtn=4)], [_rec()]) == 1 / 3
    assert verify.record_gap([_rec(trace_fingerprint="x")], [_rec()]) == math.inf
    assert verify.record_gap([_rec()], [_rec(), _rec()]) == math.inf
    assert verify.record_gap([_rec(max_stretch=float("nan"))],
                             [_rec()]) == math.inf


def test_judge_fails_what_is_left_out():
    ok, checks = verify.judge({"a": 1e-15, "b": 0.0}, {"a": 1e-10, "b": 0})
    assert ok and checks["a"] == {"value": 1e-15, "limit": 1e-10}
    assert not verify.judge({"a": 2e-10}, {"a": 1e-10})[0]
    assert not verify.judge({"a": 0.0}, {"a": 1e-10, "b": 1e-10})[0]
    assert not verify.judge({"a": 0.0, "c": 0.0}, {"a": 1e-10})[0]


def test_capture_keeps_stride_and_widest():
    class Inc:
        n_nodes, indptr, indices, data = 2, None, None, None

    cap = verify.Capture(stride=3, offset=1)
    for k in range(10):
        cap.add(Inc(), np.arange(k % 4 + 1), "MIN", np.ones(k % 4 + 1))
    assert [len(item[4]) for item in cap.kept] == [2, 1, 4]
    assert len(cap.widest[4]) == 4 and cap.count == 10
    rng = np.random.default_rng(0)
    pick = verify.sample_requests([cap], 2, rng)
    assert len(pick) == 3 and len(pick[-1][4]) == 4
