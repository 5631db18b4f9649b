"""The trace reduction, on a trace recorded on the CPU and on made rows."""
import os

import pytest

from chipbench import tracing
from chipbench.tracing import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = ("maxmin_yields_batch", "allocate_many")


def _cpu_op(ev):
    # on the CPU the compiled program's ops run on the client's thread pool
    return (ev.line.startswith("tf_XLAPjRtCpuClient")
            and not ev.name.startswith(("end:", "Threadpool", "Thunk")))


def test_recorded_cpu_trace():
    events = tracing.read_events(os.path.join(DATA, "cpu_trace.xplane.pb"))
    s = tracing.summarize(events, SPANS, is_op=_cpu_op,
                          is_program=lambda ev: False)
    assert s.n_devices == 1
    assert 0 < s.busy_s < 0.05
    assert {name for name, _ in s.top_ops} >= {"dot_general.1"}
    # four solves, three gaps between them: each gap lies partly in the
    # host's sleep outside any span and partly inside allocate_many
    assert s.gap_totals[tracing.HOST_LOOPS] > 0.005
    assert s.gap_totals["allocate_many"] > 0.004
    assert sum(s.gap_totals.values()) == pytest.approx(
        sum(sec for label, sec in s.idle_gaps if label.startswith("all ")))


def _rows():
    dev = "/device:TPU:0"
    return [
        Event(dev, "XLA Modules", "jit_maxmin_batch(17)", 100, 50),
        Event(dev, "XLA Ops", "%while.1 = f32[8] while(...)", 100, 40),
        Event(dev, "XLA Ops", "%fusion.2 = f32[8] fusion(...)", 110, 10),
        Event(dev, "XLA Ops", "%copy.3 = f32[8] copy(...)", 140, 10),
        Event(dev, "XLA Modules", "jit_maxmin_batch(17)", 400, 60),
        Event(dev, "XLA Ops", "%while.1 = f32[8] while(...)", 400, 60),
        Event(dev, "XLA Modules", "jit_lam(3)", 900, 100),
        Event(dev, "XLA Ops", "%reduce.1 = f32[8] reduce(...)", 900, 100),
        Event("/host:CPU", "python3", "allocate_many", 50, 400),
        Event("/host:CPU", "python3", "maxmin_yields_batch", 300, 130),
        Event("/host:CPU", "python3", "other", 0, 2000),
    ]


def test_made_rows():
    s = tracing.summarize(_rows(), SPANS)
    # busy: [100,150] + [400,460] + [900,1000]
    assert s.busy_s == pytest.approx(210e-9)
    assert s.program("maxmin_batch") == (pytest.approx(110e-9), 2)
    assert s.program("lam") == (pytest.approx(100e-9), 1)
    assert s.program("absent") is None
    assert s.top_ops[0] == ["%while.1", pytest.approx(100e-9)]
    # gap [150,400]: 150 in allocate_many only, 100 in maxmin_yields_batch;
    # gap [460,900]: all outside the spans
    assert s.gap_totals["allocate_many"] == pytest.approx(150e-9)
    assert s.gap_totals["maxmin_yields_batch"] == pytest.approx(100e-9)
    assert s.gap_totals[tracing.HOST_LOOPS] == pytest.approx(440e-9)
    singles = [g for g in s.idle_gaps if not g[0].startswith("all ")]
    assert singles == [[tracing.HOST_LOOPS, pytest.approx(440e-9)],
                       ["allocate_many", pytest.approx(250e-9)]]


def test_union_and_overlap():
    m = tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert m == [[0, 3], [5, 9]]
    starts = [iv[0] for iv in m]
    assert tracing.overlap(2, 6, m, starts) == 2
    assert tracing.overlap(10, 12, m, starts) == 0


def test_program_name():
    assert tracing.program_name("jit_maxmin_batch(7089746543768383914)") \
        == "maxmin_batch"
    assert tracing.program_name("lam") == "lam"


def test_no_device_no_busy():
    s = tracing.summarize([Event("/host:CPU", "python3", "allocate_many",
                                 0, 10)], SPANS)
    assert s.n_devices == 0 and s.busy_s == 0.0


def test_find_xplane_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        tracing.find_xplane(str(tmp_path))


def test_recorded_tpu_rows():
    """0.1 s of a lublin.min-sweep window on one TPU v5e: the device
    plane's op and module lines and the harness's two host spans."""
    import gzip
    import json

    with gzip.open(os.path.join(DATA, "tpu_rows.json.gz"), "rt") as f:
        rows = [Event(*r) for r in json.load(f)]
    s = tracing.summarize(rows, SPANS)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(0.000616503, rel=1e-6)
    seconds, runs = s.program("maxmin_batch")
    assert runs == 8 and 50e-6 < seconds / runs < 100e-6
    assert s.top_ops[0][0] == "%while.79"
    assert s.gap_totals["maxmin_yields_batch"] == pytest.approx(0.012135871)
    assert s.gap_totals["allocate_many"] == pytest.approx(0.003377769)
    assert s.gap_totals[tracing.HOST_LOOPS] == pytest.approx(0.078048612)
