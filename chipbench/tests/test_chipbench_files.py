"""Every file ``BENCHMARK.json`` names exists, parses and fits the harness."""
import json
import os
import re

import pytest

from chipbench import bench, stats, workload

SPEC = json.load(open(os.path.join(bench.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith("chipbench/configs/")
    data = json.load(open(os.path.join(bench.ROOT, conf["file"])))
    assert data["name"] == conf["name"]
    assert set(conf["reduced"]) == set(data["reduced"])
    for key in conf["reduced"]:
        assert key in data and NAME.match(key)
    for key in ("kind", "n_nodes", "n_jobs", "source", "guarantees"):
        assert key in data
    # the offered load the configuration states is the one its traces have
    for name, load in data.get("offered_load", {}).items():
        if name.startswith("trace_"):
            cols = workload.columns(data, int(name.split("_")[1]))
            assert workload.offered_load(cols, data["n_nodes"]) == \
                pytest.approx(load, abs=5e-5)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = bench.Cell(name)
    t = cell.traffic
    assert os.path.exists(os.path.join(bench.HERE, "entries",
                                       f"{t['entry']}.py"))
    assert bench.load_entry(t["entry"]) is not None
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert t["limits"] and all(v >= 0 for v in t["limits"].values())
    assert set(t["warm"]) == {"opt", "batch", "width"}


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(metric):
    read = bench.load_metric(metric["name"])
    assert callable(read)
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    assert set(metric["workloads"]) <= set(CELLS)
    # a metric's cells report the end-to-end metric it moves
    moved = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moved.get("workloads", CELLS))
    # no trace and no spans: the reader finds nothing and says so
    assert read(bench.Context([], 0.0, None, {})) is None


def test_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_tail_metric_names_its_percentile():
    # ten distinct forks beyond the percentile: the race cell has 38 forks
    for m in SPEC["end_to_end"]:
        if "_p" in m["name"] and m["name"].startswith("race_"):
            assert stats.percentile_of_name(m["name"]) == \
                stats.tail_percentile(38)


def test_metric_files_without_a_suffix_are_shared():
    assert bench.load_metric("lanes_per_round.race") is not None
    with pytest.raises(FileNotFoundError):
        bench.load_metric("no_such_metric.sweep")


def test_peaks_table():
    assert bench.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks_of("TPU v9 imaginary")
