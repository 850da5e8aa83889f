"""The six metrics over the scheduler loop's own account of its wall time
(``harness/readers_loop.py``), as added files: each loads for each of the
four serve cells and for no other, ``BENCHMARK.json`` lists them as their
files have them, and a rehearsal of ``m7b-serve-chat`` prints all six over
records that close over the window's wall time. (The readers on hand-made
records: ``tests/test_loop_accounting.py``, tier-1.)"""

import argparse
import json
import os
import time

import pytest

from benchmarks.harness import cells, readers_loop, serve

SERVE_CELLS = ("m7b-serve-chat", "sdar-serve-chat", "xing4-serve-longdoc",
               "nemotron-serve-chat128")
METRICS = {
    "device_dry_share": ("device_dry_share", {}),
    "after_prefill_ms": ("after_prefill_ms", {}),
    "admit_own_share": ("loop_share", {"part": "admit_own"}),
    "sched_cpu_share": ("loop_share", {"part": "cpu"}),
    "iteration_max_ms": ("iteration_max", {"part": "span"}),
    "iteration_max_host_ms": ("iteration_max", {"part": "host"})}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_every_metric_loads_for_each_serve_cell(cell):
    found = {m["name"]: m for m in cells.load_layer_metrics(cell)}
    assert set(METRICS) <= set(found)
    for name, (reader, args) in METRICS.items():
        metric = found[name]
        assert metric["module"] == "readers_loop"
        assert (metric["reader"], metric.get("args", {})) == (reader, args)
        assert callable(getattr(readers_loop, reader))
        assert metric["workloads"] == list(SERVE_CELLS)


def test_the_train_cell_reports_none_of_them():
    found = {m["name"] for m in cells.load_layer_metrics("nemo-train-lora")}
    assert not found & set(METRICS)


def test_benchmark_json_lists_the_six_as_their_files_have_them():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    listed = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in METRICS}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    files = {m["name"]: m for m in cells.load_layer_metrics(SERVE_CELLS[0])}
    for name in METRICS:
        entry, data = listed[name], files[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert all(entry[key] == data[key] for key in entry)
        assert entry["layer"] in layers and entry["moves"] in end_to_end


def _check_records(records):
    """What holds for every record of a run, and over the run."""
    for r in records:
        loop_s = r["t1"] - r["t0"]
        waited = r["t_fetched"] - r["t_dispatched"] + r["admit_wait_s"]
        assert r["inflight_wait_s"] + r["prefill_wait_s"] \
            <= r["admit_wait_s"] + 1e-9, r
        assert 0.0 <= r["idle_s"] <= r["gap_s"] + 1e-9, r
        assert 0.0 <= r["cpu_s"] <= r["cpu_span_s"] + 1e-3, r
        assert -1e-9 <= r["dry_s"] <= r["gap_s"] + loop_s - waited + 1e-9, r
    closed = sum(r["gap_s"] + r["t1"] - r["t0"] for r in records[1:])
    assert closed == pytest.approx(records[-1]["t1"] - records[0]["t1"],
                                   abs=1e-6)


def test_a_rehearsal_prints_all_six_over_records_that_close(tmp_path,
                                                            monkeypatch,
                                                            capsys):
    from mlrun_tpu.obs import ticklog

    monkeypatch.setenv("MLT_HOME", str(tmp_path / "mlt"))
    before = set(ticklog.tick_logs())
    cell = cells.rehearsed(cells.load_cell("m7b-serve-chat"))
    args = argparse.Namespace(seed=2147483939, seconds=4.0, trace=1,
                              rehearse=1)
    line = json.loads(serve.run(
        cell, cells.load_layer_metrics("m7b-serve-chat"), args, DEVICE,
        time.perf_counter()))
    assert line["correct"] is True and line["failed"] == 0
    assert set(METRICS) <= set(line["metrics"])
    read = {name: line["metrics"][name]["value"] for name in METRICS}
    assert 0.0 <= read["device_dry_share"] < 100.0
    assert read["after_prefill_ms"] > 0.0
    assert 0.0 < read["admit_own_share"] < 100.0
    assert 0.0 < read["sched_cpu_share"] <= 100.1
    assert 0.0 < read["iteration_max_host_ms"] <= read["iteration_max_ms"]
    said = [text for text in capsys.readouterr().err.splitlines()
            if text.startswith("[bench] longest iteration ")]
    assert len(said) == 1
    worst = json.loads(said[0].split("iteration ", 1)[1])
    assert worst["span_s"] * 1e3 == pytest.approx(read["iteration_max_ms"],
                                                  abs=1e-2)
    assert worst["phase"] in ("gap", "admit", "build", "dispatch", "fetch",
                              "commit")
    (name,) = set(ticklog.tick_logs()) - before
    _check_records(ticklog.get_tick_log(name).records())
