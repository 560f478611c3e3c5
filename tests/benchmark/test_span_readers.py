"""``benchmarks/span_readers.py``: the program's ``ff.*`` spans laid over
the device's operations, on hand-made traces with hand-computed answers,
and through ``run.py`` itself in a tiny cell that lists the metrics (a
copy of the benchmark: no file of it is edited)."""

import json
import os
import types

import pytest

import bench_fixtures as F

from benchmarks import span_readers as SR

SPECS = json.load(open(os.path.join(F.REPO, "benchmarks", "span_metrics.json")))
WINDOW, STEP = "ff.serve.window", "ff.fit.step_dispatch"


def _run(host=None, ops=None):
    events = {}
    if host is not None:
        events["/host:CPU"] = {
            "main": [(n, s, e - s) for n, s, e in host],
            "bench": [("_threading.py:637_wait", 0.0, 10.0)],  # not ours: never read
        }
    if ops is not None:
        events["/device:TPU:0"] = {"XLA Ops": [("%op", s, e - s) for s, e in ops]}
    return types.SimpleNamespace(trace={"events": events}, facts={})


# A serve slice the profiler cut out of a running loop (seconds).  Whole
# units: [1.00, 1.12) and [1.12, 1.30); the sync of a window that began
# before the slice, and the last window from its start on, are edges.
SERVE_HOST = [
    ("ff.serve.sync", 0.90, 0.95),
    ("ff.serve.admit", 0.98, 0.99),
    (WINDOW, 1.00, 1.10),
    ("ff.serve.prefill_dispatch", 1.00, 1.02),
    ("ff.input.h2d_place", 1.005, 1.015),
    ("ff.serve.decode_dispatch", 1.02, 1.05),
    ("ff.serve.sync", 1.05, 1.09),
    ("ff.serve.flush", 1.09, 1.10),
    ("ff.serve.admit", 1.10, 1.11),
    ("ff.compile", 1.115, 1.115001),
    (WINDOW, 1.12, 1.20),
    ("ff.serve.decode_dispatch", 1.12, 1.14),
    ("ff.serve.sync", 1.14, 1.19),
    ("ff.serve.flush", 1.19, 1.20),
    ("ff.serve.admit", 1.20, 1.21),
    ("ff.serve.idle", 1.21, 1.25),
    ("ff.serve.admit", 1.25, 1.26),
    (WINDOW, 1.30, 1.40),
    ("ff.serve.decode_dispatch", 1.30, 1.33),
    ("ff.serve.sync", 1.33, 1.39),
]
# gaps: [1.008, 1.03] straddles three spans, innermost first; [1.085, 1.105]
# sync, flush, admit; [1.22, 1.31] idle, admit, then nothing, cut at 1.30;
# [1.35, 1.38] lies past the whole units
SERVE_OPS = [(0.90, 1.008), (1.03, 1.085), (1.105, 1.22), (1.11, 1.12),
             (1.31, 1.35), (1.38, 1.50)]
SERVE_IDLE = {
    "ff.input.h2d_place": 0.007, "ff.serve.prefill_dispatch": 0.005,
    "ff.serve.decode_dispatch": 0.010, "ff.serve.sync": 0.005, "ff.serve.flush": 0.010,
    "ff.serve.admit": 0.005 + 0.010, "ff.serve.idle": 0.030, None: 0.040,
}


def test_idle_time_goes_to_the_innermost_span_and_edges_are_dropped():
    by, units = SR.idle_by_span(_run(SERVE_HOST, SERVE_OPS).trace["events"], WINDOW)
    assert units == 2
    assert set(by) == set(SERVE_IDLE)
    for name, want in SERVE_IDLE.items():
        assert by[name] == pytest.approx(want, abs=1e-9), name


@pytest.mark.parametrize("metric,want", [
    ("host_ms_per_window.tput", 1e3 * (0.10 + 0.08 - 0.04 - 0.05) / 2),
    ("admit_ms_per_window.tput", 1e3 * 0.03 / 2),  # the admit before the first unit is an edge
    ("idle_in_host_ms_per_window.tput", 1e3 * 0.047 / 2),  # not the sync's, not the sleep's
    ("idle_unattributed_share.tput", 100 * 0.040 / 0.122),
    ("compiles_in_slice.tput", 1.0),
    ("host_ms_per_window.lat", 45.0),
    ("idle_unattributed_share.lat", 100 * 0.040 / 0.122),
])
def test_serve_metrics_by_hand(metric, want):
    spec = SPECS[metric]
    fn = getattr(SR, spec["reader"].partition(":")[2])
    assert fn(_run(SERVE_HOST, SERVE_OPS), **spec["args"]) == pytest.approx(want, rel=1e-9)


# A training slice: the profiler brackets one whole fit, whose host runs
# ahead and then waits in the flush; a step of a fit the slice cut is an edge.
TRAIN_HOST = [
    ("ff.fit", 2.0, 3.0), ("ff.fit.epoch", 2.0, 2.99),
    ("ff.input.batch_wait", 2.05, 2.06), (STEP, 2.10, 2.11),
    ("ff.input.batch_wait", 2.15, 2.16), (STEP, 2.20, 2.22),
    ("ff.input.batch_wait", 2.25, 2.255), (STEP, 2.30, 2.31),
    ("ff.input.batch_wait", 2.35, 2.351), ("ff.fit.metric_flush", 2.40, 2.95),
    (STEP, 3.50, 3.51),
]
TRAIN_OPS = [(2.12, 2.50), (2.52, 2.80), (2.80, 2.90), (3.52, 3.60)]


@pytest.mark.parametrize("metric,want", [
    ("batch_wait_ms_per_step.train", 1e3 * 0.026 / 3),
    ("step_dispatch_ms_per_step.train", 1e3 * 0.04 / 3),
    # the frame's lead [2.00, 2.12], before the device's first operation: the
    # epoch, a batch wait, the first dispatch; [2.50, 2.52] under the flush;
    # [2.90, 3.00] (cut at the frame's end) under the flush, the epoch, the fit
    ("idle_in_host_ms_per_step.train", 1e3 * (0.12 + 0.02 + 0.05 + 0.04 + 0.01) / 3),
    ("idle_unattributed_share.train", 0.0),
    ("compiles_in_slice.train", 0.0),  # none is a reading
])
def test_train_metrics_by_hand(metric, want):
    spec = SPECS[metric]
    fn = getattr(SR, spec["reader"].partition(":")[2])
    assert fn(_run(TRAIN_HOST, TRAIN_OPS), **spec["args"]) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("metric", sorted(SPECS))
def test_nothing_to_read_is_none_and_never_raises(metric):
    """A program from before the spans, a slice with no whole unit, a run
    that traced nothing: the metric is left out of the line."""
    spec = SPECS[metric]
    fn = getattr(SR, spec["reader"].partition(":")[2])
    no_spans = _run([("PjitFunction(step)", 1.0, 1.5)], SERVE_OPS)
    assert fn(no_spans, **spec["args"]) is None
    assert fn(types.SimpleNamespace(trace=None, facts={}), **spec["args"]) is None
    assert fn(_run(), **spec["args"]) is None
    if "unit" in spec["args"]:
        one = _run([(WINDOW, 1.0, 1.1), ("ff.serve.sync", 1.05, 1.09), (STEP, 1.0, 1.1)],
                   SERVE_OPS)
        assert fn(one, **spec["args"]) is None  # one serve window is no whole unit; no ff.fit frame


def test_idle_needs_a_device_and_the_clock_check_reads_both():
    host_only = _run(SERVE_HOST)
    assert SR.idle_under_spans_ms_per_unit(host_only, unit=WINDOW) is None
    assert SR.span_ms_per_unit(host_only, span="ff.serve.admit", unit=WINDOW) == pytest.approx(15.0)
    # each sync's end against the end of the last operation that began before it
    clocks = _run(
        [("ff.serve.sync", 1.05, 1.0900), ("ff.serve.sync", 1.15, 1.2002)],
        [(1.00, 1.0895), (1.01, 1.02), (1.10, 1.2000), (1.25, 1.30)],
    )
    assert SR.sync_after_device_ms(clocks.trace["events"]) == pytest.approx([0.5, 0.2])
    # the device starts on a window after the host has begun it: with the line
    # above, the host's clock is at most 0.2 ms ahead and at most 0.1 ms behind
    clocks.trace["events"]["/host:CPU"]["main"] += [(WINDOW, 1.0999, 0.1), (WINDOW, 1.2100, 0.1)]
    assert SR.op_after_dispatch_ms(clocks.trace["events"]) == pytest.approx([0.1, 40.0])


def test_span_metrics_file_names_readers_layers_and_cells_that_exist():
    manifest = json.load(open(os.path.join(F.REPO, "BENCHMARK.json")))
    cells = {w["name"]: w for w in manifest["workloads"]}
    layers = {m["layer"] for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for name, spec in SPECS.items():
        mod, _, fn = spec["reader"].partition(":")
        assert mod == "benchmarks.span_readers" and callable(getattr(SR, fn))
        assert spec["layer"] in layers and spec["source"] == "program_span"
        assert spec["better"] == "lower" and spec["cells"]
        for c in spec["cells"]:
            assert c in cells and c in e2e[spec["moves"]]["workloads"]
        assert name not in {m["name"] for m in manifest["per_layer"]}


# ------------------------------------------------ through run.py, unedited
def _metric_files(suffix):
    return {
        f"layer_metrics/{name}.json": {k: v for k, v in spec.items() if k != "cells"}
        for name, spec in SPECS.items() if name.endswith(suffix)
    }


def test_a_traced_serve_run_prints_the_span_metrics(tmp_path, monkeypatch, capsys):
    files = _metric_files(".tput")
    names = [os.path.basename(p)[:-5] for p in files]
    cell = F.tiny_serve_cell("tiny_gpt.backlog", "tiny_backlog",
                             {"serve_tokens_per_s": "tokens/s"}, ["window_wall_ms.tput"] + names)
    root = F.tmp_checkout(tmp_path, {
        "configs/tiny_gpt.json": F.TINY_GPT, "workloads/tiny_gpt.backlog.json": cell,
        "traffic_mixes/tiny_backlog.json": F.TINY_BACKLOG_MIX, **files,
    })
    rc, res, _ = F.run_main(root, ["--workload", "tiny_gpt.backlog", "--seed", "7",
                                   "--seconds", "2", "--trace", "1"], monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    got = res["metrics"]
    # the CPU has no device plane: the two idle metrics are left out, the rest are numbers
    assert set(got) == {"window_wall_ms.tput", "host_ms_per_window.tput",
                        "admit_ms_per_window.tput", "compiles_in_slice.tput"}
    assert 0 < got["host_ms_per_window.tput"]["value"] < got["window_wall_ms.tput"]["value"] * 3
    assert got["admit_ms_per_window.tput"]["value"] > 0
    assert got["compiles_in_slice.tput"]["value"] == 0.0  # every shape was warmed


def test_a_traced_train_run_prints_the_span_metrics(tmp_path, monkeypatch, capsys):
    files = _metric_files(".train")
    names = [os.path.basename(p)[:-5] for p in files]
    root = F.tmp_checkout(tmp_path, {
        "configs/tiny_bert.json": F.TINY_BERT,
        "workloads/tiny_bert.train.json": dict(F.TINY_TRAIN_CELL, layer_metrics=names),
        "traffic_mixes/tiny_train.json": F.TINY_TRAIN_MIX, **files,
    })
    rc, res, _ = F.run_main(root, ["--workload", "tiny_bert.train", "--seed", "7",
                                   "--seconds", "1", "--trace", "1"], monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    got = res["metrics"]
    assert set(got) == {"batch_wait_ms_per_step.train", "step_dispatch_ms_per_step.train",
                        "compiles_in_slice.train"}
    assert got["step_dispatch_ms_per_step.train"]["value"] > 0
    assert got["batch_wait_ms_per_step.train"]["value"] > 0
    assert got["compiles_in_slice.train"]["value"] >= 0.0
