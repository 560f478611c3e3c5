"""``benchmarks/span_readers.py``: the program's ``ff.*`` spans laid over
the device's operations, on hand-made traces with hand-computed answers,
and through ``run.py`` itself in a tiny cell that lists the metrics (a
copy of the benchmark: no file of it is edited)."""

import glob
import json
import os
import types

import pytest

import bench_fixtures as F

from benchmarks import span_readers as SR

# every metric whose file names a reader of this module
SPECS = {
    os.path.basename(p)[:-5]: spec
    for p in sorted(glob.glob(os.path.join(F.REPO, "benchmarks", "layer_metrics", "*.json")))
    for spec in [json.load(open(p))]
    if spec["reader"].startswith("benchmarks.span_readers:")
}
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


def test_the_span_metrics_are_metrics_of_the_cells():
    """Fifteen files, each a ``per_layer`` entry listed by the cells that
    read it; ``span_metrics.json``, which held them unread, is gone."""
    manifest = json.load(open(os.path.join(F.REPO, "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert len(SPECS) == 15
    assert not os.path.exists(os.path.join(F.REPO, "benchmarks", "span_metrics.json"))
    for name, spec in SPECS.items():
        assert callable(getattr(SR, spec["reader"].partition(":")[2]))
        assert spec["source"] == "program_span" and spec["better"] == "lower"
        assert "cells" not in spec  # a metric's file never names cells
        cells = per_layer[name]["workloads"]
        assert cells and set(cells) <= set(e2e[spec["moves"]]["workloads"])
        kind = {"tput": "serve_saturated", "lat": "serve_steady", "train": "train_"}[
            name.rpartition(".")[2]]
        assert all(kind in c for c in cells)
    assert len(per_layer["host_ms_per_window.tput"]["workloads"]) == 3
    assert len(per_layer["compiles_in_slice.train"]["workloads"]) == 2


# ------------------------------------------------------------- idle_gaps
# A Python frame that always waits covers every gap: it may name a gap
# only where nothing better does.
WAIT = ("_threading.py:637_wait", 0.0, 10.0)


def _gap_events(host, ops):
    return {
        "/host:CPU": {"main": [(n, s, e - s) for n, s, e in host]},
        "/device:TPU:0": {"XLA Ops": [("%op", s, e - s) for s, e in ops]},
    }


def test_idle_gaps_an_ff_span_beats_a_longer_covering_frame():
    # gaps [1.0, 1.3] and [2.0, 2.1]; the window covers all of the first,
    # the admit inside it 0.2 of its 0.3: the child beats the parent, and
    # both beat the frame that covers everything
    events = _gap_events(
        [WAIT, ("ff.serve.window", 0.9, 1.4), ("ff.serve.admit", 1.05, 1.25),
         ("ff.serve.sync", 1.95, 2.2)],
        [(0.5, 1.0), (1.3, 2.0), (2.1, 2.5)],
    )
    assert SR.idle_gaps(events) == [
        ["ff.serve.admit", pytest.approx(0.3)], ["ff.serve.sync", pytest.approx(0.1)]]


def test_idle_gaps_the_runtimes_event_where_no_span_covers_and_unattributed():
    events = _gap_events(
        [("PjitFunction(step)", 1.0, 1.25), ("TransferToDevice", 1.2, 1.3),
         ("ff.fit", 3.0, 4.0), ("tiny", 5.05, 5.05005)],
        [(0.5, 1.0), (1.3, 3.2), (3.4, 5.0), (5.1, 5.2)],
    )
    # [1.0, 1.3]: no ff. span; PjitFunction covers 0.25 of it, the transfer 0.1.
    # [3.2, 3.4]: under ff.fit.  [5.0, 5.1]: only an event too short to name a gap
    assert SR.idle_gaps(events) == [
        ["PjitFunction(step)", pytest.approx(0.3)], ["ff.fit", pytest.approx(0.2)],
        ["unattributed", pytest.approx(0.1)]]


def test_idle_gaps_sums_by_name_keeps_the_k_largest_and_needs_a_device():
    host = [("ff.serve.window", 0.0, 10.0), ("ff.serve.admit", 1.0, 1.1),
            ("ff.serve.admit", 2.0, 2.1), ("ff.serve.flush", 3.0, 3.05)]
    ops = [(0.5, 1.0), (1.1, 2.0), (2.1, 3.0), (3.05, 4.0), (4.01, 5.0)]
    events = _gap_events(host, ops)
    assert SR.idle_gaps(events) == [
        ["ff.serve.admit", pytest.approx(0.2)], ["ff.serve.flush", pytest.approx(0.05)],
        ["ff.serve.window", pytest.approx(0.01)]]
    assert [n for n, _ in SR.idle_gaps(events, k=1)] == ["ff.serve.admit"]
    # only the longest gaps are named: with one, the rest are not summed in
    assert SR.idle_gaps(events, longest=1) == [["ff.serve.admit", pytest.approx(0.1)]]
    assert SR.idle_gaps({"/host:CPU": events["/host:CPU"]}) == []
    assert SR.idle_gaps({}) == []


# ------------------------------------------------ through run.py, unedited
PROGRAM_METRICS = ["prefill_device_share.tput", "prefill_ms_per_dispatch.tput",
                   "decode_ms_per_step.tput", "prefill_rows_valid_share.tput",
                   "program_relayouts.tput"]


def _serve_checkout(tmp_path):
    names = [n for n in SPECS if n.endswith(".tput")] + PROGRAM_METRICS
    cell = F.tiny_serve_cell("tiny_gpt.backlog", "tiny_backlog",
                             {"serve_tokens_per_s": "tokens/s"}, ["window_wall_ms.tput"] + names)
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_gpt.json": F.TINY_GPT, "workloads/tiny_gpt.backlog.json": cell,
        "traffic_mixes/tiny_backlog.json": F.TINY_BACKLOG_MIX,
    })


SERVE_ARGV = ["--workload", "tiny_gpt.backlog", "--seed", "7", "--seconds", "2", "--trace", "1"]


def test_a_traced_serve_run_prints_the_span_metrics(tmp_path, monkeypatch, capsys):
    rc, res, _ = F.run_main(_serve_checkout(tmp_path), SERVE_ARGV, monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    got = res["metrics"]
    # the CPU has no device plane: the idle and program-time metrics are left
    # out, the spans (recorded with the Python-frame tracer off) and the
    # counters are numbers
    assert set(got) == {"window_wall_ms.tput", "host_ms_per_window.tput",
                        "admit_ms_per_window.tput", "compiles_in_slice.tput",
                        "prefill_rows_valid_share.tput", "program_relayouts.tput"}
    assert 0 < got["host_ms_per_window.tput"]["value"] < got["window_wall_ms.tput"]["value"] * 3
    assert got["admit_ms_per_window.tput"]["value"] > 0
    assert got["compiles_in_slice.tput"]["value"] == 0.0  # every shape was warmed
    assert 0 < got["prefill_rows_valid_share.tput"]["value"] <= 100
    assert got["program_relayouts.tput"]["value"] >= 0  # a count; 0 is a reading
    assert "traced_calls_share" not in res["device"] and res["breakdown"]["idle_gaps"] == []
    f = res["facts"]
    assert f["prefill_rows_computed"] == f["prefill_dispatches"] * 4 * 8
    assert 0 < f["prefill_positions"] <= f["positions"]


def test_a_traced_serve_run_with_a_device_line(tmp_path, monkeypatch, capsys):
    """The same run, its real host spans with a made-up chip beside them
    (the CPU writes no device plane): two programs on ``XLA Modules``,
    operations that leave each traced window but its first 30 % idle."""
    from benchmarks import trace_reduce as TR

    real_load = TR.load
    made = {}

    def load(path):
        events = real_load(path)
        wins = [(s, e) for n, s, e in SR.ff_spans(events) if n == WINDOW]
        made["windows"] = len(wins)
        events["/device:TPU:0"] = {
            TR.OPS_LINE: [("%fusion.1 = f32[8]", s, 0.3 * (e - s)) for s, e in wins],
            TR.MODULES_LINE: [
                (("jit_prefill(1)", "jit_decode(2)")[i % 2], s, 0.3 * (e - s))
                for i, (s, e) in enumerate(wins)
            ],
        }
        return events

    monkeypatch.setattr(TR, "load", load)
    rc, res, _ = F.run_main(_serve_checkout(tmp_path), SERVE_ARGV, monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and made["windows"] >= 4
    got, dev = res["metrics"], res["device"]
    assert set(PROGRAM_METRICS) <= set(got) and "idle_unattributed_share.tput" in got
    assert 0 < got["prefill_device_share.tput"]["value"] < 100
    assert got["prefill_ms_per_dispatch.tput"]["value"] > 0
    assert got["decode_ms_per_step.tput"]["value"] > 0
    assert got["idle_in_host_ms_per_window.tput"]["value"] > 0
    assert dev["busy_s"] > 0 and dev["traced_calls_share"] > 0
    gaps = res["breakdown"]["idle_gaps"]
    assert gaps and all(name.startswith("ff.serve.") for name, _ in gaps[:2])
    assert not any("_threading" in name for name, _ in gaps)


def test_a_traced_train_run_prints_the_span_metrics(tmp_path, monkeypatch, capsys):
    names = [n for n in SPECS if n.endswith(".train")]
    root = F.tmp_checkout(tmp_path, {
        "configs/tiny_bert.json": F.TINY_BERT,
        "workloads/tiny_bert.train.json": dict(F.TINY_TRAIN_CELL, layer_metrics=names),
        "traffic_mixes/tiny_train.json": F.TINY_TRAIN_MIX,
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
