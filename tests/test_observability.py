"""Observability exports + zero-dead-flags guard (VERDICT r1 item 7).

Reference: ``--compgraph`` dot export (``graph.h:337-344``,
``src/utils/dot/``), ``--taskgraph`` task-graph export
(``model.cc:3666-3668``), ``--profiling`` per-op timing
(``model.cc:3650-3653``).
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from flexflow_tpu import (
    ActiMode,
    FFConfig,
    FFModel,
    LossType,
    MachineMesh,
    MetricsType,
    SGDOptimizer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_and_compile(tmp_path, **cfg_kw):
    cfg = FFConfig(batch_size=16, **cfg_kw)
    model = FFModel(cfg)
    t = model.create_tensor((16, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    t = model.dense(t, 10, name="fc2")
    model.softmax(t, name="probs")
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY],
        mesh=MachineMesh((4, 2), ("data", "model")),
    )
    return model


def test_compgraph_dot_export(tmp_path):
    dot_path = str(tmp_path / "pcg.dot")
    _build_and_compile(tmp_path, export_strategy_computation_graph_file=dot_path)
    text = open(dot_path).read()
    assert text.startswith("digraph")
    for name in ("fc1", "fc2", "probs"):
        assert name in text
    assert "mesh (4, 2)" in text
    assert "->" in text  # edges present


def test_taskgraph_json_export(tmp_path):
    tg_path = str(tmp_path / "taskgraph.json")
    _build_and_compile(tmp_path, taskgraph_file=tg_path)
    doc = json.load(open(tg_path))
    assert doc["makespan_s"] > 0
    assert doc["mesh"]["shape"] == [4, 2]
    names = {t["name"] for t in doc["tasks"]}
    assert {"fc1", "fc2", "probs"} <= names
    for t in doc["tasks"]:
        assert t["stream"] in ("compute", "comm")
        assert t["end_s"] >= t["start_s"] >= 0
        for d in t["deps"]:
            assert d in names
    assert doc["makespan_s"] == pytest.approx(
        max(t["end_s"] for t in doc["tasks"])
    )


def test_profiling_table(capsys):
    cfg = FFConfig(batch_size=16, profiling=True)
    model = FFModel(cfg)
    t = model.create_tensor((16, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    model.softmax(t, name="probs")
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        mesh=MachineMesh((1, 1), ("data", "model")),
    )
    out = capsys.readouterr().out
    assert "fc1" in out and "TOTAL" in out and "us" in out


def test_no_dead_config_flags():
    """Every FFConfig field must be consumed somewhere — 'a flag that does
    nothing is worse than no flag' (VERDICT r1).  Consumed = referenced in
    the package outside config.py, OR READ (not merely assigned by
    parse_args) inside an FFConfig method that external code calls, e.g.
    ``build_mesh`` reading ``mesh_shape``/``mesh_axis_names``."""
    import re

    fields = [f.name for f in dataclasses.fields(FFConfig)]
    src = ""
    for root, _, files in os.walk(os.path.join(REPO, "flexflow_tpu")):
        for fn in files:
            if fn.endswith(".py") and fn != "config.py":
                src += open(os.path.join(root, fn)).read()
    cfg_src = open(
        os.path.join(REPO, "flexflow_tpu", "config.py")
    ).read()

    def read_in_config(f: str) -> bool:
        for m in re.finditer(rf"self\.{f}\b", cfg_src):
            rest = cfg_src[m.end():].lstrip(" ")
            if not rest.startswith("=") or rest.startswith("=="):
                return True  # a read, not an assignment target
        return False

    dead = [f for f in fields if f not in src and not read_in_config(f)]
    assert not dead, f"parsed-but-unused config flags: {dead}"


# --------------------------------------------------- unified tracing layer
import numpy as np

from flexflow_tpu.obs import HealthMonitor, Tracer, get_tracer, set_monitor, set_tracer


@pytest.fixture(autouse=True)
def _reset_tracer():
    """The tracer is process-wide: restore the disabled default after every
    test so an enabled tracer never leaks into other test modules (it
    switches the executor onto the instrumented step path).  The monitor
    does the same switch: start from the disabled one, whatever a test
    file that ran earlier on this worker left behind."""
    set_monitor(HealthMonitor())
    yield
    set_tracer(Tracer())


def _fit_traced(tmp_path, trace_kw, steps_data=64, **cfg_kw):
    cfg = FFConfig(batch_size=16, **trace_kw, **cfg_kw)
    model = FFModel(cfg)
    t = model.create_tensor((16, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    t = model.dense(t, 10, name="fc2")
    model.softmax(t, name="probs")
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY],
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=(steps_data, 32)).astype(np.float32)
    y = rng.integers(0, 10, size=(steps_data, 1)).astype(np.int32)
    model.fit(x, y, epochs=2, verbose=False)
    return model


def test_trace_chrome_schema(tmp_path):
    """--trace-out on an MLP fit yields valid Chrome-trace JSON with
    step/compile/search spans, consistent nesting, and the counter
    vocabulary (jit cache, search candidates, OOM rejections)."""
    trace = str(tmp_path / "trace.json")
    _fit_traced(
        tmp_path, dict(trace_out=trace, trace_level="op"), search_budget=4
    )
    doc = json.load(open(trace))  # valid JSON by construction of the test
    events = doc["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no complete events recorded"
    for e in spans:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0 and e["ts"] >= 0
    names = {e["name"] for e in spans}
    # step, compile, and search layers are all represented
    assert {"train_step", "device_step", "jit_compile", "epoch"} <= names
    assert {"unity_search", "dp_solve"} & names
    cats = {e["cat"] for e in spans}
    assert {"step", "compile", "search", "fit"} <= cats
    # nesting consistency: same-thread spans either nest or are disjoint
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    eps = 1e-3  # us rounding slack
    for ivs in by_tid.values():
        for i, (s1, e1) in enumerate(ivs):
            for s2, e2 in ivs[i + 1:]:
                assert (
                    e1 <= s2 + eps or e2 <= s1 + eps  # disjoint
                    or (s1 <= s2 + eps and e2 <= e1 + eps)  # 2 inside 1
                    or (s2 <= s1 + eps and e1 <= e2 + eps)  # 1 inside 2
                ), f"partially overlapping spans: {(s1, e1)} vs {(s2, e2)}"
    counters = doc["flexflow_tpu"]["summary"]["counters"]
    assert counters["jit.cache_miss"] >= 1
    assert counters["jit.cache_hit"] >= 1  # steps after the first
    assert counters["search.candidates_explored"] > 0
    assert "search.oom_rejections" in counters  # full vocabulary present


def test_trace_summary_and_last_step_stats(tmp_path):
    trace = str(tmp_path / "t.json")
    model = _fit_traced(tmp_path, dict(trace_out=trace))
    stats = model.last_step_stats()
    assert stats is not None
    assert {"step", "total_s", "host_s", "dispatch_s", "device_s",
            "compile_s", "jit_cache"} <= set(stats)
    assert stats["jit_cache"] == "hit"  # later steps replay the jit
    assert stats["total_s"] >= stats["device_s"] >= 0
    summ = model.trace_summary()
    assert summ["phases"]["step"]["count"] > 0
    assert summ["spans"]["train_step"]["count"] == 8  # 4 batches x 2 epochs
    # memory snapshot from the compiled step's buffer assignment
    assert any(k.startswith("memory.") for k in summ["samples"])


def test_search_telemetry_counters(tmp_path):
    """Second measured search over the same ops is served from the
    profiler cost cache — hit-rate counters say so."""
    from flexflow_tpu.obs import configure
    from flexflow_tpu.search import unity_search
    from flexflow_tpu.search.simulator import OpProfiler

    tracer = configure(level="step")
    model = FFModel(FFConfig(batch_size=16))
    t = model.create_tensor((16, 32), name="x")
    t = model.dense(t, 32, name="fc1")
    model.dense(t, 8, name="fc2")
    mesh = MachineMesh((2,), ("data",))
    prof = OpProfiler(cache_file=str(tmp_path / "costs.json"), iters=1)
    for _ in range(2):
        unity_search(
            model.layers, mesh, graph_inputs=model.graph_inputs,
            budget=2, explore_meshes=False, profiler=prof,
            struct_xfers=None,
        )
    c = tracer.summary()["counters"]
    assert c["search.candidates_explored"] > 0
    assert c["profiler.cache_miss"] > 0  # first search measured
    assert c["profiler.cache_hit"] > 0  # second search hit the cache
    # hit-rate is computable from the two counters
    rate = c["profiler.cache_hit"] / (
        c["profiler.cache_hit"] + c["profiler.cache_miss"]
    )
    assert 0.0 < rate < 1.0


def test_disabled_tracer_zero_overhead(tmp_path):
    """Default config: the tracer fast path records NOTHING and writes no
    files — the acceptance guard for the untraced hot path."""
    tracer = set_tracer(Tracer())  # disabled default
    assert not tracer.enabled
    before = set(os.listdir(tmp_path))
    cwd_before = set(os.listdir("."))
    model = _fit_traced(tmp_path, {})
    assert get_tracer() is tracer  # off config leaves the tracer alone
    assert tracer.events == []  # zero recorded spans
    assert tracer.counters == {}
    assert tracer.summary()["spans"] == {}
    assert set(os.listdir(tmp_path)) == before  # no trace file written
    assert set(os.listdir(".")) == cwd_before
    # the fast path skips per-step stats (they'd force a device sync)
    assert model.last_step_stats() is None


def test_profiling_flag_gates_step_prints(capsys, tmp_path):
    """--profiling now gates per-STEP timing printouts in fit (reference
    per-iteration ELAPSED prints, model.cc:3650-3653)."""
    _fit_traced(tmp_path, {}, profiling=True)
    out = capsys.readouterr().out
    assert "[profiling] step 0:" in out
    assert "dispatch" in out and "device" in out and "jit miss" in out
    assert "jit hit" in out  # steps after the first replay the cache


def test_trace_report_cli(tmp_path):
    """tools/trace_report.py renders a trace into a non-empty per-phase
    breakdown (smoke, via the real CLI)."""
    trace = str(tmp_path / "trace.json")
    _fit_traced(tmp_path, dict(trace_out=trace, trace_level="step"),
                search_budget=2)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         trace],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "per-phase time breakdown" in out
    for needle in ("compile", "step", "train_step", "counters:",
                   "jit.cache_hit"):
        assert needle in out, f"missing {needle!r} in report:\n{out}"
    # breakdown rows are non-empty (not just headers)
    assert "(empty)" not in out


def test_keras_trace_callback(tmp_path):
    """TraceCallback records epoch spans from the keras fit loop and
    writes the trace file at train end."""
    from flexflow_tpu.frontends import keras as ff_keras

    trace = str(tmp_path / "keras_trace.json")
    model = ff_keras.Sequential([
        ff_keras.Dense(16, activation="relu"),
        ff_keras.Dense(4, activation="softmax"),
    ])
    model.compile(optimizer=ff_keras.SGD(learning_rate=0.01),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(32, 1)).astype(np.int32)
    cb = ff_keras.TraceCallback(out_path=trace)
    model.fit(x, y, batch_size=16, epochs=2, callbacks=[cb], verbose=False)
    doc = json.load(open(trace))
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "epoch" in names and "train_step" in names


def test_search_options_gate_param_parallel():
    """--enable-parameter-parallel gates vocab/in-dim partition candidates
    (reference model.cc:3620)."""
    from flexflow_tpu.search.candidates import (
        SearchOptions,
        op_candidates,
        search_options,
    )

    model = FFModel(FFConfig(batch_size=16))
    t = model.create_tensor((16, 32), name="x")
    model.dense(t, 64, name="fc")
    layer = model.layers[0]
    mesh = MachineMesh((2, 4), ("data", "model"))

    def has_in_dim_partition(cands):
        return any(
            c.output and c.output[0].partial_axes and "model" in c.output[0].partial_axes
            for c in cands
        )

    with search_options(SearchOptions(param_parallel=False)):
        assert not has_in_dim_partition(op_candidates(layer, mesh))
    with search_options(SearchOptions(param_parallel=True)):
        assert has_in_dim_partition(op_candidates(layer, mesh))
