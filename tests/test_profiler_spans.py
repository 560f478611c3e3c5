"""The program's ``ff.*`` spans in the profiler's trace (ISSUE 26,
docs/OBSERVABILITY.md "ff.* spans").

One span call, two sinks: under a ``jax.profiler`` session the serve
window's and the fit loop's phases are events of ``/host:CPU`` with the
process tracer OFF; with the tracer on the Chrome file carries them as
well; with neither the program records nothing and syncs no more often.
"""

from __future__ import annotations

import glob
import json
import os

import jax
import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.models.transformer import gpt_decoder
from flexflow_tpu.obs import (
    CORE_COUNTERS,
    HealthMonitor,
    Tracer,
    configure,
    get_tracer,
    set_monitor,
    set_tracer,
)
from flexflow_tpu.serve import ServeEngine, TrafficSpec, synthetic_requests

SLOTS, SEQ, VOCAB = 4, 48, 31
SPEC = TrafficSpec(
    n_requests=5, seed=11, prompt_len=(4, 10), max_new=(3, 8), vocab=VOCAB,
)
SERVE_PHASES = {"ff.serve.prefill_dispatch", "ff.serve.decode_dispatch",
                "ff.serve.sync", "ff.serve.flush"}
B = 16


@pytest.fixture(autouse=True)
def _all_off():
    """Every test starts and ends with the disabled process tracer and
    monitor: either one left on by another test file of the same worker
    would put ``fit`` on its instrumented, per-step-sync path."""
    set_tracer(Tracer())
    set_monitor(HealthMonitor())
    yield
    set_tracer(Tracer())
    set_monitor(HealthMonitor())


@pytest.fixture(scope="module")
def gpt():
    m = FFModel(FFConfig(batch_size=SLOTS))
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, hidden=32, heads=4,
                ff_dim=64, num_layers=1, vocab=VOCAB)
    m.compile(seed=0)
    return m


def _engine(gpt, **kw):
    return ServeEngine(gpt, slots=SLOTS, block_size=8, sync_every=4, **kw)


def _mlp():
    model = FFModel(FFConfig(batch_size=B))
    t = model.create_tensor((B, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    t = model.dense(t, 10, name="fc2")
    model.softmax(t, name="probs")
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY], seed=0,
    )
    return model


def _data(n=128):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, 32)).astype(np.float32),
            rng.integers(0, 10, size=(n, 1)).astype(np.int32))


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session (host annotations only: the
    Python-frame tracer is off, it is slow and not what is read here);
    returns ``[(name, start_ns, end_ns)]`` of the ``ff.`` events."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = str(tmp_path / "prof")
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    evs = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for line in host.lines for e in line.events if e.name.startswith("ff.")
    ]
    return sorted(evs, key=lambda e: (e[1], -e[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


# ------------------------------------------------ (a) the profiler's sink
def test_serve_window_phases_land_in_the_profilers_trace(gpt, tmp_path):
    eng = _engine(gpt)
    eng.run(synthetic_requests(SPEC))  # warm: nothing compiles below
    evs = _profiled(tmp_path, lambda: eng.run(synthetic_requests(SPEC)))
    assert not get_tracer().enabled and get_tracer().events == []
    names = {e[0] for e in evs}
    assert SERVE_PHASES | {"ff.serve.admit", "ff.serve.window",
                           "ff.input.batch_wait", "ff.input.h2d_place"} <= names
    windows = [e for e in evs if e[0] == "ff.serve.window"]
    assert len(windows) == eng.windows
    admits = [e for e in evs if e[0] == "ff.serve.admit"]
    assert len(admits) >= len(windows)
    for w in windows:
        kids = [e for e in evs if e[0] in SERVE_PHASES and _inside(e, w)]
        assert [k[0] for k in kids].count("ff.serve.sync") == 1
        assert [k[0] for k in kids][-2:] == ["ff.serve.sync", "ff.serve.flush"]
        covered = sum(k[2] - k[1] for k in kids)
        assert covered >= 0.95 * (w[2] - w[1]), (covered, w)
        # admission is outside the window, not a phase of it
        assert not any(_inside(a, w) for a in admits)
    # every phase event lies inside some window; the prefetcher's two
    # spans nest under the prefill dispatch that stages through it
    for e in evs:
        if e[0] in SERVE_PHASES:
            assert any(_inside(e, w) for w in windows)
    pf = [e for e in evs if e[0] == "ff.serve.prefill_dispatch"]
    assert len(pf) == eng.prefill_dispatches
    for e in evs:
        if e[0].startswith("ff.input."):
            assert any(_inside(e, p) for p in pf)


def test_fit_loop_phases_land_in_the_profilers_trace(tmp_path):
    model, (x, y) = _mlp(), _data()
    model.fit(x, y, epochs=1, verbose=False)  # warm
    evs = _profiled(tmp_path, lambda: model.fit(
        x, y, epochs=2, verbose=False, metrics_sync_every=4))
    assert not get_tracer().enabled and get_tracer().events == []
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    steps = 2 * (len(x) // B)
    assert len(by["ff.fit"]) == 1 and len(by["ff.fit.epoch"]) == 2
    assert len(by["ff.fit.step_dispatch"]) == steps
    assert len(by["ff.input.h2d_place"]) == steps
    # one pull more an epoch: the one that finds the loader exhausted
    assert len(by["ff.input.batch_wait"]) == steps + 2
    assert len(by["ff.fit.metric_flush"]) == 4  # 8 batches / K=4, 2 epochs
    (fit,) = by["ff.fit"]
    assert all(_inside(ep, fit) for ep in by["ff.fit.epoch"])
    for name in ("ff.fit.step_dispatch", "ff.input.batch_wait",
                 "ff.input.h2d_place", "ff.fit.metric_flush"):
        for e in by[name]:
            assert any(_inside(e, ep) for ep in by["ff.fit.epoch"]), e
    # the input stage's spans close before the step is dispatched
    for d in by["ff.fit.step_dispatch"]:
        assert not any(_inside(e, d) for e in by["ff.input.h2d_place"])


# ------------------------------------------------ (b) nothing on, nothing added
def test_no_tracer_no_session_records_nothing_and_adds_no_sync(gpt):
    tracer = get_tracer()
    eng = _engine(gpt)
    rep = eng.run(synthetic_requests(SPEC))
    assert rep.host_syncs == rep.windows == eng.windows  # one sync a window
    model, (x, y) = _mlp(), _data()
    model.fit(x, y, epochs=2, verbose=False)
    assert model.executor.host_syncs == 2  # the two epoch-end flushes
    assert model.last_step_stats() is None  # the fast path ran
    assert get_tracer() is tracer
    assert tracer.events == [] and tracer.counters == {}
    assert tracer.summary()["spans"] == {}


def test_span_is_an_annotation_when_off_and_null_below_its_level():
    from flexflow_tpu.obs.trace import _NULL_SPAN

    off = Tracer()
    assert off.span("x", cat="fit", level="op") is _NULL_SPAN
    assert Tracer("step").span("x", cat="fit", level="op") is _NULL_SPAN
    sp = off.span("window", cat="serve", slot=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as s:
        s.set(tokens=5)  # what a site gets back keeps .set()
    assert off.events == []
    on = Tracer("op")
    with on.span("fit", cat="fit") as s:
        s.set(epochs=1)
        with on.span("batch", cat="fit", level="op"):
            pass
    assert [(e["name"], e["cat"]) for e in on.events] == [("batch", "fit"), ("fit", "fit")]
    assert on.events[1]["args"] == {"epochs": 1}


@pytest.mark.parametrize("name,cat,label", [
    ("window", "serve", "ff.serve.window"),
    ("batch_wait", "input", "ff.input.batch_wait"),
    ("fit", "fit", "ff.fit"),
    ("compile", "compile", "ff.compile"),
    ("epoch", "fit", "ff.fit.epoch"),
])
def test_annotation_names_are_the_contract(tmp_path, name, cat, label):
    def both():
        with Tracer().span(name, cat=cat):
            pass
        with Tracer("step").span(name, cat=cat):
            pass

    assert [e[0] for e in _profiled(tmp_path, both)] == [label, label]


# ------------------------------------------------ (c) the tracer's sink
def test_chrome_trace_keeps_its_spans_and_gains_the_new_ones(tmp_path):
    out = str(tmp_path / "trace.json")
    cfg_model = FFModel(FFConfig(batch_size=B, trace_out=out))
    t = cfg_model.create_tensor((B, 32), name="x")
    t = cfg_model.dense(t, 10, name="fc")
    cfg_model.softmax(t, name="probs")
    cfg_model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY], seed=0,
    )
    x, y = _data(64)
    ckpt = str(tmp_path / "ck")
    cfg_model.fit(x, y, epochs=1, verbose=False, metrics_sync_every=2,
                  checkpoint_every=2, checkpoint_path=ckpt)
    doc = json.load(open(out))
    spans = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "X":
            spans.setdefault((e["cat"], e["name"]), []).append(e)
    old = {("fit", "fit"), ("fit", "epoch"), ("step", "train_step"),
           ("step", "device_step"), ("compile", "jit_compile"), ("compile", "init_params")}
    new = {("fit", "step_dispatch"), ("fit", "metric_flush"), ("fit", "checkpoint_snapshot"),
           ("input", "batch_wait"), ("input", "h2d_place")}
    assert old | new <= set(spans)
    assert len(spans[("fit", "step_dispatch")]) == len(spans[("step", "train_step")]) == 4
    assert len(spans[("fit", "checkpoint_snapshot")]) == 2
    # same clock, same thread: the instrumented step lies inside its dispatch span
    for d, s in zip(spans[("fit", "step_dispatch")], spans[("step", "train_step")]):
        assert d["ts"] <= s["ts"] and s["ts"] + s["dur"] <= d["ts"] + d["dur"] + 1e-3
    # the compile listener marks the step program's build as an instant
    assert any(e.get("ph") == "i" and e["name"] == "compile" for e in doc["traceEvents"])


def test_serve_spans_reach_the_tracer_when_it_is_on(gpt):
    eng = _engine(gpt)
    eng.run(synthetic_requests(SPEC))
    tracer = configure(level="step")
    eng.run(synthetic_requests(SPEC))
    eng.drain()
    spans = tracer.summary()["spans"]
    assert spans["window"]["count"] == spans["sync"]["count"] == eng.windows
    assert spans["flush"]["count"] == eng.windows
    assert spans["admit"]["count"] >= eng.windows
    assert spans["drain"]["count"] == 1
    assert spans["prefill_dispatch"]["count"] == eng.prefill_dispatches
    assert all(spans[n]["cat"] == "serve" for n in ("window", "sync", "flush", "admit", "drain"))
    # the window-path counters nobody read are gone, the rest of the glossary stays
    counters = tracer.summary()["counters"]
    assert "serve.windows" not in counters and "serve.decode_steps" not in counters
    assert "serve.windows" not in CORE_COUNTERS and "executor.host_syncs" in counters


def test_open_loop_sleep_is_its_own_span(gpt, tmp_path):
    eng = _engine(gpt)
    eng.run(synthetic_requests(SPEC))
    late = synthetic_requests(TrafficSpec(
        n_requests=1, seed=3, prompt_len=(4, 6), max_new=(2, 3), vocab=VOCAB))
    late[0].arrival_s = 0.03
    evs = _profiled(tmp_path, lambda: eng.run(late))
    idle = [e for e in evs if e[0] == "ff.serve.idle"]
    windows = [e for e in evs if e[0] == "ff.serve.window"]
    assert idle and windows
    assert sum(e[2] - e[1] for e in idle) >= 0.02e9
    assert all(i[2] <= windows[0][1] for i in idle)  # traffic's wait, not the engine's


# ------------------------------------------------ (e) compiles
def test_a_compile_is_marked_and_a_cache_hit_is_not(tmp_path):
    get_tracer().span("warm", cat="compile")  # the first span registers the listener
    f = jax.jit(lambda a: a * 2 + 1)
    f(np.ones((3,), np.float32))

    def calls():
        f(np.ones((3,), np.float32))   # built above: no mark
        f(np.ones((5,), np.float32))   # a new shape compiles: one mark
        f(np.ones((5,), np.float32))

    evs = _profiled(tmp_path, calls)
    assert [e[0] for e in evs] == ["ff.compile"]
