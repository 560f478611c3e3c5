"""Set-up traced inside the program (ISSUE 37, docs/OBSERVABILITY.md
"Set-up: ff.setup spans and the tally").

``ff.setup.model`` is the whole of ``FFModel.compile``,
``ff.setup.step_program`` the step program's first build and compile (or
cache load), ``ff.setup.engine`` the whole of ``ServeEngine.__init__``
with ``serve_programs`` and ``warmup`` inside.  They are timed at every
tracer level into a process-wide tally that ``set_tracer`` does not
replace, and jax's own trace / lowering / compile / persistent-cache
events are counted under the innermost one.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.models.transformer import gpt_decoder
from flexflow_tpu.obs import (
    HealthMonitor,
    Tracer,
    configure,
    get_tracer,
    set_monitor,
    set_tracer,
    setup_summary,
)
from flexflow_tpu.obs import trace as trace_mod
from flexflow_tpu.serve import ServeEngine, TrafficSpec, synthetic_requests

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, SEQ, VOCAB, B = 4, 48, 31, 16
SPEC = TrafficSpec(n_requests=4, seed=5, prompt_len=(4, 10), max_new=(3, 6), vocab=VOCAB)


@pytest.fixture(autouse=True)
def _fresh():
    """A fresh tally a test (the process's is shared by every test of a
    worker) and the disabled tracer and monitor around it."""
    saved = trace_mod._SETUP
    trace_mod._SETUP = trace_mod._SetupTally()
    set_tracer(Tracer())
    set_monitor(HealthMonitor())
    yield
    trace_mod._SETUP = saved
    set_tracer(Tracer())
    set_monitor(HealthMonitor())


def _mlp():
    model = FFModel(FFConfig(batch_size=B))
    t = model.create_tensor((B, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    model.softmax(model.dense(t, 10, name="fc2"), name="probs")
    model.compile(optimizer=SGDOptimizer(lr=0.01), seed=0)
    return model


def _data(n=64):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, 32)).astype(np.float32),
            rng.integers(0, 10, size=(n, 1)).astype(np.int32))


def _gpt():
    m = FFModel(FFConfig(batch_size=SLOTS))
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, hidden=32, heads=4,
                ff_dim=64, num_layers=1, vocab=VOCAB)
    m.compile(seed=0)
    return m


def _profiled(tmp_path, fn):
    """``[(name, start_ns, end_ns)]`` of the ``ff.`` host events of a
    profiler session around ``fn`` (as ``test_profiler_spans.py``)."""
    import glob

    import jax
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


def _weights(model) -> int:
    return sum(len(ws) for ws in model.executor._wspecs.values())


# ------------------------------------------------ the spans, tracer off
def test_setup_spans_nest_under_the_profiler_with_the_tracer_off(tmp_path):
    def setup():
        model = _mlp()
        x, y = _data()
        model.fit(x, y, epochs=1, verbose=False)
        ServeEngine(_gpt(), slots=SLOTS, block_size=8)

    evs = _profiled(tmp_path, setup)
    assert not get_tracer().enabled and get_tracer().events == []
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    models = by["ff.setup.model"]
    assert len(models) == 2  # the MLP's compile, the decoder's
    for e in by["ff.compile.init_params"]:
        assert any(_inside(e, m) for m in models)
    (step,) = by["ff.setup.step_program"]
    assert not any(_inside(step, m) for m in models)
    (engine,) = by["ff.setup.engine"]
    (progs,) = by["ff.setup.serve_programs"]
    (warm,) = by["ff.setup.warmup"]
    assert _inside(progs, engine) and _inside(warm, engine)
    assert progs[2] <= warm[1]  # built, then warmed
    s = setup_summary()
    assert {n: v["count"] for n, v in s["spans"].items()} == {
        "model": 2, "step_program": 1, "engine": 1, "serve_programs": 1, "warmup": 1,
    }
    outer = sum(e[2] - e[1] for e in models + [step, engine]) / 1e9
    assert s["outer_s"] == pytest.approx(outer, rel=0.05, abs=2e-3)


def test_init_programs_are_counted_under_the_models_compile():
    model = _mlp()
    s = setup_summary()
    init = s["programs"]["model"]["init_fn"]
    # one jitted init program a weight: traced, lowered and compiled each
    assert init["lowerings"] == init["compiles"] == _weights(model) == 4
    assert init["traces"] == 4 and init["lower_s"] > 0 and init["compile_s"] > 0
    # no persistent cache in the suite (conftest): neither hit nor miss
    assert init["cache_hits"] == init["cache_misses"] == 0
    assert s["spans"]["model"]["count"] == 1 and "step_program" not in s["spans"]


def test_the_step_program_is_counted_on_both_step_paths():
    x, y = _data()
    fast = _mlp()
    fast.fit(x, y, epochs=1, verbose=False)
    s = setup_summary()
    assert s["spans"]["step_program"]["count"] == 1
    assert s["programs"]["step_program"]["step"]["lowerings"] == 1
    tracer = configure(level="step")
    traced = _mlp()
    traced.executor.train_step([x[:B]], y[:B])
    s = setup_summary()
    assert s["spans"]["step_program"]["count"] == 2
    assert s["programs"]["step_program"]["step"]["compiles"] == 2
    # with the tracer on the same spans are Chrome events and summary rows
    spans = tracer.summary()["spans"]
    assert spans["step_program"]["cat"] == spans["model"]["cat"] == "setup"
    assert spans["step_program"]["count"] == 1
    names = [e["name"] for e in tracer.events if e.get("ph") == "X"]
    # the old spans stay inside the new one
    assert names.index("build_step") < names.index("jit_compile") < names.index("step_program")


# ------------------------------------------------ the tally is the process's
def test_the_tally_survives_a_new_tracer():
    configure(level="step")
    _mlp()
    before = setup_summary()
    set_tracer(Tracer())
    assert setup_summary() == before
    assert before["spans"]["model"]["count"] == 1


def test_serve_windows_and_fit_steps_add_nothing():
    model = _mlp()
    x, y = _data()
    model.fit(x, y, epochs=1, verbose=False)
    eng = ServeEngine(_gpt(), slots=SLOTS, block_size=8, sync_every=4)
    eng.run(synthetic_requests(SPEC))
    before = json.dumps(setup_summary(), sort_keys=True)
    # fit compiles a fresh metric accumulator an epoch: outside every
    # ff.setup span, so not counted
    model.fit(x, y, epochs=2, verbose=False)
    eng.run(synthetic_requests(SPEC))
    assert json.dumps(setup_summary(), sort_keys=True) == before


def test_a_trace_inside_a_trace_counts_once():
    import jax

    # lax primitives, not jnp's operators (which are jitted functions too)
    inner = jax.jit(lambda a: jax.lax.add(a, a))
    outer = jax.jit(lambda a: jax.lax.mul(inner(a), a))
    with get_tracer().span("probe", cat="setup"):
        outer(np.ones((3,), np.float32))
    progs = setup_summary()["programs"]["probe"]
    assert sum(p["traces"] for p in progs.values()) == 2
    assert sum(p["lowerings"] for p in progs.values()) == 1
    # the outer trace's seconds exclude the inner one's
    total = sum(p["trace_s"] for p in progs.values())
    assert total <= setup_summary()["spans"]["probe"]["seconds"]


# ------------------------------------------------ the persistent cache
_CHILD = """
import json
import numpy as np
from flexflow_tpu import ActiMode, FFConfig, FFModel
from flexflow_tpu.obs import setup_summary
m = FFModel(FFConfig(batch_size=8))
t = m.create_tensor((8, 16))
m.softmax(m.dense(m.dense(t, 32, ActiMode.RELU), 4))
m.compile(seed=0)
rng = np.random.default_rng(0)
m.fit(rng.normal(size=(16, 16)).astype(np.float32),
      rng.integers(0, 4, size=(16, 1)).astype(np.int32), epochs=1, verbose=False)
progs = [p for d in setup_summary()["programs"].values() for p in d.values()]
print("TALLY", json.dumps({k: sum(p[k] for p in progs)
                           for k in ("cache_hits", "cache_misses", "compiles")}))
"""


def _child_tally(env_extra, cwd):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=cwd,
                       timeout=600, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("TALLY ")]
    return json.loads(line[len("TALLY "):])


def test_a_cold_then_a_warm_persistent_cache(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    cold = _child_tally(env, str(tmp_path))
    assert cold["cache_misses"] >= 1 and cold["cache_hits"] == 0
    warm = _child_tally(env, str(tmp_path))
    assert warm["cache_hits"] >= 1 and warm["cache_misses"] == 0
    assert warm["compiles"] == cold["compiles"]  # a load is a compile's event


def test_no_listing_of_the_cache_directory_in_the_runtime():
    runtime = os.path.join(REPO, "flexflow_tpu", "runtime")
    for name in os.listdir(runtime):
        if name.endswith(".py"):
            with open(os.path.join(runtime, name)) as f:
                src = f.read()
            assert "_compile_cache_entries" not in src, name
            assert "compilation_cache_dir" not in src, name
