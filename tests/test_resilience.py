"""Resilience subsystem tests (ISSUE 12, docs/RESILIENCE.md).

Covers the deterministic fault-injection substrate (seeded plans,
fire-once latching, the zero-overhead-when-off ledger pin), atomic
manifest checkpoints (SIGKILL torture, torn-file and digest-mismatch
refusal), kill-and-resume BIT-identity of the fit loop, elastic
recovery onto a shrunken mesh, the ``--health restore`` rewind, serve
drain/restart stream bit-identity, queue-deadline expiry, and the
coordinator connect retry loop.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import (  # noqa: E402
    ActiMode,
    AdamOptimizer,
    CheckpointError,
    FaultPlan,
    FFConfig,
    FFModel,
    LossType,
    MachineMesh,
    RecoveryPolicy,
    Tracer,
    get_fault_plan,
    set_fault_plan,
)
from flexflow_tpu.model import (  # noqa: E402
    _checkpoint_digest,
    _write_checkpoint_atomic,
)
from flexflow_tpu.obs import (  # noqa: E402
    HealthMonitor,
    configure,
    set_monitor,
    set_tracer,
)
from flexflow_tpu.runtime.faults import FaultEvent, InjectedFault  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, D, C = 16, 16, 8
N = B * 4  # 4 batches per epoch


@pytest.fixture(autouse=True)
def _reset_globals():
    """Fault plan, monitor, and tracer are process-wide singletons —
    restore the disabled defaults after every test so an installed plan
    never tortures a neighbour test."""
    yield
    set_fault_plan(None)
    set_monitor(HealthMonitor())
    set_tracer(Tracer())


def _build(mesh=None, **cfg_kw):
    cfg = FFConfig(batch_size=B, learning_rate=0.05, **cfg_kw)
    model = FFModel(cfg)
    t = model.create_tensor((B, D))
    t = model.dense(t, 32, ActiMode.RELU)
    t = model.dense(t, C)
    model.softmax(t)
    model.compile(
        optimizer=AdamOptimizer(alpha=1e-2),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        mesh=mesh or MachineMesh((1, 1), ("data", "model")),
        seed=0,
    )
    return model


def _data(n=N):
    rng = np.random.default_rng(0)
    return (
        rng.normal(size=(n, D)).astype(np.float32),
        rng.integers(0, C, size=(n, 1)).astype(np.int32),
    )


def _flat_weights(model):
    return {
        f"{ln}/{wn}": w
        for ln, ws in model.get_weights().items()
        for wn, w in ws.items()
    }


def _assert_bit_identical(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ------------------------------------------------------------ fault plans
def test_fault_plan_parse_deterministic():
    """Same (spec, seed) -> same resolved steps, including the random
    ``@~lo-hi`` form; the identity string is stable too."""
    spec = "device_loss@~10-90,serve:sigterm@3,loader_stall@5:0.2"
    p1 = FaultPlan.parse(spec, seed=7)
    p2 = FaultPlan.parse(spec, seed=7)
    assert [(e.site, e.kind, e.step, e.arg) for e in p1.events] == [
        (e.site, e.kind, e.step, e.arg) for e in p2.events
    ]
    assert p1.identity == p2.identity
    loss = next(e for e in p1.events if e.kind == "device_loss")
    assert 10 <= loss.step <= 90
    stall = next(e for e in p1.events if e.kind == "loader_stall")
    assert stall.arg == 0.2


def test_fault_plan_fires_exactly_once():
    """The fired latch: a restored run replays step N without replaying
    the fault (otherwise recovery would re-kill itself forever)."""
    plan = FaultPlan([FaultEvent(kind="device_loss", step=3)])

    class _Ex:
        _step_count = 5  # already past the fault step

    with pytest.raises(InjectedFault) as ei:
        plan.on_train_step(_Ex())
    assert ei.value.kind == "device_loss" and ei.value.step == 3
    plan.on_train_step(_Ex())  # latched: no second injection


def test_fault_plan_file_round_trip(tmp_path):
    path = str(tmp_path / "plan.json")
    with open(path, "w") as f:
        json.dump({"seed": 3, "spec": "fit:device_loss@~5-9"}, f)
    p1 = FaultPlan.from_file(path)
    p2 = FaultPlan.from_file(path)
    assert p1.events[0].step == p2.events[0].step
    assert 5 <= p1.events[0].step <= 9


def test_fault_plan_rejects_bad_grammar():
    with pytest.raises(ValueError, match="lacks '@step'"):
        FaultPlan.parse("device_loss")
    with pytest.raises(AssertionError, match="unknown fault kind"):
        FaultPlan.parse("meteor_strike@3")
    with pytest.raises(ValueError, match="nan_grads"):
        FaultPlan.parse("serve:nan_grads@3")


def test_resilience_flags_parse():
    cfg = FFConfig()
    rest = cfg.parse_args([
        "--fault-plan", "fit:device_loss@6",
        "--checkpoint-every", "2",
        "--checkpoint-path", "/tmp/ck.npz",
        "--resume", "/tmp/old.npz",
        "--max-restores", "3",
        "--coordinator-retries", "4",
        "--coordinator-backoff-s", "0.5",
        "--serve-watchdog-s", "1.5",
        "--serve-shed-windows", "8",
        "--serve-drain-file", "/tmp/drain.npz",
        "leftover",
    ])
    assert cfg.fault_plan == "fit:device_loss@6"
    assert cfg.checkpoint_every == 2
    assert cfg.checkpoint_path == "/tmp/ck.npz"
    assert cfg.resume_from == "/tmp/old.npz"
    assert cfg.max_restores == 3
    assert cfg.coordinator_retries == 4
    assert cfg.coordinator_backoff_s == 0.5
    assert cfg.serve_watchdog_s == 1.5
    assert cfg.serve_shed_windows == 8
    assert cfg.serve_drain_file == "/tmp/drain.npz"
    assert rest == ["leftover"]


def test_zero_overhead_when_faults_off():
    """Ledger pin (the disabled-tracer pattern): with no plan installed
    the fault hook must not add a single host sync — a 2-epoch fit still
    performs exactly the two epoch-end flushes."""
    assert get_fault_plan() is None
    x, y = _data(128)  # 8 batches/epoch, default K > 8
    m = _build()
    m.fit(x, y, epochs=2, verbose=False)
    assert m.executor.host_syncs == 2


# ----------------------------------------------------- atomic checkpoints
def test_atomic_checkpoint_writes_and_loads(tmp_path):
    x, y = _data()
    m = _build()
    m.executor.train_step([x[:B]], y[:B])
    path = m.save_checkpoint(str(tmp_path / "ck"))
    assert path.endswith(".npz") and os.path.exists(path)
    # no temp residue after a clean write
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
    m2 = _build()
    manifest = m2.load_checkpoint(path)
    assert manifest["schema"] == "ffckpt/2"
    assert manifest["step"] == 1
    assert manifest["digest"].startswith("sha256:")
    _assert_bit_identical(_flat_weights(m), _flat_weights(m2))


def test_torn_checkpoint_refused(tmp_path):
    x, y = _data()
    m = _build()
    m.executor.train_step([x[:B]], y[:B])
    path = m.save_checkpoint(str(tmp_path / "ck"))
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])  # the torn tail of a dead writer
    with pytest.raises(CheckpointError, match="torn or truncated"):
        _build().load_checkpoint(path)


def test_digest_mismatch_refused(tmp_path):
    """A structurally valid npz whose bytes drifted from the manifest
    digest (bit rot, a partial copy) must refuse to load, naming both
    digests — never silently feed corrupt weights into training."""
    x, y = _data()
    m = _build()
    m.executor.train_step([x[:B]], y[:B])
    path = m.save_checkpoint(str(tmp_path / "ck"))
    with np.load(path, allow_pickle=False) as z:
        flat = {k: z[k] for k in z.files}
    key = next(k for k in flat if k.startswith("params/"))
    flat[key] = flat[key] + 1.0  # corrupt one tensor, keep the manifest
    with open(path, "wb") as f:
        np.savez(f, **flat)
    with pytest.raises(CheckpointError, match="sha256:"):
        _build().load_checkpoint(path)


def test_sigkill_mid_write_never_leaves_torn_file(tmp_path):
    """Kill torture: a writer process SIGKILLed while rewriting the same
    checkpoint in a tight loop must leave a COMPLETE file — the atomic
    temp+fsync+replace means a reader sees the previous or the next
    checkpoint, never a torn one."""
    path = str(tmp_path / "ck.npz")
    script = (
        "import sys\n"
        "import numpy as np\n"
        "from flexflow_tpu.model import _write_checkpoint_atomic\n"
        "path = sys.argv[1]\n"
        "rng = np.random.default_rng(0)\n"
        "i = 0\n"
        "while True:\n"
        "    flat = {f'params/l{j}/w':"
        " rng.normal(size=(128, 128)).astype(np.float32)"
        " for j in range(4)}\n"
        "    flat['meta/step_count'] = np.asarray(i)\n"
        "    _write_checkpoint_atomic("
        "path, flat, {'schema': 'ffckpt/2', 'step': i})\n"
        "    i += 1\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", script, path], cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.time() + 120
        while not os.path.exists(path):  # wait out the jax import
            assert proc.poll() is None, "writer died before first write"
            assert time.time() < deadline, "writer never produced a file"
            time.sleep(0.05)
        time.sleep(0.2)  # let it into the rewrite loop, then kill mid-write
        proc.kill()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    # the surviving file must verify end to end: parseable npz, manifest
    # present, content digest matching the payload bytes
    with np.load(path, allow_pickle=False) as z:
        flat = {k: z[k] for k in z.files}
    manifest = json.loads(bytes(flat.pop("meta/manifest")).decode())
    assert manifest["schema"] == "ffckpt/2"
    assert _checkpoint_digest(flat) == manifest["digest"]


# ------------------------------------------------------ kill-and-resume
def test_kill_and_resume_bit_identical(tmp_path):
    """THE acceptance pin: a run killed by an injected device loss and
    resumed from its last checkpoint ends BIT-identical to the
    uninterrupted run — weights, optimizer state, step count, and the
    shuffled data order all replay exactly."""
    x, y = _data()
    ck = str(tmp_path / "ck.npz")

    ref = _build()
    ref.fit(x, y, epochs=2, shuffle=True, verbose=False)

    set_fault_plan(FaultPlan.parse("fit:device_loss@6", seed=0))
    killed = _build()
    with pytest.raises(InjectedFault):
        killed.fit(
            x, y, epochs=2, shuffle=True, verbose=False,
            checkpoint_every=1, checkpoint_path=ck,
        )
    set_fault_plan(None)

    resumed = _build()  # fresh process-equivalent: fresh init, then load
    resumed.fit(x, y, epochs=2, shuffle=True, verbose=False, resume=ck)
    assert resumed.executor._step_count == ref.executor._step_count == 8
    _assert_bit_identical(_flat_weights(ref), _flat_weights(resumed))


@pytest.mark.parametrize("kill_at", [2, 6])
def test_kill_and_resume_unshuffled_bit_identical(tmp_path, kill_at):
    """The same pin for an unshuffled epoch, whose batches are views of
    the dataset: killed mid-epoch (the first epoch's third batch, or the
    second epoch's), the resumed run lands on the batch the cursor names
    and ends bit-identical."""
    x, y = _data()
    ck = str(tmp_path / "ck.npz")
    ref = _build()
    ref.fit(x, y, epochs=2, verbose=False)

    set_fault_plan(FaultPlan.parse(f"fit:device_loss@{kill_at}", seed=0))
    killed = _build()
    with pytest.raises(InjectedFault):
        killed.fit(
            x, y, epochs=2, verbose=False,
            checkpoint_every=1, checkpoint_path=ck,
        )
    set_fault_plan(None)
    cursor = _build().load_checkpoint(ck)["loader"]
    assert 0 < cursor["batch"] < N // B and not cursor["shuffle"]

    resumed = _build()
    stepped = []
    step = resumed.executor.train_step

    def spy(inputs, labels):
        stepped.append(np.asarray(labels))
        return step(inputs, labels)

    resumed.executor.train_step = spy
    resumed.fit(x, y, epochs=2, verbose=False, resume=ck)
    first = cursor["batch"] * B
    np.testing.assert_array_equal(stepped[0].reshape(-1), y[first:first + B, 0])
    assert resumed.executor._step_count == ref.executor._step_count == 8
    _assert_bit_identical(_flat_weights(ref), _flat_weights(resumed))


def test_resume_refuses_mismatched_data_order(tmp_path):
    """The manifest cursor is only valid for the original data order —
    resuming with a different shuffle seed must refuse truthfully, not
    silently diverge."""
    x, y = _data()
    ck = str(tmp_path / "ck.npz")
    m = _build()
    set_fault_plan(FaultPlan.parse("fit:device_loss@6", seed=0))
    with pytest.raises(InjectedFault):
        m.fit(
            x, y, epochs=2, shuffle=True, verbose=False,
            checkpoint_every=1, checkpoint_path=ck,
        )
    set_fault_plan(None)
    with pytest.raises(CheckpointError, match="data\\s+order would diverge"):
        _build().fit(
            x, y, epochs=2, shuffle=True, seed=1, verbose=False, resume=ck
        )
    with pytest.raises(CheckpointError, match="batches/epoch"):
        _build().fit(
            x[: B * 2], y[: B * 2], epochs=2, shuffle=True, verbose=False,
            resume=ck,
        )


# ------------------------------------------------------ elastic recovery
def test_elastic_recovery_shrinks_mesh_and_continues(tmp_path):
    """The 2-slice golden: a device loss on a (2, 4) mesh shrinks to the
    surviving (1, 4), re-resolves the strategy, restores the last
    checkpoint, and finishes the run — with ``health.restores`` and
    ``recovery_s`` observable in the trace summary."""
    tracer = configure(level="step")
    x, y = _data()
    ck = str(tmp_path / "ck.npz")
    set_fault_plan(FaultPlan.parse("fit:device_loss@3", seed=0))
    m = _build(mesh=MachineMesh((2, 4), ("data", "model")))
    policy = RecoveryPolicy(max_recoveries=1)
    pm = m.fit(
        x, y, epochs=2, verbose=False,
        checkpoint_every=1, checkpoint_path=ck, recovery=policy,
    )
    assert policy.recoveries == 1
    assert policy.last_recovery_s > 0
    assert tuple(m.strategy.mesh.shape) == (1, 4)
    assert pm.train_all > 0
    summary = tracer.summary()
    assert summary["counters"]["health.restores"] == 1.0
    assert summary["samples"]["recovery_s"]["last"] > 0
    # steps 1-2 committed, the faulted batch is skipped (its data is
    # replayed only on a cursor-based resume), and the restored run
    # finishes the remaining 5 batches on the surviving mesh
    assert m.executor._step_count == 7


def test_recovery_budget_spent_reraises():
    policy = RecoveryPolicy(max_recoveries=0)
    m = _build()
    err = InjectedFault("device_loss", 1, "fit")
    assert policy.matches(err)
    assert policy.matches(RuntimeError("DATA TRANSFER FAILED on slice 1"))
    assert not policy.matches(RuntimeError("shape mismatch"))
    with pytest.raises(RuntimeError, match="recovery budget spent"):
        policy.recover(m, err)


def test_health_restore_rewinds_past_poison(tmp_path):
    """``--health restore``: an injected NaN weight poisoning trips the
    monitor, fit rewinds to the last good checkpoint, skips the poison
    batch, and completes with finite loss."""
    x, y = _data()
    ck = str(tmp_path / "ck.npz")
    set_fault_plan(FaultPlan.parse("fit:nan_grads@3", seed=0))
    m = _build(
        health="restore", health_dir=str(tmp_path / "bundles"),
        max_restores=2,
    )
    pm = m.fit(
        x, y, epochs=2, verbose=False,
        checkpoint_every=1, checkpoint_path=ck,
    )
    assert pm.train_all > 0
    x0, _ = _data()
    out = np.asarray(m.eval_batch([x0[:B]]))
    assert np.isfinite(out).all(), "restore left poisoned weights behind"


def test_health_restore_budget_exhausted_raises(tmp_path):
    """With ``--max-restores 0`` the same poisoning surfaces as the
    HealthError it is — restore never becomes an infinite retry loop."""
    from flexflow_tpu.obs import HealthError

    x, y = _data()
    ck = str(tmp_path / "ck.npz")
    set_fault_plan(FaultPlan.parse("fit:nan_grads@3", seed=0))
    m = _build(
        health="restore", health_dir=str(tmp_path / "bundles"),
        max_restores=0,
    )
    with pytest.raises(HealthError):
        m.fit(
            x, y, epochs=2, verbose=False,
            checkpoint_every=1, checkpoint_path=ck,
        )


# --------------------------------------------------- coordinator retries
def test_coordinator_retry_backoff_then_success(monkeypatch):
    import flexflow_tpu.runtime.distributed as dist

    calls, sleeps = [], []

    def fake_init(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise RuntimeError("connection refused: coordinator not up")

    monkeypatch.setattr(dist.jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(dist.time, "sleep", sleeps.append)
    monkeypatch.setattr(dist, "_initialized", False)
    dist.initialize_distributed(
        "host:1234", 2, 0, retries=3, backoff_s=0.5,
    )
    assert len(calls) == 3
    assert sleeps == [0.5, 1.0]  # exponential: backoff_s * 2**attempt
    monkeypatch.setattr(dist, "_initialized", False)


def test_coordinator_retry_exhausted_lists_attempts(monkeypatch):
    import flexflow_tpu.runtime.distributed as dist

    def fake_init(**kw):
        raise RuntimeError("deadline exceeded waiting for coordinator")

    monkeypatch.setattr(dist.jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(dist.time, "sleep", lambda s: None)
    monkeypatch.setattr(dist, "_initialized", False)
    with pytest.raises(RuntimeError) as ei:
        dist.initialize_distributed(
            "host:1234", 2, 0, retries=2, backoff_s=0.01,
        )
    msg = str(ei.value)
    assert "after 3 attempt(s)" in msg
    assert "--coordinator-retries 2" in msg
    assert "attempt 1:" in msg and "attempt 3:" in msg


def test_coordinator_non_transient_error_raises_immediately(monkeypatch):
    import flexflow_tpu.runtime.distributed as dist

    calls = []

    def fake_init(**kw):
        calls.append(kw)
        raise RuntimeError("protocol version mismatch")

    monkeypatch.setattr(dist.jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(dist, "_initialized", False)
    with pytest.raises(RuntimeError, match="protocol version mismatch"):
        dist.initialize_distributed(
            "host:1234", 2, 0, retries=5, backoff_s=0.01,
        )
    assert len(calls) == 1  # retrying a deterministic failure hides it


# ---------------------------------------------------------- serve side
SLOTS, SEQ, VOCAB = 4, 48, 31


@pytest.fixture(scope="module")
def serve_model():
    from flexflow_tpu.models.transformer import gpt_decoder

    cfg = FFConfig(batch_size=SLOTS)
    m = FFModel(cfg)
    gpt_decoder(
        m, SLOTS, SEQ, hidden=32, heads=4, ff_dim=64, num_layers=2,
        vocab=VOCAB, use_flash=False,
    )
    m.compile(seed=0)
    return m


def _mk_requests(n=6, seed=0):
    from flexflow_tpu.serve import Request

    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(4, 10))
        out.append(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int64),
            max_new_tokens=8 + int(rng.integers(0, 6)),
            id=i,
        ))
    return out


def test_serve_drain_restart_bit_identical(serve_model, tmp_path):
    """SIGTERM drain acceptance: an injected SIGTERM mid-run spills
    in-flight slots to an ffdrain/1 file; a fresh engine restores it and
    finishes — and every request's combined token stream is BIT-identical
    to an undrained run's."""
    from flexflow_tpu.serve import RequestState, ServeEngine
    from flexflow_tpu.serve.engine import load_drain

    base_eng = ServeEngine(serve_model, slots=SLOTS, block_size=8,
                           sync_every=4)
    base = _mk_requests()
    base_eng.run(base)
    want = {r.id: list(r.tokens) for r in base}
    assert all(r.state is RequestState.FINISHED for r in base)

    drain_file = str(tmp_path / "drain.npz")
    set_fault_plan(FaultPlan.parse("serve:sigterm@2", seed=0))
    eng2 = ServeEngine(serve_model, slots=SLOTS, block_size=8,
                       sync_every=4, drain_path=drain_file)
    reqs = _mk_requests()
    rep2 = eng2.run(reqs)
    set_fault_plan(None)
    assert eng2.drained and rep2.drained
    assert os.path.exists(drain_file)

    eng3 = ServeEngine(serve_model, slots=SLOTS, block_size=8,
                       sync_every=4)
    restored = eng3.resume_from_drain(load_drain(drain_file))
    assert restored, "sigterm@2 should leave unfinished work to restore"
    eng3.run()

    got = {r.id: list(r.tokens) for r in reqs
           if r.state is RequestState.FINISHED}
    got.update({r.id: list(r.tokens) for r in restored})
    assert got == want, "drain/restart changed a token stream"


def test_drain_file_torn_refused(serve_model, tmp_path):
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.serve.engine import load_drain, save_drain

    eng = ServeEngine(serve_model, slots=SLOTS, block_size=8, sync_every=4)
    reqs = _mk_requests(3)
    for r in reqs:
        eng.sched.submit(r)
    payload = eng.drain()
    path = save_drain(str(tmp_path / "d.npz"), payload)
    back = load_drain(path)
    assert [d["id"] for d in back["requests"]] == [r.id for r in reqs]
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(CheckpointError, match="torn or truncated"):
        load_drain(path)


def test_deadline_expiry_counted():
    """A request queued past its deadline_ms is rejected with a truthful
    reason and counted — in the scheduler and per tenant."""
    from flexflow_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedKVCache,
        Request,
        RequestState,
    )

    kv = PagedKVCache(2, 4, 8, slots=2, block_size=8, max_seq_len=64)
    sched = ContinuousBatchingScheduler(2, kv)
    r1 = sched.submit(Request(prompt=np.arange(4), max_new_tokens=8), now=0.0)
    r2 = sched.submit(Request(prompt=np.arange(4), max_new_tokens=8), now=0.0)
    r3 = sched.submit(
        Request(prompt=np.arange(4), max_new_tokens=8, deadline_ms=5.0),
        now=0.0,
    )
    admitted = sched.admit(now=0.0)
    assert any(r is r1 for r in admitted)
    assert any(r is r2 for r in admitted)
    assert sched.admit(now=1.0) == []  # 1000 ms queued > 5 ms deadline
    assert r3.state is RequestState.REJECTED
    assert "deadline 5 ms exceeded" in r3.finish_reason
    assert sched.expired == 1
    assert sched.tenant_summary()["default"]["expired"] == 1


def test_shed_batch_queue_rejects_truthfully():
    from flexflow_tpu.serve import (
        ContinuousBatchingScheduler,
        PagedKVCache,
        Request,
        RequestState,
    )

    kv = PagedKVCache(2, 4, 8, slots=2, block_size=8, max_seq_len=64)
    sched = ContinuousBatchingScheduler(2, kv)
    reqs = [
        sched.submit(Request(prompt=np.arange(4), max_new_tokens=8, id=i))
        for i in range(2)
    ]
    n = sched.shed_batch_queue(0.0, "slo pressure")
    assert n == 2 and sched.shed == 2
    for r in reqs:
        assert r.state is RequestState.REJECTED
        assert "shed" in r.finish_reason and "slo pressure" in r.finish_reason


def test_serve_watchdog_fires_on_slow_windows(serve_model):
    """An absurdly tight watchdog budget flags every window — the
    counter lands in the report (a real deploy alerts on it)."""
    from flexflow_tpu.serve import ServeEngine

    eng = ServeEngine(serve_model, slots=SLOTS, block_size=8,
                      sync_every=4, watchdog_s=1e-9)
    rep = eng.run(_mk_requests(4))
    assert rep.watchdog_fires > 0
    assert rep.watchdog_fires <= rep.windows
