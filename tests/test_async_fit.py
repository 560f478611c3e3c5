"""Async training pipeline (ISSUE 4): device-side metric accumulation
with K-step host flush, the 3-stage input pipeline (loader producer
thread -> device placement look-ahead -> step), host-sync accounting,
windowed R17 observe, and the sync/async parity contract.

Acceptance pins:
  * epoch-end ``PerfMetrics`` parity — sync (K=1 float path) vs async
    (jitted device accumulator) — across the MLP and DLRM smoke models;
  * ``executor.host_syncs`` per epoch ≈ num_batches/K async and
    == num_batches sync, visible in the trace summary;
  * the all-off fast path issues ZERO per-step host syncs (counter-based
    zero-overhead guard, mirroring ``tests/test_health.py``'s);
  * the recompile trigger fires within K steps under windowed observe;
  * HealthMonitor NaN detection latency is unchanged (K forced to 1);
  * eval's padded tail rows never enter the metrics;
  * an unshuffled epoch is fed as views of the dataset, bit-identical to
    gathered rows; a shuffled one as owned copies from its loader.
"""

import math
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import (
    ActiMode,
    FFConfig,
    FFModel,
    LossType,
    MachineMesh,
    MetricsType,
    RecompileState,
    SGDOptimizer,
)
from flexflow_tpu.dataloader import (
    BatchIterator,
    DevicePrefetcher,
    SingleDataLoader,
)
from flexflow_tpu.metrics import DeviceMetricAccumulator, PerfMetrics
from flexflow_tpu.obs import (
    HealthError,
    HealthMonitor,
    Tracer,
    set_monitor,
    set_tracer,
)

B = 16


@pytest.fixture(autouse=True)
def _reset_obs():
    """Monitor and tracer are process-wide; restore the disabled defaults
    so an enabled one never leaks into the fast-path assertions."""
    yield
    set_monitor(HealthMonitor())
    set_tracer(Tracer())


def _mlp_model(mesh=None, **cfg_kw):
    cfg = FFConfig(batch_size=B, **cfg_kw)
    model = FFModel(cfg)
    t = model.create_tensor((B, 32), name="x")
    t = model.dense(t, 64, ActiMode.RELU, name="fc1")
    t = model.dense(t, 10, name="fc2")
    model.softmax(t, name="probs")
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[
            MetricsType.ACCURACY,
            MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
        ],
        mesh=mesh,
        seed=0,
    )
    return model


def _mlp_data(n=128, bad=False):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 32)).astype(np.float32)
    if bad:
        x[0, 0] = np.nan
    y = rng.integers(0, 10, size=(n, 1)).astype(np.int32)
    return x, y


def _dlrm_model():
    from flexflow_tpu.models.dlrm import dlrm

    cfg = FFConfig(batch_size=B)
    model = FFModel(cfg)
    dlrm(model, B, embedding_sizes=(64,) * 2, mlp_bot=(4, 16, 16),
         mlp_top=(16, 8, 2), sparse_feature_size=16)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[MetricsType.MEAN_SQUARED_ERROR],
        mesh=MachineMesh((1, 1), ("data", "model")),
        seed=0,
    )
    return model


def _dlrm_data(n=96):
    rng = np.random.default_rng(1)
    xs = [rng.integers(0, 64, size=(n, 1)).astype(np.int32) for _ in range(2)]
    xs.append(rng.normal(size=(n, 4)).astype(np.float32))
    y = rng.uniform(size=(n, 2)).astype(np.float32)
    return xs, y


def _pm_fields(pm: PerfMetrics):
    return {
        "train_all": pm.train_all,
        "train_correct": pm.train_correct,
        "cce": pm.cce_loss,
        "scce": pm.sparse_cce_loss,
        "mse": pm.mse_loss,
        "rmse": pm.rmse_loss,
        "mae": pm.mae_loss,
    }


# ------------------------------------------------ epoch-end metric parity
def test_perfmetrics_parity_sync_vs_async_mlp():
    """Sync (per-step float path) and async (jitted device accumulator)
    fits produce the same epoch-end PerfMetrics to float32 tolerance."""
    x, y = _mlp_data()
    pm_sync = _mlp_model().fit(x, y, epochs=2, verbose=False,
                               metrics_sync_every=1)
    pm_async = _mlp_model().fit(x, y, epochs=2, verbose=False,
                                metrics_sync_every=4)
    s, a = _pm_fields(pm_sync), _pm_fields(pm_async)
    assert s["train_all"] == a["train_all"] == 128
    assert s["train_correct"] == a["train_correct"]  # exact integer count
    for k in ("cce", "scce", "mse", "rmse", "mae"):
        assert a[k] == pytest.approx(s[k], rel=1e-5, abs=1e-5), k


def test_perfmetrics_parity_sync_vs_async_dlrm():
    xs, y = _dlrm_data()
    pm_sync = _dlrm_model().fit(xs, y, epochs=1, verbose=False,
                                metrics_sync_every=1)
    pm_async = _dlrm_model().fit(xs, y, epochs=1, verbose=False,
                                 metrics_sync_every=3)
    s, a = _pm_fields(pm_sync), _pm_fields(pm_async)
    assert s["train_all"] == a["train_all"] == 96
    assert a["mse"] == pytest.approx(s["mse"], rel=1e-5, abs=1e-5)


def test_device_metric_accumulator_math():
    """drain() returns Σ metric*rows and the row count; resets after."""
    import jax.numpy as jnp

    acc = DeviceMetricAccumulator()
    acc.add({"m": jnp.float32(2.0)}, 4)
    acc.add({"m": jnp.float32(3.0)}, 8)
    assert acc.count == 12
    sums, count = acc.drain()
    assert count == 12
    assert sums["m"] == pytest.approx(2.0 * 4 + 3.0 * 8)
    assert acc.count == 0 and acc.drain() == ({}, 0)


# ------------------------------------------------- host-sync accounting
def test_host_syncs_async_vs_sync_counts():
    """host_syncs per epoch == num_batches sync, ceil(num_batches/K)
    async (the acceptance cadence)."""
    x, y = _mlp_data(128)  # 8 batches/epoch
    m = _mlp_model()
    m.fit(x, y, epochs=2, verbose=False, metrics_sync_every=1)
    assert m.executor.host_syncs == 16  # 8 per epoch
    m2 = _mlp_model()
    m2.fit(x, y, epochs=2, verbose=False, metrics_sync_every=4)
    assert m2.executor.host_syncs == 4  # 2 per epoch
    m3 = _mlp_model()
    m3.fit(x, y, epochs=2, verbose=False, metrics_sync_every=3)
    assert m3.executor.host_syncs == 6  # ceil(8/3)=3 per epoch
    # stall ledger moved in sync mode
    assert m.executor.host_stall_s >= 0.0


def test_host_syncs_visible_in_trace_summary():
    from flexflow_tpu.obs import configure

    tracer = configure(level="step")
    x, y = _mlp_data(128)
    m = _mlp_model()
    m.fit(x, y, epochs=1, verbose=False, metrics_sync_every=4)
    counters = tracer.summary()["counters"]
    assert counters["executor.host_syncs"] == 2.0  # 8 batches / K=4
    assert counters["fit.metric_flushes"] == 2.0
    assert tracer.summary()["samples"]["fit.prefetch_depth"]["last"] >= 1


def test_zero_per_step_syncs_all_off():
    """Zero-overhead guard (counter-based, mirrors test_health.py's):
    with tracing/health/profiling all off and default K, a 2-epoch fit
    performs exactly one host sync per epoch — zero per step — and the
    executor records no per-step stats (no forced sync anywhere)."""
    x, y = _mlp_data(128)  # 8 batches/epoch, default K=32 > 8
    m = _mlp_model()
    pm = m.fit(x, y, epochs=2, verbose=False)
    assert m.executor.host_syncs == 2  # the two epoch-end flushes
    assert m.last_step_stats() is None  # fast path: no block_until_ready
    assert pm.train_all == 128
    # and the effective-K resolution is the documented auto default
    from flexflow_tpu.model import DEFAULT_METRICS_SYNC_EVERY

    assert m._resolve_metrics_sync_every(None) == DEFAULT_METRICS_SYNC_EVERY
    assert m._resolve_metrics_sync_every(7) == 7


# ----------------------------------------------- windowed R17 recompile
def test_recompile_trigger_fires_within_k_steps():
    """Under windowed observe the trigger still sees every iteration
    value (fires at its exact condition) and the recompile lands at the
    next flush — within K steps of the condition becoming true."""
    x, y = _mlp_data(128)  # 8 batches
    m = _mlp_model()
    seen_iters = []

    def trigger(rs):
        seen_iters.append(rs.iteration)
        return rs.iteration == 2 and rs.recompilations == 0

    rs = RecompileState(trigger, lambda model: None)
    m.fit(x, y, epochs=1, verbose=False, recompile_state=rs,
          metrics_sync_every=4)
    assert rs.recompilations == 1
    assert rs.iteration == 8  # every step observed
    assert 2 in seen_iters  # the exact condition iteration was evaluated
    assert rs.last_loss is not None and math.isfinite(rs.last_loss)


def test_recompile_immediate_when_sync():
    """K=1: the trigger fires on the very step its condition holds
    (reference per-iteration recompile_on_condition semantics)."""
    x, y = _mlp_data(64)
    m = _mlp_model()
    recompiled_at = []

    def trigger(rs):
        return rs.iteration == 2 and rs.recompilations == 0

    def alter(model):
        recompiled_at.append(True)

    rs = RecompileState(trigger, alter)
    m.fit(x, y, epochs=1, verbose=False, recompile_state=rs,
          metrics_sync_every=1)
    assert rs.recompilations == 1 and recompiled_at == [True]


# ----------------------------------------------------- health latency
def test_health_forces_sync_and_detects_nan_at_onset(tmp_path):
    """An enabled monitor forces effective K=1 (per-step observation is
    its purpose), so NaN detection latency under a requested K-step
    flush is unchanged: the raise fires at the onset step."""
    x, y = _mlp_data(64, bad=True)  # batch 0 poisoned -> NaN at step 0
    with pytest.raises(HealthError) as ei:
        _mlp_model(
            health="raise", metrics_sync_every=8,
            health_dir=str(tmp_path / "bundles"),
        ).fit(x, y, epochs=1, verbose=False)
    assert ei.value.step == 0  # detected immediately, not K steps later
    assert ei.value.reason == "non_finite_loss"


def test_health_monitor_forces_k1_resolution():
    m = _mlp_model(metrics_out="/dev/null", metrics_sync_every=16)
    assert m._resolve_metrics_sync_every(None) == 1
    assert m._resolve_metrics_sync_every(16) == 1


def test_profiling_forces_k1_and_reports_stall(capsys):
    m = _mlp_model(profiling=True)
    assert m._resolve_metrics_sync_every(None) == 1
    x, y = _mlp_data(32)
    m.fit(x, y, epochs=1, verbose=False)
    out = capsys.readouterr().out
    assert "stall" in out and "[profiling] step" in out
    stats = m.last_step_stats()
    assert stats is not None and stats["host_stall_s"] == stats["device_s"]


# ------------------------------------------------------------- eval
def test_eval_padded_tail_rows_never_enter_metrics():
    """n=40 with bs=16 pads the 8-row tail to 16; the padded duplicate
    rows must not contribute — pinned by exact agreement with a
    divisible batching of the same 40 rows, and by the row count."""
    x, y = _mlp_data(40)
    m = _mlp_model()
    pm_pad = m.eval(x, y, batch_size=16)  # 16+16+8(+8 pad)
    pm_div = m.eval(x, y, batch_size=8)  # divisible: no padding at all
    assert pm_pad.train_all == pm_div.train_all == 40
    assert pm_pad.train_correct == pm_div.train_correct
    assert pm_pad.accuracy == pytest.approx(pm_div.accuracy)
    assert pm_pad.sparse_cce_loss == pytest.approx(
        pm_div.sparse_cce_loss, rel=1e-5
    )


def test_eval_single_host_sync():
    x, y = _mlp_data(64)
    m = _mlp_model()
    base = m.executor.host_syncs
    m.eval(x, y, batch_size=16)
    assert m.executor.host_syncs == base + 1  # one drain for the whole pass


# ---------------------------------------------------- input pipeline
def _aligned_loaders(n, bs, shuffle, seed=7):
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    y = np.arange(n, dtype=np.int64).reshape(n, 1)
    return [
        SingleDataLoader(x, bs, None, None, shuffle=shuffle, seed=seed),
        SingleDataLoader(y, bs, None, None, shuffle=shuffle, seed=seed),
    ]


def test_python_prefetch_order_parity_with_unprefetched():
    """The producer thread yields EXACTLY the batches the inline path
    yields, shuffled or not, across epochs."""
    for shuffle in (False, True):
        plain = BatchIterator(_aligned_loaders(128, 16, shuffle))
        pre = BatchIterator(_aligned_loaders(128, 16, shuffle),
                            prefetch_depth=3)
        for _epoch in range(2):
            plain.reset()
            pre.reset()
            a, b = list(plain), list(pre)
            assert len(a) == len(b) == 8
            for (ax, ay), (bx, by) in zip(a, b):
                np.testing.assert_array_equal(ax, bx)
                np.testing.assert_array_equal(ay, by)


def test_python_prefetch_shuffle_contract_matches_native():
    """Same semantic contract as native/ffdl.cc: the epoch order is a
    permutation, rows stay aligned across arrays, epochs reshuffle, and
    the same seed reproduces — pinned here for the pure-Python producer
    (and in test_native_loader.py for the C++ ring)."""
    it = BatchIterator(_aligned_loaders(128, 16, True), prefetch_depth=2)
    it.reset()
    first = [(bx.copy(), by.copy()) for bx, by in it]
    all_x = np.concatenate([bx for bx, _ in first]).ravel()
    all_y = np.concatenate([by for _, by in first]).ravel()
    np.testing.assert_array_equal(all_x.astype(np.int64), all_y)  # aligned
    np.testing.assert_array_equal(np.sort(all_y), np.arange(128))  # perm
    assert not np.array_equal(all_y, np.arange(128))  # actually shuffled
    it.reset()
    second = np.concatenate([by.copy() for _, by in it]).ravel()
    assert not np.array_equal(second, all_y)  # epochs reshuffle
    it2 = BatchIterator(_aligned_loaders(128, 16, True), prefetch_depth=2)
    it2.reset()
    again = np.concatenate([by.copy() for _, by in it2]).ravel()
    np.testing.assert_array_equal(again, all_y)  # seed-deterministic


def test_python_prefetch_clean_shutdown():
    """Abandoning the iterator mid-epoch stops and joins the producer —
    no thread leak, no hang on the bounded queue."""
    it = BatchIterator(_aligned_loaders(256, 8, False), prefetch_depth=2)
    it.reset()
    before = {t.ident for t in threading.enumerate()}
    gen = iter(it)
    next(gen)
    next(gen)
    gen.close()  # consumer walks away with the queue full
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = [
            t for t in threading.enumerate()
            if t.ident not in before and t.name == "ffdl-py-prefetch"
        ]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"producer thread leaked: {leaked}"


def test_python_prefetch_propagates_producer_errors():
    class Boom(SingleDataLoader):
        def next_batch(self, idx):
            if idx == 2:
                raise RuntimeError("loader exploded")
            return super().next_batch(idx)

    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    it = BatchIterator([Boom(x, 8, None, None)], prefetch_depth=2)
    with pytest.raises(RuntimeError, match="loader exploded"):
        list(it)


def test_device_prefetcher_places_ahead_and_preserves_order():
    placed = []

    def place(b):
        placed.append(b)
        return b * 10

    pf = DevicePrefetcher(iter([1, 2, 3, 4, 5]), place, depth=3)
    out = []
    for v in pf:
        # by the time batch i is yielded, placement ran ahead of it
        out.append((v, len(placed)))
    assert [v for v, _ in out] == [10, 20, 30, 40, 50]
    assert out[0][1] >= 3  # depth batches staged before the first yield


def test_fit_with_explicit_python_loader_prefetch_converges():
    """End-to-end: separable data through the full async pipeline
    (producer thread + placement look-ahead + K-flush) still learns."""
    rng = np.random.default_rng(0)
    n = 256
    centers = rng.normal(size=(4, 16)).astype(np.float32) * 3
    yl = rng.integers(0, 4, size=n)
    x = (centers[yl] + rng.normal(size=(n, 16))).astype(np.float32)
    yl = yl.astype(np.int32).reshape(n, 1)
    cfg = FFConfig(batch_size=32, epochs=3, learning_rate=0.05,
                   prefetch_depth=2)
    model = FFModel(cfg)
    t = model.create_tensor((32, 16))
    t = model.dense(t, 32, ActiMode.RELU)
    t = model.dense(t, 4)
    model.softmax(t)
    model.compile(optimizer=SGDOptimizer(lr=0.05),
                  loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[MetricsType.ACCURACY])
    pm = model.fit(x, yl, shuffle=True, verbose=False)
    assert pm.accuracy > 0.8
    assert model.executor.host_syncs == 3  # one flush per epoch (8 < K)


# ------------------------------------ unshuffled epochs fed as views
def _spy_placement(monkeypatch, model):
    """Record each batch ``fit`` hands to placement, and the names of the
    threads alive when it did."""
    fed, threads = [], set()
    place = model.executor.place_batch

    def spy(batch):
        fed.append(batch)
        threads.update(t.name for t in threading.enumerate())
        return place(batch)

    monkeypatch.setattr(model.executor, "place_batch", spy)
    return fed, threads


@pytest.mark.parametrize(
    "shuffle,native",
    [(False, True), (False, False), (True, True), (True, False)],
    ids=["views", "views-no-native-build", "native-ring", "python-producer"],
)
def test_fit_feeds_views_unshuffled_and_owned_copies_shuffled(
    monkeypatch, shuffle, native
):
    """An unshuffled epoch hands placement views of the caller's arrays
    and builds no native ring and no producer thread; a shuffled one
    hands owned copies from the loader it always had."""
    from flexflow_tpu.runtime import native as nat

    if native and not nat.native_available():
        pytest.skip("native loader not built in this environment")
    if not native:
        monkeypatch.setattr(nat, "native_available", lambda: False)
    rings = []

    class CountedRing(nat.NativeBatchIterator):
        def __init__(self, *a, **k):
            rings.append(k.get("shuffle"))
            super().__init__(*a, **k)

    monkeypatch.setattr(nat, "NativeBatchIterator", CountedRing)
    x, y = _mlp_data(128)
    m = _mlp_model()
    fed, threads = _spy_placement(monkeypatch, m)
    m.fit(x, y, epochs=1, shuffle=shuffle, verbose=False)
    assert len(fed) == 8
    if shuffle:
        assert not any(
            np.shares_memory(bx, x) or np.shares_memory(by, y) for bx, by in fed
        )
        assert rings == ([True] if native else [])
        assert ("ffdl-py-prefetch" in threads) == (not native)
    else:
        for i, (bx, by) in enumerate(fed):
            assert np.shares_memory(bx, x) and np.shares_memory(by, y)
            np.testing.assert_array_equal(bx, x[i * B:(i + 1) * B])
        assert rings == [] and "ffdl-py-prefetch" not in threads


def test_view_iterator_contract():
    """Whole batches only, every epoch from batch 0, and arrays whose row
    counts differ are refused."""
    from flexflow_tpu.dataloader import ViewIterator

    x = np.arange(70, dtype=np.float32).reshape(35, 2)
    y = np.arange(35, dtype=np.int32).reshape(35, 1)
    it = ViewIterator([x, y], 8)
    assert it.num_batches == 4
    for _ in range(2):
        it.reset()
        got = list(it)
        assert len(got) == 4
        np.testing.assert_array_equal(got[3][1], y[24:32])
    with pytest.raises(ValueError, match="sample count"):
        ViewIterator([x, y[:34]], 8)


def _gathered(kind):
    """A loader that gathers and copies the rows in order, as ``fit``
    did for unshuffled epochs before they were fed as views."""
    from flexflow_tpu.runtime.native import NativeBatchIterator, native_available

    if kind == "native":
        if not native_available():
            pytest.skip("native loader not built in this environment")
        return lambda arrays, bs: NativeBatchIterator(arrays, bs, prefetch_depth=3)
    return lambda arrays, bs: BatchIterator(
        [SingleDataLoader(a, bs, None, None) for a in arrays], prefetch_depth=3
    )


@pytest.mark.parametrize(
    "devices,gather", [(1, "native"), (4, "native"), (4, "python")]
)
def test_fit_on_views_bit_identical_to_gathered_rows(monkeypatch, devices, gather):
    """Views and gathered copies of the same rows train to the same bits,
    on one device and data parallel over four, with the metric sync
    deferred so steps are still queued when the next batch is taken."""
    import flexflow_tpu.model as model_mod

    x, y = _mlp_data(128)
    runs = []
    for feed in ("views", gather):
        if feed != "views":
            monkeypatch.setattr(model_mod, "ViewIterator", _gathered(gather))
        m = _mlp_model(mesh=MachineMesh((devices, 1), ("data", "model")))
        pm = m.fit(x, y, epochs=2, verbose=False)
        assert np.isfinite(pm.sparse_cce_loss)
        runs.append((pm.sparse_cce_loss, m.get_weights()))
    (loss_v, w_v), (loss_g, w_g) = runs
    assert loss_v == loss_g
    for lname in w_v:
        for wname in w_v[lname]:
            np.testing.assert_array_equal(w_v[lname][wname], w_g[lname][wname])


def test_fit_on_views_keeps_nothing_of_the_dataset():
    """Writing to ``x`` and ``y`` after ``fit`` returns changes nothing the
    model holds: its weights, and the step it takes next."""
    x, y = _mlp_data(128)
    x2, y2 = _mlp_data(64)
    ref, m = _mlp_model(), _mlp_model()
    ref.fit(x.copy(), y.copy(), epochs=1, verbose=False)
    m.fit(x, y, epochs=1, verbose=False)
    held = m.get_weights()
    x[:] = np.nan
    y[:] = 0
    for lname, ws in m.get_weights().items():
        for wname, w in ws.items():
            np.testing.assert_array_equal(w, held[lname][wname])
    ref.fit(x2, y2, epochs=1, verbose=False)
    m.fit(x2, y2, epochs=1, verbose=False)
    w_ref = ref.get_weights()
    for lname, ws in m.get_weights().items():
        for wname, w in ws.items():
            np.testing.assert_array_equal(w, w_ref[lname][wname])


@pytest.mark.parametrize("shuffle", [False, True])
@pytest.mark.parametrize("k", [4, 1])
def test_fit_counts_batches_viewed_and_gathered(shuffle, k):
    from flexflow_tpu.obs import configure

    tracer = configure(level="step")
    x, y = _mlp_data(128)  # 8 batches an epoch
    _mlp_model().fit(
        x, y, epochs=2, shuffle=shuffle, verbose=False, metrics_sync_every=k
    )
    c = tracer.summary()["counters"]
    steps = 16.0
    assert (c["fit.batches_viewed"], c["fit.batches_gathered"]) == (
        (0.0, steps) if shuffle else (steps, 0.0)
    )


# ------------------------------------------------------------- config
def test_cli_flags_parse():
    cfg = FFConfig()
    rest = cfg.parse_args([
        "--metrics-sync-every", "8", "--prefetch-depth", "5", "--other",
    ])
    assert cfg.metrics_sync_every == 8
    assert cfg.prefetch_depth == 5
    assert rest == ["--other"]


# ------------------------------------------------- bench_compare metadata
def test_bench_compare_metrics_sync_every_is_comparable_metadata(tmp_path):
    """A record carrying metrics_sync_every still gates against a legacy
    baseline without the field — the difference is a printed note, not a
    refusal (contrast machine_model)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = json.load(open(os.path.join(repo, "BENCH_r05.json")))["parsed"]
    cur = json.loads(json.dumps(base))
    cur["metrics_sync_every"] = 32
    cur["value"] = round(base["value"] * 0.8, 2)  # 20% drop must still gate
    cur_path = str(tmp_path / "current.json")
    json.dump(cur, open(cur_path, "w"))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "bench_compare.py"),
         cur_path, "--baseline", os.path.join(repo, "BENCH_r05.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1, r.stdout + r.stderr  # legacy baseline gated
    assert "REGRESSED" in r.stdout
    assert "metrics_sync_every" in r.stdout  # the metadata note printed
