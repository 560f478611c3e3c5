"""Scan-stacked repeated blocks (ISSUE 5, docs/PERF.md).

Covers: structure-hash positives/negatives (initializers, attrs,
dtypes), chain detection on the BERT PCG, stacked-vs-unrolled parity
(loss + metrics over >= 5 steps, both remat policies, with dropout rng
and under a dp x tp strategy), checkpoint round-trip in BOTH directions
across layouts, the --stack-blocks off/auto/on gating, the
block-collapsed search (winners unchanged, costs identical), the
bench_compare compile gate / stack_blocks metadata, and the
trace_report block_scan rollup.
"""

import json
import os

import jax
import numpy as np
import pytest

from flexflow_tpu import (
    AdamOptimizer,
    FFConfig,
    FFModel,
    LossType,
    MachineMesh,
)
from flexflow_tpu.blocks import BlockChain, detect_block_chains, layer_signature
from flexflow_tpu.fftype import ActiMode, DataType, MetricsType
from flexflow_tpu.initializer import GlorotUniform
from flexflow_tpu.models.transformer import transformer_encoder
from flexflow_tpu.parallel.strategy import tensor_parallel_strategy

BS, SEQ, HID = 4, 16, 32


def _bert(stack="off", layers=4, remat="none", seed=0, dropout=0.0,
          mesh=None, strategy=None, **cfg_kw):
    cfg = FFConfig(
        batch_size=BS, stack_blocks=stack, remat_policy=remat, **cfg_kw
    )
    m = FFModel(cfg)
    transformer_encoder(
        m, batch=BS, seq=SEQ, hidden=HID, heads=4, ff_dim=2 * HID,
        num_layers=layers, vocab=100, num_classes=8, use_flash=False,
        raw_input=True, dropout=dropout,
    )
    m.compile(
        optimizer=AdamOptimizer(alpha=1e-3),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.ACCURACY],
        seed=seed,
        # the virtual 8-device test mesh does not divide batch 4
        mesh=mesh or MachineMesh((1, 1), ("data", "model")),
        strategy=strategy,
    )
    return m


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(BS, SEQ, HID)).astype(np.float32)
    y = rng.integers(0, 8, size=(BS, 1)).astype(np.int32)
    return x, y


# ------------------------------------------------------------- detection
def test_detects_bert_chain():
    m = _bert(layers=6)
    chains = detect_block_chains(m.layers, min_depth=4)
    assert len(chains) == 1
    c = chains[0]
    assert (c.block_len, c.depth) == (7, 6)
    # carry is the block output: same shape/dtype as the chain input
    assert c.template[-1].outputs[0].shape == (BS, SEQ, HID)


def test_signature_negative_cases():
    """Differing initializers, attrs, or dtypes must NOT merge."""
    m = FFModel(FFConfig(batch_size=4))
    t = m.create_tensor((4, 32))
    a = m.dense(t, 32, ActiMode.RELU, kernel_initializer=GlorotUniform(0))
    b = m.dense(a, 32, ActiMode.RELU, kernel_initializer=GlorotUniform(0))
    la, lb = m.layers[-2], m.layers[-1]
    # same-config initializers built separately DO merge (value identity)
    assert layer_signature(la) == layer_signature(lb)
    c = m.dense(b, 32, ActiMode.RELU, kernel_initializer=GlorotUniform(7))
    assert layer_signature(m.layers[-1]) != layer_signature(lb)
    d = m.dense(c, 32, ActiMode.RELU, use_bias=False)  # attrs differ
    assert layer_signature(m.layers[-1]) != layer_signature(lb)
    m.dense(d, 32, ActiMode.GELU)  # activation differs
    assert layer_signature(m.layers[-1]) != layer_signature(lb)
    # dtype difference (cast attrs)
    m2 = FFModel(FFConfig(batch_size=4))
    t2 = m2.create_tensor((4, 32))
    m2.cast(t2, DataType.FLOAT)
    m2.cast(m2.layers[-1].outputs[0], DataType.HALF)
    assert layer_signature(m2.layers[-2]) != layer_signature(m2.layers[-1])


def test_heterogeneous_initializer_breaks_chain():
    """4 same-shape dense layers, one seeded differently: no depth-4
    chain may survive (it would silently re-distribute that layer's
    init)."""
    m = FFModel(FFConfig(batch_size=4))
    t = m.create_tensor((4, 32))
    for i in range(4):
        init = GlorotUniform(9) if i == 2 else GlorotUniform(0)
        t = m.dense(t, 32, ActiMode.RELU, kernel_initializer=init)
    chains = detect_block_chains(m.layers, min_depth=2)
    assert all(c.depth * c.block_len < 4 for c in chains), [
        (c.start, c.block_len, c.depth) for c in chains
    ]


def test_uniform_dense_tower_detected():
    m = FFModel(FFConfig(batch_size=4))
    t = m.create_tensor((4, 32))
    for _ in range(5):
        t = m.dense(t, 32, ActiMode.RELU)
    chains = detect_block_chains(m.layers, min_depth=4)
    assert len(chains) == 1 and chains[0].block_len == 1
    assert chains[0].depth == 5


# ----------------------------------------------------------- gating knob
def test_stack_blocks_off_is_unrolled():
    m = _bert(stack="off", layers=6)
    ex = m.executor
    assert ex._block_chains == []
    assert all(not isinstance(s, BlockChain) for s in ex._segments)
    assert ex._stacked_slices == {}


def test_auto_threshold_and_on():
    # depth-3 chain: auto declines, on stacks
    m_auto = _bert(stack="auto", layers=3)
    assert m_auto.executor._block_chains == []
    m_on = _bert(stack="on", layers=3)
    assert len(m_on.executor._block_chains) == 1
    # depth-6: auto stacks
    m6 = _bert(stack="auto", layers=6)
    assert len(m6.executor._block_chains) == 1
    # stacked storage: template buckets hold (depth, ...) arrays
    ex = m6.executor
    wq = ex.params["enc0_attn"]["wq"]
    assert wq.shape[0] == 6
    assert "enc3_attn" not in ex.params


def test_stateful_chain_declined():
    """Identical BatchNorm layers form a structural chain, but running
    stats cannot ride the scan carry — the executor must decline."""
    cfg = FFConfig(batch_size=4, stack_blocks="on")
    m = FFModel(cfg)
    t = m.create_tensor((4, 8, 4, 4))
    for _ in range(4):
        t = m.batch_norm(t, relu=True)
    t = m.flat(t)
    t = m.dense(t, 8)
    m.softmax(t)
    m.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    assert m.executor._block_chains == []
    # but the chain IS structurally there — only executability declined
    assert detect_block_chains(m.layers, min_depth=2)


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("remat", ["none", "all"])
def test_stacked_vs_unrolled_fit_parity(remat):
    """Loss + metrics bit-close over 5 steps, both remat policies; init
    is bit-identical by construction (same per-layer fold_in keys)."""
    m_off = _bert(stack="off", layers=4, remat=remat)
    m_on = _bert(stack="auto", layers=4, remat=remat)
    w_off, w_on = m_off.get_weights(), m_on.get_weights()
    assert set(w_off) == set(w_on)
    for ln in w_off:
        for wn in w_off[ln]:
            np.testing.assert_array_equal(w_off[ln][wn], w_on[ln][wn])
    x, y = _batch()
    for step in range(5):
        l1, m1 = m_off.executor.train_step([x], y)
        l2, m2 = m_on.executor.train_step([x], y)
        assert float(l1) == pytest.approx(float(l2), rel=2e-4), step
        for k in m1:
            assert float(m1[k]) == pytest.approx(
                float(m2[k]), rel=2e-4, abs=1e-6
            ), (step, k)


def test_parity_with_dropout_rng():
    """Dropout streams inside the scan derive from the member layer
    names' crc32 (scan xs) — identical to the unrolled fold_in."""
    m_off = _bert(stack="off", layers=4, dropout=0.1, seed=3)
    m_on = _bert(stack="auto", layers=4, dropout=0.1, seed=3)
    x, y = _batch(1)
    for _ in range(3):
        l1, _ = m_off.executor.train_step([x], y)
        l2, _ = m_on.executor.train_step([x], y)
        assert float(l1) == pytest.approx(float(l2), rel=2e-4)


def test_parity_sharded_dp_tp():
    """Stacked weights under a dp x tp strategy: the (depth, ...) arrays
    carry (None, *per-layer spec) shardings and the scan computes the
    same losses."""
    mesh = MachineMesh((2, 2), ("data", "model"))

    def build(stack):
        cfg = FFConfig(batch_size=BS, stack_blocks=stack)
        m = FFModel(cfg)
        transformer_encoder(
            m, batch=BS, seq=SEQ, hidden=HID, heads=4, ff_dim=2 * HID,
            num_layers=4, vocab=100, num_classes=8, use_flash=False,
            raw_input=True,
        )
        st = tensor_parallel_strategy(m.layers, mesh)
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-3),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            mesh=mesh, strategy=st, seed=0,
        )
        return m

    m_off, m_on = build("off"), build("auto")
    assert len(m_on.executor._block_chains) == 1
    wq = m_on.executor.params["enc0_attn"]["wq"]
    assert wq.shape[0] == 4
    x, y = _batch(2)
    for _ in range(3):
        l1, _ = m_off.executor.train_step([x], y)
        l2, _ = m_on.executor.train_step([x], y)
        assert float(l1) == pytest.approx(float(l2), rel=5e-4)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_both_directions(tmp_path):
    """Old (per-layer/unrolled) checkpoints load into stacked executors
    and vice versa, optimizer moments included."""
    x, y = _batch()
    m_un = _bert(stack="off", layers=4)
    for _ in range(3):
        m_un.executor.train_step([x], y)
    p1 = str(tmp_path / "unrolled.npz")
    m_un.save_checkpoint(p1)

    m_st = _bert(stack="auto", layers=4, seed=99)  # different init
    m_st.load_checkpoint(p1)
    l_un, _ = m_un.executor.train_step([x], y)
    l_st, _ = m_st.executor.train_step([x], y)
    assert float(l_un) == pytest.approx(float(l_st), rel=2e-4)
    assert m_st.executor._step_count == m_un.executor._step_count

    p2 = str(tmp_path / "stacked.npz")
    m_st.save_checkpoint(p2)
    # stacked checkpoints are written per-layer: no (depth, ...) arrays
    with np.load(p2) as z:
        assert f"params/enc1_attn/wq" in z.files
        assert z["params/enc1_attn/wq"].shape == (HID, HID)
    m_un2 = _bert(stack="off", layers=4, seed=123)
    m_un2.load_checkpoint(p2)
    l_a, _ = m_st.executor.train_step([x], y)
    l_b, _ = m_un2.executor.train_step([x], y)
    assert float(l_a) == pytest.approx(float(l_b), rel=2e-4)


def test_get_set_weights_per_layer_view():
    m = _bert(stack="auto", layers=4)
    w = m.get_weights()
    assert "enc2_attn" in w and w["enc2_attn"]["wq"].shape == (HID, HID)
    assert m.weight_shape("enc2_attn", "wq") == (HID, HID)
    new = np.full((HID, HID), 0.5, np.float32)
    m.set_weights({"enc2_attn": {"wq": new}})
    np.testing.assert_array_equal(m.get_weights()["enc2_attn"]["wq"], new)
    # the stacked storage took the slice write at depth 2
    np.testing.assert_array_equal(
        np.asarray(m.executor.params["enc0_attn"]["wq"])[2], new
    )
    with pytest.raises(KeyError):
        m.set_weights({"nope": {"wq": new}})


def test_recompile_preserves_weights_across_layout_flip():
    """A recompile that flips --stack-blocks keeps weights + moments."""
    x, y = _batch()
    m = _bert(stack="auto", layers=4)
    for _ in range(2):
        m.executor.train_step([x], y)
    w_before = m.get_weights()
    m.config.stack_blocks = "off"
    m.recompile()
    assert m.executor._block_chains == []
    w_after = m.get_weights()
    for ln in w_before:
        for wn in w_before[ln]:
            np.testing.assert_allclose(
                w_before[ln][wn], w_after[ln][wn], rtol=1e-6
            )


def test_recompile_invalidates_block_memos():
    """R17 alter functions mutate layer attrs IN PLACE (guids unchanged)
    — after recompile, chain detection must see the altered graph, not
    the memoized one."""
    cfg = FFConfig(batch_size=4, stack_blocks="on")
    m = FFModel(cfg)
    t = m.create_tensor((4, 32))
    for _ in range(4):
        t = m.dense(t, 32, ActiMode.RELU)
    m.softmax(t)
    m.compile(
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        mesh=MachineMesh((1, 1), ("data", "model")),
    )
    chains = m.executor._block_chains
    assert chains and chains[0].depth == 4
    altered = m.layers[1]
    altered.attrs["activation"] = ActiMode.GELU  # in-place alter
    m.recompile()
    for c in m.executor._block_chains:
        assert altered not in [l for b in c.layers for l in b]


# ------------------------------------------------------ search collapse
def test_dp_collapse_same_winner_and_cost():
    from flexflow_tpu.search.dp import SearchHelper

    m = _bert(stack="off", layers=6)
    mesh = MachineMesh((2, 4), ("data", "model"))
    c1, a1 = SearchHelper(
        m.layers, m.graph_inputs, mesh, collapse_blocks=False
    ).solve()
    h2 = SearchHelper(m.layers, m.graph_inputs, mesh, collapse_blocks=True)
    assert h2._chain_at, "expected a collapsible chain"
    c2, a2 = h2.solve()
    assert c1 == pytest.approx(c2, rel=1e-9)
    assert set(a1) == set(a2)
    for g in a1:
        assert a1[g].key() == a2[g].key(), g


def test_estimate_cost_collapse_identical():
    from flexflow_tpu.search.cost import estimate_strategy_cost

    m = _bert(stack="off", layers=6)
    mesh = MachineMesh((2, 4), ("data", "model"))
    st = tensor_parallel_strategy(m.layers, mesh)
    c1 = estimate_strategy_cost(m.layers, st, collapse_blocks=False)
    c2 = estimate_strategy_cost(m.layers, st, collapse_blocks=True)
    assert c1 == pytest.approx(c2, rel=1e-9)


def test_memory_estimate_unchanged_by_memo():
    from flexflow_tpu.search.memory import strategy_memory_per_device
    from flexflow_tpu.parallel.strategy import data_parallel_strategy

    m = _bert(stack="off", layers=6)
    mesh = MachineMesh((2, 4), ("data", "model"))
    st = data_parallel_strategy(m.layers, mesh)
    total = strategy_memory_per_device(m.layers, st)
    # hand-check: doubling depth ~doubles the per-block contribution
    m2 = _bert(stack="off", layers=12)
    st2 = data_parallel_strategy(m2.layers, mesh)
    total2 = strategy_memory_per_device(m2.layers, st2)
    assert total2 > total * 1.5



# -------------------------------------------------- block_scan telemetry
def test_block_scan_span_emitted():
    from flexflow_tpu.obs import Tracer, get_tracer, set_tracer

    old = get_tracer()
    try:
        set_tracer(Tracer(level="op"))
        m = _bert(stack="auto", layers=4)
        x, y = _batch()
        m.executor.train_step([x], y)
        ev = [
            e for e in get_tracer().events
            if e.get("ph") == "X" and e["name"] == "block_scan"
        ]
        assert ev, "no block_scan span recorded"
        assert ev[0]["args"]["depth"] == 4
        assert ev[0]["args"]["layers"] == 7
    finally:
        set_tracer(old)


def test_trace_report_block_scan_rollup():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    doc = {
        "traceEvents": [
            {"name": "block_scan", "cat": "step", "ph": "X", "ts": 0,
             "dur": 5000.0, "args": {"depth": 24, "layers": 7}},
            {"name": "train_step", "cat": "step", "ph": "X", "ts": 0,
             "dur": 9000.0, "args": {}},
        ],
        "flexflow_tpu": {"summary": {"wall_s": 0.01, "level": "op"}},
    }
    out = trace_report.render(doc)
    assert "block_scan rollup" in out
    assert "depth=24 x 7 layers" in out


def test_stack_blocks_flag_parsing():
    cfg = FFConfig()
    rest = cfg.parse_args(["--stack-blocks", "off", "-b", "8"])
    assert cfg.stack_blocks == "off"
    assert cfg.batch_size == 8
    assert rest == []


# ------------------------------------------------------- bench_compare
def _bc_main(argv):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import bench_compare
    finally:
        sys.path.pop(0)
    return bench_compare.main(argv)


def test_bench_compare_compile_regression_gates(tmp_path, capsys):
    base = {"metric": "m", "value": 100.0, "backend": "cpu",
            "jit_compile_s": 1.0, "stack_blocks": "off"}
    cur = dict(base, jit_compile_s=2.0, stack_blocks="auto")
    bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
    bp.write_text(json.dumps(base))
    cp.write_text(json.dumps(cur))
    rc = _bc_main([str(cp), "--baseline", str(bp)])
    out = capsys.readouterr().out
    assert rc == 1, out  # 2x compile time regresses past 15%
    assert "compile" in out and "REGRESSED" in out
    # stack_blocks is comparable metadata: a note, never a refusal
    assert "stack_blocks differs" in out

    ok = dict(base, jit_compile_s=1.05)
    op = tmp_path / "ok.json"
    op.write_text(json.dumps(ok))
    assert _bc_main([str(op), "--baseline", str(bp)]) == 0
    # compile-time IMPROVEMENT never fails the gate
    fast = dict(base, jit_compile_s=0.1)
    fp = tmp_path / "fast.json"
    fp.write_text(json.dumps(fast))
    assert _bc_main([str(fp), "--baseline", str(bp)]) == 0
