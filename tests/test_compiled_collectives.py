"""Compiled-HLO collective regressions for the multichip driver configs.

The moe+zero1 phase's full-remat regression lives in test_zero1; this
covers the other dryrun_multichip phase — the dp×tp×sp transformer step —
asserting the SPMD partitioner lowers it without the replicate-everything
fallback and with a bounded all-gather count.  (The reference's analog
guarantee is structural: deliberate partitions via
``create_input_partition``, ``src/runtime/model.cc:2921-2940``.)
"""

import numpy as np

import flexflow_tpu  # noqa: F401  (pins the CPU platform via conftest)


def _build_transformer_step():
    import __graft_entry__ as ge

    model = ge._build(
        batch=4, seq=64, hidden=128, heads=8, ff_dim=256,
        num_layers=2, num_classes=8, mesh_shape=(2, 2, 2),
    )
    ex = model.executor
    x = np.random.default_rng(0).normal(size=(4, 64, 128)).astype(np.float32)
    y = np.zeros((4, 1), np.int32)
    step = ex._step_jit = ex._build_step()
    xs = [
        ex._place(a, ex._input_pspec(t), t.shape[0])
        for a, t in zip([x], ex.graph_inputs)
    ]
    ys = ex._place(y, ex._label_pspec(), ex.graph_inputs[0].shape[0])
    return ex, step, xs, ys


def test_transformer_dp_tp_sp_step_compiles_without_full_remat(capfd):
    from flexflow_tpu.analysis import extract_collectives

    ex, step, xs, ys = _build_transformer_step()
    capfd.readouterr()
    compiled = step.lower(ex.params, ex.state, ex.opt_state, xs, ys, 0).compile()
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, err
    txt = compiled.as_text()
    # The budgets count via the analyzer's shared HLO walker
    # (flexflow_tpu.analysis.extract_collectives) — the same extraction
    # ffcheck's collective audit reconciles, so the budget tests and the
    # analyzer can never disagree about what counts as a collective.
    # The walker must be byte-identical to the raw text scan it replaced
    # (`-start` async forms count as the op): pinned here.
    summary = extract_collectives(txt)
    assert summary["all-gather"] == txt.count(" all-gather(")
    assert summary["all-reduce"] == txt.count(" all-reduce(")
    # collective budget for 2 encoder blocks under dp=2 x tp=2 x sp=2:
    # measured at pin time 5 all-gathers + 16 all-reduces (TP boundary
    # psums fwd+bwd, SP gathers, grad sync); headroom for XLA drift, but
    # far below the replicate-everything fallback (O(params) gathers).
    # Re-measured 17 all-gathers under this jaxlib's SPMD partitioner
    # (tier-1 triage, ISSUE 8) — the budget tracks partitioner drift
    # while the ~40 weights keep the fallback bound an order above it.
    n_ag = summary["all-gather"]
    assert n_ag <= 20, f"all-gather count regressed: {n_ag}"
    n_ar = summary["all-reduce"]
    # 16 at pin time; re-measured 82 under this jaxlib (the partitioner
    # now emits per-weight grad reductions instead of fusing them) —
    # verified identical at the pre-PR commit, so the budget tracks the
    # partitioner, the guard stays the full-remat assert above
    assert n_ar <= 100, f"all-reduce count regressed: {n_ar}"
    loss, _ = ex.train_step(
        [np.random.default_rng(1).normal(size=(4, 64, 128)).astype(np.float32)],
        np.zeros((4, 1), np.int32),
    )
    assert np.isfinite(float(loss))


def test_grad_overlap_off_is_byte_identical():
    """--grad-overlap off must leave the compiled step BYTE-IDENTICAL
    (modulo source-line metadata) to a build where the knob was never
    set, with zero collective-permutes — the ring decomposition must
    not leak into the fused path.  (The r15 budgets above — 17 AG / 82
    AR at pin time — ride the same guarantee: the dp×tp×sp test runs
    with the knob absent, i.e. off.)"""
    import re

    import jax

    from flexflow_tpu import (
        AdamOptimizer, FFConfig, FFModel, LossType, MachineMesh,
    )
    from flexflow_tpu.analysis import extract_collectives
    from flexflow_tpu.fftype import MetricsType
    from flexflow_tpu.models.transformer import transformer_encoder

    if len(jax.devices()) < 8:
        import pytest

        pytest.skip("needs the 8 virtual CPU devices")

    def _hlo(**cfg_kw):
        cfg = FFConfig(batch_size=8, stack_blocks="on", **cfg_kw)
        m = FFModel(cfg)
        transformer_encoder(
            m, batch=8, seq=16, hidden=32, heads=4, ff_dim=64,
            num_layers=4, vocab=100, num_classes=8, use_flash=False,
            raw_input=True,
        )
        m.compile(
            optimizer=AdamOptimizer(alpha=1e-3),
            loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[MetricsType.ACCURACY], seed=0,
            mesh=MachineMesh((8, 1), ("data", "model")),
        )
        ex = m.executor
        x = np.zeros((8, 16, 32), np.float32)
        y = np.zeros((8, 1), np.int32)
        xs = [ex._place(x, ex._input_pspec(t), t.shape[0])
              for t in ex.graph_inputs]
        ys = ex._place(y, ex._label_pspec(), 8)
        step = ex._build_step()
        txt = step.lower(
            ex.params, ex.state, ex.opt_state, xs, ys, 0
        ).compile().as_text()
        txt = re.sub(r", metadata=\{[^}]*\}", "", txt)
        # the tables of source locations that jax 0.9 prints at the head
        # of a module hold the line each ``_hlo(...)`` below sits on
        return re.sub(
            r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
            r"(?:\d+ .*\n)*",
            "", txt, flags=re.M,
        )

    default = _hlo()
    off = _hlo(grad_overlap="off")
    assert off == default
    assert extract_collectives(off)["collective-permute"] == 0
