"""A tiny copy of ``qwen3_next_80b_a3b.train_b1_s8192`` through
``benchmarks/run.py`` on the CPU, past its look for a chip (as
``test_bench_runs.py`` does it for the other cells): the program agrees
with its reference, the fp8 control and a planted fault do not, and the
work the MFU share counts is the hand count."""

import json
import os
import types

import numpy as np
import pytest

import bench_fixtures as F

from benchmarks import work, work_qwen3_next as wq
from benchmarks.jobs import train_lm

TINY_MODEL = {
    "hidden_size": 64, "vocab_size": 128, "num_hidden_layers": 4,
    "full_attention_interval": 4, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "router_num_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
}
TINY_QWEN = {
    "name": "tiny_qwen", "source": "test", "family": "qwen3_next",
    "builder": "flexflow_tpu.models.qwen3_next:qwen3_next_decoder",
    "builder_args": {
        "hidden": 64, "heads": 4, "ff_dim": 32, "num_layers": 4, "vocab": 128,
        "kv_heads": 2, "head_dim": 16, "rotary_dim": 4, "rope_theta": 1e7,
        "linear_k_heads": 2, "linear_v_heads": 4, "linear_k_dim": 16, "linear_v_dim": 16,
        "conv_kernel": 4, "router_experts": 16, "first_expert": 4, "held_experts": 8,
        "top_k": 4, "shared_ff_dim": 32,
    },
    "compute_dtype": "float32", "model": TINY_MODEL,
    "optimizer": {"name": "adam", "alpha": 1e-3, "beta1": 0.9, "beta2": 0.999,
                  "epsilon": 1e-8},
    "reduced": [], "assumed": {},
}
TINY_MIX = {"batch": 2, "seq": 32, "steps_per_fit": 4}
# float32 on the CPU: the program and the reference differ by the order
# of float32 sums; Adam's sqrt(v) turns a few near-zero gradient entries
# on their sign, so the change's norm gets 2 %
TINY_CELL = {
    "name": "tiny_qwen.train", "config": "tiny_qwen", "traffic": "tiny_lm", "job": "train_lm",
    "chips": 1, "why": "test", "search_budget": 8, "remat_policy": "none",
    "end_to_end": {"train_tokens_per_s": "tokens/s"},
    "layer_metrics": ["host_syncs_per_step.train", "held_rows_per_token.train",
                      "expert_load_max_over_mean.train"],
    "correct_limits": {"loss_gap_step1": 2e-4, "loss_gap_step2": 2e-4,
                       "loss_gap_step3": 2e-4, "grad_norm_gap_worst_leaf": 2e-3,
                       "grad_norm_gap_mean_leaf": 5e-4,
                       "change_norm_gap_worst_leaf": 2e-2, "rows_over_budget": 0},
}


def _checkout(tmp_path, cell=TINY_CELL):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_qwen.json": TINY_QWEN,
        "workloads/tiny_qwen.train.json": cell,
        "traffic_mixes/tiny_lm.json": TINY_MIX,
    })


def _argv(seed=2 ** 31 + 5, trace="0"):
    return ["--workload", "tiny_qwen.train", "--seed", str(seed), "--seconds", "1",
            "--trace", trace]


def test_lm_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    rc, res, err = F.run_main(_checkout(tmp_path), _argv(), monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res["checks"])[-1] == "rows_over_budget" and len(res["checks"]) == 7
    assert list(res["checks"])[3:5] == ["grad_norm_gap_worst_leaf", "grad_norm_gap_mean_leaf"]
    assert res["checks"]["rows_over_budget"] == {"value": 0.0, "limit": 0}
    f = res["facts"]
    assert res["attempted"] == f["steps"] and f["steps"] % 4 == 0
    # two host syncs a fit call, the routing counters among what they fetch
    assert f["host_syncs"] <= 2 * f["steps"] // 4
    # a layer's 8 held of 16 experts take about half of the 4 choices a token
    assert 1.5 < f["held_rows"] / f["tokens"] < 2.5
    assert f["expert_load_max_over_mean"] >= 1.0
    assert f["train_flops_per_step"] == wq.decoder_train_flops_per_step(
        TINY_MODEL, batch=2, seq=32)


def test_a_number_without_a_limit_is_reported_and_decides_nothing(tmp_path, monkeypatch, capsys):
    limits = {k: v for k, v in TINY_CELL["correct_limits"].items()
              if "loss" not in k and "mean" not in k}
    cell = dict(TINY_CELL, correct_limits=limits)
    rc, res, _ = F.run_main(_checkout(tmp_path, cell), _argv(), monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    assert list(res["checks"]) == ["grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf",
                                   "rows_over_budget"]
    f = res["facts"]
    assert sorted(f["unlimited_gaps"]) == ["grad_norm_gap_mean_leaf"] + [
        f"loss_gap_step{i}" for i in (1, 2, 3)]
    assert all(0 <= v < 2e-4 for v in f["unlimited_gaps"].values())
    # one entry a fit call: three first steps, the warm call, the window's calls
    series = f["held_rows_per_token_by_fit_call"]
    assert len(series) == 4 + f["steps"] // 4 and all(1.5 < r < 2.5 for r in series)
    assert len(f["grad_norm_gap_worst_leaves"]) == 3


def _break_step(monkeypatch, wrap):
    real = train_lm.build_model

    def build(config, cell, seed):
        model = real(config, cell, seed)
        ex = model.executor
        ex.train_step = wrap(ex, ex.train_step)
        return model

    monkeypatch.setattr(train_lm, "build_model", build)


def _second_half_left_out(ex, step):
    def broken(inputs, labels):
        # the second sequence of the batch is left out, the first taken twice
        import jax.numpy as jnp

        def first_half_twice(a):
            a = jnp.asarray(a)
            h = a.shape[0] // 2
            return jnp.concatenate([a[:h], a[:h]])

        return step([first_half_twice(x) for x in inputs], first_half_twice(labels))

    return broken


def test_lm_fault_comes_out_not_correct(tmp_path, monkeypatch, capsys):
    _break_step(monkeypatch, _second_half_left_out)
    rc, res, _ = F.run_main(_checkout(tmp_path), _argv(), monkeypatch, capsys)
    assert rc == 0 and res["correct"] is False
    over = {n for n, c in res["checks"].items() if not c["value"] <= c["limit"]}
    assert over and "rows_over_budget" not in over


def _ctx(seed=7):
    return types.SimpleNamespace(
        cell=dict(TINY_CELL, mix=TINY_MIX), config=TINY_QWEN, seed=seed, seconds=1.0,
        work=work, trace=False, trace_dir=None,
    )


@pytest.mark.parametrize("what,passes", [("highest", True), ("control", False),
                                         ("half_batch", False)])
def test_lm_control_and_half_batch_come_out_not_correct(what, passes):
    """The reference in fp8, or with half of the batch's tokens left
    out, in the program's place fails the limits the float32 program
    passes; the reference in its own precision passes."""
    checks = train_lm.prove(_ctx(), what)
    assert all(v <= lim for _, v, lim in checks) is passes


def test_lm_program_in_small_passes_agrees_with_its_reference():
    """``prove --what passes``: the program built with passes too small
    for one, so that the compared steps take three a layer, within the
    limits of ``correct``; its loop left nothing out."""
    out = {n: (v, lim) for n, v, lim in train_lm.prove(_ctx(), "passes")}
    assert all(v <= lim for v, lim in out.values())
    assert out["passes_a_layer_and_step"][0] >= 2.5
    assert out["rows_over_budget"] == (0.0, 0)
    assert {"grad_norm_gap_worst_leaf", "grad_norm_gap_mean_leaf",
            "change_norm_gap_worst_leaf"} <= set(out)


def test_lm_routing_probe_reads_what_rounding_moves():
    """The share of tokens whose chosen experts differ between the
    reference at bfloat16 operands and the reference proper: at most a
    near-tie at this size, while fp8 operands move some."""
    import jax.numpy as jnp

    from benchmarks import weights as W
    from benchmarks.reference import qwen3_next as ref

    out = train_lm.prove(_ctx(), "routing")
    assert [n for n, _, _ in out] == [f"tokens_with_other_expert_set.layer{i}" for i in range(4)]
    assert all(0.0 <= v <= 0.02 for _, v, _ in out)
    params = W.make(ref.param_shapes(TINY_MODEL), 7)
    x, _ = train_lm.token_rows(2, 32, 128, 7)
    sets = {p: np.asarray(ref.routed_sets(params, jnp.asarray(x), TINY_MODEL, p))
            for p in ("highest", "fp8")}
    assert sets["highest"].shape == (4, 64, 4)
    assert np.any(sets["fp8"] != sets["highest"])


def test_token_rows_are_seeded_and_shifted():
    x, y = train_lm.token_rows(8, 32, 128, 2 ** 31 + 5)
    x3, y3 = train_lm.token_rows(3, 32, 128, 2 ** 31 + 5)
    assert x.dtype == np.int32 and x.shape == y.shape == (8, 32)
    assert np.array_equal(x[:3], x3) and np.array_equal(y[:3], y3)
    assert np.array_equal(x[:, 1:], y[:, :-1]) and 0 <= x.min() and x.max() < 128
    assert len({r.tobytes() for r in x}) == 8
    assert not np.array_equal(x, train_lm.token_rows(8, 32, 128, 6)[0])


def test_qwen3_next_flops_hand_counted():
    doc = json.load(open(os.path.join(
        F.REPO, "benchmarks", "configs", "qwen3_next_80b_a3b.json")))
    m = doc["model"]
    # the source's keys repeated at the file's top level say what ``model`` says
    assert all(doc[k] == v for k, v in m.items() if k in doc)
    assert {"hidden_size", "num_experts", "vocab_size", "rope_theta"} <= set(doc)
    parts = wq.forward_flops_per_token(m, 8192)
    # full attention: q+gate 2*2048*8192, k and v 2*2048*512 each, o 2*4096*2048;
    # 4096.5 keys a token on average x 16 heads x (2*256 scores + 2*256 values)
    assert parts["full_mixers"] == 2 * 2048 * (8192 + 1024) + 2 * 4096 * 2048 + 4096.5 * 16 * 1024
    # router 2*2048*512; shared expert 3 matrices 2*2048*512 and its gate 2*2048;
    # 10 * 32 / 512 = 0.625 held rows a token through 3 matrices 2*2048*512
    assert parts["moe"] == 4 * (2 * 2048 * 512 + 6 * 2048 * 512 + 4096 + 0.625 * 6 * 2048 * 512)
    assert parts["head"] == 2 * 2048 * 18992
    # linear mixer: in 2*2048*(12288+64), out 2*4096*2048, conv 2*8192*4, and per
    # value head 32.5 chunk-mates x (4*128 + 2*256 + 2*128) + 3 state products 2*128*128
    assert parts["linear_mixers"] == 3 * (
        2 * 2048 * 12352 + 2 * 4096 * 2048 + 2 * 8192 * 4 + 32 * (32.5 * 1280 + 6 * 128 * 128))
    step = wq.decoder_train_flops_per_step(m, batch=1, seq=8192)
    assert step == 3 * 8192 * sum(parts.values()) and 11.0e12 < step < 11.8e12
    assert wq.held_parameters(m) == 625_667_136  # the configuration's 625.7 M
