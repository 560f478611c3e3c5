"""A tiny copy of ``nemotron3_nano_30b_a3b.serve_saturated_reasoning``
through ``benchmarks/run.py`` on the CPU, past its look for a chip (as
``test_bench_trinity_mini.py`` does it): the program agrees with its
reference; the fp8 control, two planted faults --
``routed_scaling_factor`` left out, ``relu`` for ``relu2`` -- and a
state read as zero at every chunk boundary do not; the work the shares
count is the hand count; the published file holds the published
widths."""

import json
import math
import os
import types

import bench_fixtures as F

from benchmarks import work, work_nemotron_h as wn
from benchmarks.jobs import serve_hybrid_lm

TINY_MODEL = {
    "hidden_size": 64, "vocab_size": 128, "num_hidden_layers": 9,
    "hybrid_override_pattern": "EMEMEMEM*", "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "router_num_experts": 8, "first_expert": 0, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "mlp_hidden_act": "relu2",
}
TINY_NEMOTRON = {
    "name": "tiny_nemotron_h", "source": "test", "family": "nemotron_h",
    "builder": "flexflow_tpu.models.nemotron_h:nemotron_h_decoder",
    "builder_args": {
        "hidden": 64, "heads": 4, "ff_dim": 32, "num_layers": 9, "vocab": 128,
        "pattern": "EMEMEMEM*", "kv_heads": 2, "head_dim": 16, "mamba_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "state_size": 16, "conv_kernel": 4, "chunk": 8,
        "router_experts": 8, "first_expert": 0, "held_experts": 4, "top_k": 2,
        "shared_ff_dim": 48, "use_flash": False,
    },
    "compute_dtype": "float32", "model": TINY_MODEL, "reduced": [], "assumed": {},
}
TINY_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 0,
            "prompt_len": [6, 44], "max_new": [4, 12],
            "backlog_min": 40, "backlog_requests_per_s": 400}
TINY_METRICS = ["window_wall_ms.tput", "slot_occupancy.tput", "step_mfu.tput",
                "experts_touched_per_call.tput", "expert_load_max_over_mean.tput",
                "state_pool_share.tput", "held_rows_per_token.tput"]
TINY_CELL = {
    "name": "tiny_nemotron_h.backlog", "config": "tiny_nemotron_h", "traffic": "tiny_reasoning",
    "job": "serve_hybrid_lm", "chips": 1, "why": "test",
    "engine": {"slots": 4, "max_seq": 64, "block_size": 8, "prefill_chunk": 8,
               "sync_every": 4, "attn": "auto", "kv_dtype": "fp32"},
    "end_to_end": {"serve_tokens_per_s": "tokens/s"}, "layer_metrics": TINY_METRICS,
    # float32 on the CPU: the program's and the reference's logits differ
    # by the order of float32 sums (the chunked scan against the
    # recurrence among them), so a served token is the reference's argmax
    # or lies within that of it
    "correct_limits": {"served_logit_gap_max": 1e-3, "served_logit_gap_mean": 1e-4,
                       "finished_with_wrong_token_count": 0},
}


def _checkout(tmp_path):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_nemotron_h.json": TINY_NEMOTRON,
        "workloads/tiny_nemotron_h.backlog.json": TINY_CELL,
        "traffic_mixes/tiny_reasoning.json": TINY_MIX,
    })


def _argv(seed=2 ** 31 + 5, trace="0"):
    return ["--workload", "tiny_nemotron_h.backlog", "--seed", str(seed), "--seconds", "2",
            "--trace", trace]


def test_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    rc, res, err = F.run_main(_checkout(tmp_path), _argv(), monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    f = res["facts"]
    assert f["requests_finished"] > 6 and f["sample_tokens"] > 20
    assert f["host_syncs"] == f["windows"]  # the counters ride the window's one sync
    assert f["sample_longest"] > 8 + 8  # several chunks, then decode through the state
    per_slot = 4 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert f["state_pool_bytes"] == 4 * per_slot and 0 < f["state_pool_bytes"] < f["pool_bytes"]
    # the cut's drain spills what is in flight, state and keys together
    assert f["state_slots_held"] == 4 and f["state_spills"] <= 4 and f["state_restores"] == 0
    assert 0 < f["ssm_rows"] <= 4 * f["positions"]
    assert f["ssm_state_bytes_per_call"] > 0
    assert 0 < f["experts_touched"] <= 4 * f["moe_layer_calls"]  # 4 held of 8
    assert f["moe_layer_calls"] == 4 * (f["decode_steps"] + f["prefill_dispatches"])
    # top-2 of 8 with 4 held: a row a position a layer under an even router
    assert 0.5 < f["moe_rows"] / f["moe_layer_positions"] < 1.5
    assert f["expert_load_max_over_mean"] >= 1.0
    assert f["paged_bytes_per_call"] > 0 and f["serve_flops"] > 0
    assert list(res["checks"]) == ["served_logit_gap_max", "served_logit_gap_mean",
                                   "finished_with_wrong_token_count"]


def _ctx(seed=7, seconds=3.0):
    return types.SimpleNamespace(
        cell=dict(TINY_CELL, mix=TINY_MIX), config=TINY_NEMOTRON, seed=seed, seconds=seconds,
        work=work, trace=False, trace_dir=None,
    )


def test_control_and_planted_faults_come_out_not_correct(monkeypatch):
    # some hundreds of tokens, as on the chip (test_bench_runs.py says why)
    monkeypatch.setattr(serve_hybrid_lm, "pick_sample", lambda fin, seed: fin[:40])
    out = dict((n, (v, lim)) for n, v, lim in serve_hybrid_lm.prove(
        _ctx(), "program+control+no_route_scale+relu_experts"))
    for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
        v, lim = out[f"program:{stat}"]
        assert v <= lim
    for what in ("control", "no_route_scale", "relu_experts"):
        for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
            v, lim = out[f"{what}:{stat}"]
            assert v > lim, (what, stat)
        assert out[f"{what}:finished_with_wrong_token_count"][0] == 0


def test_a_state_read_as_zero_at_chunk_boundaries_comes_out_not_correct(monkeypatch):
    import jax.numpy as jnp

    from flexflow_tpu.ops import ssm

    real = ssm.mamba2_mixer

    def forgetful(params, u, a, conv_state=None, ssm_state=None, n_valid=None):
        if u.shape[1] > 1 and ssm_state is not None:  # a prefill chunk of a serve program
            conv_state, ssm_state = jnp.zeros_like(conv_state), jnp.zeros_like(ssm_state)
        return real(params, u, a, conv_state, ssm_state, n_valid)

    monkeypatch.setattr(ssm, "mamba2_mixer", forgetful)
    monkeypatch.setattr(serve_hybrid_lm, "pick_sample", lambda fin, seed: fin[:40])
    out = dict((n, (v, lim)) for n, v, lim in serve_hybrid_lm.prove(_ctx(), "program"))
    for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
        v, lim = out[f"program:{stat}"]
        assert v > lim, stat


def test_work_is_the_hand_count():
    m = dict(TINY_MODEL)
    # prompt 20, prefill chunk 8, scan chunk 8: chunks [0,8) [8,16) [16,20)
    w = wn.served_request_work(prompt_len=20, prefill_pos=20, new_tokens=3,
                               prefill_chunk=8, scan_chunk=8)
    assert w["positions"] == 20 + 2 and w["logit_rows"] == 3 and w["lane_calls"] == 3 + 2
    assert w["kv_reads_full"] == 8 + 16 + 20 + 21 + 22
    assert w["pairs_full"] == sum(range(1, 21)) + 21 + 22
    assert w["scan_pairs"] == 36 + 36 + 10  # n (n + 1) / 2 a scan chunk
    # a dispatch of 8 rows in scan chunks of 4: two chunks of 4 a dispatch
    w4 = wn.served_request_work(prompt_len=20, prefill_pos=20, new_tokens=1,
                                prefill_chunk=8, scan_chunk=4)
    assert w4["scan_pairs"] == 5 * 10 and w4["logit_rows"] == 1
    h, d, cw, N = 64, 32, 32 + 2 * 2 * 16, 16
    assert wn.mamba_dims(m) == (d, cw)
    assert wn.layer_flops_per_position(m, "M") == (
        2 * h * (d + cw + 4) + 2 * d * h + 2 * cw * 4 + 4 * d * N)
    assert wn.layer_flops_per_position(m, "*") == 2 * h * (64 + 2 * 32) + 2 * 64 * h
    assert wn.layer_flops_per_position(m, "E") == 2 * h * 8 + 4 * h * 48
    assert wn.expert_flops_per_row(m) == 4 * h * 32
    tot = dict(w, held_rows=77)
    per_pos = 4 * wn.layer_flops_per_position(m, "M") + 4 * wn.layer_flops_per_position(
        m, "E") + wn.layer_flops_per_position(m, "*")
    assert wn.serve_flops(m, tot) == (
        22 * per_pos + 77 * 4 * h * 32 + 4 * 2 * 82 * (2 * 16 + d)
        + 4 * 4 * 16 * w["pairs_full"] + 2 * 3 * h * 128)
    # without the program's counter: what an even router sends the held half
    assert wn.serve_flops(m, w) == wn.serve_flops(m, dict(w, held_rows=22 * 4 * 2 * 0.5))
    # one attention layer: the kernel's bytes and operations are that layer's
    assert wn.paged_attention_bytes(m, w, 2) == 2 * w["kv_reads_full"] * 2 * 16 * 2 + 2 * 22 * 4 * 16 * 2
    assert wn.paged_attention_flops(m, w) == 4 * 4 * 16 * w["pairs_full"]
    assert wn.state_bytes_per_slot_layer(m, 2) == d * N * 4 + cw * 3 * 2
    assert wn.ssm_state_bytes(m, w, 2) == 5 * 4 * 2 * (d * N * 4 + cw * 3 * 2)


def test_published_file_holds_the_published_widths():
    doc = json.load(open(os.path.join(F.REPO, "benchmarks", "configs",
                                      "nemotron3_nano_30b_a3b.json")))
    m = doc["model"]
    want = dict(hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
                n_groups=8, conv_kernel=4, chunk_size=128, num_attention_heads=32,
                num_key_value_heads=2, head_dim=128, moe_intermediate_size=1856,
                intermediate_size=1856, router_num_experts=128, num_experts_per_tok=6,
                norm_topk_prob=True, routed_scaling_factor=2.5,
                moe_shared_expert_intermediate_size=3712, n_shared_experts=1,
                layer_norm_epsilon=1e-5, mlp_hidden_act="relu2", time_step_min=0.001,
                time_step_max=0.1, time_step_floor=1e-4, n_group=1, topk_group=1,
                residual_in_fp32=False, tie_word_embeddings=False, sliding_window=None)
    assert {k: m[k] for k in want} == want
    assert all(doc[k] == v for k, v in m.items())  # the source's keys at the top level too
    assert doc["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert (m["num_hidden_layers"], m["hybrid_override_pattern"], m["n_routed_experts"],
            m["first_expert"], m["vocab_size"]) == (9, "EMEMEMEM*", 64, 0, 65536)
    pub = doc["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (52, 128, 131072)
    full = pub["hybrid_override_pattern"]
    assert len(full) == 52 and (full.count("M"), full.count("E"), full.count("*")) == (23, 23, 6)
    assert full[34:43] == "EMEMEMEM*"  # published layers 34-42 are the nine held
    b = doc["builder_args"]
    assert (b["hidden"], b["heads"], b["kv_heads"], b["head_dim"], b["ff_dim"], b["num_layers"],
            b["pattern"], b["mamba_heads"], b["mamba_head_dim"], b["n_groups"], b["state_size"],
            b["conv_kernel"], b["chunk"], b["router_experts"], b["held_experts"], b["first_expert"],
            b["top_k"], b["shared_ff_dim"], b["route_scale"], b["expert_act"], b["vocab"]) == (
        2688, 32, 2, 128, 1856, 9, "EMEMEMEM*", 64, 64, 8, 128, 4, 128, 128, 64, 0, 6, 3712,
        2.5, "relu2", 65536)
    assert doc["param_dtype"] == doc["compute_dtype"] == "bfloat16"
    for k in ("no_rotary", "projection_split_order", "gated_norm", "selection_bias",
              "router_float32", "weights", "state_dtypes"):
        assert k in doc["assumed"]
    # 3,166,244,352 parameters as the deployment says
    from benchmarks.reference import nemotron_h as R
    n = sum(math.prod(s) for ws in R.param_shapes(m).values() for s in ws.values())
    assert n == 3166244352 and "3,166,244,352" in doc["deployment"]
    # a position's work: about 0.8 GFLOP outside attention's pairs
    per_pos = sum(wn.layer_flops_per_position(m, k) for k in "EMEMEMEM*") + 4 * 3 * wn.expert_flops_per_row(m)
    assert 0.75e9 < per_pos < 0.8e9
    assert wn.state_bytes_per_slot_layer(m, 2) == 64 * 64 * 128 * 4 + 6144 * 3 * 2
    mix = json.load(open(os.path.join(F.REPO, "benchmarks", "traffic_mixes",
                                      "serve_saturated_reasoning.json")))
    assert {k: mix[k] for k in mix if k != "why"} == {
        "mode": "fixed_set", "shape_seed": 0, "block": 32, "rate_rps": 0,
        "prompt_len": [128, 1024], "max_new": [256, 1024], "backlog_min": 512,
        "backlog_requests_per_s": 24}
    cell = json.load(open(os.path.join(
        F.REPO, "benchmarks", "workloads",
        "nemotron3_nano_30b_a3b.serve_saturated_reasoning.json")))
    e = cell["engine"]
    assert (e["max_seq"], e["block_size"], e["sync_every"], e["attn"], e["kv_dtype"]) == (
        2176, 16, 4, "auto", "fp32")
    assert e["slots"] in (64, 128, 256) and e["prefill_chunk"] in (128, 256)
    assert cell["chips"] == 1 and cell["job"] == "serve_hybrid_lm" and len(cell["why"]) <= 200
    assert len(cell["layer_metrics"]) == 22  # PR 34's twelve, then PR 36's spans, programs, relayouts
    assert "pool_copy_share.tput" not in cell["layer_metrics"]
    assert "kv_rows_visible_share.tput" not in cell["layer_metrics"]
