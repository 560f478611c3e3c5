"""A tiny copy of ``trinity_mini.serve_saturated_long`` through
``benchmarks/run.py`` on the CPU, past its look for a chip (as
``test_bench_runs.py`` does it for the other cells): the program agrees
with its reference; the fp8 control and two planted faults -- a sliding
layer attending the whole context, ``route_scale`` left out -- do not;
the work the shares count is the hand count; the published file holds
the published widths."""

import json
import os
import types

import pytest

import bench_fixtures as F

from benchmarks import work, work_afmoe as wa
from benchmarks.jobs import serve_lm

TINY_MODEL = {
    "hidden_size": 64, "vocab_size": 128, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"], "sliding_window": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
}
TINY_AFMOE = {
    "name": "tiny_afmoe", "source": "test", "family": "afmoe",
    "builder": "flexflow_tpu.models.afmoe:afmoe_decoder",
    "builder_args": {
        "hidden": 64, "heads": 4, "ff_dim": 32, "num_layers": 5, "vocab": 128, "kv_heads": 2,
        "head_dim": 16, "dense_ff_dim": 96, "num_dense_layers": 1, "num_experts": 8,
        "top_k": 2, "shared_ff_dim": 32, "layer_types": TINY_MODEL["layer_types"],
        "sliding_window": 8, "use_flash": False,
    },
    "compute_dtype": "float32", "model": TINY_MODEL, "reduced": [], "assumed": {},
}
TINY_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 0,
            "prompt_len": [6, 44], "max_new": [4, 12],
            "backlog_min": 40, "backlog_requests_per_s": 400}
TINY_METRICS = ["window_wall_ms.tput", "slot_occupancy.tput", "step_mfu.tput",
                "experts_touched_per_call.tput", "expert_load_max_over_mean.tput",
                "kv_rows_visible_share.tput"]
TINY_CELL = {
    "name": "tiny_afmoe.backlog", "config": "tiny_afmoe", "traffic": "tiny_long",
    "job": "serve_lm", "chips": 1, "why": "test",
    "engine": {"slots": 4, "max_seq": 64, "block_size": 8, "prefill_chunk": 8,
               "sync_every": 4, "attn": "auto", "kv_dtype": "fp32"},
    "end_to_end": {"serve_tokens_per_s": "tokens/s"}, "layer_metrics": TINY_METRICS,
    # float32 on the CPU: the program's and the reference's logits differ
    # by the order of float32 sums, so a served token is the reference's
    # argmax or lies within that of it
    "correct_limits": {"served_logit_gap_max": 1e-3, "served_logit_gap_mean": 1e-4,
                       "finished_with_wrong_token_count": 0},
}


def _checkout(tmp_path):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_afmoe.json": TINY_AFMOE,
        "workloads/tiny_afmoe.backlog.json": TINY_CELL,
        "traffic_mixes/tiny_long.json": TINY_MIX,
    })


def _argv(seed=2 ** 31 + 5, trace="0"):
    return ["--workload", "tiny_afmoe.backlog", "--seed", str(seed), "--seconds", "2",
            "--trace", trace]


def test_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    rc, res, err = F.run_main(_checkout(tmp_path), _argv(), monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    f = res["facts"]
    assert f["requests_finished"] > 6 and f["sample_tokens"] > 20
    assert f["host_syncs"] == f["windows"]  # the counters ride the window's one sync
    assert f["sample_longest"] > 8 + 8  # past the window and a chunk
    assert 0 < f["kv_rows_visible"] < f["kv_rows_context"]
    assert 0 < f["experts_touched"] <= 8 * f["moe_layer_calls"]
    assert f["moe_layer_calls"] == 4 * (f["decode_steps"] + f["prefill_dispatches"])
    assert f["expert_load_max_over_mean"] >= 1.0 and f["moe_rows"] > 0
    assert f["paged_bytes_per_call"] > 0 and f["serve_flops"] > 0
    assert list(res["checks"]) == ["served_logit_gap_max", "served_logit_gap_mean",
                                   "finished_with_wrong_token_count"]


def _ctx(seed=7, seconds=3.0):
    return types.SimpleNamespace(
        cell=dict(TINY_CELL, mix=TINY_MIX), config=TINY_AFMOE, seed=seed, seconds=seconds,
        work=work, trace=False, trace_dir=None,
    )


def test_control_and_planted_faults_come_out_not_correct(monkeypatch):
    # some hundreds of tokens, as on the chip (test_bench_runs.py says why)
    monkeypatch.setattr(serve_lm, "pick_sample", lambda fin, seed: fin[:40])
    out = dict((n, (v, lim)) for n, v, lim in serve_lm.prove(
        _ctx(), "program+control+full_window+no_route_scale"))
    v, lim = out["program:served_logit_gap_max"]
    assert v <= lim
    assert out["program:served_logit_gap_mean"][0] <= out["program:served_logit_gap_mean"][1]
    for what in ("control", "full_window", "no_route_scale"):
        for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
            v, lim = out[f"{what}:{stat}"]
            assert v > lim, (what, stat)
        assert out[f"{what}:finished_with_wrong_token_count"][0] == 0


def test_work_is_the_hand_count():
    m = dict(TINY_MODEL)
    # prompt 20, chunk 8, window 8: chunks [0,8) [8,16) [16,20)
    w = wa.served_request_work(prompt_len=20, prefill_pos=20, new_tokens=3,
                               prefill_chunk=8, window=8)
    assert w["positions"] == 20 + 2 and w["logit_rows"] == 3
    # full: reads 8 + 16 + 20, then decode at 20 and 21 reads 21 + 22
    assert w["kv_reads_full"] == 8 + 16 + 20 + 21 + 22
    # window: chunk 1 reads 8; chunk 2 sees from 8 - 7 = 1: 15 rows; chunk 3
    # from 16 - 7 = 9: 11 rows; decode reads 8 each
    assert w["kv_reads_window"] == 8 + 15 + 11 + 8 + 8
    assert w["pairs_full"] == sum(range(1, 21)) + 21 + 22
    assert w["pairs_window"] == sum(min(p + 1, 8) for p in range(20)) + 8 + 8
    # layer 0 dense, layer 1 routed: q + gate, k, v, o; FFN
    h, H, KV, d = 64, 4, 2, 16
    proj = 2 * h * (2 * H * d + 2 * KV * d) + 2 * H * d * h
    assert wa.layer_matmul_flops_per_position(m, 0) == proj + 6 * h * 96
    assert wa.layer_matmul_flops_per_position(m, 1) == proj + 2 * h * 8 + 6 * h * 32 * (1 + 2)
    assert wa.layer_windows(m) == [8, 8, 8, 8, 0]
    tot = w
    reads = 4 * tot["kv_reads_window"] + tot["kv_reads_full"]
    assert wa.paged_attention_bytes(m, tot, 2) == 2 * reads * KV * d * 2 + 5 * 2 * 22 * H * d * 2
    pairs = 4 * tot["pairs_window"] + tot["pairs_full"]
    assert wa.paged_attention_flops(m, tot) == 4 * H * d * pairs
    assert wa.serve_flops(m, tot) == (
        22 * sum(wa.layer_matmul_flops_per_position(m, i) for i in range(5))
        + 4 * H * d * pairs + 2 * 3 * h * 128)


def test_published_file_holds_the_published_widths():
    doc = json.load(open(os.path.join(F.REPO, "benchmarks", "configs", "trinity_mini.json")))
    m = doc["model"]
    want = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                sliding_window=2048, intermediate_size=6144, num_experts=128,
                moe_intermediate_size=1024, num_experts_per_tok=8, score_func="sigmoid",
                route_norm=True, route_scale=2.826, num_shared_experts=1, vocab_size=200192,
                rope_theta=10000, rms_norm_eps=1e-5)
    assert {k: m[k] for k in want} == want
    assert all(doc[k] == v for k, v in m.items())  # the source's keys at the top level too
    assert doc["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert (m["num_hidden_layers"], m["num_dense_layers"]) == (5, 1)
    assert m["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert doc["published"]["num_hidden_layers"] == 32 and doc["published"]["num_dense_layers"] == 2
    b = doc["builder_args"]
    assert (b["hidden"], b["heads"], b["kv_heads"], b["head_dim"], b["ff_dim"], b["dense_ff_dim"],
            b["num_experts"], b["top_k"], b["vocab"], b["sliding_window"], b["route_scale"]) == (
        2048, 32, 4, 128, 1024, 6144, 128, 8, 200192, 2048, 2.826)
    # 4,241,534,720 parameters as the deployment says
    from benchmarks.reference import afmoe as R
    import math
    n = sum(math.prod(s) for ws in R.param_shapes(m).values() for s in ws.values())
    assert n == 4241534720
    mix = json.load(open(os.path.join(F.REPO, "benchmarks", "traffic_mixes",
                                      "serve_saturated_long.json")))
    assert {k: mix[k] for k in mix if k != "why"} == {
        "mode": "fixed_set", "shape_seed": 0, "block": 64, "rate_rps": 0,
        "prompt_len": [256, 8192], "max_new": [64, 256], "backlog_min": 256,
        "backlog_requests_per_s": 24}
