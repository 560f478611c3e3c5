"""A tiny copy of ``phi4_mini_flash_reasoning.serve_saturated_long_reasoning``
through ``benchmarks/run.py`` on the CPU, past its look for a chip (as
``test_bench_nemotron3_nano.py`` does it): the program agrees with its
reference and counts the cross-decoder's rows; the fp8 control and the
three planted faults -- no second map subtracted, window layers that see
every key, memory units that read ones -- do not; the work the shares
count is the hand count; the published file holds the published widths;
the selective scan's regex finds the scan in the compiled programs."""

import json
import math
import os
import re
import types

import bench_fixtures as F

from benchmarks import work, work_phi4flash as wp
from benchmarks.jobs import serve_yoco_lm

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "num_hidden_layers": 8, "vocab_size": 128, "sliding_window": 8,
    "mb_per_layer": 2, "layer_norm_eps": 1e-5, "mamba_expand": 2, "mamba_d_state": 4,
    "mamba_d_conv": 4, "mamba_dt_rank": 8,
}
TINY_PHI = {
    "name": "tiny_phi4flash", "source": "test", "family": "phi4flash",
    "builder": "flexflow_tpu.models.phi4flash:phi4flash_decoder",
    "builder_args": {"hidden": 64, "heads": 4, "kv_heads": 2, "ff_dim": 96, "num_layers": 8,
                     "vocab": 128, "sliding_window": 8, "d_inner": 128, "state_size": 4,
                     "dt_rank": 8},
    "compute_dtype": "float32", "model": TINY_MODEL, "reduced": [], "assumed": {},
}
TINY_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 0,
            "prompt_len": [6, 44], "max_new": [4, 12],
            "backlog_min": 40, "backlog_requests_per_s": 400}
TINY_CELL = {
    "name": "tiny_phi4flash.backlog", "config": "tiny_phi4flash", "traffic": "tiny_long",
    "job": "serve_yoco_lm", "chips": 1, "why": "test",
    "engine": {"slots": 4, "max_seq": 64, "block_size": 8, "prefill_chunk": 8,
               "sync_every": 4, "attn": "auto", "kv_dtype": "fp32"},
    "end_to_end": {"serve_tokens_per_s": "tokens/s"},
    "layer_metrics": ["window_wall_ms.tput", "slot_occupancy.tput", "step_mfu.tput",
                      "state_pool_share.tput", "kv_rows_visible_share.tput",
                      "cross_decoder_rows_share.tput"],
    # float32 on the CPU: the program's and the reference's logits differ
    # by the order of float32 sums, so a served token is the reference's
    # argmax or lies within that of it.  At these widths the scores are
    # small and a pair's two maps alike, so lambda 0 moves a logit by
    # ~1e-4 on average: the mean's limit sits below that
    "correct_limits": {"served_logit_gap_max": 1e-3, "served_logit_gap_mean": 1e-5,
                       "finished_with_wrong_token_count": 0},
}


def _checkout(tmp_path):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_phi4flash.json": TINY_PHI,
        "workloads/tiny_phi4flash.backlog.json": TINY_CELL,
        "traffic_mixes/tiny_long.json": TINY_MIX,
    })


def test_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    argv = ["--workload", "tiny_phi4flash.backlog", "--seed", str(2 ** 31 + 9), "--seconds",
            "1", "--trace", "0"]
    rc, res, err = F.run_main(_checkout(tmp_path), argv, monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    f = res["facts"]
    assert f["requests_finished"] > 6 and f["sample_tokens"] > 20
    assert f["host_syncs"] == f["windows"]
    # the cross-decoder: every slot a decode step, one row a lane a prefill group
    assert f["cross_decoder_rows"] == 4 * f["decode_steps"] + f["prefill_lanes_computed"]
    assert f["trunk_rows"] == 4 * f["decode_steps"] + 8 * f["prefill_lanes_computed"]
    assert f["cross_decoder_rows"] < f["trunk_rows"]
    # three Mamba-1 layers a slot: a (128, 4) float32 state, 3 x 128 conv inputs
    assert f["state_pool_bytes"] == 4 * 3 * (128 * 4 * 4 + 3 * 128 * 4)
    assert 0 < f["kv_rows_visible"] < f["kv_rows_context"]
    assert f["paged_bytes_per_call"] > 0 and f["serve_flops"] > 0


def _ctx(seed=7, seconds=1.0):
    return types.SimpleNamespace(
        cell=dict(TINY_CELL, mix=TINY_MIX), config=TINY_PHI, seed=seed, seconds=seconds,
        work=work, trace=False, trace_dir=None,
    )


def test_control_and_a_planted_fault_come_out_not_correct(monkeypatch):
    """Through ``prove`` as on the chip; the other two faults move the
    model's logits in ``tests/test_serve_phi4flash.py``."""
    # some hundreds of tokens, as on the chip (test_bench_runs.py says why)
    monkeypatch.setattr(serve_yoco_lm, "pick_sample", lambda fin, seed: fin[:40])
    out = dict((n, (v, lim)) for n, v, lim in serve_yoco_lm.prove(
        _ctx(), "program+control+no_diff"))
    for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
        v, lim = out[f"program:{stat}"]
        assert v <= lim
    assert set(serve_yoco_lm.FAULTS) == {"no_diff", "full_window", "gmu_ones"}
    for what in ("control", "no_diff"):
        for stat in ("served_logit_gap_max", "served_logit_gap_mean"):
            v, lim = out[f"{what}:{stat}"]
            assert v > lim, (what, stat)


def test_work_is_the_hand_count():
    m = dict(TINY_MODEL)
    assert wp.layer_kinds(m) == "MSMSmFGC" and wp.self_layers(m) == 6
    assert wp.counts(m) == {"window": 2, "full": 1, "cross": 1, "state": 3}
    # prompt 20, chunk 8, window 8: chunks [0,8) [8,16) [16,20), then 2 steps
    w = wp.served_request_work(prompt_len=20, prefill_pos=20, new_tokens=3,
                               prefill_chunk=8, window=8)
    assert w["positions"] == 22 and w["logit_rows"] == 3 and w["lane_calls"] == 5
    assert w["cross_rows"] == 3 + 2
    assert w["kv_reads_full"] == 8 + 16 + 20 + 21 + 22
    assert w["cross_kv_reads"] == w["cross_pairs"] == 8 + 16 + 20 + 21 + 22
    assert w["pairs_full"] == sum(range(1, 21)) + 21 + 22
    assert w["kv_reads_win"] == 8 + (16 - 1) + (20 - 9) + 8 + 8
    assert w["pairs_win"] == sum(min(p + 1, 8) for p in range(20)) + 8 + 8
    h, H, hd, d, N, R, f = 64, 4, 16, 128, 4, 8, 96
    mlp = 6 * h * f
    assert wp.layer_flops_per_row(m, "M") == mlp + 2 * h * 2 * d + 2 * d * (R + 2 * N) + (
        2 * R * d + 2 * d * h + 2 * d * 4 + 4 * d * N)
    assert wp.layer_flops_per_row(m, "S") == mlp + 2 * h * (4 + 4) * hd + 2 * H * hd * h
    assert wp.layer_flops_per_row(m, "G") == mlp + 4 * h * d
    assert wp.layer_flops_per_row(m, "C") == mlp + 4 * H * hd * h
    per_self = sum(wp.layer_flops_per_row(m, k) for k in "MSMSmF")
    per_cross = sum(wp.layer_flops_per_row(m, k) for k in "GC")
    assert wp.serve_flops(m, w) == (
        22 * per_self + 5 * per_cross + 6 * H * hd * (2 * w["pairs_win"] + w["pairs_full"]
                                                      + w["cross_pairs"]) + 2 * 3 * h * 128)
    # three readers of a cache a call: the full layer, the window layers' rings, the cross layer
    assert wp.paged_attention_bytes(m, w, 2) == 2 * 2 * 16 * 2 * (
        w["kv_reads_full"] + 2 * w["kv_reads_win"] + w["cross_kv_reads"]) + 3 * 4 * 16 * 2 * (
        3 * 22 + 5)
    assert wp.kernel_layers(m) == 4
    assert wp.state_bytes_per_slot_layer(m, 2) == d * N * 4 + d * 3 * 2
    assert wp.ssm_state_bytes(m, w, 2) == 5 * 3 * 2 * (d * N * 4 + d * 3 * 2)


def test_published_file_holds_the_published_widths():
    doc = json.load(open(os.path.join(F.REPO, "benchmarks", "configs",
                                      "phi4_mini_flash_reasoning.json")))
    m = doc["model"]
    want = dict(hidden_size=2560, intermediate_size=10240, num_attention_heads=40,
                num_key_value_heads=20, num_hidden_layers=32, vocab_size=200064,
                sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
                max_position_embeddings=262144, model_type="phi4flash",
                mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160)
    assert {k: m[k] for k in want} == want
    assert all(doc[k] == v for k, v in m.items() if not k.startswith("mamba_"))
    assert doc["reduced"] == []
    for k in ("mamba_sizes", "memory_point", "no_attention_bias", "no_positions",
              "window_convention", "head_pairing", "gate_up_order", "lambda", "subln", "weights"):
        assert k in doc["assumed"]
    b = doc["builder_args"]
    assert (b["hidden"], b["heads"], b["kv_heads"], b["ff_dim"], b["num_layers"], b["vocab"],
            b["sliding_window"], b["d_inner"], b["state_size"], b["dt_rank"], b["conv_kernel"],
            b["mb_per_layer"]) == (2560, 40, 20, 10240, 32, 200064, 512, 5120, 16, 160, 4, 2)
    assert doc["param_dtype"] == doc["compute_dtype"] == "bfloat16"
    from benchmarks.reference import phi4flash as R
    ks = R.layer_kinds(m)
    assert (ks.count("M") + ks.count("m"), ks.count("S"), ks.count("F"), ks.count("G"),
            ks.count("C")) == (9, 8, 1, 7, 7)
    assert ks.index("m") == 16 and ks.index("F") == 17
    n = sum(math.prod(s) for ws in R.param_shapes(m).values() for s in ws.values())
    assert n == 3852457984 and "3,852,457,984" in doc["deployment"]
    # a prompt row costs the self-decoder's 3.93 GFLOP, a generated row every layer's
    per_self = sum(wp.layer_flops_per_row(m, k) for k in ks[:18])
    per_cross = sum(wp.layer_flops_per_row(m, k) for k in ks[18:])
    assert 3.9e9 < per_self < 3.95e9 and 2.7e9 < per_cross < 2.8e9
    mix = json.load(open(os.path.join(F.REPO, "benchmarks", "traffic_mixes",
                                      "backlog_long_reasoning.json")))
    assert {k: mix[k] for k in mix if k != "why"} == {
        "mode": "fixed_set", "shape_seed": 0, "block": 32, "rate_rps": 0,
        "prompt_len": [1024, 8192], "max_new": [256, 1024], "backlog_min": 256,
        "backlog_requests_per_s": 8}
    cell = json.load(open(os.path.join(
        F.REPO, "benchmarks", "workloads",
        "phi4_mini_flash_reasoning.serve_saturated_long_reasoning.json")))
    e = cell["engine"]
    assert (e["max_seq"], e["block_size"], e["prefill_chunk"], e["sync_every"], e["attn"],
            e["kv_dtype"]) == (9216, 16, 256, 4, "auto", "fp32")
    assert e["slots"] in (32, 48, 64)
    assert cell["chips"] == 1 and cell["job"] == "serve_yoco_lm" and len(cell["why"]) <= 200
    assert "prefill_rows_valid_share.tput" not in cell["layer_metrics"]
    assert {"selective_scan_share.tput", "cross_decoder_rows_share.tput"} <= set(
        cell["layer_metrics"])


def _scan_regex():
    spec = json.load(open(os.path.join(F.REPO, "benchmarks", "layer_metrics",
                                       "selective_scan_share.tput.json")))
    return re.compile(spec["args"]["regex"])


def test_selective_scan_regex_finds_the_scan_in_the_compiled_programs():
    """The Mamba-1 mixer at the published widths, a decode row and a
    prefill chunk a lane from a state, compiled here (shapes only): the
    metric's regex matches the state's update and read-out in both, and
    none of the projections around them."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.ssm import mamba1_mixer

    a = dict(d_inner=5120, state_size=16, conv_kernel=4, dt_rank=160)
    bf, f32 = jnp.bfloat16, jnp.float32
    params = {
        "in_proj": (2560, 10240, bf), "conv": (5120, 4, bf), "conv_bias": (5120, bf),
        "x_proj": (5120, 192, bf), "dt_proj": (160, 5120, bf), "dt_bias": (5120, f32),
        "A_log": (5120, 16, f32), "D": (5120, f32), "out_proj": (5120, 2560, bf),
    }
    params = {k: jax.ShapeDtypeStruct(v[:-1], v[-1]) for k, v in params.items()}
    rx = _scan_regex()
    for s in (1, 16):
        def f(p, u, conv, ssm, n):
            out, conv, ssm, _ = mamba1_mixer(p, u, a, conv, ssm, n)
            return out, conv, ssm

        text = jax.jit(f).lower(
            params, jax.ShapeDtypeStruct((2, s, 2560), bf), jax.ShapeDtypeStruct((2, 3, 5120), bf),
            jax.ShapeDtypeStruct((2, 5120, 16), f32), jax.ShapeDtypeStruct((2,), jnp.int32),
        ).compile().as_text()
        lines = [ln.strip() for ln in text.splitlines()]
        hits = [ln for ln in lines if rx.search(ln)]
        assert hits, s
        # the projections' results are no (.., 5120, 16) array
        assert not any(rx.search(ln) for ln in lines if "dot(" in ln and "16]" not in ln)
