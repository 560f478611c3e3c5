"""A tiny copy of ``minicpm_sala.serve_backlog_long_context`` through
``benchmarks/run.py`` on the CPU, past its look for a chip (as
``test_bench_phi4flash.py`` does it): the program agrees with its
reference -- on the served tokens and on the logits the engine kept for
them -- and counts on the device the keys its sparse layer read; the fp8
control and the three planted faults -- the sparse layer attending every
key, the lightning state rounded to bfloat16, no lightning decay -- do
not; the work the shares count is
the hand count; the published file holds the published widths and the
cut; the scopes the device-time shares read are in the compiled
programs, and a trace's operations are charged to them by name."""

import json
import math
import os
import types

import numpy as np

import bench_fixtures as F

from benchmarks import work, work_sala as ws
from benchmarks.jobs import serve_sala

ALL_TYPES = ["lightning-attn"] * 3 + ["minicpm4"] + ["lightning-attn"] * 3 + ["minicpm4"]
IDS = [3, 4, 5, 6]
TINY_MODEL = {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "intermediate_size": 384, "vocab_size": 512, "lightning_nh": 4, "lightning_head_dim": 64,
    "published_num_hidden_layers": 8, "layer_ids": IDS, "mixer_types": [ALL_TYPES[l] for l in IDS],
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "scale_emb": 12.0, "scale_depth": 1.4,
    "dim_model_base": 64,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 4,
                      "window_size": 32, "dense_len": 64},
}
TINY_SALA = {
    "name": "tiny_sala", "source": "test", "family": "minicpm_sala",
    "builder": "flexflow_tpu.models.minicpm_sala:minicpm_sala_decoder",
    "builder_args": {"hidden": 256, "heads": 4, "kv_heads": 2, "head_dim": 64, "ff_dim": 384,
                     "vocab": 512, "num_layers": 8, "mixer_types": ALL_TYPES, "layer_ids": IDS,
                     "lightning_heads": 4, "lightning_head_dim": 64, "dim_model_base": 64,
                     "kernel": 8, "stride": 4, "block": 16, "topk": 4, "window": 32,
                     "dense_len": 64},
    "compute_dtype": "float32", "model": TINY_MODEL, "reduced": [], "assumed": {},
}
TINY_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 0,
            "prompt_len": [70, 110], "max_new": [4, 24],
            "backlog_min": 40, "backlog_requests_per_s": 400}
TINY_CELL = {
    "name": "tiny_sala.backlog", "config": "tiny_sala", "traffic": "tiny_long_context",
    "job": "serve_sala", "chips": 1, "why": "test",
    "engine": {"slots": 4, "max_seq": 144, "block_size": 16, "prefill_chunk": 32,
               "sync_every": 4, "attn": "auto", "kv_dtype": "fp32"},
    "end_to_end": {"serve_tokens_per_s": "tokens/s"},
    "layer_metrics": ["window_wall_ms.tput", "slot_occupancy.tput", "step_mfu.tput",
                      "state_pool_share.tput", "keys_attended_share.tput"],
    # float32 on the CPU: the program's and the reference's logits differ
    # by the order of float32 sums, so a served token is the reference's
    # argmax or lies within that of it, and its logit the reference's to
    # that; the means' limits sit far below what the faults move a logit by
    "correct_limits": {"served_logit_gap_max": 1e-3, "served_logit_gap_mean": 1e-5,
                       "served_logit_err_mean": 1e-5, "finished_with_wrong_token_count": 0},
}


def _checkout(tmp_path):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_sala.json": TINY_SALA,
        "workloads/tiny_sala.backlog.json": TINY_CELL,
        "traffic_mixes/tiny_long_context.json": TINY_MIX,
    })


def test_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    argv = ["--workload", "tiny_sala.backlog", "--seed", str(2 ** 31 + 11), "--seconds",
            "1", "--trace", "0"]
    rc, res, err = F.run_main(_checkout(tmp_path), argv, monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0, res
    f = res["facts"]
    assert f["requests_finished"] > 4 and f["sample_tokens"] > 20
    assert f["host_syncs"] == f["windows"]
    # the sparse layer read fewer keys than there were, in decode calls too
    assert 0 < f["sparse_keys_attended"] < f["sparse_keys_visible"]
    assert 0 < f["sparse_decode_keys_attended"] < f["sparse_decode_keys_visible"]
    assert f["sparse_decode_keys_share"] < 100.0 * f["sparse_keys_attended"] / f[
        "sparse_keys_visible"]
    assert res["checks"]["served_logit_err_mean"]["value"] < 1e-5
    assert f["served_logit_err_max"] < 1e-4
    log = f["window_log"]
    assert len(log) == f["windows"] and sum(w[2] for w in log) == f["decode_steps"]
    assert sum(w[1] for w in log) == f["prefill_chunks"]
    # three lightning layers a slot: a (4, 64, 64) float32 state; one index
    assert f["state_pool_bytes"] == 4 * 3 * 4 * 64 * 64 * 4
    assert f["index_pool_bytes"] == 4 * 2 * (144 // 4) * 64 * 4
    assert f["sparse_bytes_per_call"] > 0 and f["serve_flops"] > 0
    assert f["selection_flip_share_bf16"] >= 0


def _ctx(seed=7, seconds=1.0):
    return types.SimpleNamespace(
        cell=dict(TINY_CELL, mix=TINY_MIX), config=TINY_SALA, seed=seed, seconds=seconds,
        work=work, trace=False, trace_dir=None,
    )


def test_control_and_the_planted_faults_come_out_not_correct(monkeypatch):
    """Through ``prove`` as on the chip: the fp8 control past every
    mean's limit, and the lightning state rounded to bfloat16 (patched
    in while its engine is built and run) past the limit of the served
    logits' distance from the reference's, though it flips few argmaxes
    at these widths.  (``tests/test_minicpm_sala.py`` reads every
    planted fault on the engine's logits.)"""
    monkeypatch.setattr(serve_sala, "pick_sample", lambda fin, seed: fin[:40])
    assert set(serve_sala.FAULTS) == {"dense", "state_bf16", "no_decay"}
    out = dict((n, (v, lim)) for n, v, lim in serve_sala.prove(_ctx(), "control+state_bf16"))
    for what in ("control", "state_bf16"):
        v, lim = out[f"{what}:served_logit_err_mean"]
        assert v > lim, what
        assert out[f"{what}:selection_flip_share_bf16"][0] >= 0
    v, lim = out["control:served_logit_gap_mean"]
    assert v > lim


def test_work_is_the_hand_count():
    m = dict(TINY_MODEL)
    assert ws.counts(m) == {"sparse": 1, "lightning": 3} and ws.kernel_layers(m) == 2
    # prompt 70 (dense below 64), chunk 32: prefill 70, then 9 decode steps
    w = ws.served_request_work(m, prompt_len=70, prefill_pos=70, new_tokens=10,
                               prefill_chunk=32)
    t = np.arange(79)
    att = [p + 1 if p < 64 else min(p + 1, 3 * 16 + p % 16 + 1) for p in t]
    assert w["positions"] == 79 and w["logit_rows"] == 10 and w["lane_calls"] == 3 + 9
    assert w["pairs"] == sum(att)
    # prompt blocks [0,16) .. [48,64) dense, [64,70) sparse: 3 whole blocks and 6 rows
    units = 16 + 32 + 48 + 64 + (3 * 16 + 6)
    assert w["unit_rows"] == units + sum(att[70:])
    assert w["index_entries"] == sum(max((p + 1 - 8) // 4 + 1, 0) for p in t)
    h, H, d, f, v = 256, 4, 64, 384, 512
    per_sparse = 6 * h * f + 2 * h * (2 * H * d + 2 * 2 * d) + 2 * H * d * h
    per_light = 6 * h * f + 10 * h * 4 * 64 + 4 * 4 * 64 ** 2
    assert ws.layer_flops_per_row(m, "minicpm4") == per_sparse
    assert ws.layer_flops_per_row(m, "lightning-attn") == per_light
    assert ws.serve_flops(m, w) == (79 * (per_sparse + 3 * per_light) + 4 * H * d * w["pairs"]
                                    + 2 * H * d * w["index_entries"] + 2 * 10 * h * v)
    assert ws.sparse_attention_bytes(m, w, 2) == 2 * 2 * w["unit_rows"] * d * 2 + 2 * 79 * H * d * 2
    assert ws.sparse_attention_flops(m, w) == 4 * H * d * w["pairs"]
    assert ws.lightning_state_bytes(m, w) == 12 * 3 * 2 * 4 * 64 * 64 * 4


def test_published_file_holds_the_published_widths_and_the_cut():
    doc = json.load(open(os.path.join(F.REPO, "benchmarks", "configs", "minicpm_sala.json")))
    m = doc["model"]
    want = dict(hidden_size=4096, intermediate_size=16384, num_attention_heads=32,
                num_key_value_heads=2, head_dim=128, vocab_size=73448, lightning_nh=32,
                lightning_head_dim=128, rms_norm_eps=1e-6, scale_emb=12, scale_depth=1.4,
                dim_model_base=256, rope_theta=10000, num_hidden_layers=8,
                published_num_hidden_layers=32, model_type="minicpm_sala")
    assert {k: m[k] for k in want} == want
    assert doc["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert m["layer_ids"] == list(range(9, 17))
    assert m["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert doc["builder_args"]["mixer_types"][9:17] == m["mixer_types"]
    assert len(doc["builder_args"]["mixer_types"]) == 32
    assert all(doc[k] == v for k, v in m.items()
               if k not in ("layer_ids", "published_num_hidden_layers", "sparse_config"))
    for k in ("sparse_config", "infllm_v2", "prefill_selection", "lightning_decay",
              "no_qkv_activation", "output_norm_axis", "output_gate_width", "mup_denominator"):
        assert k in doc["assumed"]
    assert "four-stage pipeline of 9 / 8 / 8 / 7" in doc["deployment"]
    from benchmarks.reference import minicpm_sala as R
    n = sum(math.prod(s) for ws_ in R.param_shapes(m).values() for s in ws_.values())
    assert n == 2820569088 and "2,820,569,088" in doc["deployment"]
    mix = json.load(open(os.path.join(F.REPO, "benchmarks", "traffic_mixes",
                                      "backlog_long_context.json")))
    assert {k: mix[k] for k in mix if k != "why"} == {
        "mode": "fixed_set", "shape_seed": 0, "block": 48, "rate_rps": 0,
        "prompt_len": [8192, 16384], "max_new": [512, 1536], "backlog_min": 256,
        "backlog_requests_per_s": 4}
    cell = json.load(open(os.path.join(
        F.REPO, "benchmarks", "workloads", "minicpm_sala.serve_backlog_long_context.json")))
    e = cell["engine"]
    assert (e["max_seq"], e["block_size"], e["attn"], e["kv_dtype"]) == (
        18432, 64, "auto", "fp32")
    assert e["slots"] in (32, 48) and e["prefill_chunk"] % 64 == 0
    assert cell["chips"] == 1 and cell["job"] == "serve_sala" and len(cell["why"]) <= 200
    # the span metrics' .tput cells are pinned to names with serve_saturated,
    # the program-time ones to three cells (PERF.md 7); the paged kernel's
    # regex finds no call of this cell's layers
    for pinned in ("host_ms_per_window.tput", "program_relayouts.tput",
                   "prefill_device_share.tput", "paged_attention_roofline.tput",
                   "admit_ms_per_window.tput", "compiles_in_slice.tput"):
        assert pinned not in cell["layer_metrics"]
    assert {"lightning_state_share.tput", "sparse_select_share.tput",
            "sparse_attention_roofline.tput", "keys_attended_share.tput"} <= set(
        cell["layer_metrics"])


def test_scopes_are_found_in_the_compiled_programs_and_charged_by_name(monkeypatch):
    """The tiny model's compiled decode and prefill programs carry the
    two scopes in their metadata; a trace whose operations are named
    after them is charged to each scope by the program it ran in."""
    from flexflow_tpu import FFConfig, FFModel, MachineMesh
    from flexflow_tpu.models.minicpm_sala import minicpm_sala_decoder
    from flexflow_tpu.serve import ServeEngine

    mdl = FFModel(FFConfig(batch_size=2))
    minicpm_sala_decoder(mdl, 2, 96, **TINY_SALA["builder_args"])
    mdl.compile(seed=0, mesh=MachineMesh((1, 1), ("data", "model")))
    eng = ServeEngine(mdl, slots=2, block_size=16, prefill_chunk=32, attn="gather")
    texts = eng._program_texts(eng._params_arg)
    for text in texts:
        scopes = serve_sala._op_scopes(text)
        assert any("ff.lightning" in s.split("/") for s in scopes.values())
        assert any("ff.sparse.select" in s.split("/") for s in scopes.values())
    dec = serve_sala._op_scopes(texts[0])
    light = next(n for n, s in dec.items() if "ff.lightning" in s.split("/"))
    other = next(n for n, s in dec.items() if "ff." not in s)
    events = {"/device:TPU:0": {
        "XLA Modules": [("jit_decode(1)", 0.0, 1.0)],
        "XLA Ops": [(f"%{light} = f32[1] fusion()", 0.1, 0.2),
                    (f"%{other} = f32[1] fusion()", 0.4, 0.6)],
    }}
    ctx = types.SimpleNamespace(trace=True, trace_dir="unused")
    import benchmarks.trace_reduce as TR

    monkeypatch.setattr(TR, "find_xplane", lambda d: d)
    monkeypatch.setattr(TR, "load", lambda path: events)
    got = serve_sala.scope_shares(ctx, eng)
    assert got["scope_map_coverage"] == 100.0
    assert abs(got["lightning_state_share"] - 25.0) < 1e-9 and got["sparse_select_share"] == 0.0
