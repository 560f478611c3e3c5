"""Helpers for the benchmark's tests: a temporary copy of the benchmark
with tiny cells beside the real ones, and a run of ``run.py``'s ``main``
that skips its look for a chip."""

from __future__ import annotations

import importlib
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_BERT = {
    "name": "tiny_bert", "source": "test", "family": "bert_encoder",
    "builder": "flexflow_tpu.models.transformer:transformer_encoder",
    "builder_args": {"hidden": 32, "heads": 4, "ff_dim": 64, "num_layers": 4,
                     "num_classes": 8, "raw_input": True},
    "compute_dtype": "float32",
    "model": {"hidden_size": 32, "num_attention_heads": 4, "intermediate_size": 64,
              "num_hidden_layers": 4, "num_labels": 8, "layer_norm_eps": 1e-5,
              "hidden_act": "gelu_tanh"},
    "optimizer": {"name": "adam", "alpha": 1e-3, "beta1": 0.9, "beta2": 0.999,
                  "epsilon": 1e-8},
    "reduced": [], "assumed": {},
}

TINY_TRAIN_MIX = {"batch": 8, "seq": 16, "steps_per_fit": 3}

TINY_GPT = {
    "name": "tiny_gpt", "source": "test", "family": "gpt2_decoder",
    "builder": "flexflow_tpu.models.transformer:gpt_decoder",
    "builder_args": {"hidden": 32, "heads": 4, "ff_dim": 64, "num_layers": 4,
                     "vocab": 96, "use_flash": False},
    "compute_dtype": "float32",
    "model": {"n_embd": 32, "n_head": 4, "n_layer": 4, "n_inner": 64, "n_ctx": 64,
              "n_positions": 64, "vocab_size": 96, "layer_norm_epsilon": 1e-5,
              "activation_function": "gelu_new"},
    "reduced": [], "assumed": {},
}

TINY_SERVE_ENGINE = {"slots": 4, "max_seq": 64, "block_size": 8, "prefill_chunk": 8,
                     "sync_every": 4, "attn": "auto", "kv_dtype": "fp32"}

TINY_BACKLOG_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 0,
                    "prompt_len": [6, 30], "max_new": [4, 12],
                    "backlog_min": 40, "backlog_requests_per_s": 400}

TINY_RATE_MIX = {"mode": "fixed_set", "shape_seed": 0, "block": 16, "rate_rps": 20.0,
                 "prompt_len": [6, 30], "max_new": [4, 12], "duration": "window"}


def tiny_serve_cell(name, traffic, end_to_end, layer_metrics):
    return {
        "name": name, "config": "tiny_gpt", "traffic": traffic, "job": "serve",
        "chips": 1, "why": "test", "engine": dict(TINY_SERVE_ENGINE),
        "end_to_end": end_to_end, "layer_metrics": layer_metrics,
        "correct_limits": {"served_logit_gap_max": 1e-3,
                           "finished_with_wrong_token_count": 0},
    }


TINY_TRAIN_CELL = {
    "name": "tiny_bert.train", "config": "tiny_bert", "traffic": "tiny_train", "job": "train",
    "chips": 1, "why": "test",
    "search_budget": -1,
    "end_to_end": {"train_tokens_per_s": "tokens/s"},
    "layer_metrics": ["host_syncs_per_step.train", "init_params_s"],
    "correct_limits": {"loss_gap_step1": 1e-4, "loss_gap_step2": 1e-4,
                       "loss_gap_step3": 1e-4, "grad_norm_gap_worst_leaf": 1e-3,
                       "change_norm_gap_worst_leaf": 1e-3},
}


def tmp_checkout(tmp_path, extra: dict):
    """Copy ``benchmarks/`` under ``tmp_path`` (as package
    ``bench_copy_<n>.benchmarks`` would clash with the real one, so the
    copy is loaded by path), link the program beside it, and write
    ``extra`` = ``{relative path: json-able}`` into it."""
    root = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    os.symlink(os.path.join(REPO, "flexflow_tpu"), os.path.join(root, "flexflow_tpu"))
    for rel, doc in extra.items():
        path = os.path.join(root, "benchmarks", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            if isinstance(doc, str):
                f.write(doc)
            else:
                json.dump(doc, f)
    return root


def load_run_module(root):
    """``benchmarks/run.py`` of the checkout at ``root`` as a module whose
    HERE/ROOT point there (the jobs, readers and references it imports
    are the repo's own ``benchmarks`` package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(root, "benchmarks", "run.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAKE_DEVICE = {"platform": "cpu", "kind": "test", "count": 1}


def run_main(root, argv, monkeypatch, capsys):
    """Drive ``main`` past the look for a chip; returns (rc, result)."""
    mod = load_run_module(root)
    peaks = json.load(open(os.path.join(REPO, "benchmarks", "peaks.json")))["TPU v5 lite"]
    monkeypatch.setattr(mod, "device_or_exit", lambda chips: (dict(FAKE_DEVICE), peaks))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
    rc = mod.main(argv)
    out = capsys.readouterr()
    lines = [l for l in out.out.strip().splitlines() if l.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None), out.err
