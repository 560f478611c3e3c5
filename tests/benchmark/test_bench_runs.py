"""Whole runs of ``benchmarks/run.py`` at a tiny size on the CPU, past
its look for a chip: each reference against the program, the control
coming out not correct, and the timed path broken underneath."""

import types

import numpy as np
import pytest

import bench_fixtures as F

from benchmarks import work
from benchmarks.jobs import serve, train


def _train_checkout(tmp_path):
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_bert.json": F.TINY_BERT,
        "workloads/tiny_bert.train.json": F.TINY_TRAIN_CELL,
        "traffic_mixes/tiny_train.json": F.TINY_TRAIN_MIX,
    })


def _serve_checkout(tmp_path):
    backlog = F.tiny_serve_cell(
        "tiny_gpt.backlog", "tiny_backlog", {"serve_tokens_per_s": "tokens/s"},
        ["window_wall_ms.tput", "slot_occupancy.tput", "step_mfu.tput"])
    rate = F.tiny_serve_cell(
        "tiny_gpt.rate", "tiny_rate", {"ttft_p95_ms": "ms", "tpot_p95_ms": "ms"},
        ["queue_wait_p95_ms.lat", "generator_lag_p95_ms.lat"])
    return F.tmp_checkout(tmp_path, {
        "configs/tiny_gpt.json": F.TINY_GPT,
        "workloads/tiny_gpt.backlog.json": backlog,
        "workloads/tiny_gpt.rate.json": rate,
        "traffic_mixes/tiny_backlog.json": F.TINY_BACKLOG_MIX,
        "traffic_mixes/tiny_rate.json": F.TINY_RATE_MIX,
    })


def _argv(workload, seed=2 ** 31 + 5, seconds="1", trace="0"):
    return ["--workload", workload, "--seed", str(seed), "--seconds", seconds, "--trace", trace]


def _shape_ok(res, metrics):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == set(metrics)
    assert all(set(m) == {"value", "unit"} for m in res["metrics"].values())
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())


# ------------------------------------------------------------------ training
def test_train_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    rc, res, err = F.run_main(_train_checkout(tmp_path), _argv("tiny_bert.train"),
                              monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    _shape_ok(res, {"train_tokens_per_s", "setup_s"})
    assert res["attempted"] % 3 == 0 and res["attempted"] >= 3
    # the numbers compared are the last lines of standard error, each beside its limit
    last = [l for l in err.strip().splitlines() if l.startswith("check ")]
    assert [l.split()[1].rstrip(":") for l in last] == list(res["checks"])


def _break_step(monkeypatch, wrap):
    real = train.build_model

    def build(config, cell, seed):
        model = real(config, cell, seed)
        ex = model.executor
        ex.train_step = wrap(ex, ex.train_step)
        return model

    monkeypatch.setattr(train, "build_model", build)


def _state_unchanged(ex, step):
    import jax
    import jax.numpy as jnp

    def broken(inputs, labels):
        keep = jax.tree.map(jnp.copy, (ex.params, ex.opt_state))
        out = step(inputs, labels)
        ex.params, ex.opt_state = keep
        return out

    return broken


def _half_batch(ex, step):
    def broken(inputs, labels):
        # the second half of the batch is left out, the mean taken over the rest
        import jax.numpy as jnp

        def first_half_twice(a):
            a = jnp.asarray(a)
            h = a.shape[0] // 2
            return jnp.concatenate([a[:h], a[:h]])

        return step([first_half_twice(x) for x in inputs], first_half_twice(labels))

    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_train_fault_comes_out_not_correct(fault, tmp_path, monkeypatch, capsys):
    _break_step(monkeypatch, fault)
    rc, res, _ = F.run_main(_train_checkout(tmp_path), _argv("tiny_bert.train"),
                            monkeypatch, capsys)
    assert rc == 0 and res["correct"] is False
    over = {n for n, c in res["checks"].items() if not c["value"] <= c["limit"]}
    if fault is _state_unchanged:
        # an unmoved leaf reads 1 by the measure of the change
        assert res["checks"]["change_norm_gap_worst_leaf"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert over


def _ctx(cell, config, mix, seed=7, seconds=1.5):
    return types.SimpleNamespace(
        cell=dict(cell, mix=mix), config=config, seed=seed, seconds=seconds, work=work,
        trace=False, trace_dir=None,
    )


def test_train_control_comes_out_not_correct():
    """The reference in fp8 in the program's place fails the limits the
    float32 program passes; the reference in its own precision passes."""
    ctx = _ctx(F.TINY_TRAIN_CELL, F.TINY_BERT, F.TINY_TRAIN_MIX)
    control = train.prove(ctx, "control")
    assert any(not v <= lim for _, v, lim in control)
    same = train.prove(ctx, "highest")
    assert all(v <= lim for _, v, lim in same)


# ------------------------------------------------------------------- serving
def test_backlog_run_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    rc, res, _ = F.run_main(_serve_checkout(tmp_path), _argv("tiny_gpt.backlog", seconds="1.5"),
                            monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    _shape_ok(res, {"serve_tokens_per_s", "setup_s"})
    f = res["facts"]
    assert f["requests_finished"] > 6 and f["sample_tokens"] > 20
    assert f["host_syncs"] == f["windows"] and f["requests_offered"] > f["requests_started"]
    assert f["window_s"] == pytest.approx(1.5, abs=0.5)  # cut by the engine's own drain


def test_rate_run_counts_every_request_due(tmp_path, monkeypatch, capsys):
    rc, res, _ = F.run_main(_serve_checkout(tmp_path), _argv("tiny_gpt.rate", seconds="1.5"),
                            monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    _shape_ok(res, {"ttft_p95_ms", "tpot_p95_ms", "setup_s"})
    f = res["facts"]
    assert res["attempted"] == f["requests_offered"] == f["requests_finished"]
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0


def test_altered_token_comes_out_not_correct(tmp_path, monkeypatch, capsys):
    real = serve.build_engine

    def build(config, cell, seed, shapes):
        model, engine = real(config, cell, seed, shapes)
        decode = engine._decode
        vocab = config["model"]["vocab_size"]

        def altered(*args):
            res = decode(*args)
            nxt = (res[0] + 1) % vocab  # every decode step's token, where it is produced
            return (nxt,) + tuple(res[1:])

        engine._decode = altered
        return model, engine

    monkeypatch.setattr(serve, "build_engine", build)
    rc, res, _ = F.run_main(_serve_checkout(tmp_path), _argv("tiny_gpt.backlog", seconds="1.5"),
                            monkeypatch, capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["served_logit_gap_max"]["value"] > res["checks"]["served_logit_gap_max"]["limit"]


def test_serve_control_comes_out_not_correct(monkeypatch):
    # some hundreds of tokens, as on the chip: at a tiny vocabulary a few
    # dozen positions can all happen to agree between fp8 and float32
    monkeypatch.setattr(serve, "SAMPLE_REQUESTS", 40)
    cell = F.tiny_serve_cell("tiny_gpt.backlog", "tiny_backlog",
                             {"serve_tokens_per_s": "tokens/s"}, [])
    out = dict((n, (v, lim)) for n, v, lim in
               serve.prove(_ctx(cell, F.TINY_GPT, F.TINY_BACKLOG_MIX), "program+control"))
    v, lim = out["program:served_logit_gap_max"]
    assert v <= lim
    v, lim = out["control:served_logit_gap_max"]
    assert v > lim
