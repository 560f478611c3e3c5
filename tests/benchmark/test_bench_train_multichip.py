"""The four-chip training cell's job (``jobs/train_multichip.py``) on the
CPU's virtual devices: its reference takes a step's gradient as the
mean over micro-batches, which is the whole batch's mean-loss gradient;
and a tiny copy of the cell runs through ``run.py`` and agrees with it."""

import types

import bench_fixtures as F
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.jobs import train, train_multichip
from benchmarks.reference import bert_encoder as R


@pytest.mark.parametrize("micro", [2, 4])
def test_micro_batched_readings_are_the_whole_batchs(micro):
    import jax.numpy as jnp

    cfg, opt = F.TINY_BERT["model"], F.TINY_BERT["optimizer"]
    x, y = train.synthetic_classes(3 * 8, 16, cfg["hidden_size"], cfg["num_labels"], 3)
    batches = [(jnp.asarray(x[i * 8:(i + 1) * 8]), jnp.asarray(y[i * 8:(i + 1) * 8, 0]))
               for i in range(3)]
    params = W.make(R.param_shapes(cfg), 5)
    whole = R.train_readings(params, batches, cfg, opt)
    parts = train_multichip.micro_train_readings(R, params, batches, cfg, opt, micro=micro)
    np.testing.assert_allclose(parts["loss"], whole["loss"], rtol=1e-5)
    for key in ("grad_norm", "change_norm"):
        for layer, ws in whole[key].items():
            for w, v in ws.items():
                np.testing.assert_allclose(parts[key][layer][w], v, rtol=2e-4, atol=1e-7)


def test_four_chip_cell_runs_and_agrees_with_its_reference(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(train_multichip, "MICRO_BATCH", 4)
    cell = dict(F.TINY_TRAIN_CELL, name="tiny_bert.train4", job="train_multichip", chips=4)
    root = F.tmp_checkout(tmp_path, {
        "configs/tiny_bert.json": F.TINY_BERT,
        "workloads/tiny_bert.train4.json": cell,
        "traffic_mixes/tiny_train.json": F.TINY_TRAIN_MIX,
    })
    argv = ["--workload", "tiny_bert.train4", "--seed", str(2 ** 31 + 3), "--seconds", "1",
            "--trace", "0"]
    rc, res, err = F.run_main(root, argv, monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True, (res, err[-2000:])
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # the job put its own reference back when it was done
    assert train.reference_readings is not train_multichip.reference_readings


def test_prove_reads_the_control_through_micro_batches(monkeypatch):
    monkeypatch.setattr(train_multichip, "MICRO_BATCH", 4)
    ctx = types.SimpleNamespace(
        cell=dict(F.TINY_TRAIN_CELL, mix=F.TINY_TRAIN_MIX), config=F.TINY_BERT, seed=9)
    same = dict((n, v) for n, v, _ in train_multichip.prove(ctx, "highest"))
    assert all(v == 0.0 for v in same.values())
    control = dict((n, (v, lim)) for n, v, lim in train_multichip.prove(ctx, "control"))
    assert any(v > lim for v, lim in control.values())


def test_prove_reads_a_step_without_the_all_reduce(monkeypatch):
    monkeypatch.setattr(train_multichip, "MICRO_BATCH", 2)
    cell = dict(F.TINY_TRAIN_CELL, mix=F.TINY_TRAIN_MIX, chips=4)
    ctx = types.SimpleNamespace(cell=cell, config=F.TINY_BERT, seed=9)
    local = train_multichip.prove(ctx, "no_allreduce")
    assert any(v > lim for _, v, lim in local)
    # the first chip's rows alone: half_batch's rows halved again
    x3, y3 = train.synthetic_classes(24, 16, F.TINY_BERT["model"]["hidden_size"],
                                     F.TINY_BERT["model"]["num_labels"], 9)
    rows = train_multichip.reference_readings(ctx, x3, y3, rows=slice(0, 2))
    truth = train_multichip.reference_readings(ctx, x3, y3)
    assert local == train.compare(rows, truth, cell["correct_limits"])
