"""Model-zoo integration tests — the TPU analog of the reference's
example-driven CI (``tests/multi_gpu_tests.sh``, SURVEY §4.4): every app
architecture builds, compiles to a jitted SPMD step, and trains a step on
the virtual mesh.

Small spatial sizes / vocabs keep CPU time bounded; the architectures are
the reference's (cited in each builder's docstring).
"""

import numpy as np
import pytest

from flexflow_tpu import (
    AdamOptimizer,
    FFConfig,
    FFModel,
    LossType,
    MachineMesh,
    SGDOptimizer,
)
from flexflow_tpu.models import (
    alexnet,
    candle_uno,
    dlrm,
    dlrm_strategy,
    inception_v3,
    moe_classifier,
    moe_encoder,
    resnet,
    resnext50,
    xdl,
)


def _train_steps(model, logits, xs, y, loss, steps=2, mesh=None, strategy=None, opt=None):
    model.compile(
        optimizer=opt or SGDOptimizer(lr=0.01),
        loss_type=loss,
        mesh=mesh or MachineMesh((1, 1), ("data", "model")),
        strategy=strategy,
    )
    losses = []
    for _ in range(steps):
        l, _ = model.executor.train_step(xs, y)
        losses.append(float(l))
    assert np.all(np.isfinite(losses)), losses
    return losses


def test_alexnet_builds_and_trains():
    batch = 4
    model = FFModel(FFConfig(batch_size=batch))
    out = alexnet(model, batch, num_classes=10, height=64, width=64)
    assert out.shape == (batch, 10)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 3, 64, 64)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch, 1)).astype(np.int32)
    _train_steps(model, out, [x], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY)


def test_resnet_builds_and_trains_dp():
    batch = 8
    model = FFModel(FFConfig(batch_size=batch))
    out = resnet(model, batch, num_classes=10, layers=(1, 1, 1, 1),
                 height=64, width=64)
    assert out.shape == (batch, 10)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 3, 64, 64)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch, 1)).astype(np.int32)
    mesh = MachineMesh((8, 1), ("data", "model"))
    _train_steps(model, out, [x], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                 mesh=mesh)


def test_resnext_builds_and_trains():
    batch = 2
    model = FFModel(FFConfig(batch_size=batch))
    out = resnext50(model, batch, num_classes=10, height=64, width=64)
    assert out.shape == (batch, 10)
    # grouped conv present
    assert any(l.attrs.get("groups", 1) == 32 for l in model.layers)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 3, 64, 64)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch, 1)).astype(np.int32)
    losses = _train_steps(
        model, out, [x], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY, steps=2
    )
    assert model.num_parameters > 1e6
    assert losses[1] != losses[0], "no parameter movement"


def test_inception_builds_and_trains():
    batch = 2
    model = FFModel(FFConfig(batch_size=batch))
    out = inception_v3(model, batch, num_classes=10, height=75, width=75)
    assert out.shape == (batch, 10)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 3, 75, 75)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch, 1)).astype(np.int32)
    losses = _train_steps(
        model, out, [x], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY, steps=2
    )
    assert model.num_parameters > 1e6
    assert losses[1] != losses[0], "no parameter movement"


def test_dlrm_trains_param_parallel():
    """DLRM with vocab-sharded embedding tables over the model axis
    (parameter parallelism, SURVEY §2.4) on a dp2 x tp4 mesh."""
    batch = 8
    vocabs = (1024, 1024, 512)
    model = FFModel(FFConfig(batch_size=batch))
    out = dlrm(model, batch, embedding_sizes=vocabs, sparse_feature_size=16,
               bag_size=2, mlp_bot=(4, 16, 16), mlp_top=(64, 16, 2))
    assert out.shape == (batch, 2)
    mesh = MachineMesh((2, 4), ("data", "model"))
    strat = dlrm_strategy(model.layers, mesh)
    rng = np.random.default_rng(0)
    xs = [rng.integers(0, v, size=(batch, 2)).astype(np.int32) for v in vocabs]
    xs.append(rng.normal(size=(batch, 4)).astype(np.float32))
    y = rng.normal(size=(batch, 2)).astype(np.float32)
    # graph inputs are ordered by creation: sparse_0..2 then dense
    losses = _train_steps(model, out, xs, y, LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                          steps=3, mesh=mesh, strategy=strat)
    assert losses[-1] < losses[0]


def test_xdl_trains():
    batch = 8
    vocabs = (512, 512)
    model = FFModel(FFConfig(batch_size=batch))
    out = xdl(model, batch, embedding_sizes=vocabs, sparse_feature_size=16,
              mlp=(32, 2))
    rng = np.random.default_rng(0)
    xs = [rng.integers(0, v, size=(batch, 1)).astype(np.int32) for v in vocabs]
    y = rng.normal(size=(batch, 2)).astype(np.float32)
    _train_steps(model, out, xs, y, LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)


def test_candle_uno_trains():
    batch = 4
    model = FFModel(FFConfig(batch_size=batch))
    shapes = {"dose": 1, "cell.rnaseq": 64, "drug.descriptors": 128}
    out = candle_uno(model, batch, dense_layers=(32, 32),
                     dense_feature_layers=(32, 32), feature_shapes=shapes)
    assert out.shape == (batch, 1)
    rng = np.random.default_rng(0)
    from flexflow_tpu.models.candle_uno import INPUT_FEATURES

    xs = [
        rng.normal(size=(batch, shapes[ft])).astype(np.float32)
        for ft in INPUT_FEATURES.values()
    ]
    y = rng.normal(size=(batch, 1)).astype(np.float32)
    losses = _train_steps(model, out, xs, y, LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                          steps=3)
    assert losses[-1] < losses[0]


def test_moe_classifier_trains():
    batch = 16
    model = FFModel(FFConfig(batch_size=batch))
    out = moe_classifier(model, batch, in_dim=32, num_exp=4, num_select=2,
                         hidden=16, num_classes=10)
    assert out.shape == (batch, 10)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 32)).astype(np.float32)
    y = rng.integers(0, 10, size=(batch, 1)).astype(np.int32)
    losses = _train_steps(model, out, [x], y,
                          LossType.SPARSE_CATEGORICAL_CROSSENTROPY, steps=4,
                          opt=AdamOptimizer(alpha=1e-3))
    assert losses[-1] < losses[0]


def test_moe_encoder_trains():
    batch, seq = 4, 8
    model = FFModel(FFConfig(batch_size=batch))
    out = moe_encoder(model, batch, seq, hidden=16, heads=2, num_layers=1,
                      num_exp=4, num_select=2, num_classes=8)
    assert out.shape == (batch, 8)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, seq, 16)).astype(np.float32)
    y = rng.integers(0, 8, size=(batch, 1)).astype(np.int32)
    _train_steps(model, out, [x], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                 opt=AdamOptimizer(alpha=1e-3))


def test_gpt_decoder_builds_and_trains_tp():
    """Causal-LM decoder family (GPT-2 style): pre-LN causal blocks,
    learned positional parameter, LM head — trains under dp x tp with
    next-token labels and the loss drops."""
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.parallel.strategy import tensor_parallel_strategy

    batch, seq, vocab = 4, 16, 64
    model = FFModel(FFConfig(batch_size=batch, learning_rate=0.1))
    out = gpt_decoder(
        model, batch, seq, hidden=32, heads=4, ff_dim=64, num_layers=2,
        vocab=vocab,
    )
    assert out.shape == (batch * seq, vocab)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    # next-token labels: shift left, last position predicts a pad id
    y = np.roll(ids, -1, axis=1).reshape(batch * seq, 1).astype(np.int32)
    mesh = MachineMesh((2, 2), ("data", "model"))
    losses = _train_steps(
        model, out, [ids], y, LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        steps=6, mesh=mesh,
        strategy=tensor_parallel_strategy(model.layers, mesh),
        opt=AdamOptimizer(alpha=0.01),
    )
    assert losses[-1] < losses[0], losses


def test_gpt_generate_continues_learned_cycle():
    """gpt_generate (reference-style seq_length iterative decoding) must
    reproduce a pattern the decoder was trained on: train on cyclic
    next-token data, then greedily decode a continuation and check it
    follows the cycle."""
    from flexflow_tpu.models.transformer import gpt_decoder, gpt_generate

    batch, seq, vocab, period = 8, 16, 12, 4
    cfg = FFConfig(batch_size=batch)
    model = FFModel(cfg)
    gpt_decoder(
        model, batch, seq, hidden=48, heads=4, ff_dim=96, num_layers=2,
        vocab=vocab, use_flash=False,
    )
    model.compile(
        optimizer=AdamOptimizer(alpha=5e-3),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        seed=0,
        # the subject is generation, not parallelism: 150 steps of
        # eight-device collectives beside five other workers aborted the
        # worker when the rendezvous gave up (ROADMAP D0)
        mesh=MachineMesh((1, 1), ("data", "model")),
    )
    rng = np.random.default_rng(0)
    ex = model.executor
    loss = None
    for _ in range(150):
        starts = rng.integers(0, period, size=(batch, 1))
        ids = (starts + np.arange(seq + 1)) % period  # cycle 0..period-1
        x = ids[:, :seq].astype(np.int32)
        y = ids[:, 1:].reshape(batch * seq, 1).astype(np.int32)
        loss, _ = ex.train_step([x], y)
    assert float(loss) < 0.1, f"decoder failed to learn the cycle: {loss}"

    prompt = ((np.arange(6) + 2) % period).reshape(1, 6)
    prompt = np.repeat(prompt, batch, axis=0).astype(np.int32)
    out = gpt_generate(model, prompt, max_new_tokens=8)
    assert out.shape == (batch, 14)
    expected = (np.arange(14) + 2) % period
    np.testing.assert_array_equal(out[0], expected)
    # greedy decode is deterministic across rows with identical prompts
    assert (out == out[0]).all()


def test_kv_cache_decode_matches_masked_path():
    """Round-5 verdict #9: the KV-cache decode step produces EXACTLY the
    greedy continuation of the reference-style full-prefix path, its
    per-step probabilities match, and the whole generation runs on ONE
    compiled program (no retrace as the prefix grows — the structural
    guarantee that step time is prefix-independent)."""
    from flexflow_tpu.models.gpt_decode import (
        GPTDecodeSession,
        gpt_generate_cached,
    )
    from flexflow_tpu.models.transformer import gpt_decoder, gpt_generate

    batch, seq, vocab = 4, 24, 17
    cfg = FFConfig(batch_size=batch)
    model = FFModel(cfg)
    gpt_decoder(
        model, batch, seq, hidden=32, heads=4, ff_dim=64, num_layers=2,
        vocab=vocab, use_flash=False,
    )
    model.compile(seed=0)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, vocab, size=(batch, 5)).astype(np.int32)

    ref = gpt_generate(model, prompt, max_new_tokens=12)
    out, sess = gpt_generate_cached(model, prompt, max_new_tokens=12)
    np.testing.assert_array_equal(out, ref)

    # per-position probability parity vs the masked full forward
    cur = np.zeros((batch, seq), np.int32)
    cur[:, : out.shape[1]] = out
    full = np.asarray(model.eval_batch([cur])).reshape(batch, seq, vocab)
    sess.reset()
    for t in range(out.shape[1] - 1):
        probs = np.asarray(sess.step(out[:, t], t))
        np.testing.assert_allclose(probs, full[:, t], rtol=2e-4, atol=2e-5)

    # ONE compiled program serves every position: zero retraces after the
    # session's warmup, however long the prefix grows
    assert sess._trace_count == 0, sess._trace_count

    # session reuse across calls keeps the same compiled step
    out2, sess2 = gpt_generate_cached(
        model, prompt, max_new_tokens=6, session=sess
    )
    assert sess2 is sess and sess._trace_count == 0
    np.testing.assert_array_equal(out2, ref[:, :11])


def test_kv_cache_decode_under_tensor_parallel():
    """The decode step jit inherits the executor's SHARDED params (TP
    over the model axis): GSPMD inserts the collectives, and the cached
    path still matches the full-prefix path exactly."""
    from flexflow_tpu.models.gpt_decode import gpt_generate_cached
    from flexflow_tpu.models.transformer import gpt_decoder, gpt_generate
    from flexflow_tpu.parallel.strategy import tensor_parallel_strategy

    batch, seq, vocab = 4, 16, 16
    cfg = FFConfig(batch_size=batch)
    m = FFModel(cfg)
    gpt_decoder(m, batch, seq, hidden=32, heads=4, ff_dim=64, num_layers=2,
                vocab=vocab, use_flash=False)
    mesh = MachineMesh((2, 4), ("data", "model"))
    m.compile(
        mesh=mesh, strategy=tensor_parallel_strategy(m.layers, mesh), seed=0
    )
    prompt = np.random.default_rng(0).integers(
        0, vocab, size=(batch, 5)
    ).astype(np.int32)
    ref = gpt_generate(m, prompt, max_new_tokens=6)
    out, sess = gpt_generate_cached(m, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(out, ref)
    # the no-retrace guarantee is MOST at risk under sharded params (the
    # session warmup exists exactly for mesh-induced cache relayouts)
    assert sess._trace_count == 0, sess._trace_count
