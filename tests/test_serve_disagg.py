"""Disaggregated prefill/decode serving tests (ISSUE 13,
docs/SERVING.md "Disaggregated prefill/decode").

Covers the split-pool cluster's bit-identity against the colocated
engine, the cross-geometry KV spill→restore property, the ffkv/1 wire
codec (round-trip + tamper detection), the in-process transport
contract (capacity backpressure, FIFO delivery), the disagg search arm
golden on the 2-slice machine model (different winning meshes per
pool), the handoff audit via analyze_disagg_cluster, the per-phase
serve_report section (gracefully absent on pre-r13 streams), the
ffmetrics/1 additive vocabulary interop, bursty traffic determinism,
and the ``--disagg`` driver path.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import FFConfig, FFModel, MachineMesh  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
    DisaggregatedCluster,
    HandoffError,
    InProcessTransport,
    PagedKVCache,
    ServeEngine,
    TrafficSpec,
    decode_handoff,
    encode_handoff,
    synthetic_requests,
)

SLOTS, SEQ, VOCAB = 4, 48, 31
SHAPE = dict(hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=VOCAB)


def _machine_2slice():
    from flexflow_tpu.search.cost import TPUMachineModel

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "machine_configs", "v5p_2slice.json",
    )
    return TPUMachineModel.from_file(path)


@pytest.fixture(scope="module")
def model():
    cfg = FFConfig(batch_size=SLOTS)
    m = FFModel(cfg)
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, **SHAPE)
    m.compile(seed=0)
    return m


def _streams(engines):
    out = {}
    for eng in engines:
        for r in eng.sched.finished:
            out[r.id] = np.asarray(r.tokens, np.int32)
    return out


# ------------------------------------------------------------ cluster
N_AB = 6
AB_SPEC = TrafficSpec(
    n_requests=N_AB, seed=3, prompt_len=(4, 10), max_new=(3, 8),
    vocab=VOCAB,
)


@pytest.fixture(scope="module")
def ab(model, tmp_path_factory):
    """One colocated-vs-cluster A/B run (with ffmetrics streams),
    shared by the bit-identity, audit, and report tests below — the
    same run carries all three facts."""
    d = tmp_path_factory.mktemp("disagg_ab")
    old, new = str(d / "colocated.jsonl"), str(d / "disagg.jsonl")
    eng = ServeEngine(
        model, slots=SLOTS, block_size=8, sync_every=4, metrics_out=old,
    )
    rep_c = eng.run(synthetic_requests(AB_SPEC))
    cluster = DisaggregatedCluster(
        model, prefill_slots=SLOTS, decode_slots=SLOTS,
        prefill_block_size=8, decode_block_size=16, sync_every=4,
        machine=_machine_2slice(), metrics_out=new,
    )
    rep_d = cluster.run(synthetic_requests(AB_SPEC))
    return dict(
        eng=eng, cluster=cluster, rep_c=rep_c, rep_d=rep_d,
        old=old, new=new,
    )


@pytest.mark.slow
def test_disagg_bit_identical_to_colocated(ab):
    """Acceptance pin: the split-pool topology must not change the
    math — every request's token stream byte-equal to the colocated
    engine's, across MISMATCHED pool KV geometries, with real
    migrations and a decode pool that never prefills."""
    cluster, rep_c, rep_d = ab["cluster"], ab["rep_c"], ab["rep_d"]
    col = _streams([ab["eng"]])
    dis = _streams([cluster.prefill, cluster.decode])

    assert set(col) == set(dis) == set(range(N_AB))
    for i in col:
        assert np.array_equal(col[i], dis[i]), f"request {i} diverged"
    assert rep_d.requests_finished == rep_c.requests_finished == N_AB
    assert rep_d.new_tokens == rep_c.new_tokens
    # phase separation is structural: every multi-token request crossed
    # the wire, and the decode pool never executed a prefill chunk
    assert rep_d.migrated > 0
    assert cluster.decode.prefill_chunks == 0
    assert cluster.prefill.sched.idle and cluster.decode.sched.idle
    assert rep_d.split == f"p{SLOTS}+d{SLOTS}"
    assert rep_d.migrated_kv_bytes > 0
    # the priced DCN delay landed in the report percentiles
    assert rep_d.handoff_p99_ms is not None and rep_d.handoff_p99_ms > 0
    assert rep_d.transport_backpressure == 0


@pytest.mark.slow
def test_disagg_handoff_audit_clean(ab):
    """ffcheck's handoff audit (analyze_disagg_cluster) is clean on a
    real workload: digests verify, pool caches are distinct buffers,
    no request is live in both pools, and both pools' standard serve
    checks pass under the renamed programs."""
    from flexflow_tpu.analysis import analyze_disagg_cluster

    cluster = ab["cluster"]
    report = analyze_disagg_cluster(cluster)
    assert report.ok, report.format_human()
    assert any(p.startswith("prefill.") for p in report.programs)
    assert any(p.startswith("decode.") for p in report.programs)
    assert "disagg.handoff" in report.programs
    # the audit saw real frames
    assert cluster.audit and all(
        row.get("digest_ok") and row.get("admitted")
        for row in cluster.audit
    )


# ------------------------------------------- cross-geometry spill/restore
def _dense_payload(rng, L, H, D, length, kv_dtype="fp32"):
    """A restore-shaped payload in the pool's storage dtype: fp32
    carries raw floats; int8/fp8 carry elements quantized with the
    pool's own contract (per-position scales, quantize_kv) so the
    round trip has no re-quantization step anywhere."""
    payload = {"length": length, "layers": {}}
    if kv_dtype in ("int8", "fp8"):
        import jax.numpy as jnp

        from flexflow_tpu.serve.kvcache import quantize_kv

        payload["kv_dtype"] = kv_dtype
    for i in range(L):
        d = {}
        for part in ("k", "v"):
            x = rng.normal(size=(H, length, D)).astype(np.float32)
            if kv_dtype in ("int8", "fp8"):
                # (length, H, D) layout yields per-position scales
                q, s = quantize_kv(
                    jnp, jnp.asarray(x.transpose(1, 0, 2)), kv_dtype
                )
                d[part] = np.asarray(q).transpose(1, 0, 2)
                d["s" + part] = np.asarray(s)
            else:
                d[part] = x
        payload["layers"][f"layer{i}"] = d
    return payload


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8", "fp8"])
def test_kv_spill_restore_cross_geometry_property(kv_dtype):
    """Property test: a dense KV payload restores bit-exactly into a
    pool with a DIFFERENT block_size/num_blocks geometry (the
    prefill→decode handoff), for random lengths including non-multiples
    of either block size — at every storage dtype.  For quantized
    pools the elements AND their per-position scales must survive the
    src→dst hop verbatim (spill→restore→spill is re-quantization-free
    by contract)."""
    L, H, D = 2, 3, 5
    rng = np.random.default_rng(42)
    geoms = [(8, 16), (16, 8), (4, 20), (20, 4), (8, 12), (12, 8)]
    parts = ("k", "v") + (
        ("sk", "sv") if kv_dtype in ("int8", "fp8") else ()
    )
    for bs_src, bs_dst in geoms:
        for _ in range(2):
            length = int(rng.integers(1, 60))
            kv_src = PagedKVCache(
                L, H, D, slots=2, block_size=bs_src, max_seq_len=64,
                prefix_sharing=False, kv_dtype=kv_dtype,
            )
            kv_dst = PagedKVCache(
                L, H, D, slots=3, block_size=bs_dst, max_seq_len=64,
                prefix_sharing=False, kv_dtype=kv_dtype,
            )
            payload = _dense_payload(rng, L, H, D, length, kv_dtype)
            # write via restore into the source geometry, spill the
            # dense bytes back out, restore THAT into the destination
            kv_src.restore(0, payload, length)
            hop = kv_src.spill(0, length)
            # the frame is dense (H, length, D) a layer, whatever the
            # pools' own geometry (position-major rows, ISSUE 29)
            assert hop["layers"]["layer0"]["k"].shape == (H, length, D)
            kv_dst.restore(1, hop, length)
            back = kv_dst.spill(1, length)
            for i in range(L):
                for part in parts:
                    np.testing.assert_array_equal(
                        back["layers"][f"layer{i}"][part],
                        payload["layers"][f"layer{i}"][part],
                        err_msg=f"bs {bs_src}->{bs_dst} len {length} "
                                f"layer{i}/{part} ({kv_dtype})",
                    )
            assert back.get("kv_dtype") == payload.get("kv_dtype")
            kv_src.check_invariants()
            kv_dst.check_invariants()


def test_kv_restore_refuses_model_shape_mismatch():
    kv = PagedKVCache(2, 4, 8, slots=2, block_size=8, max_seq_len=64)
    bad = {
        "length": 10,
        "layers": {
            f"layer{i}": {
                "k": np.zeros((3, 10, 8), np.float32),  # heads=3 != 4
                "v": np.zeros((3, 10, 8), np.float32),
            }
            for i in range(2)
        },
    }
    with pytest.raises(ValueError, match="model shape"):
        kv.restore(0, bad, 10)
    # the failed restore released its reservation
    assert kv.can_reserve(64)


# ------------------------------------------------------------ wire codec
def test_ffkv_roundtrip_and_tamper_detection():
    d = {
        "id": 7,
        "prompt": np.arange(5, dtype=np.int32),
        "max_new_tokens": 9,
        "eos_id": None,
        "tenant": "tenant0",
        "tier": "interactive",
        "deadline_ms": 0.0,
        "preemptions": 1,
        "tokens": [3],
        "arrival_s": 0.25,
        "arrival_abs_s": 100.25,
        "t_submit": 100.25,
        "t_admitted": 100.3,
        "t_first_token": 100.4,
        "kv_spill": {
            "length": 5,
            "layers": {
                "layer0": {
                    "k": np.ones((2, 5, 3), np.float32),
                    "v": np.full((2, 5, 3), 2.0, np.float32),
                },
            },
        },
    }
    frame = encode_handoff(d)
    assert isinstance(frame, bytes) and len(frame) > 0
    out = decode_handoff(frame)
    assert out["id"] == 7 and out["tokens"] == [3]
    assert out["tier"] == "interactive" and out["preemptions"] == 1
    assert out["t_first_token"] == pytest.approx(100.4)
    np.testing.assert_array_equal(out["prompt"], d["prompt"])
    np.testing.assert_array_equal(
        out["kv_spill"]["layers"]["layer0"]["k"],
        d["kv_spill"]["layers"]["layer0"]["k"],
    )
    # a flipped byte in the payload region must not decode silently
    tampered = bytearray(frame)
    tampered[len(tampered) // 2] ^= 0xFF
    with pytest.raises(HandoffError):
        decode_handoff(bytes(tampered))
    # truncation is torn, not silent
    with pytest.raises(HandoffError):
        decode_handoff(frame[: len(frame) // 2])


# ------------------------------------------------------------- transport
def test_transport_capacity_and_fifo_delivery():
    tr = InProcessTransport(capacity=2)
    assert tr.try_send(b"a", now=0.0, delay_s=0.5)
    assert tr.try_send(b"b", now=0.0, delay_s=0.1)
    # full: backpressure, counted, nothing dropped
    assert not tr.try_send(b"c", now=0.0, delay_s=0.0)
    assert tr.send_rejects == 1 and tr.pending() == 2
    # FIFO: frame "a" (ready at 0.5) heads the queue, so "b" (ready at
    # 0.1) must NOT be delivered around it at t=0.2 — no reordering
    assert tr.recv_ready(0.2) == []
    got = tr.recv_ready(0.6)
    assert got == [b"a", b"b"]
    assert tr.pending() == 0
    assert tr.frames_delivered == 2 and tr.frames_sent == 2


# ------------------------------------------------------------ search arm
def test_unity_search_disagg_arm_2slice_golden(model):
    """Acceptance golden: with ServeSpec(disagg=True) on the 2-slice
    machine model, the search prices every slice split and the two
    pools pick DIFFERENT winning strategies — prefill (compute-bound
    forward) goes pure data-parallel, decode (weight-streaming) shards
    the model axis."""
    from flexflow_tpu.search import unity_search
    from flexflow_tpu.serve.objective import ServeSpec

    machine = _machine_2slice()
    mesh = MachineMesh((2, 8), ("data", "model"))
    st = unity_search(
        model.layers, mesh, graph_inputs=model.graph_inputs, budget=5,
        machine=machine, objective="serve",
        serve=ServeSpec(slots=8, kv_len=32, slo_p99_ms=50.0, disagg=True),
    )
    assert st is not None and st.serve_price is not None
    arm = st.serve_price.get("disagg")
    assert arm is not None, "disagg arm missing from serve_price"
    assert arm["split"] == "1+1"  # 2 slices -> 1 prefill + 1 decode
    pf, dc = arm["prefill"], arm["decode"]
    assert pf["mesh"] != dc["mesh"], (pf, dc)
    # prefill: pure DP over the slice's 8 chips; decode: model-axis TP
    assert pf["mesh"] == [8, 1]
    assert dc["mesh"] == [4, 2]
    assert arm["handoff_ms"] > 0 and arm["handoff_bytes"] > 0
    assert arm["cost"] > 0 and dc["tok_s"] > 0
    # the attached per-pool strategies are real Strategy objects
    assert st.disagg_prefill is not None and st.disagg_decode is not None
    assert st.disagg_prefill.ops and st.disagg_decode.ops
    # JSON-able (the driver prints serve_price)
    json.dumps(arm)
    # disagg=False keeps the legacy price shape (no arm)
    st0 = unity_search(
        model.layers, mesh, graph_inputs=model.graph_inputs, budget=5,
        machine=machine, objective="serve",
        serve=ServeSpec(slots=8, kv_len=32, slo_p99_ms=50.0),
    )
    assert "disagg" not in (st0.serve_price or {})


# --------------------------------------------------- metrics + reporting
@pytest.mark.slow
def test_metrics_phase_vocab_and_serve_report(capsys, ab):
    """The r13 vocabulary is additive: disagg streams tag each window
    with its pool and carry handoff facts; serve_report renders the
    per-phase section for them and stays silent on a pre-r13
    (colocated) stream."""
    from flexflow_tpu.obs.metrics import read_metrics

    old, new, rep = ab["old"], ab["new"], ab["rep_d"]
    assert rep.migrated > 0

    recs_old = read_metrics(old)
    recs_new = read_metrics(new)
    assert recs_old and recs_new
    serve_old = [r["metrics"]["serve"] for r in recs_old]
    serve_new = [r["metrics"]["serve"] for r in recs_new]
    # old stream: no r13 keys at all
    assert all("phase" not in s for s in serve_old)
    # new stream: every window tagged, both pools present, handoff
    # facts on the windows that landed migrations
    phases = {s["phase"] for s in serve_new}
    assert phases == {"prefill", "decode"}
    handoffs = [ms for s in serve_new for ms in s.get("handoff_ms", ())]
    assert len(handoffs) == rep.migrated and all(ms > 0 for ms in handoffs)
    assert sum(s.get("migrated_blocks", 0) for s in serve_new) > 0
    assert sum(s.get("handoff_bytes", 0) for s in serve_new) > 0
    # a reader of the OLD vocabulary sees nothing broken in the new
    # stream (same top-level record fields, serve dict a superset)
    for s in serve_new:
        assert "queue_depth" in s and "occupancy" in s

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import serve_report

    assert serve_report.main([str(new)]) == 0
    text_new = capsys.readouterr().out
    assert "disaggregated pools" in text_new
    assert "KV handoff" in text_new
    assert "prefill" in text_new and "decode" in text_new

    assert serve_report.main([str(old)]) == 0
    text_old = capsys.readouterr().out
    assert "disaggregated pools" not in text_old  # graceful absence
    assert "latency percentiles" in text_old


# --------------------------------------------------------------- traffic
def test_burst_factor_default_is_legacy_byte_identical():
    """burst_factor=1.0 consumes exactly the legacy rng draws — arrival
    times, prompts, and budgets all byte-equal to the pre-r13
    generator, and the identity string is unchanged."""
    spec = TrafficSpec(
        n_requests=10, seed=7, rate_rps=50.0, prompt_len=(4, 12),
        max_new=(4, 24), vocab=256,
    )
    assert spec.identity == "seed7/n10/p4-12/g4-24/r50/v256"
    reqs = synthetic_requests(spec)
    # hand-replay of the legacy generator's exact draw order
    rng = np.random.default_rng(spec.seed)
    t = 0.0
    for r in reqs:
        t += float(rng.exponential(1.0 / spec.rate_rps))
        plen = int(rng.integers(4, 13))
        gen = int(rng.integers(4, 25))
        prompt = rng.integers(0, 256, size=(plen,)).astype(np.int32)
        assert r.arrival_s == t
        assert r.max_new_tokens == gen
        np.testing.assert_array_equal(r.prompt, prompt)


def test_burst_factor_bursty_deterministic_and_suffixed():
    base = dict(
        n_requests=40, seed=11, rate_rps=50.0, prompt_len=(4, 12),
        max_new=(4, 24), vocab=256,
    )
    bursty = TrafficSpec(burst_factor=4.0, **base)
    plain = TrafficSpec(**base)
    assert bursty.identity.endswith("/b4")
    assert plain.identity + "/b4" == bursty.identity
    a = synthetic_requests(bursty)
    b = synthetic_requests(bursty)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    ta = np.asarray([r.arrival_s for r in a])
    tp = np.asarray([r.arrival_s for r in synthetic_requests(plain)])
    assert not np.array_equal(ta, tp)
    # Markov modulation clumps arrivals: the coefficient of variation
    # of inter-arrival gaps exceeds the Poisson stream's on this seed
    # (a deterministic fact of the fixed draw sequence, not a flake)
    cv = lambda x: np.std(x) / np.mean(x)  # noqa: E731
    assert cv(np.diff(ta)) > cv(np.diff(tp))
    # multi-tenant shapes take the same clock
    mt = TrafficSpec(tenants=2, shared_prefix=4, burst_factor=4.0, **base)
    reqs = synthetic_requests(mt)
    assert len(reqs) == 40 and reqs[0].tenant == "tenant0"
    assert mt.identity.endswith("/t2/sp4/i0/b4")


# ---------------------------------------------------------------- driver
def test_serve_driver_disagg_refuses_resume_drain(capsys):
    """--resume-drain is colocated-only; the conflict is refused at
    flag-validation time, before any model is built."""
    from flexflow_tpu.serve.driver import main as serve_main

    rc = serve_main(["--disagg", "--resume-drain", "x.npz"])
    assert rc == 2


@pytest.mark.slow
def test_serve_driver_cli_disagg(tmp_path, capsys):
    from flexflow_tpu.serve.driver import main as serve_main

    out = tmp_path / "drv.jsonl"
    machine = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "machine_configs", "v5p_2slice.json",
    )
    rc = serve_main([
        "--requests", "3", "--serve-slots", "2", "--seq", "32",
        "--hidden", "32", "--ff-dim", "64", "--vocab", "31",
        "--num-layers", "1",
        "--prompt-len", "2:4", "--gen-len", "2:4",
        "--disagg", "--disagg-decode-slots", "2",
        "--burst-factor", "2", "--rate", "30",
        "--machine-model-file", machine,
        "--metrics-out", str(out),
    ])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(line)
    assert doc["metric"] == "serve_demo"
    assert doc["requests_finished"] == 3
    assert doc["serve_traffic"].endswith("/b2")
    assert doc["split"] == "p2+d2"
    assert doc["migrated"] >= 1
    assert out.exists()
