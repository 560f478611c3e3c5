"""Live introspection tests (ISSUE 17, docs/OBSERVABILITY.md).

Covers the rotation-aware ``follow=True`` tailing mode of the stream
readers, the zero-cost pin for the status server (the SAME workload
with the ops plane on vs off produces identical token streams, an
identical host-sync ledger, and ffmetrics/ffspan streams identical up
to wall-clock timings), mid-run liveness of all four endpoints while
an engine is actually serving, the Prometheus text-exposition grammar
of ``/metricz``, and the driver's truthful startup failures (bad
policy file, already-bound status port).
"""

from __future__ import annotations

import json
import re
import socket
import threading
import time
import urllib.request

import pytest

import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
)

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.models.transformer import gpt_decoder  # noqa: E402
from flexflow_tpu.obs import get_monitor, set_monitor  # noqa: E402
from flexflow_tpu.obs.aggregate import MetricsAggregator  # noqa: E402
from flexflow_tpu.obs.metrics import (  # noqa: E402
    MetricsStream,
    read_metrics,
)
from flexflow_tpu.obs.slo import SLOEngine, SLOPolicy  # noqa: E402
from flexflow_tpu.obs.spans import SPAN_SCHEMA, read_spans  # noqa: E402
from flexflow_tpu.serve import (  # noqa: E402
    ServeEngine,
    TrafficSpec,
    synthetic_requests,
)
from flexflow_tpu.serve.introspect import StatusServer  # noqa: E402

SLOTS, SEQ, VOCAB = 4, 48, 31
SHAPE = dict(hidden=32, heads=4, ff_dim=64, num_layers=2, vocab=VOCAB)
# the deterministic pin workload: batch arrival -> window count and
# token streams depend only on the seed, never on wall time
SPEC = TrafficSpec(
    n_requests=16, seed=0, rate_rps=0.0,
    prompt_len=(4, 8), max_new=(8, 16), vocab=VOCAB,
)
# the liveness workload: paced arrivals keep the engine serving for a
# fraction of a second of REAL time so mid-run polls land mid-run
LIVE_SPEC = TrafficSpec(
    n_requests=24, seed=1, rate_rps=40.0,
    prompt_len=(4, 8), max_new=(8, 16), vocab=VOCAB,
)


@pytest.fixture(autouse=True)
def _isolate_process_monitor():
    """The serve-driver tests here pass ``--metrics-out``, and FFModel
    construction wires the PROCESS-WIDE health monitor to the config —
    restore it afterwards so later test files keep the uninstrumented
    fast path (zero forced syncs, ``last_step_stats() is None``)."""
    before = get_monitor()
    yield
    set_monitor(before)


@pytest.fixture(scope="module")
def model():
    cfg = FFConfig(batch_size=SLOTS)
    m = FFModel(cfg)
    gpt_decoder(m, SLOTS, SEQ, use_flash=False, **SHAPE)
    m.compile(seed=0)
    return m


def _tokens(eng):
    return {r.id: list(r.tokens) for r in eng.sched.finished}


_VOLATILE = re.compile(r"(^t$|^t0$|^t1$|_s$|_ms$|per_s$)")


def _norm(x):
    """Strip every wall-clock-derived field (timestamps, durations,
    rates) so two runs of the same workload compare byte-identical."""
    if isinstance(x, dict):
        return {
            k: _norm(v) for k, v in x.items() if not _VOLATILE.search(k)
        }
    if isinstance(x, list):
        return [_norm(v) for v in x]
    return x


def _canon(records):
    return json.dumps([_norm(r) for r in records], sort_keys=True)


def _get(base, path, timeout=2.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


# ----------------------------------------------------- follow-mode tailing
def _write_rec(stream, i):
    stream.append({
        "schema": "ffmetrics/1", "step": i, "t": float(i),
        "pad": "x" * 80,  # forces frequent rotation at tiny max_mb
        "metrics": {"serve": {"queue_depth": i}},
    })


def test_follow_tails_live_appends_across_rotation(tmp_path):
    """The tailer sees every record exactly once, in order, while the
    writer rotates the live file underneath it."""
    path = str(tmp_path / "m.jsonl")
    got, stop = [], threading.Event()

    def consume():
        for rec in read_metrics(path, follow=True, poll_s=0.005,
                                stop=stop.is_set):
            got.append(rec["step"])

    th = threading.Thread(target=consume, daemon=True)
    th.start()  # starts before the file even exists
    s = MetricsStream(path, max_mb=0.0003)  # ~300 bytes per file
    for i in range(30):
        _write_rec(s, i)
        if i % 7 == 0:
            time.sleep(0.01)  # let the tailer cross a rotation live
    s.close()
    assert s.rotations >= 2
    deadline = time.time() + 10.0
    while len(got) < 30 and time.time() < deadline:
        time.sleep(0.01)
    stop.set()
    th.join(timeout=5.0)
    assert got == list(range(30))


def test_follow_catches_up_on_already_rotated_set(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = MetricsStream(path, max_mb=0.0003)
    for i in range(20):
        _write_rec(s, i)
    s.close()
    assert s.rotations >= 1
    # stop immediately: drain what is on disk, then end
    got = [r["step"] for r in read_metrics(path, follow=True,
                                           stop=lambda: True)]
    assert got == list(range(20))
    # non-follow read agrees
    assert [r["step"] for r in read_metrics(path)] == got


def test_follow_tolerates_torn_tail_until_completed(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": "ffmetrics/1", "step": 0}) + "\n")
        f.write('{"schema": "ffmetrics/1", "st')  # torn mid-write
    got = [r["step"] for r in read_metrics(path, follow=True,
                                           stop=lambda: True)]
    assert got == [0]  # the torn line is held, not mis-parsed
    with open(path, "a") as f:
        f.write('ep": 1}\n')  # the write completes
    got = [r["step"] for r in read_metrics(path, follow=True,
                                           stop=lambda: True)]
    assert got == [0, 1]


def test_read_spans_follow_filters_schema(tmp_path):
    path = str(tmp_path / "mixed.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": "ffmetrics/1", "step": 0}) + "\n")
        f.write(json.dumps({
            "schema": SPAN_SCHEMA, "name": "queue", "trace": "r0",
            "span": "r0/q", "parent": None, "t0": 0.0, "t1": 1.0,
        }) + "\n")
    out = list(read_spans(path, follow=True, stop=lambda: True))
    assert [s["schema"] for s in out] == [SPAN_SCHEMA]


def test_aggregator_ingest_follow(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = MetricsStream(path)
    for i in range(5):
        _write_rec(s, i)
    s.close()
    agg = MetricsAggregator()
    n = agg.ingest_follow("serve", path, stop=lambda: True)
    assert n == 5
    assert agg.aggregate_report()["fleet"]["sources"] == 1


# --------------------------------------------- on/off pin + mid-run polls
@pytest.fixture(scope="module")
def ops_ab(model, tmp_path_factory):
    """Three runs on one model: the pin pair (OFF without the ops
    plane, ON with StatusServer + SLOEngine attached, SAME workload),
    then a paced liveness run polled mid-flight from this thread."""
    d = tmp_path_factory.mktemp("introspect_ab")

    # OFF — no slo, no server
    m_off = str(d / "m_off.jsonl")
    s_off = str(d / "s_off.jsonl")
    eng_off = ServeEngine(
        model, slots=SLOTS, block_size=8, sync_every=4,
        metrics_out=m_off, spans_out=s_off,
    )
    rep_off = eng_off.run(synthetic_requests(SPEC))

    # ON — slo evaluating every window + live endpoints on an
    # ephemeral port (latency targets non-binding: host-speed-proof)
    m_on = str(d / "m_on.jsonl")
    s_on = str(d / "s_on.jsonl")
    alerts = str(d / "alerts.jsonl")
    slo = SLOEngine(
        SLOPolicy(max_queue_depth=2, fast_windows=2, slow_windows=4,
                  ttft_p99_ms=1e9, tpot_p99_ms=1e9),
        alerts_out=alerts,
    )
    eng_on = ServeEngine(
        model, slots=SLOTS, block_size=8, sync_every=4,
        metrics_out=m_on, spans_out=s_on, slo=slo,
    )
    srv = StatusServer(0)  # port 0 -> ephemeral, recorded on srv.port
    srv.attach(eng_on, slo=slo, metrics_path=m_on, spans_path=s_on,
               meta={"traffic": SPEC.identity})
    srv.start()
    rep_on = eng_on.run(synthetic_requests(SPEC))
    # freeze the pin streams and token maps BEFORE the liveness run
    # reuses the engine and appends to the same files
    pin = {
        "m_off": read_metrics(m_off), "m_on": read_metrics(m_on),
        "s_off": read_spans(s_off), "s_on": read_spans(s_on),
        "tok_off": _tokens(eng_off), "tok_on": _tokens(eng_on),
    }

    # liveness run: paced arrivals, polled while the thread serves
    base = f"http://127.0.0.1:{srv.port}"
    samples = {"/healthz": [], "/statusz": [], "/spanz?n=8": [],
               "/metricz": []}
    box = {}

    def serve():
        box["rep"] = eng_on.run(synthetic_requests(LIVE_SPEC))

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    while th.is_alive():
        for path in samples:
            try:
                samples[path].append(_get(base, path))
            except OSError:
                pass
        time.sleep(0.02)
    th.join()
    time.sleep(0.3)  # let the follower threads drain the file tails
    final = {p: _get(base, p) for p in samples}
    srv.close()
    slo.close()
    return dict(
        d=d, rep_off=rep_off, rep_on=rep_on, eng_off=eng_off,
        eng_on=eng_on, slo=slo, pin=pin, samples=samples, final=final,
        rep_live=box["rep"], alerts=alerts,
    )


def test_ops_plane_off_equals_on(ops_ab):
    """THE pin: attaching the SLO engine + status server changes no
    tokens, adds zero host syncs, and leaves both streams identical up
    to wall-clock timings."""
    ab = ops_ab
    assert ab["pin"]["tok_off"] == ab["pin"]["tok_on"]
    assert ab["rep_off"].host_syncs == ab["rep_on"].host_syncs
    assert ab["rep_off"].windows == ab["rep_on"].windows
    pin = ab["pin"]
    assert len(pin["m_off"]) == len(pin["m_on"])
    assert _canon(pin["m_off"]) == _canon(pin["m_on"])
    assert len(pin["s_off"]) == len(pin["s_on"])
    assert _canon(pin["s_off"]) == _canon(pin["s_on"])
    # and the overloaded pin run actually exercised the SLO engine
    assert ab["slo"].windows >= ab["rep_on"].windows
    assert ab["slo"].alerts_fired >= 1  # 16 reqs vs max_queue_depth=2


def test_endpoints_serve_live_data_mid_run(ops_ab):
    samples = ops_ab["samples"]
    for path, hits in samples.items():
        codes = [c for c, _, _ in hits]
        assert 200 in codes, f"{path} never answered mid-run: {codes}"
    # at least one mid-run /healthz caught the engine actively serving
    healths = [json.loads(b) for c, _, b in samples["/healthz"]
               if c == 200]
    assert any(h.get("state") == "serving" for h in healths)
    assert all(h["ok"] for h in healths)
    # /statusz carried a real window snapshot while the run was live
    stats = [json.loads(b) for c, _, b in samples["/statusz"] if c == 200]
    assert any(
        (s.get("snapshot") or {}).get("record") for s in stats
    )


def test_statusz_final_is_complete_and_truthful(ops_ab):
    code, ctype, body = ops_ab["final"]["/statusz"]
    assert code == 200 and ctype.startswith("application/json")
    doc = json.loads(body)
    # the run completed without a drain request: still "serving", with
    # an empty queue and no active requests (truthful, not "drained")
    assert doc["health"]["state"] == "serving"
    assert doc["health"]["queue_depth"] == 0
    assert doc["health"]["active"] == 0
    assert doc["meta"]["traffic"] == SPEC.identity
    # the follower tailed the file: fleet rollup has the serve source
    assert doc["fleet"]["sources"] >= 1
    assert "serve" in doc["sources"]
    # SLO state + scaling recommendation ride along
    assert doc["slo"]["windows"] == ops_ab["slo"].windows
    assert doc["alerts"], "overload alerts should surface in /statusz"
    assert doc["scaling"]["action"] in (
        "scale_up", "scale_down", "hold", "drain",
    )
    assert doc["scaling"]["reason"]


def test_spanz_returns_recent_spans(ops_ab):
    code, _, body = ops_ab["final"]["/spanz?n=8"]
    assert code == 200
    doc = json.loads(body)
    assert doc["n"] == len(doc["spans"]) <= 8
    assert doc["ring"] >= doc["n"] > 0
    for s in doc["spans"]:
        assert s["schema"] == SPAN_SCHEMA


def test_404_lists_endpoints(ops_ab):
    # the server is gone by test time; re-check shape on a fresh one
    with StatusServer(0) as srv:
        srv.start()
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=2)
            assert False, "expected HTTP 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            doc = json.loads(e.read())
            assert "/statusz" in doc["endpoints"]
        # unattached server is honest about being idle
        code, _, body = _get(f"http://127.0.0.1:{srv.port}", "/healthz")
        assert code == 200 and json.loads(body)["state"] == "idle"


# ------------------------------------------------------ /metricz grammar
def _assert_prometheus(text):
    """Validate Prometheus text exposition format 0.0.4: HELP/TYPE
    comment pairs, then ``name{labels} value`` samples whose family was
    declared, values parseable (incl. NaN/+Inf)."""
    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
        r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
        r' (\S+)$'
    )
    typed, samples = {}, 0
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            assert len(parts) >= 4, line
            if parts[1] == "TYPE":
                assert parts[3] in ("counter", "gauge", "histogram",
                                    "summary", "untyped"), line
                typed[parts[2]] = parts[3]
            continue
        assert not line.startswith("#"), f"stray comment: {line!r}"
        m = sample_re.match(line)
        assert m, f"malformed sample line: {line!r}"
        name = m.group(1)
        assert name in typed, f"sample {name} missing # TYPE"
        float(m.group(3))  # NaN/+Inf/-Inf all parse
        if typed[name] == "counter":
            assert name.endswith("_total"), name
        samples += 1
    assert samples > 0, "empty exposition"
    return typed


def test_metricz_is_valid_prometheus_exposition(ops_ab):
    code, ctype, body = ops_ab["final"]["/metricz"]
    assert code == 200
    assert ctype == "text/plain; version=0.0.4; charset=utf-8"
    typed = _assert_prometheus(body.decode())
    # the three vocabularies all render: window record, fleet rollup,
    # SLO/alert state
    assert any(n.startswith("ffmetrics_serve_") for n in typed)
    assert any(n.startswith("ffagg_fleet_") for n in typed)
    assert "ffalert_availability" in typed
    assert "ffalert_fired_total" in typed


# ------------------------------------------------ disagg duck-typing
class _FakeSched:
    queue_depth = 2
    active: dict = {}
    shed = 0


class _FakeEngine:
    def __init__(self, drained=False):
        self.windows = 3
        self._drain_requested = drained
        self.drained = drained
        self.watchdog_fires = 0
        self.sched = _FakeSched()
        self.publish_status = False
        self.status_snapshot = None


class _FakeCluster:
    def __init__(self):
        self.prefill = _FakeEngine()
        self.decode = _FakeEngine(drained=True)
        self.publish_status = False
        self.status_snapshot = {"split": "p4+d4", "pools": {}}


def test_cluster_health_covers_both_pools():
    """attach() flips publish_status on the cluster AND both pools, and
    /healthz rolls the per-pool state up (duck-typed — the same path a
    real DisaggregatedCluster takes through the serve driver)."""
    with StatusServer(0) as srv:
        cluster = _FakeCluster()
        srv.attach(cluster)
        assert cluster.publish_status
        assert cluster.prefill.publish_status
        assert cluster.decode.publish_status
        srv.start()
        code, _, body = _get(
            f"http://127.0.0.1:{srv.port}", "/healthz")
        assert code == 200
        doc = json.loads(body)
        assert set(doc["pools"]) == {"prefill", "decode"}
        assert doc["pools"]["prefill"]["queue_depth"] == 2
        assert doc["state"] == "drained"  # any drained pool wins
        code, _, body = _get(
            f"http://127.0.0.1:{srv.port}", "/statusz")
        assert json.loads(body)["snapshot"]["split"] == "p4+d4"


def test_poolz_reports_the_pool_and_its_relayouts(model):
    """/poolz: the K/V pool's geometry and ``pool_relayouts`` of the
    attached engine, computed on the first request and answered from
    memory afterwards (the programs are fixed at build)."""
    eng = ServeEngine(model, slots=SLOTS, block_size=8, sync_every=4,
                      attn="gather")
    with StatusServer(0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        srv.start()
        code, _, body = _get(base, "/poolz", timeout=60.0)
        assert code == 200 and json.loads(body) == {}  # nothing attached
        srv.attach(eng)
        code, _, body = _get(base, "/poolz", timeout=60.0)
        doc = json.loads(body)
        assert code == 200
        assert doc["pool_shape"] == list(eng.kv.cache_k.shape)
        assert doc["pool_shape"][-1] == eng.kv.heads * eng.kv.head_dim
        assert doc["block_size"] == 8 and doc["attn_kernel"] == "gather"
        assert doc["pool_relayouts"] == 0  # the CPU's scatter is in place
        assert doc["weight_casts"] == 0  # float32 compute: nothing to cast
        assert doc["attn_walk"] is None  # the gather arm walks no table
        first = srv._poolz
        _get(base, "/poolz", timeout=60.0)
        assert srv._poolz is first


# ------------------------------------------------- driver truthful startup
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_driver_status_port_conflict_exits_nonzero(capsys):
    from flexflow_tpu.serve.driver import main as serve_main

    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        rc = serve_main([
            "--requests", "2", "--serve-status-port", str(port),
        ])
    finally:
        blocker.close()
    assert rc == 1
    err = capsys.readouterr().err
    assert "cannot bind status port" in err
    assert str(port) in err
    assert "--serve-status-port" in err  # tells the user the fix


def test_driver_bad_policy_file_exits_nonzero(tmp_path, capsys):
    from flexflow_tpu.serve.driver import main as serve_main

    bad = tmp_path / "policy.json"
    bad.write_text("{not json")
    rc = serve_main([
        "--requests", "2", "--serve-slo-policy", str(bad),
    ])
    assert rc == 1
    assert "cannot load SLO policy" in capsys.readouterr().err


def test_driver_summary_carries_slo_and_scaling(tmp_path, capsys):
    from flexflow_tpu.serve.driver import main as serve_main

    out = tmp_path / "m.jsonl"
    alerts = tmp_path / "a.jsonl"
    rc = serve_main([
        "--requests", "3", "--serve-slots", "2", "--seq", "32",
        "--prompt-len", "2:4", "--gen-len", "2:4",
        "--metrics-out", str(out),
        "--serve-status-port", str(_free_port()),
        "--serve-alerts-out", str(alerts),
    ])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["requests_finished"] == 3
    assert doc["slo"]["windows"] >= 1
    assert 0.0 <= doc["slo"]["availability"] <= 1.0
    assert doc["scaling"]["action"] in (
        "scale_up", "scale_down", "hold", "drain",
    )
    assert doc["scaling"]["reason"]


# ------------------------------------------------------------- config
def test_config_flags_parse():
    cfg = FFConfig()
    rest = cfg.parse_args([
        "--serve-slo-policy", "p.json",
        "--serve-alerts-out", "a.jsonl",
        "--serve-status-port", "8017",
    ])
    assert rest == []
    assert cfg.serve_slo_policy == "p.json"
    assert cfg.serve_alerts_out == "a.jsonl"
    assert cfg.serve_status_port == 8017
    assert FFConfig().serve_status_port == 0  # off by default
