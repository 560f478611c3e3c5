"""Live serve introspection: a read-only ops plane on ``--serve-status-port``.

A stdlib :class:`~http.server.ThreadingHTTPServer` (no new deps) bound
on localhost, serving five endpoints while an engine or cluster runs:

  ===========  =========================================================
  endpoint     body
  ===========  =========================================================
  /healthz     liveness + drain/shed state (JSON; always cheap)
  /statusz     the full picture: latest window snapshot, fleet rollup
               (``aggregate_report()["fleet"]``), SLO/alert/budget
               state, scaling recommendation, strategy + traffic
               identities (JSON)
  /spanz?n=    the last ``n`` ffspan/1 records (JSON; default 64)
  /metricz     Prometheus text exposition (obs/export.py)
  /poolz       the K/V pool's geometry, ``pool_relayouts`` (whole-pool
               copies in the compiled decode and prefill programs),
               ``weight_casts`` (converts of a float32 weight to the
               compute dtype in them: 0, the weights are handed over
               cast), ``expert_matmul`` (``kernel`` | ``xla``: which
               grouped matmul the expert layers run, by their widths),
               ``expert_relayouts`` (copies of a whole expert stack in
               them), ``attn_walk`` (the paged kernel's grid and
               pages a compute block), ``prefill_group_lanes`` and,
               once a window has run, ``prefill_lanes_computed``
               (lanes a prefill group computes; lanes the run's
               dispatches computed) and ``cross_decoder_rows`` (rows
               the layers after the last one that writes a pool
               computed; None without such layers) (JSON; compiles both programs
               anew on first use, on this server's own thread, then
               answers from memory)
  ===========  =========================================================

The zero-sync contract, stated once: the serve hot path NEVER talks to
this server.  At each window boundary — strictly after the window's
single host sync — the engine publishes an immutable snapshot dict by
plain reference assignment (``self.status_snapshot = snap``), which is
atomic in Python; the HTTP threads read whichever reference is current.
No locks, no queues, no syncs on the hot path, and the serve streams
stay byte-identical with the server on or off (pinned in
tests/test_introspect.py, the same way tests/test_spans.py pins
tracing).  Locks exist only on the server side, guarding ITS OWN
follower state (the rolling :class:`MetricsAggregator` and the span
ring fed by ``read_metrics(follow=True)`` tailers).

Startup is truthful: the constructor binds the port immediately, so a
port already in use raises ``OSError`` before any model is built — the
driver exits nonzero with the message instead of silently picking
another port.
"""

from __future__ import annotations

import json
import threading
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional
from urllib.parse import parse_qs, urlsplit

from flexflow_tpu.obs import get_tracer
from flexflow_tpu.obs.aggregate import MetricsAggregator
from flexflow_tpu.obs.export import render_prometheus
from flexflow_tpu.obs.metrics import json_safe, read_metrics
from flexflow_tpu.obs.slo import scaling_recommendation
from flexflow_tpu.obs.spans import SPAN_SCHEMA

__all__ = ["StatusServer"]


class _Handler(BaseHTTPRequestHandler):
    # the server loop must never block a serve window on a slow client;
    # ThreadingHTTPServer gives every request its own daemon thread
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt: str, *args: Any) -> None:
        pass  # stdout belongs to the driver's JSON summary line

    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, doc: Dict[str, Any], code: int = 200) -> None:
        body = json.dumps(
            json_safe(doc), sort_keys=True, allow_nan=False,
        ).encode()
        self._send(code, body, "application/json")

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        st: "StatusServer" = self.server.status  # type: ignore[attr-defined]
        url = urlsplit(self.path)
        try:
            if url.path == "/healthz":
                self._send_json(st.health())
            elif url.path == "/statusz":
                self._send_json(st.statusz())
            elif url.path == "/spanz":
                q = parse_qs(url.query)
                n = int(q.get("n", ["64"])[0])
                self._send_json(st.spanz(n))
            elif url.path == "/poolz":
                self._send_json(st.poolz())
            elif url.path == "/metricz":
                self._send(
                    200, st.metricz().encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            else:
                self._send_json(
                    {"error": f"no such endpoint {url.path!r}",
                     "endpoints": [
                         "/healthz", "/statusz", "/spanz", "/metricz",
                         "/poolz",
                     ]},
                    code=404,
                )
        except BrokenPipeError:
            pass  # client went away mid-response; nothing to clean up
        except Exception as e:  # a handler bug must not kill the server
            try:
                self._send_json(
                    {"error": f"{type(e).__name__}: {e}"}, code=500,
                )
            except Exception:
                pass


class StatusServer:
    """The introspection server (module docstring).

    Lifecycle::

        srv = StatusServer(port)          # binds NOW — OSError on conflict
        srv.attach(engine, slo=slo, metrics_path=..., spans_path=...)
        srv.start()                       # HTTP + follower threads
        ...                               # engine.run() — zero syncs added
        srv.close()

    ``attach`` flips the target's ``publish_status`` flag (and both
    pools' for a :class:`DisaggregatedCluster`), which is all the hot
    path ever sees of this server.
    """

    SPAN_RING = 512  # /spanz keeps this many most-recent spans

    def __init__(self, port: int, host: str = "127.0.0.1") -> None:
        # bind in the constructor: a conflict surfaces as OSError here,
        # before any model compile — the driver's truthful-failure path
        self.httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self.httpd.status = self  # type: ignore[attr-defined]
        self.httpd.daemon_threads = True
        self.host = host
        self.port = int(self.httpd.server_address[1])
        self._target: Optional[Any] = None
        self._slo: Optional[Any] = None
        self._meta: Dict[str, Any] = {}
        self._metrics_path: Optional[str] = None
        self._spans_path: Optional[str] = None
        # follower state — server-side only, behind the server's lock
        self._lock = threading.Lock()
        self._agg = MetricsAggregator()
        self._last_record: Optional[Dict[str, Any]] = None
        self._spans: deque = deque(maxlen=self.SPAN_RING)
        self._closing = False
        self._threads: list = []
        self._poolz: Optional[Dict[str, Any]] = None  # fixed at build

    # --- wiring -------------------------------------------------------
    def attach(
        self,
        target: Any,
        slo: Optional[Any] = None,
        metrics_path: Optional[str] = None,
        spans_path: Optional[str] = None,
        meta: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Point the server at an engine or cluster (duck-typed: a
        cluster has ``prefill``/``decode`` pools) and, optionally, the
        stream files to live-tail and the run identities for
        ``/statusz``."""
        self._target = target
        self._slo = slo
        self._metrics_path = metrics_path
        self._spans_path = spans_path
        self._meta = dict(meta or {})
        target.publish_status = True
        for pool in ("prefill", "decode"):
            eng = getattr(target, pool, None)
            if eng is not None and hasattr(eng, "publish_status"):
                eng.publish_status = True

    def start(self) -> "StatusServer":
        t = threading.Thread(
            target=self.httpd.serve_forever, name="statusz-http",
            daemon=True,
        )
        t.start()
        self._threads.append(t)
        if self._metrics_path:
            t = threading.Thread(
                target=self._follow_metrics, name="statusz-metrics",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        if self._spans_path and self._spans_path != self._metrics_path:
            t = threading.Thread(
                target=self._follow_spans, name="statusz-spans",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        return self

    def close(self) -> None:
        self._closing = True
        try:
            self.httpd.shutdown()
        finally:
            self.httpd.server_close()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []

    def __enter__(self) -> "StatusServer":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # --- follower threads (rotation-aware live tailing) ---------------
    def _follow_metrics(self) -> None:
        for rec in read_metrics(
            self._metrics_path, follow=True, stop=lambda: self._closing,
        ):
            if rec.get("schema") == SPAN_SCHEMA:
                # spans share the reader contract; when both streams
                # are one file this single tailer feeds both views
                with self._lock:
                    self._spans.append(rec)
                continue
            with self._lock:
                src = (
                    ((rec.get("metrics") or {}).get("serve") or {})
                    .get("phase") or "serve"
                )
                self._agg.ingest(src, rec)
                self._last_record = rec

    def _follow_spans(self) -> None:
        for rec in read_metrics(
            self._spans_path, follow=True, stop=lambda: self._closing,
        ):
            if rec.get("schema") != SPAN_SCHEMA:
                continue
            with self._lock:
                self._spans.append(rec)

    # --- endpoint bodies ----------------------------------------------
    @staticmethod
    def _engine_health(eng: Any) -> Dict[str, Any]:
        return {
            "windows": eng.windows,
            "drain_requested": bool(eng._drain_requested),
            "drained": bool(eng.drained),
            "watchdog_fires": eng.watchdog_fires,
            "shed_total": eng.sched.shed,
            "queue_depth": eng.sched.queue_depth,
            "active": len(eng.sched.active),
        }

    def health(self) -> Dict[str, Any]:
        t = self._target
        if t is None:
            return {"ok": True, "state": "idle"}
        if hasattr(t, "prefill") and hasattr(t, "decode"):
            pools = {
                "prefill": self._engine_health(t.prefill),
                "decode": self._engine_health(t.decode),
            }
            drained = any(p["drained"] for p in pools.values())
            draining = any(p["drain_requested"] for p in pools.values())
            doc: Dict[str, Any] = {"pools": pools}
        else:
            doc = self._engine_health(t)
            drained, draining = doc["drained"], doc["drain_requested"]
        doc["ok"] = True
        doc["state"] = (
            "drained" if drained else "draining" if draining else "serving"
        )
        return doc

    def statusz(self) -> Dict[str, Any]:
        with self._lock:
            report = self._agg.aggregate_report()
            alerts_tail = (
                list(self._slo.alerts[-16:]) if self._slo is not None
                else []
            )
        slo_state = self._slo.state() if self._slo is not None else None
        doc: Dict[str, Any] = {
            "health": self.health(),
            "snapshot": getattr(self._target, "status_snapshot", None),
            "fleet": report["fleet"],
            "sources": report["sources"],
            "slo": slo_state,
            "alerts": alerts_tail,
            "meta": self._meta,
        }
        if self._slo is not None:
            doc["scaling"] = scaling_recommendation(
                report, self._slo.policy,
            )
        return doc

    @staticmethod
    def _engine_pool(eng: Any) -> Dict[str, Any]:
        out = {
            "pool_shape": list(eng.kv.cache_k.shape),
            "kv_dtype": eng.kv.kv_dtype,
            "block_size": eng.kv.block_size,
            "attn_kernel": eng.attn_kernel,
            "pool_relayouts": eng.pool_relayouts(),
            "weight_casts": eng.weight_casts(),
            "expert_matmul": eng.expert_matmul(),
            "expert_relayouts": eng.expert_relayouts(),
            "attn_walk": eng.attn_walk(),
            "hbm_bytes": eng.kv.hbm_bytes(),
            "prefill_group_lanes": eng.prefill_group_lanes,
        }
        if eng.kv.window_layers:
            # the window group: its own pool, a ring a slot
            out["window_pool_shape"] = list(eng.kv.win_k.shape)
            out["window"] = eng.kv.window
            out["ring_blocks"] = eng.kv.ring_blocks
        if eng.kv.state_layers:
            # the state group: two arrays a layer, a row a slot
            out["state_layers"] = eng.kv.state_layers
            conv = eng.kv.state_conv[0]
            out["state_conv_shape"] = None if conv is None else list(conv.shape)
            out["state_ssm_shape"] = list(eng.kv.state_ssm[0].shape)
            out["state_pool_bytes"] = eng.kv.state_bytes()
            out["state_bytes_per_slot"] = eng.kv.state_bytes_per_slot
        return out

    def poolz(self) -> Dict[str, Any]:
        t = self._target
        if t is None:
            return {}
        if self._poolz is None:
            if hasattr(t, "prefill") and hasattr(t, "decode"):
                self._poolz = {"pools": {
                    "prefill": self._engine_pool(t.prefill),
                    "decode": self._engine_pool(t.decode),
                }}
            else:
                self._poolz = self._engine_pool(t)
        # geometry is fixed at build; what the groups hold and the expert
        # layers' counters are the newest window's (the /statusz snapshot)
        snap = getattr(t, "status_snapshot", None)
        if isinstance(snap, dict) and "kv_pages_held" in snap:
            return dict(self._poolz, kv_pages_held=snap["kv_pages_held"],
                        moe=snap.get("moe"),
                        prefill_lanes_computed=snap.get("prefill_lanes_computed"),
                        cross_decoder_rows=snap.get("cross_decoder_rows"))
        return self._poolz

    def spanz(self, n: int = 64) -> Dict[str, Any]:
        with self._lock:
            tail = list(self._spans)[-max(0, n):]
            total = len(self._spans)
        return {"spans": tail, "ring": total, "n": len(tail)}

    def metricz(self) -> str:
        with self._lock:
            rec = self._last_record
            fleet = self._agg.aggregate_report()["fleet"]
        # the live snapshot beats the file tail when both exist — same
        # vocabulary, zero staleness
        snap = getattr(self._target, "status_snapshot", None)
        if isinstance(snap, dict) and isinstance(snap.get("record"), dict):
            rec = snap["record"]
        tracer = get_tracer()
        return render_prometheus(
            record=rec,
            fleet=fleet if fleet.get("sources") else None,
            slo_state=self._slo.state() if self._slo is not None else None,
            counters=dict(tracer.counters) if tracer.enabled else None,
        )
