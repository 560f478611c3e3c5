"""Disaggregated prefill/decode serving (docs/SERVING.md).

Prefill is compute-bound (a forward pass over the whole prompt); decode
is weight-bound (every weight streams from HBM per token).  At scale
they belong on SEPARATE submeshes: a prefill pool sized for compute and
a decode pool sized for weight-streaming, connected by a KV handoff —
the reference repo's ``triton/`` Legion inference backend is the
precedent for serving as its own deployment topology.

:class:`DisaggregatedCluster` runs one prefill-only
:class:`~flexflow_tpu.serve.engine.ServeEngine` pool and one
decode-only pool (each keeps its own paged KV pool, scheduler, SLO
tiers, and one-host-sync-per-window flush discipline) and routes:

1. **admit** — arrivals enter the PREFILL pool's scheduler (tiered
   FIFO, unchanged);
2. **migrate** — a request that completes prefill (its first token
   flushed, TTFT stamped) is popped from the prefill pool, its KV
   spilled (:meth:`PagedKVCache.spill` — the dense, geometry-free
   payload), framed as digest-stamped ``ffkv/1`` bytes (wire.py), and
   offered to the :class:`~flexflow_tpu.serve.transport.Transport`
   (bounded — backpressure holds the payload host-side and retries);
3. **deliver** — frames whose priced DCN latency
   (:func:`~flexflow_tpu.search.cost.estimate_kv_handoff_time` on the
   cluster's :class:`~flexflow_tpu.parallel.network.NetworkedMachineModel`)
   has elapsed are digest-verified and re-queued on the DECODE pool as
   ``PREEMPTED`` requests — the scheduler's existing restore path
   scatters the payload into the decode pool's geometry (which may use
   a different ``block_size``; the payload is dense) and the request
   rejoins decode mid-stream, bit-exactly.

Greedy decode + bit-exact spill/restore ⇒ the cluster's per-request
token streams equal a colocated engine's byte for byte (the A/B test
pins this), while decode windows never interleave prefill chunks — the
interference the colocated engine pays under bursty arrivals.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.obs import SpanRecorder
from flexflow_tpu.serve.engine import ServeEngine, ServeReport, _pct
from flexflow_tpu.serve.scheduler import Request, RequestState
from flexflow_tpu.serve.transport import InProcessTransport, Transport
from flexflow_tpu.serve.wire import (
    HandoffError,
    decode_handoff,
    encode_handoff,
    kv_payload_nbytes,
)

__all__ = ["DisaggregatedCluster", "DisaggReport"]


@dataclasses.dataclass
class DisaggReport(ServeReport):
    """The cluster run artifact: the colocated report vocabulary plus
    the per-phase and handoff aggregates (bench/serve_report render
    these; absent fields on old streams stay absent — additive)."""

    split: str = ""  # "p{prefill_slots}+d{decode_slots}" (slots per pool)
    migrated: int = 0  # requests handed prefill -> decode
    migrated_kv_bytes: int = 0  # dense payload bytes across the wire
    handoff_p50_ms: Optional[float] = None
    handoff_p99_ms: Optional[float] = None
    # MEASURED send→deliver transit (PR 16) beside the priced values
    # above — populated only on traced runs (--serve-spans-out), so an
    # untraced cluster's report is unchanged
    handoff_observed_p50_ms: Optional[float] = None
    handoff_observed_p99_ms: Optional[float] = None
    transport_backpressure: int = 0  # bounded-queue send rejects
    prefill_windows: int = 0
    decode_windows: int = 0
    prefill_occupancy_mean: float = 0.0
    decode_occupancy_mean: float = 0.0


class DisaggregatedCluster:
    """A prefill pool + a decode pool over disjoint submeshes, with a
    priced KV handoff between them (module docstring).

    On CPU CI both pools typically share ONE compiled model (same
    weights — the bit-identity precondition); on real hardware each
    pool compiles its own strategy for its own submesh (the disagg
    search arm picks both, ``serve_price["disagg"]``).  The pools may
    use different KV geometries: ``decode_block_size`` etc. need not
    match the prefill pool's — the handoff payload is dense and
    restore re-chunks.
    """

    def __init__(
        self,
        model,
        *,
        decode_model=None,
        prefill_slots: int = 4,
        decode_slots: int = 4,
        prefill_block_size: int = 16,
        decode_block_size: int = 16,
        prefill_num_blocks: Optional[int] = None,
        decode_num_blocks: Optional[int] = None,
        prefill_chunk: int = 32,
        sync_every: int = 4,
        eos_id: Optional[int] = None,
        metrics_out: Optional[str] = None,
        machine=None,
        transport: Optional[Transport] = None,
        transport_capacity: int = 16,
        prefix_sharing: bool = True,
        slo_ms: float = 50.0,
        attn: str = "auto",
        kv_dtype: str = "fp32",
        weight_dtype: str = "fp32",
        spans_out: Optional[str] = None,
        metrics_max_mb: float = 0.0,
        slo=None,
    ) -> None:
        self.machine = machine
        # ONE shared ffspan/1 recorder for both pools (obs/spans.py):
        # same clock base, one span-id space, one stream — the decode
        # pool's spans parent under the prefill pool's via the trace
        # context the ffkv/1 frame carries.  None = tracing off; the
        # router then adds no work and no fields anywhere (pinned).
        self.spans = (
            SpanRecorder(spans_out, max_mb=metrics_max_mb)
            if spans_out else None
        )
        self.prefill = ServeEngine(
            model,
            slots=prefill_slots,
            block_size=prefill_block_size,
            num_blocks=prefill_num_blocks,
            prefill_chunk=prefill_chunk,
            sync_every=sync_every,
            eos_id=eos_id,
            metrics_out=metrics_out,
            prefix_sharing=prefix_sharing,
            slo_ms=slo_ms,
            attn=attn,
            kv_dtype=kv_dtype,
            weight_dtype=weight_dtype,
            phase="prefill",
            span_recorder=self.spans,
            metrics_max_mb=metrics_max_mb,
            slo=slo,
        )
        self.decode = ServeEngine(
            decode_model if decode_model is not None else model,
            slots=decode_slots,
            block_size=decode_block_size,
            num_blocks=decode_num_blocks,
            prefill_chunk=prefill_chunk,
            sync_every=sync_every,
            eos_id=eos_id,
            metrics_out=metrics_out,
            prefix_sharing=prefix_sharing,
            slo_ms=slo_ms,
            attn=attn,
            kv_dtype=kv_dtype,
            weight_dtype=weight_dtype,
            phase="decode",
            span_recorder=self.spans,
            metrics_max_mb=metrics_max_mb,
            slo=slo,
        )
        self.transport = (
            transport if transport is not None
            else InProcessTransport(capacity=transport_capacity)
        )
        # spilled-but-unsent payloads (transport backpressure): the
        # router's host-side hold buffer, (req_dict, frame, t_spill)
        self._outbox: List[Tuple[Dict[str, Any], bytes, float]] = []
        # per-migration audit trail the ffcheck handoff audit reads:
        # id, frame bytes, priced delay, digest_ok, restore_clean
        self.audit: List[Dict[str, Any]] = []
        self.migrated = 0
        self.migrated_kv_bytes = 0
        self.handoff_ms: List[float] = []
        # traced runs only: send-time stamps (req id -> (t_send_rel,
        # priced_delay_s)) and the measured send->deliver transits that
        # land beside the priced estimates in the report
        self._sent: Dict[int, Tuple[float, float]] = {}
        self.handoff_observed_ms: List[float] = []
        # ONE shared SLO engine for both pools (obs/slo.py — per-phase
        # counter deltas inside keep the two streams from double
        # counting); live introspection publishes a cluster-level
        # snapshot by atomic reference swap, same contract as the
        # engines' own (serve/introspect.py flips publish_status)
        self.slo = slo
        self.publish_status = False
        self.status_snapshot: Optional[Dict[str, Any]] = None

    def _now(self) -> float:
        return time.perf_counter()

    # --- routing ------------------------------------------------------------
    def _migrate(self, now_rel: float) -> None:
        """Pop every completed-prefill request out of the prefill pool
        (its first token flushed this window), spill its KV, and frame
        it for the wire.  Runs at the window boundary — the spill rides
        the same host-sync budget the preemption path uses."""
        sched = self.prefill.sched
        for slot in sorted(sched.active):
            req = sched.active[slot]
            if req.state is not RequestState.DECODE:
                continue
            # live KV positions: the full prompt (the first generated
            # token is the decode pool's first step input — no KV yet);
            # same arithmetic as drain()/preemption
            t_e0 = self.spans.now() if self.spans is not None else 0.0
            live = req.prompt_len + max(0, req.done_tokens - 1)
            kv = self.prefill.kv.spill(slot, live)
            del sched.active[slot]
            sched.free_slots.append(slot)
            req.slot = -1
            d = {
                "id": int(req.id),
                "prompt": np.asarray(req.prompt, np.int32),
                "max_new_tokens": int(req.max_new_tokens),
                "eos_id": req.eos_id,
                "tenant": req.tenant,
                "tier": req.tier,
                "deadline_ms": req.deadline_ms,
                "session": req.session,
                "preemptions": int(req.preemptions),
                "tokens": list(req.tokens),
                "kv_spill": kv,
                # latency bookkeeping crosses the wire with the request
                "arrival_s": req.arrival_s,
                "arrival_abs_s": req.arrival_abs_s,
                "t_submit": req.t_submit,
                "t_admitted": req.t_admitted,
                "t_first_token": req.t_first_token,
            }
            if self.spans is not None and req.trace_id is not None:
                # pre-allocate the encode span's id so the wire frame
                # can name it as the decode pool's parent — the span
                # itself is emitted below once the encode time is known
                enc_id = self.spans.next_id()
                d["trace"] = {
                    "trace_id": req.trace_id, "parent": enc_id,
                }
            frame = encode_handoff(d)
            self.migrated_kv_bytes += kv_payload_nbytes(kv)
            if self.spans is not None and req.trace_id is not None:
                self.spans.span(
                    "handoff_encode", req, t_e0, self.spans.now(),
                    pool="prefill", span_id=enc_id,
                    bytes=len(frame), kv_bytes=kv_payload_nbytes(kv),
                )
            self._outbox.append((d, frame, now_rel))

    def _pump(self, now_rel: float) -> None:
        """Send what the bounded queue will take, then deliver every
        frame whose priced DCN latency has elapsed into the decode
        pool's queue (digest-verified first)."""
        from flexflow_tpu.search.cost import estimate_kv_handoff_time

        still: List[Tuple[Dict[str, Any], bytes, float]] = []
        for d, frame, t_spill in self._outbox:
            delay = estimate_kv_handoff_time(len(frame), self.machine)
            if not self.transport.try_send(
                frame, now=now_rel, delay_s=delay,
            ):
                still.append((d, frame, t_spill))  # backpressure: retry
                continue
            if self.spans is not None and d.get("trace") is not None:
                self._sent[int(d["id"])] = (self.spans.now(), delay)
        self._outbox = still
        for frame in self.transport.recv_ready(now_rel):
            self._deliver(frame)

    def _deliver(self, frame: bytes) -> None:
        from flexflow_tpu.search.cost import estimate_kv_handoff_time

        t_d0 = self.spans.now() if self.spans is not None else 0.0
        delay_ms = estimate_kv_handoff_time(len(frame), self.machine) * 1e3
        entry: Dict[str, Any] = {
            "bytes": len(frame), "delay_ms": delay_ms,
            "digest_ok": False, "admitted": False,
        }
        self.audit.append(entry)
        try:
            d = decode_handoff(frame)  # digest-verified or raises
        except HandoffError as e:
            entry["error"] = str(e)
            return
        entry["digest_ok"] = True
        entry["id"] = int(d["id"])
        sched = self.decode.sched
        req = Request(
            prompt=d["prompt"],
            max_new_tokens=int(d["max_new_tokens"]),
            id=int(d["id"]),
            eos_id=d.get("eos_id"),
            tenant=d.get("tenant", "default"),
            tier=d.get("tier", "batch"),
            deadline_ms=d.get("deadline_ms"),
            session=d.get("session"),
        )
        req.tokens = [int(t) for t in d.get("tokens", ())]
        req.preemptions = int(d.get("preemptions", 0))
        req.arrival_s = float(d.get("arrival_s") or 0.0)
        req.arrival_abs_s = d.get("arrival_abs_s")
        req.t_submit = d.get("t_submit")
        req.t_admitted = d.get("t_admitted")
        req.t_first_token = d.get("t_first_token")
        req.kv_spill = d["kv_spill"]
        req.state = RequestState.PREEMPTED
        # wire-propagated trace context: adopt the prefill pool's trace
        # id BEFORE the fits check so a delivery-time reject still lands
        # in the request's timeline; the transit span parents under the
        # encode span the frame names, and measured transit sits beside
        # the priced estimate in its attrs
        tr = d.get("trace")
        sent = self._sent.pop(int(d["id"]), None)
        obs_ms: Optional[float] = None
        if self.spans is not None and tr is not None:
            req.trace_id = tr["trace_id"]
            req.span_parent = tr.get("parent")
            if sent is not None:
                obs_ms = (t_d0 - sent[0]) * 1e3
                self.handoff_observed_ms.append(obs_ms)
                transit_id = self.spans.span(
                    "handoff_transit", req, sent[0], t_d0,
                    parent=tr.get("parent"), pool="decode",
                    bytes=len(frame), priced_ms=delay_ms,
                    observed_ms=obs_ms,
                )
                if transit_id:
                    req.span_parent = transit_id
        # the decode pool's geometry differs from the prefill pool's —
        # re-check admissibility truthfully instead of assuming
        if not sched.kv.fits_with_sharing(req.max_len, req.prompt):
            sched._reject(
                req,
                self.spans.now() if self.spans is not None
                else self._now(),
            )
            return
        # bypass submit(): the request is mid-stream (PREEMPTED with a
        # payload), exactly the drain-resume convention
        sched._queues[req.tier].append(req)
        sched._next_id = max(sched._next_id, req.id) + 1
        if self.spans is not None and req.trace_id is not None:
            restore_id = self.spans.span(
                "handoff_restore", req, t_d0, self.spans.now(),
                pool="decode", bytes=len(frame),
            )
            if restore_id:
                req.span_parent = restore_id
            # decode-side queue wait starts at delivery, not at the
            # original submit — the queue span measures this admission
            req.t_enqueued = self.spans.now()
        entry["admitted"] = True
        self.migrated += 1
        self.handoff_ms.append(delay_ms)
        self.decode.note_handoff(
            delay_ms,
            self.decode.kv.blocks_for(req.kv_spill["length"]),
            len(frame),
            observed_ms=obs_ms,
        )

    def handoff_audit(self) -> List[Dict[str, Any]]:
        """The invariants ffcheck's handoff audit pins (ANALYSIS.md):
        every delivered frame digest-verified, no cross-pool KV-buffer
        donation (the pools' device arrays must be distinct — donating
        one pool's buffer into the other's program would corrupt both),
        no request simultaneously active in both pools, and both pools'
        CoW write-isolation clean.  Returns violation rows; empty ==
        safe."""
        out: List[Dict[str, Any]] = []
        for entry in self.audit:
            if not entry.get("digest_ok"):
                out.append({
                    "check": "handoff_digest",
                    "message": entry.get(
                        "error", "frame failed digest verification"
                    ),
                })
        # in-flight frames must already verify (tamper-on-the-wire)
        in_flight = getattr(self.transport, "in_flight", None)
        if in_flight is not None:
            for _ready_at, frame in in_flight():
                try:
                    decode_handoff(frame)
                except HandoffError as e:
                    out.append({
                        "check": "handoff_digest",
                        "message": f"in-flight frame: {e}",
                    })
        if (self.prefill.kv.cache_k is self.decode.kv.cache_k
                or self.prefill.kv.cache_v is self.decode.kv.cache_v):
            out.append({
                "check": "handoff_donation",
                "message": (
                    "prefill and decode pools share a KV device buffer "
                    "— cross-pool donation would corrupt both pools"
                ),
            })
        both = (
            {r.id for r in self.prefill.sched.active.values()}
            & {r.id for r in self.decode.sched.active.values()}
        )
        for rid in sorted(both):
            out.append({
                "check": "handoff_duplicate",
                "message": (
                    f"request {rid} active in BOTH pools — the router "
                    "must pop before it delivers"
                ),
            })
        for pool, eng in (
            ("prefill", self.prefill), ("decode", self.decode),
        ):
            for slot, idx, blk in eng.kv.shared_write_hazards():
                out.append({
                    "check": "serve_cow",
                    "message": (
                        f"{pool} pool slot{slot}/block{idx} writable "
                        f"but shared (physical {blk})"
                    ),
                })
        return out

    # --- the cluster loop ---------------------------------------------------
    def run(
        self, requests: Optional[Sequence[Request]] = None,
    ) -> DisaggReport:
        """Serve an open-loop workload through both pools until every
        request finishes (prefill-pool finishes included: a request
        whose budget is one token, or that hits EOS on its first token,
        never crosses the wire)."""
        pending = sorted(requests or (), key=lambda r: (r.arrival_s, r.id))
        t0 = self._now()
        if self.spans is not None:
            # the cluster owns the shared recorder's clock base — both
            # pools stamp spans on ONE run-relative timeline
            self.spans.set_base(t0)
        for eng in (self.prefill, self.decode):
            eng._refresh_weights()
            eng._t0 = t0
            eng.windows = eng.decode_steps = eng.prefill_chunks = 0
            eng.peak_active = 0
            eng._occ_sum = 0.0
        p_syncs0 = self.prefill.model.executor.host_syncs
        d_syncs0 = self.decode.model.executor.host_syncs
        same_exec = self.prefill.model.executor is self.decode.model.executor
        p_fin0 = len(self.prefill.sched.finished)
        d_fin0 = len(self.decode.sched.finished)
        rej0 = (
            len(self.prefill.sched.rejected)
            + len(self.decode.sched.rejected)
        )
        pre0 = self.prefill.sched.preemptions + self.decode.sched.preemptions
        self.migrated = 0
        self.migrated_kv_bytes = 0
        self.handoff_ms = []
        self.handoff_observed_ms = []
        self._sent = {}
        bp0 = getattr(self.transport, "send_rejects", 0)
        n_sub = 0
        while True:
            now = self._now() - t0
            while (n_sub < len(pending)
                   and pending[n_sub].arrival_s <= now):
                r = pending[n_sub]
                self.prefill.sched.submit(r, now=now)
                r.arrival_abs_s = t0 + r.arrival_s
                n_sub += 1
            self.prefill.sched.admit(now=now)
            if self.prefill.sched.active:
                self.prefill._window()
            self._migrate(self._now() - t0)
            self._pump(self._now() - t0)
            self.decode.sched.admit(now=self._now() - t0)
            if self.decode.sched.active:
                self.decode._window()
            self._pump(self._now() - t0)
            if self.publish_status:
                # cluster rollup beside the per-pool snapshots the
                # engines publish at their own window boundaries
                self.status_snapshot = {
                    "t": time.time(),
                    "split": (
                        f"p{self.prefill.slots}+d{self.decode.slots}"
                    ),
                    "pools": {
                        "prefill": self.prefill.status_snapshot,
                        "decode": self.decode.status_snapshot,
                    },
                    "migrated": self.migrated,
                    "migrated_kv_bytes": self.migrated_kv_bytes,
                    "outbox": len(self._outbox),
                    "transport_pending": self.transport.pending(),
                }
            if (n_sub >= len(pending)
                    and self.prefill.sched.idle
                    and not self._outbox
                    and self.transport.pending() == 0
                    and self.decode.sched.idle):
                break
            if (not self.prefill.sched.active
                    and not self.decode.sched.active):
                # idle until the next arrival or in-flight delivery
                waits = []
                if n_sub < len(pending):
                    waits.append(
                        pending[n_sub].arrival_s - (self._now() - t0)
                    )
                in_flight = getattr(self.transport, "in_flight", None)
                if in_flight is not None and self.transport.pending():
                    waits.append(
                        min(t for t, _ in in_flight())
                        - (self._now() - t0)
                    )
                dt = min(waits) if waits else 0.0
                if dt > 0:
                    time.sleep(min(dt, 0.05))
        wall = self._now() - t0
        fin = (
            self.prefill.sched.finished[p_fin0:]
            + self.decode.sched.finished[d_fin0:]
        )
        fin.sort(key=lambda r: r.id)
        syncs = (
            self.prefill.model.executor.host_syncs - p_syncs0
            if same_exec
            else (self.prefill.model.executor.host_syncs - p_syncs0)
            + (self.decode.model.executor.host_syncs - d_syncs0)
        )
        rep = self._report(wall, fin, syncs, rej0, pre0)
        rep.transport_backpressure = (
            getattr(self.transport, "send_rejects", 0) - bp0
        )
        self.prefill.metrics.close()
        self.decode.metrics.close()
        if self.spans is not None:
            self.spans.close()
        return rep

    def _report(
        self, wall: float, fin: List[Request], host_syncs: int,
        rej0: int, pre0: int,
    ) -> DisaggReport:
        lat = [r.latency_ms() for r in fin]
        new_tokens = sum(r.done_tokens for r in fin)
        per_tier: Dict[str, Dict[str, Any]] = {}
        for tier in sorted({r.tier for r in fin}):
            rs = [r.latency_ms() for r in fin if r.tier == tier]
            per_tier[tier] = {
                "finished": len(rs),
                "ttft_p50_ms": _pct([d["ttft_ms"] for d in rs], 50),
                "ttft_p99_ms": _pct([d["ttft_ms"] for d in rs], 99),
                "tpot_p99_ms": _pct([d["tpot_ms"] for d in rs], 99),
            }
        pw, dw = self.prefill.windows, self.decode.windows
        return DisaggReport(
            wall_s=wall,
            new_tokens=new_tokens,
            tok_s=new_tokens / wall if wall > 0 else 0.0,
            requests_finished=len(fin),
            requests_rejected=(
                len(self.prefill.sched.rejected)
                + len(self.decode.sched.rejected) - rej0
            ),
            ttft_p50_ms=_pct([d["ttft_ms"] for d in lat], 50),
            ttft_p99_ms=_pct([d["ttft_ms"] for d in lat], 99),
            tpot_p50_ms=_pct([d["tpot_ms"] for d in lat], 50),
            tpot_p99_ms=_pct([d["tpot_ms"] for d in lat], 99),
            occupancy_mean=(
                self.decode._occ_sum / dw if dw else 0.0
            ),
            windows=pw + dw,
            decode_steps=self.decode.decode_steps,
            prefill_chunks=self.prefill.prefill_chunks,
            host_syncs=host_syncs,
            per_request=[
                {
                    "id": r.id, "prompt_len": r.prompt_len,
                    "tokens": list(r.tokens), "reason": r.finish_reason,
                    "tenant": r.tenant, "tier": r.tier,
                    "preemptions": r.preemptions,
                    **r.latency_ms(),
                }
                for r in fin
            ],
            prefix_hit_rate=self.decode.kv.prefix_hit_rate,
            preemptions=(
                self.prefill.sched.preemptions
                + self.decode.sched.preemptions - pre0
            ),
            per_tier=per_tier,
            peak_active=max(
                self.prefill.peak_active, self.decode.peak_active,
            ),
            split=f"p{self.prefill.slots}+d{self.decode.slots}",
            migrated=self.migrated,
            migrated_kv_bytes=self.migrated_kv_bytes,
            handoff_p50_ms=_pct(self.handoff_ms, 50),
            handoff_p99_ms=_pct(self.handoff_ms, 99),
            handoff_observed_p50_ms=_pct(self.handoff_observed_ms, 50),
            handoff_observed_p99_ms=_pct(self.handoff_observed_ms, 99),
            prefill_windows=pw,
            decode_windows=dw,
            prefill_occupancy_mean=(
                self.prefill._occ_sum / pw if pw else 0.0
            ),
            decode_occupancy_mean=(
                self.decode._occ_sum / dw if dw else 0.0
            ),
        )
