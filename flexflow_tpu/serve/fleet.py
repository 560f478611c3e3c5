"""Fleet tier: a multi-replica serving control plane (docs/SERVING.md).

One :class:`~flexflow_tpu.serve.engine.ServeEngine` (or disagg cluster)
is a single cell; millions of users need many.  :class:`FleetRouter`
fronts N replica engines and composes five prior PRs' seams into a
control plane, without touching the data plane they pinned:

* **Prefix-cache-aware routing** — each replica exports a bounded
  prefix-residency digest at its window boundary (the PR-11
  cumulative-hash keys already in ``PagedKVCache._index``); the router
  scores a request by how many of its leading FULL blocks are resident
  per replica and sends it where the most consecutive blocks hit,
  falling back to least-queue-depth on zero hits.  ``round_robin`` and
  ``least_loaded`` are the baseline policies the fleet A/B compares
  against.
* **Session affinity + live KV migration** — a multi-turn session
  (``Request.session``, traffic.py ``session_turns``) follows its KV:
  follow-up turns route to the session's home replica.  When that home
  drains (autoscaler) or spillover rebalances, the session's live
  blocks spill (the drain/preemption arithmetic) and cross
  replica→replica as digest-stamped ``ffkv/1`` frames over the same
  :class:`~flexflow_tpu.serve.transport.Transport` seam the disagg
  handoff uses — generation continues bit-identically on the
  destination (greedy decode + bit-exact spill/restore, the currency
  every serve PR trades in).
* **SLO-tiered spillover** — an interactive request whose chosen
  replica is over the policy's queue bound spills to the least-loaded
  healthy replica instead; batch requests rely on the engines' own
  truthful shedding (reasons preserved verbatim).
* **Closed-loop autoscaling** — every replica's window records tee
  into one :class:`~flexflow_tpu.obs.aggregate.MetricsAggregator` (the
  in-process equivalent of tailing its ``ffmetrics/1`` stream);
  :class:`FleetAutoscaler` periodically calls
  :func:`~flexflow_tpu.obs.slo.scaling_recommendation` on the rollup
  and ACTS: ``scale_up`` builds a replica through the normal engine
  warmup, ``scale_down``/``drain`` raises the PR-12 drain flag
  (``request_drain`` — the SIGTERM discipline) on the emptiest replica;
  the router evacuates its sessions at the next window boundary, then
  retires it and calls ``MetricsAggregator.remove_source`` so stale
  gauges stop feeding the next recommendation.

Every router decision, migration, delivery, and scaling action is one
record on the versioned ``fffleet/1`` JSONL stream (``--fleet-out``;
``tools/serve_report.py --fleet`` renders it).

**The one-sync-per-window contract survives.**  The router only ever
reads window-boundary snapshots (digest/queue/occupancy refreshed
strictly after each replica's ``_window()``, which already paid its one
host sync), and spills ride the same host-side path preemption uses —
so the fleet adds ZERO host syncs (ledger-pinned: syncs == windows) and
each replica's token streams stay bit-identical to a solo engine served
the same admission order (pinned by the A/B identity test).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.obs.aggregate import MetricsAggregator
from flexflow_tpu.obs.metrics import MetricsStream, read_metrics
from flexflow_tpu.obs.slo import SLOPolicy, scaling_recommendation
from flexflow_tpu.serve.engine import ServeEngine, ServeReport, _pct
from flexflow_tpu.serve.scheduler import Request, RequestState
from flexflow_tpu.serve.transport import InProcessTransport
from flexflow_tpu.serve.wire import (
    HandoffError,
    decode_handoff,
    encode_handoff,
    kv_payload_nbytes,
)

__all__ = [
    "FLEET_SCHEMA",
    "ROUTING_POLICIES",
    "FleetRouter",
    "FleetAutoscaler",
    "FleetReport",
    "read_fleet",
]

# fleet decision stream schema id: bump ONLY on incompatible layout
# changes (adding event fields is compatible — readers use .get)
FLEET_SCHEMA = "fffleet/1"

ROUTING_POLICIES = ("prefix", "round_robin", "least_loaded")

# bound on the per-replica prefix-residency digest the router keeps: a
# replica with more indexed blocks exports its newest keys only, so the
# router's per-window snapshot cost stays O(bound), not O(pool)
DIGEST_MAX_KEYS = 4096


def read_fleet(path: str) -> List[Dict[str, Any]]:
    """Parse an ``fffleet/1`` stream (rotation-aware, torn-tail
    tolerant — the shared :func:`read_metrics` contract); foreign
    records in the file are skipped, not crashed on."""
    return [
        r for r in read_metrics(path) if r.get("schema") == FLEET_SCHEMA
    ]


@dataclasses.dataclass
class FleetReport(ServeReport):
    """The fleet run artifact: the engine report vocabulary plus the
    control-plane aggregates (bench/serve_report render these; absent
    fields on old records stay absent — additive)."""

    replicas: int = 0  # live replicas at end of run
    replicas_peak: int = 0
    routing: str = ""
    routed: Dict[str, int] = dataclasses.field(default_factory=dict)
    prefix_routed: int = 0  # requests placed by a prefix-digest hit
    # pooled across every replica's PagedKVCache (sum hits/sum lookups)
    fleet_prefix_hit_rate: Optional[float] = None
    migrations: int = 0  # replica→replica ffkv/1 deliveries admitted
    migrated_kv_bytes: int = 0
    spillovers: int = 0  # SLO-tiered cross-replica spills
    scale_ups: int = 0
    scale_downs: int = 0
    sessions: int = 0  # distinct session ids routed
    per_replica: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict
    )


class _TeeMetrics:
    """In-process stand-in for live-tailing a replica's ``ffmetrics/1``
    file (``MetricsAggregator.ingest_follow``): wraps the engine's
    stream so every window record ALSO folds into the fleet aggregator
    the moment it is built.  ``enabled`` is forced True so the engine
    builds its window record even with no file attached — the record is
    the autoscaler's signal, file or not; the wrapped stream still only
    writes when a path was configured."""

    def __init__(
        self, inner: MetricsStream, agg: MetricsAggregator, source: str,
    ) -> None:
        self.inner, self.agg, self.source = inner, agg, source
        self.enabled = True

    def append(self, record: Dict[str, Any]) -> None:
        if self.inner.enabled:
            self.inner.append(record)
        self.agg.ingest(self.source, record)

    def close(self) -> None:
        self.inner.close()


class _Replica:
    """One engine behind the router, plus the window-boundary snapshot
    the routing policies read (the one-sync contract: decisions consume
    ONLY this snapshot, never the live scheduler mid-window)."""

    def __init__(self, name: str, engine: ServeEngine, inbox) -> None:
        self.name = name
        self.engine = engine
        self.inbox = inbox  # Transport carrying frames TO this replica
        self.routed = 0
        self.draining = False  # evacuation pending at next boundary
        self.retired = False  # drained, removed from the aggregator
        self.fin0 = len(engine.sched.finished)
        self.rej0 = len(engine.sched.rejected)
        self.pre0 = engine.sched.preemptions
        # window-boundary snapshot (refreshed after _window's one sync)
        self.digest: frozenset = frozenset()
        self.queue_depth = 0
        self.active = 0

    @property
    def load(self) -> int:
        return self.queue_depth + self.active

    def refresh_snapshot(self) -> None:
        """Export the bounded prefix-residency digest + load gauges.
        Host-side dict reads only — zero device interaction."""
        idx = self.engine.kv._index
        if len(idx) > DIGEST_MAX_KEYS:
            # newest keys win: recent prompts are the likeliest repeats
            keys = list(idx.keys())[-DIGEST_MAX_KEYS:]
            self.digest = frozenset(keys)
        else:
            self.digest = frozenset(idx.keys())
        self.queue_depth = self.engine.sched.queue_depth
        self.active = len(self.engine.sched.active)


class FleetAutoscaler:
    """The closed loop: fleet rollup → recommendation → action.

    Pure decision state lives here (cadence, cooldown, bounds); the
    router owns execution (building engines, raising drain flags) so
    the autoscaler stays testable as a policy object."""

    def __init__(
        self,
        policy: SLOPolicy,
        aggregator: MetricsAggregator,
        *,
        min_replicas: int = 1,
        max_replicas: int = 8,
        decide_every: int = 4,
        cooldown: int = 8,
    ) -> None:
        assert min_replicas >= 1 and max_replicas >= min_replicas
        self.policy = policy
        self.agg = aggregator
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.decide_every = max(1, int(decide_every))
        self.cooldown = max(0, int(cooldown))
        self._last_action_tick: Optional[int] = None
        self.actions: List[Dict[str, Any]] = []

    def decide(self, tick: int, n_live: int) -> Optional[Dict[str, str]]:
        """The recommendation to act on this tick, or None (off-cadence,
        cooling down, or the action is a no-op at the replica bounds).
        The returned dict is ``scaling_recommendation``'s verbatim —
        truthful reason included."""
        if tick % self.decide_every != 0:
            return None
        if (self._last_action_tick is not None
                and tick - self._last_action_tick < self.cooldown):
            return None
        rec = scaling_recommendation(self.agg.aggregate_report(),
                                     self.policy)
        action = rec["action"]
        if action == "scale_up" and n_live < self.max_replicas:
            return rec
        if action in ("scale_down", "drain") and n_live > self.min_replicas:
            return rec
        return None

    def acted(self, tick: int, rec: Dict[str, str]) -> None:
        self._last_action_tick = tick
        self.actions.append(dict(rec))


class FleetRouter:
    """N replica engines behind one admission point (module docstring).

    On CPU CI every replica shares ONE compiled model (same weights —
    the bit-identity precondition, exactly the disagg pools'
    arrangement); on real hardware each replica is its own host process
    and the Transport seam carries the frames for real.  All replicas
    use the same KV geometry (one ``block_size``), which is what makes
    the cumulative-hash prefix keys comparable across replicas and the
    migration payload restorable anywhere.
    """

    def __init__(
        self,
        model,
        *,
        replicas: int = 2,
        routing: str = "prefix",
        slots: Optional[int] = None,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefill_chunk: int = 32,
        sync_every: int = 4,
        eos_id: Optional[int] = None,
        metrics_out: Optional[str] = None,
        fleet_out: Optional[str] = None,
        machine=None,
        prefix_sharing: bool = True,
        slo_ms: float = 50.0,
        attn: str = "auto",
        kv_dtype: str = "fp32",
        weight_dtype: str = "fp32",
        metrics_max_mb: float = 0.0,
        slo=None,
        policy: Optional[SLOPolicy] = None,
        autoscale: bool = False,
        min_replicas: int = 1,
        max_replicas: int = 8,
        autoscale_every: int = 4,
        autoscale_cooldown: int = 8,
        transport_capacity: int = 16,
    ) -> None:
        if routing not in ROUTING_POLICIES:
            raise ValueError(
                f"unknown routing policy {routing!r}; "
                f"choose one of {ROUTING_POLICIES}"
            )
        assert replicas >= 1
        self.model = model
        self.routing = routing
        self.machine = machine
        # shared SLO burn-rate engine (obs/slo.py): every replica feeds
        # it — per-phase deltas inside keep N streams from double
        # counting, exactly the disagg arrangement
        self.slo = slo
        self.policy = policy or (
            slo.policy if slo is not None else SLOPolicy()
        )
        self.agg = MetricsAggregator()
        self.stream = MetricsStream(fleet_out, max_mb=metrics_max_mb)
        self.events: List[Dict[str, Any]] = []
        self._engine_kwargs = dict(
            slots=slots, block_size=block_size, num_blocks=num_blocks,
            prefill_chunk=prefill_chunk, sync_every=sync_every,
            eos_id=eos_id, prefix_sharing=prefix_sharing, slo_ms=slo_ms,
            attn=attn, kv_dtype=kv_dtype, weight_dtype=weight_dtype,
            metrics_max_mb=metrics_max_mb,
        )
        self._metrics_base = metrics_out
        self._transport_capacity = int(transport_capacity)
        self.replicas: Dict[str, _Replica] = {}
        self._n_created = 0
        self._rr = 0  # round-robin cursor
        self._next_id = 0  # fleet-wide ids for id-less submissions
        self.session_home: Dict[str, str] = {}
        # (dest replica name, request dict, ffkv/1 frame, t_spill) — the
        # host-side hold buffer under transport backpressure
        self._outbox: List[Tuple[str, Dict[str, Any], bytes, float]] = []
        # per-delivery audit trail (digest_ok/admitted — the disagg
        # handoff-audit convention, replica→replica edition)
        self.audit: List[Dict[str, Any]] = []
        self.migrations = 0
        self.migrated_kv_bytes = 0
        self.spillovers = 0
        self.prefix_routed = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self.replicas_peak = 0
        self.autoscaler = (
            FleetAutoscaler(
                self.policy, self.agg,
                min_replicas=min_replicas, max_replicas=max_replicas,
                decide_every=autoscale_every, cooldown=autoscale_cooldown,
            )
            if autoscale else None
        )
        self._t0: Optional[float] = None
        for _ in range(int(replicas)):
            self._add_replica()

    def _now(self) -> float:
        return time.perf_counter()

    def _event(self, event: str, t: float, **fields: Any) -> None:
        rec: Dict[str, Any] = {
            "schema": FLEET_SCHEMA, "event": event,
            "t": round(float(t), 6),
        }
        rec.update(fields)
        self.events.append(rec)
        self.stream.append(rec)

    # --- replica lifecycle --------------------------------------------------
    def _add_replica(self) -> _Replica:
        """Build one replica through the NORMAL engine construction (the
        model is already compiled — engine warmup is pool allocation +
        scheduler state, which is exactly what a warm scale-up is)."""
        name = f"replica{self._n_created}"
        self._n_created += 1
        eng = ServeEngine(
            self.model,
            metrics_out=(
                f"{self._metrics_base}.{name}"
                if self._metrics_base else None
            ),
            phase=name,
            slo=self.slo,
            **self._engine_kwargs,
        )
        # tee every window record into the fleet aggregator (the
        # autoscaler's signal) without touching what the file says
        eng.metrics = _TeeMetrics(eng.metrics, self.agg, name)
        rep = _Replica(
            name, eng,
            InProcessTransport(capacity=self._transport_capacity),
        )
        self.replicas[name] = rep
        if self._t0 is not None:
            # joined mid-run: adopt the run clock + fresh counters, the
            # same reset run()/the cluster loop performs at start
            eng._t0 = self._t0
            eng.windows = eng.decode_steps = eng.prefill_chunks = 0
            eng.peak_active = 0
            eng._occ_sum = 0.0
        self.replicas_peak = max(self.replicas_peak, len(self._live()))
        return rep

    def _live(self) -> List[_Replica]:
        return [r for r in self.replicas.values() if not r.retired]

    def _routable(self) -> List[_Replica]:
        return [
            r for r in self.replicas.values()
            if not r.retired and not r.draining
        ]

    # --- routing ------------------------------------------------------------
    def _prefix_target(
        self, req: Request, live: List[_Replica],
    ) -> Tuple[_Replica, str]:
        """Most consecutive leading full blocks resident wins; ties go
        to the lighter replica; zero hits anywhere falls back to
        least-queue-depth.  Remaining fallback ties rotate through the
        round-robin cursor rather than pinning to the first name — a
        cold fleet would otherwise herd every tenant's FIRST request
        (no digests yet) onto one replica, and every later hit would
        keep them there; rotation spreads distinct prefixes across
        replicas while hits still pin each repeat to its blocks."""
        kv0 = live[0].engine.kv
        nb = kv0.shareable_blocks(req.prompt)
        keys = [kv0._prefix_key(req.prompt, b + 1) for b in range(nb)]
        best: Optional[_Replica] = None
        best_score = 0
        for rep in live:
            score = 0
            for k in keys:
                if k in rep.digest:
                    score += 1
                else:
                    break
            if score > best_score or (
                score == best_score and score > 0 and best is not None
                and (rep.load, rep.name) < (best.load, best.name)
            ):
                best, best_score = rep, score
        if best is None or best_score == 0:
            qmin = min(r.queue_depth for r in live)
            cands = [r for r in live if r.queue_depth == qmin]
            lmin = min(r.load for r in cands)
            cands = [r for r in cands if r.load == lmin]
            rep = cands[self._rr % len(cands)]
            self._rr += 1
            return rep, "prefix_miss_least_queue"
        self.prefix_routed += 1
        return best, f"prefix_hit:{best_score}"

    def _route_target(self, req: Request) -> Tuple[_Replica, str]:
        live = self._routable()
        assert live, "no routable replicas"
        if req.session is not None:
            home = self.session_home.get(req.session)
            rep = self.replicas.get(home) if home is not None else None
            if rep is not None and not rep.retired and not rep.draining:
                return rep, "affinity"
        if self.routing == "round_robin":
            rep = live[self._rr % len(live)]
            self._rr += 1
            return rep, "round_robin"
        if self.routing == "least_loaded":
            return min(live, key=lambda r: (r.load, r.name)), "least_loaded"
        return self._prefix_target(req, live)

    def route(self, req: Request, now: float = 0.0) -> _Replica:
        """Place one request on a replica (and submit it there).  The
        decision reads ONLY window-boundary snapshots; the submit itself
        is the scheduler's normal host-side path."""
        if req.id < 0:
            req.id = self._next_id
        self._next_id = max(self._next_id, req.id) + 1
        rep, reason = self._route_target(req)
        # SLO-tiered spillover: an interactive request never queues
        # behind an over-bound backlog while a healthy replica has room
        # — it spills to the least-loaded one FIRST (batch relies on
        # the engines' own shedding, reasons preserved verbatim)
        if (req.tier == "interactive"
                and rep.queue_depth > self.policy.max_queue_depth):
            alt = min(self._routable(), key=lambda r: (r.load, r.name))
            if alt is not rep:
                self.spillovers += 1
                self._event(
                    "spillover", now, request=int(req.id),
                    src=rep.name, dst=alt.name, tier=req.tier,
                    reason=(
                        f"queue depth {rep.queue_depth} on {rep.name} "
                        f"over policy max {self.policy.max_queue_depth}"
                    ),
                )
                rep, reason = alt, "spillover"
        rep.routed += 1
        if req.session is not None:
            self.session_home[req.session] = rep.name
        rep.engine.sched.submit(req, now=now)
        self._event(
            "route", now, request=int(req.id), replica=rep.name,
            policy=self.routing, reason=reason, tier=req.tier,
            session=req.session,
        )
        return rep

    # --- migration (replica → replica over ffkv/1) --------------------------
    def _frame_out(
        self, rep: _Replica, req: Request, dest: _Replica, now_rel: float,
        why: str,
    ) -> None:
        """Spill one ACTIVE request off ``rep`` and frame it for
        ``dest`` — the drain()/preemption spill arithmetic, then the
        disagg wire discipline.  Queued requests never come through
        here (they carry no KV; see ``_evacuate``)."""
        sched = rep.engine.sched
        slot = req.slot
        assert sched.active.get(slot) is req, (req.id, slot)
        del sched.active[slot]
        if req.state is RequestState.DECODE and req.done_tokens > 0:
            live = req.prompt_len + max(0, req.done_tokens - 1)
            kv = rep.engine.kv.spill(slot, live)
        else:
            # mid-prefill: drop the partial KV, re-ingest bit-identically
            # on the destination (deterministic prefill)
            rep.engine.kv.release(slot)
            kv = None
            req.prefill_pos = 0
        sched.free_slots.append(slot)
        req.slot = -1
        d: Dict[str, Any] = {
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
            # latency bookkeeping crosses replicas with the request
            "arrival_s": req.arrival_s,
            "arrival_abs_s": req.arrival_abs_s,
            "t_submit": req.t_submit,
            "t_admitted": req.t_admitted,
            "t_first_token": req.t_first_token,
        }
        frame = encode_handoff(d)
        self.migrated_kv_bytes += kv_payload_nbytes(kv)
        self._outbox.append((dest.name, d, frame, now_rel))
        self._event(
            "migrate", now_rel, request=int(req.id), src=rep.name,
            dst=dest.name, session=req.session, bytes=len(frame),
            kv_bytes=kv_payload_nbytes(kv), why=why,
        )

    def migrate_session(
        self, session: str, dest_name: Optional[str] = None,
        now_rel: float = 0.0,
    ) -> int:
        """Live-migrate every ACTIVE request of ``session`` off its home
        replica (mid-generation — the bit-identity acceptance path).
        Returns the number of requests framed."""
        home = self.session_home.get(session)
        rep = self.replicas.get(home) if home is not None else None
        if rep is None:
            return 0
        candidates = [
            r for r in self._routable() if r.name != rep.name
        ]
        if dest_name is not None:
            dest = self.replicas[dest_name]
        elif candidates:
            dest = min(candidates, key=lambda r: (r.load, r.name))
        else:
            return 0
        n = 0
        for slot in sorted(rep.engine.sched.active):
            req = rep.engine.sched.active[slot]
            if req.session == session:
                self._frame_out(rep, req, dest, now_rel, "migrate_session")
                n += 1
        # queued turns of the session follow their KV
        for tier, q in rep.engine.sched._queues.items():
            keep = [r for r in q if r.session != session]
            moved = [r for r in q if r.session == session]
            q.clear()
            q.extend(keep)
            for r in moved:
                dest.engine.sched._queues[tier].append(r)
                dest.engine.sched._next_id = max(
                    dest.engine.sched._next_id, r.id,
                ) + 1
        if n or dest_name is not None:
            self.session_home[session] = dest.name
        return n

    def _evacuate(self, rep: _Replica, now_rel: float) -> Dict[str, int]:
        """Drain discipline, fleet edition: every active slot spills and
        crosses to a healthy replica as an ``ffkv/1`` frame; every
        queued request re-routes wholesale (no KV yet — nothing to
        carry).  Zero requests are dropped; sessions re-home with their
        KV."""
        rep.draining = True
        others = [r for r in self._routable() if r.name != rep.name]
        assert others, "cannot evacuate the last routable replica"
        moved_active = 0
        sessions: set = set()
        for slot in sorted(rep.engine.sched.active):
            req = rep.engine.sched.active[slot]
            if req.session is not None:
                home = self.session_home.get(req.session)
                dest = next(
                    (r for r in others if r.name == home), None,
                ) or min(others, key=lambda r: (r.load, r.name))
            else:
                dest = min(others, key=lambda r: (r.load, r.name))
            self._frame_out(rep, req, dest, now_rel, "drain")
            if req.session is not None:
                sessions.add(req.session)
                self.session_home[req.session] = dest.name
            moved_active += 1
        moved_queued = 0
        for tier, q in rep.engine.sched._queues.items():
            while q:
                req = q.popleft()
                dest = min(others, key=lambda r: (r.load, r.name))
                # drain-resume convention: admissibility was proven at
                # submit; re-enter the destination's queue directly
                dest.engine.sched._queues[tier].append(req)
                dest.engine.sched._next_id = max(
                    dest.engine.sched._next_id, req.id,
                ) + 1
                if req.session is not None:
                    sessions.add(req.session)
                    self.session_home[req.session] = dest.name
                self._event(
                    "reroute", now_rel, request=int(req.id),
                    src=rep.name, dst=dest.name, tier=tier,
                    session=req.session, why="drain",
                )
                moved_queued += 1
        return {
            "active": moved_active, "queued": moved_queued,
            "sessions": len(sessions),
        }

    def _retire(self, rep: _Replica, now_rel: float,
                moved: Dict[str, int]) -> None:
        rep.retired = True
        rep.engine.drained = True
        removed = self.agg.remove_source(rep.name)
        self._event(
            "retire", now_rel, replica=rep.name,
            sessions_migrated=moved["sessions"],
            active_migrated=moved["active"],
            queued_rerouted=moved["queued"],
            aggregator_source_removed=removed,
        )

    # --- transport pump -----------------------------------------------------
    def _pump(self, now_rel: float) -> None:
        """Send what each destination's bounded inbox will take, then
        deliver every frame whose priced DCN latency has elapsed
        (digest-verified first) — the disagg pump, per replica."""
        from flexflow_tpu.search.cost import estimate_kv_handoff_time

        still: List[Tuple[str, Dict[str, Any], bytes, float]] = []
        for dest_name, d, frame, t_spill in self._outbox:
            dest = self.replicas[dest_name]
            delay = estimate_kv_handoff_time(len(frame), self.machine)
            if not dest.inbox.try_send(frame, now=now_rel, delay_s=delay):
                still.append((dest_name, d, frame, t_spill))
                continue
        self._outbox = still
        for rep in self.replicas.values():
            for frame in rep.inbox.recv_ready(now_rel):
                self._deliver(rep, frame, now_rel)

    def _deliver(self, rep: _Replica, frame: bytes,
                 now_rel: float) -> None:
        from flexflow_tpu.search.cost import estimate_kv_handoff_time

        if rep.retired or rep.draining:
            # the destination drained while the frame was in flight —
            # redirect to the lightest healthy replica
            rep = min(self._routable(), key=lambda r: (r.load, r.name))
        delay_ms = estimate_kv_handoff_time(len(frame), self.machine) * 1e3
        entry: Dict[str, Any] = {
            "bytes": len(frame), "delay_ms": delay_ms,
            "digest_ok": False, "admitted": False, "replica": rep.name,
        }
        self.audit.append(entry)
        try:
            d = decode_handoff(frame)  # digest-verified or raises
        except HandoffError as e:
            entry["error"] = str(e)
            self._event(
                "deliver", now_rel, replica=rep.name, digest_ok=False,
                admitted=False, error=str(e), bytes=len(frame),
            )
            return
        entry["digest_ok"] = True
        entry["id"] = int(d["id"])
        sched = rep.engine.sched
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
        kv = d.get("kv_spill")
        # destination geometry equals the source's by construction, but
        # re-check admissibility truthfully instead of assuming
        if not sched.kv.fits_with_sharing(req.max_len, req.prompt):
            sched._reject(req, self._now())
            self._event(
                "deliver", now_rel, request=int(req.id),
                replica=rep.name, digest_ok=True, admitted=False,
                reason=req.finish_reason,
            )
            return
        if kv is not None:
            # mid-stream: PREEMPTED with a payload — the scheduler's
            # restore path scatters it bit-exactly (drain convention)
            req.kv_spill = kv
            req.state = RequestState.PREEMPTED
        else:
            req.state = RequestState.QUEUED
            req.prefill_pos = 0
        sched._queues[req.tier].append(req)
        sched._next_id = max(sched._next_id, req.id) + 1
        if req.session is not None:
            self.session_home[req.session] = rep.name
        entry["admitted"] = True
        self.migrations += 1
        rep.engine.note_handoff(
            delay_ms,
            rep.engine.kv.blocks_for(kv["length"]) if kv else 0,
            len(frame),
        )
        self._event(
            "deliver", now_rel, request=int(req.id), replica=rep.name,
            digest_ok=True, admitted=True, session=req.session,
            bytes=len(frame), mid_stream=kv is not None,
        )

    # --- autoscaling --------------------------------------------------------
    def _autoscale(self, tick: int, now_rel: float) -> None:
        if self.autoscaler is None:
            return
        rec = self.autoscaler.decide(tick, len(self._routable()))
        if rec is None:
            return
        action = rec["action"]
        if action == "scale_up":
            rep = self._add_replica()
            rep.refresh_snapshot()
            self.scale_ups += 1
            self.autoscaler.acted(tick, rec)
            self._event(
                "scale_up", now_rel, replica=rep.name,
                reason=rec["reason"], replicas=len(self._routable()),
            )
        else:  # scale_down | drain → the PR-12 drain discipline
            victim = min(
                self._routable(),
                key=lambda r: (r.active, r.queue_depth, r.name),
            )
            victim.engine.request_drain()
            self.scale_downs += 1
            self.autoscaler.acted(tick, rec)
            self._event(
                "scale_down", now_rel, replica=victim.name,
                action=action, reason=rec["reason"],
            )

    # --- audit --------------------------------------------------------------
    def handoff_audit(self) -> List[Dict[str, Any]]:
        """Digest violations across every replica→replica delivery plus
        frames still in flight — the disagg handoff-audit convention.
        Empty == every migration verified."""
        out: List[Dict[str, Any]] = []
        for entry in self.audit:
            if not entry.get("digest_ok"):
                out.append({
                    "check": "fleet_handoff_digest",
                    "message": entry.get(
                        "error", "frame failed digest verification"
                    ),
                })
        for rep in self.replicas.values():
            in_flight = getattr(rep.inbox, "in_flight", None)
            if in_flight is None:
                continue
            for _ready_at, frame in in_flight():
                try:
                    decode_handoff(frame)
                except HandoffError as e:
                    out.append({
                        "check": "fleet_handoff_digest",
                        "message": f"in-flight frame to {rep.name}: {e}",
                    })
        return out

    # --- the fleet loop -----------------------------------------------------
    def run(
        self, requests: Optional[Sequence[Request]] = None,
    ) -> FleetReport:
        """Serve an open-loop workload across the fleet until every
        request finishes.  Replicas step in a stable order; routing,
        migration, and scaling all happen strictly BETWEEN windows —
        the ledger test pins host_syncs == total windows."""
        pending = sorted(requests or (), key=lambda r: (r.arrival_s, r.id))
        t0 = self._t0 = self._now()
        syncs0 = self.model.executor.host_syncs
        for rep in self.replicas.values():
            eng = rep.engine
            eng._refresh_weights()
            eng._t0 = t0
            eng.windows = eng.decode_steps = eng.prefill_chunks = 0
            eng.peak_active = 0
            eng._occ_sum = 0.0
            rep.fin0 = len(eng.sched.finished)
            rep.rej0 = len(eng.sched.rejected)
            rep.pre0 = eng.sched.preemptions
            rep.refresh_snapshot()
        n_sub = 0
        tick = 0
        while True:
            now = self._now() - t0
            while (n_sub < len(pending)
                   and pending[n_sub].arrival_s <= now):
                r = pending[n_sub]
                self.route(r, now=now)
                r.arrival_abs_s = t0 + r.arrival_s
                n_sub += 1
            for rep in list(self.replicas.values()):
                if rep.retired:
                    continue
                now = self._now() - t0
                rep.engine.sched.admit(now=now)
                if rep.engine.sched.active:
                    rep.engine._window()
            now = self._now() - t0
            # --- window boundary: everything below is host-side -------
            for rep in self.replicas.values():
                if not rep.retired:
                    rep.refresh_snapshot()
            for rep in list(self.replicas.values()):
                if (rep.engine._drain_requested and not rep.retired
                        and len(self._routable()) > 1):
                    moved = self._evacuate(rep, now)
                    self._retire(rep, now, moved)
            self._pump(now)
            tick += 1
            self._autoscale(tick, now)
            if (n_sub >= len(pending)
                    and not self._outbox
                    # a retired replica's inbox can still hold frames
                    # that were in flight when it drained — they
                    # redirect at delivery, so they too must land first
                    and all(
                        rep.inbox.pending() == 0
                        and (rep.retired or rep.engine.sched.idle)
                        for rep in self.replicas.values()
                    )):
                break
            if not any(
                rep.engine.sched.active
                for rep in self.replicas.values() if not rep.retired
            ):
                waits = []
                if n_sub < len(pending):
                    waits.append(
                        pending[n_sub].arrival_s - (self._now() - t0)
                    )
                for rep in self.replicas.values():
                    in_flight = getattr(rep.inbox, "in_flight", None)
                    if in_flight is not None and rep.inbox.pending():
                        waits.append(
                            min(t for t, _ in in_flight())
                            - (self._now() - t0)
                        )
                dt = min(waits) if waits else 0.0
                if dt > 0:
                    time.sleep(min(dt, 0.05))
        wall = self._now() - t0
        rep_out = self._report(
            wall, self.model.executor.host_syncs - syncs0,
        )
        self._event(
            "summary", wall, replicas=rep_out.replicas,
            routing=self.routing, migrations=self.migrations,
            spillovers=self.spillovers, scale_ups=self.scale_ups,
            scale_downs=self.scale_downs,
            fleet_prefix_hit_rate=rep_out.fleet_prefix_hit_rate,
            requests_finished=rep_out.requests_finished,
            tpot_p99_ms=rep_out.tpot_p99_ms,
            ttft_p99_ms=rep_out.ttft_p99_ms,
            per_replica=rep_out.per_replica,
        )
        for rep in self.replicas.values():
            rep.engine.metrics.close()
        self.stream.close()
        self._t0 = None
        return rep_out

    def _report(self, wall: float, host_syncs: int) -> FleetReport:
        fin: List[Request] = []
        for rep in self.replicas.values():
            fin.extend(rep.engine.sched.finished[rep.fin0:])
        fin.sort(key=lambda r: r.id)
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
        windows = sum(r.engine.windows for r in self.replicas.values())
        occ_sum = sum(r.engine._occ_sum for r in self.replicas.values())
        hits = sum(
            r.engine.kv.prefix_hits for r in self.replicas.values()
        )
        lookups = sum(
            r.engine.kv.prefix_lookups for r in self.replicas.values()
        )
        per_replica: Dict[str, Dict[str, Any]] = {}
        for rep in self.replicas.values():
            eng = rep.engine
            lat_r = [
                r.latency_ms() for r in eng.sched.finished[rep.fin0:]
            ]
            per_replica[rep.name] = {
                "routed": rep.routed,
                "finished": len(eng.sched.finished) - rep.fin0,
                "rejected": len(eng.sched.rejected) - rep.rej0,
                "tpot_p99_ms": _pct([d["tpot_ms"] for d in lat_r], 99),
                "windows": eng.windows,
                "occupancy_mean": (
                    eng._occ_sum / eng.windows if eng.windows else 0.0
                ),
                "prefix_hit_rate": eng.kv.prefix_hit_rate,
                "preemptions": eng.sched.preemptions - rep.pre0,
                "drained": rep.retired,
            }
        return FleetReport(
            wall_s=wall,
            new_tokens=new_tokens,
            tok_s=new_tokens / wall if wall > 0 else 0.0,
            requests_finished=len(fin),
            requests_rejected=sum(
                len(r.engine.sched.rejected) - r.rej0
                for r in self.replicas.values()
            ),
            ttft_p50_ms=_pct([d["ttft_ms"] for d in lat], 50),
            ttft_p99_ms=_pct([d["ttft_ms"] for d in lat], 99),
            tpot_p50_ms=_pct([d["tpot_ms"] for d in lat], 50),
            tpot_p99_ms=_pct([d["tpot_ms"] for d in lat], 99),
            occupancy_mean=occ_sum / windows if windows else 0.0,
            windows=windows,
            decode_steps=sum(
                r.engine.decode_steps for r in self.replicas.values()
            ),
            prefill_chunks=sum(
                r.engine.prefill_chunks for r in self.replicas.values()
            ),
            host_syncs=host_syncs,
            per_request=[
                {
                    "id": r.id, "prompt_len": r.prompt_len,
                    "tokens": list(r.tokens), "reason": r.finish_reason,
                    "tenant": r.tenant, "tier": r.tier,
                    "session": r.session,
                    "preemptions": r.preemptions,
                    **r.latency_ms(),
                }
                for r in fin
            ],
            prefix_hit_rate=(hits / lookups) if lookups else None,
            preemptions=sum(
                r.engine.sched.preemptions - r.pre0
                for r in self.replicas.values()
            ),
            per_tier=per_tier,
            peak_active=max(
                (r.engine.peak_active for r in self.replicas.values()),
                default=0,
            ),
            replicas=len(self._live()),
            replicas_peak=self.replicas_peak,
            routing=self.routing,
            routed={
                r.name: r.routed for r in self.replicas.values()
            },
            prefix_routed=self.prefix_routed,
            fleet_prefix_hit_rate=(hits / lookups) if lookups else None,
            migrations=self.migrations,
            migrated_kv_bytes=self.migrated_kv_bytes,
            spillovers=self.spillovers,
            scale_ups=self.scale_ups,
            scale_downs=self.scale_downs,
            sessions=len(self.session_home),
            per_replica=per_replica,
        )
