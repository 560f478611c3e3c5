"""Continuous-batching scheduler with SLO tiers (docs/SERVING.md).

The reference's inference story is a Legion backend serving one model
instance per request stream; here the unit of batching is the SLOT — a
lane of the fixed-slot compiled decode step.  Requests of any length
are admitted FIFO into free slots, each with a KV-block reservation for
its full declared budget; a sequence that finishes (EOS or token
budget) releases its slot and blocks *mid-flight*, and the next queued
request takes them without recompiling anything — the compiled step's
shapes never change, only the block tables and position vectors fed
through it.

Admission policy (pinned by tests/test_serve.py):

* **strict FIFO within a tier** — a tier's queue head blocks admission
  until both a slot and its KV reservation are available (no
  reordering, no starvation of long requests behind short ones);
* **graceful rejection** — a request whose budget could never fit the
  pool (``prompt + max_new_tokens`` over the per-sequence table limit,
  or more *private* blocks than the whole pool owns — prefix sharing
  changes the budget arithmetic, and the reasons say which bound bit)
  is rejected at submit with a reason, not crashed on later;
* **reservation at admission** — blocks for the full budget are taken
  up front (see kvcache.py), so decode windows never fault on
  allocation.  Admission charges only UNSHARED blocks: the reservation
  re-attaches indexed prefix blocks, and ``prefill_pos`` starts past
  them.

**SLO tiers (PR 11).**  Every request carries a ``tenant`` label and a
``tier`` — ``"interactive"`` (latency-sensitive: chat turns, tab
completions) or ``"batch"`` (throughput work: evals, digests; the
default, which keeps single-tier workloads exactly the old strict
FIFO).  Interactive requests admit first, and when one is waiting with
no admissible slot the scheduler PREEMPTS a batch request: the victim's
live K/V is spilled to host through :meth:`PagedKVCache.spill` (the
per-layer checkpoint convention), its slot and blocks are released, and
it re-queues at the FRONT of the batch tier in ``PREEMPTED`` state.  On
re-admission the spill payload is restored bit-exactly (shared prefix
blocks re-attach from the index; private positions scatter back), so
the victim resumes its exact token stream — the round-trip test pins
this.  Spill and restore happen at flush boundaries inside the window's
one host sync, so the zero-per-step-sync ledger is untouched.
"""

from __future__ import annotations

import dataclasses
import enum
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from flexflow_tpu.serve.kvcache import PagedKVCache

__all__ = ["Request", "RequestState", "ContinuousBatchingScheduler", "TIERS"]

TIERS = ("interactive", "batch")


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    PREEMPTED = "preempted"  # spilled to host, waiting to resume
    FINISHED = "finished"
    REJECTED = "rejected"


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a token budget, an optional
    EOS, a tenant/tier label, and the latency bookkeeping the metrics
    stream reports."""

    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    id: int = -1
    eos_id: Optional[int] = None
    arrival_s: float = 0.0  # open-loop arrival offset (traffic.py)
    tenant: str = "default"
    tier: str = "batch"  # "interactive" | "batch"
    # queue-wait deadline: a request still QUEUED this many ms after
    # submit is expired with a truthful reason instead of waiting
    # forever (docs/RESILIENCE.md).  None = wait indefinitely.
    deadline_ms: Optional[float] = None
    # multi-turn session id (fleet.py): follow-up turns reuse it so the
    # router keeps the session on the replica holding its KV.  None =
    # sessionless (every pre-fleet workload), which changes nothing.
    session: Optional[str] = None

    # --- filled in by the scheduler/engine ---
    state: RequestState = RequestState.QUEUED
    slot: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    # each served token's logit, where the engine keeps them (token_logits)
    token_logits: List[float] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0  # prompt positions with live KV so far
    finish_reason: Optional[str] = None  # "eos" | "length" | "rejected:*"
    preemptions: int = 0
    kv_spill: Optional[Dict[str, Any]] = None  # spill payload while PREEMPTED
    shared_prefix_pos: int = 0  # prompt positions served from shared blocks
    t_submit: Optional[float] = None
    arrival_abs_s: Optional[float] = None  # engine clock: t0 + arrival_s
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None  # TTFT clock stop
    t_done: Optional[float] = None
    # --- distributed tracing (ffspan/1, obs/spans.py) ---
    # trace_id is one id per request per run; span_parent is the span id
    # this pool's child spans nest under (the root span, or the handoff
    # restore span once the request crossed pools).  t_enqueued is the
    # run-relative time of the LAST enqueue (submit, preemption requeue,
    # handoff delivery) — each queue span measures one admission wait,
    # not the request's whole life.  All None when tracing is off.
    trace_id: Optional[str] = None
    span_parent: Optional[str] = None
    t_enqueued: Optional[float] = None

    def __post_init__(self) -> None:
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        assert len(self.prompt) >= 1, "empty prompt"
        assert self.max_new_tokens >= 1
        assert self.tier in TIERS, f"unknown tier {self.tier!r}"

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))

    @property
    def max_len(self) -> int:
        """Positions this request may ever occupy (= KV reservation)."""
        return self.prompt_len + self.max_new_tokens

    @property
    def done_tokens(self) -> int:
        return len(self.tokens)

    def latency_ms(self) -> Dict[str, Optional[float]]:
        ttft = (
            (self.t_first_token - self.arrival_abs_s) * 1e3
            if self.t_first_token is not None
            and self.arrival_abs_s is not None
            else None
        )
        tpot = None
        if (
            self.t_done is not None
            and self.t_first_token is not None
            and len(self.tokens) > 1
        ):
            tpot = (
                (self.t_done - self.t_first_token)
                / (len(self.tokens) - 1) * 1e3
            )
        return {"ttft_ms": ttft, "tpot_ms": tpot}


class ContinuousBatchingScheduler:
    """Tiered FIFO admission of :class:`Request`s into ``slots`` decode
    lanes backed by a :class:`PagedKVCache` (see module docstring)."""

    def __init__(self, slots: int, kvcache: PagedKVCache) -> None:
        assert kvcache.slots == slots, (kvcache.slots, slots)
        self.slots = slots
        self.kv = kvcache
        self._queues: Dict[str, deque] = {t: deque() for t in TIERS}
        self.free_slots: deque = deque(range(slots))
        self.active: Dict[int, Request] = {}  # slot -> request
        self.finished: List[Request] = []
        self.rejected: List[Request] = []
        self.preemptions = 0  # cumulative spill events
        self.expired = 0  # deadline_ms expiries while queued
        self.shed = 0  # batch requests shed under SLO pressure
        self._next_id = 0
        # optional ffspan/1 recorder + pool label, set by the owning
        # engine (obs/spans.py).  Every emission below is behind a None
        # check and only reads host-side clocks the scheduler already
        # stamps — tracing off leaves this class's behavior byte-for-
        # byte identical, tracing on adds zero host syncs.
        self.spans = None
        self.pool: Optional[str] = None
        # last published immutable status snapshot (serve/introspect.py)
        # — written by publish_status() via atomic reference swap, read
        # lock-free by the status server; None until first publication
        self.last_status: Optional[Dict[str, Any]] = None

    @property
    def queue(self) -> List[Request]:
        """Pending requests in admission order (interactive tier ahead
        of batch; FIFO within each)."""
        return [r for t in TIERS for r in self._queues[t]]

    # --- submission --------------------------------------------------------
    def submit(self, req: Request, now: float = 0.0) -> Request:
        """Queue a request, or reject it outright when its budget could
        never be served by this cache (graceful — the request comes back
        marked REJECTED, nothing raises).  Prefix sharing participates:
        a budget that overflows the pool raw but fits once its indexed
        prefix blocks are discounted IS queued, and a rejection reason
        says whether shared blocks were considered."""
        if req.id < 0:
            req.id = self._next_id
        self._next_id = max(self._next_id, req.id) + 1
        req.t_submit = now
        req.t_enqueued = now
        if self.spans is not None:
            self.spans.begin_trace(req)
        if not self.kv.fits_with_sharing(req.max_len, req.prompt):
            self._reject(req, now)
            return req
        req.state = RequestState.QUEUED
        self._queues[req.tier].append(req)
        return req

    def _reject(self, req: Request, now: float) -> None:
        total, shared = self.kv.blocks_needed(req.max_len, req.prompt)
        reason = (
            f"rejected: max_len {req.max_len} needs "
            f"{total} blocks, pool holds "
            f"{self.kv.allocatable_blocks} "
            f"(table limit {self.kv.max_seq_len} positions)"
        )
        # distinguish "never fits" from "fits after shared blocks" so
        # the message stays truthful under prefix sharing
        if shared > 0:
            reason += (
                f"; {shared} shared prefix blocks discounted, "
                f"{total - shared} private blocks still exceed the pool"
            )
        else:
            reason += "; never fits (no shared prefix applies)"
        req.state = RequestState.REJECTED
        req.finish_reason = reason
        req.t_done = now
        self.rejected.append(req)
        if self.spans is not None:
            self.spans.span("reject", req, now, now, pool=self.pool,
                            reason=reason)
            self.spans.root(req, req.t_submit if req.t_submit is not None
                            else now, now, "rejected", pool=self.pool)

    # --- admission ---------------------------------------------------------
    def _place(self, req: Request, now: float) -> None:
        slot = self.free_slots.popleft()
        resumed = req.kv_spill is not None
        if self.spans is not None:
            # drain- or handoff-delivered requests enter the queue
            # without going through submit(); give them a trace late
            self.spans.begin_trace(req)
            t_q0 = (req.t_enqueued if req.t_enqueued is not None
                    else (req.t_submit if req.t_submit is not None else now))
            self.spans.span("queue", req, t_q0, now, pool=self.pool,
                            tier=req.tier, tenant=req.tenant,
                            resumed=resumed)
        if req.kv_spill is not None:
            # resuming a preempted request: restore the spilled K/V
            # bit-exactly and rejoin the decode pool directly (its
            # prompt was fully ingested before the spill)
            self.kv.restore(slot, req.kv_spill, req.max_len,
                            prompt=req.prompt)
            req.kv_spill = None
            req.state = RequestState.DECODE
            req.prefill_pos = req.prompt_len
            if self.spans is not None:
                self.spans.span("restore", req, now, self.spans.now(),
                                pool=self.pool,
                                preemptions=req.preemptions)
        else:
            self.kv.reserve(slot, req.max_len, prompt=req.prompt)
            req.state = RequestState.PREFILL
            # shared prefix blocks already hold these positions' K/V —
            # prefill starts past them (never past the last prompt
            # token: shareable_blocks() keeps it private, so the first
            # next-token distribution is always computed)
            req.prefill_pos = req.shared_prefix_pos = min(
                self.kv.shared_len(slot), req.prompt_len - 1
            )
        req.slot = slot
        if req.t_admitted is None:
            req.t_admitted = now
        self.active[slot] = req

    def _admit_tier(self, tier: str, now: float) -> List[Request]:
        out: List[Request] = []
        q = self._queues[tier]
        while q and self.free_slots:
            req = q[0]
            if not self.kv.fits_with_sharing(req.max_len, req.prompt):
                # the shared blocks that justified queueing were evicted
                # — reject late rather than block the tier forever
                q.popleft()
                self._reject(req, now)
                continue
            if not self.kv.can_reserve(req.max_len, req.prompt):
                break
            q.popleft()
            self._place(req, now)
            out.append(req)
        return out

    def _preempt_one(self, now: float) -> bool:
        """Spill ONE batch-tier victim to host and recycle its slot +
        blocks.  Victim choice: the most recently admitted batch DECODE
        request (least sunk work lost); a mid-PREFILL batch request is
        the fallback (its KV is cheap to rebuild, so it just re-queues
        without a payload).  Returns False when no victim exists."""
        decode_victims = [
            r for r in self.active.values()
            if r.tier == "batch" and r.state is RequestState.DECODE
        ]
        prefill_victims = [
            r for r in self.active.values()
            if r.tier == "batch" and r.state is RequestState.PREFILL
        ]
        pool = decode_victims or prefill_victims
        if not pool:
            return False
        victim = max(pool, key=lambda r: (r.t_admitted or 0.0, r.slot))
        slot = victim.slot
        del self.active[slot]
        if victim.state is RequestState.DECODE:
            # positions written so far: the full prompt + one KV write
            # per decode step taken (the latest token is still pending
            # as the next step's input, so it has no KV yet)
            live = victim.prompt_len + max(0, victim.done_tokens - 1)
            victim.kv_spill = self.kv.spill(slot, live)
        else:
            # mid-prefill: drop the partial KV, re-ingest on resume
            self.kv.release(slot)
            victim.kv_spill = None
            victim.prefill_pos = 0
        self.free_slots.append(slot)
        victim.slot = -1
        victim.state = RequestState.PREEMPTED
        victim.preemptions += 1
        self.preemptions += 1
        victim.t_enqueued = now
        if self.spans is not None:
            self.spans.span(
                "spill", victim, now, self.spans.now(), pool=self.pool,
                spilled_kv=victim.kv_spill is not None,
                preemptions=victim.preemptions,
            )
        self._queues["batch"].appendleft(victim)  # resume first
        return True

    def _expire(self, now: float) -> int:
        """Sweep every tier queue for requests past their
        ``deadline_ms``: each is rejected with a truthful reason (how
        long it waited vs its deadline) instead of occupying the queue
        forever.  Runs before admission so an expired queue head never
        blocks a live request behind it."""
        n = 0
        for q in self._queues.values():
            keep = deque()
            while q:
                req = q.popleft()
                waited_ms = (now - (req.t_submit or 0.0)) * 1e3
                if (req.deadline_ms is not None
                        and waited_ms > req.deadline_ms):
                    req.state = RequestState.REJECTED
                    req.finish_reason = (
                        f"rejected: deadline {req.deadline_ms:.0f} ms "
                        f"exceeded while queued (waited {waited_ms:.0f} ms"
                        f", tier {req.tier!r})"
                    )
                    req.t_done = now
                    self.rejected.append(req)
                    self.expired += 1
                    n += 1
                    if self.spans is not None:
                        self.spans.span(
                            "expire", req, now, now, pool=self.pool,
                            waited_ms=waited_ms,
                            deadline_ms=req.deadline_ms,
                        )
                        self.spans.root(
                            req,
                            req.t_submit if req.t_submit is not None
                            else now, now, "expired", pool=self.pool,
                        )
                else:
                    keep.append(req)
            q.extend(keep)
        return n

    def shed_batch_queue(self, now: float, reason: str) -> int:
        """Graceful load shedding under sustained SLO pressure: reject
        every QUEUED batch-tier request with ``reason`` (truthful — it
        names the pressure that triggered the shed).  Active slots are
        untouched; interactive requests are never shed."""
        q = self._queues["batch"]
        n = len(q)
        while q:
            req = q.popleft()
            req.state = RequestState.REJECTED
            req.finish_reason = f"rejected: shed ({reason})"
            req.t_done = now
            self.rejected.append(req)
            if self.spans is not None:
                self.spans.span("reject", req, now, now, pool=self.pool,
                                reason=req.finish_reason)
                self.spans.root(
                    req, req.t_submit if req.t_submit is not None
                    else now, now, "shed", pool=self.pool,
                )
        self.shed += n
        return n

    def admit(self, now: float = 0.0) -> List[Request]:
        """Admit queue-head requests into free slots while both a slot
        and the KV reservation (net of shared blocks) are available.
        Interactive requests admit first and preempt batch slots when
        they cannot be placed otherwise.  Deadline-expired requests are
        swept out first (:meth:`_expire`)."""
        self._expire(now)
        out = self._admit_tier("interactive", now)
        while self._queues["interactive"]:
            if not self._preempt_one(now):
                break
            out.extend(self._admit_tier("interactive", now))
        out.extend(self._admit_tier("batch", now))
        return out

    def finish(self, req: Request, now: float, reason: str) -> None:
        """Mid-flight slot recycling: release the slot + blocks; the
        very next :meth:`admit` can hand them to a queued request —
        the compiled step is untouched."""
        assert self.active.get(req.slot) is req, (req.id, req.slot)
        del self.active[req.slot]
        self.kv.release(req.slot)
        self.free_slots.append(req.slot)
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.t_done = now
        req.slot = -1
        self.finished.append(req)
        if self.spans is not None:
            # `now` here is the engine's absolute perf_counter clock
            # (latency_ms pairs it with t_first_token) — span times are
            # run-relative, so take the recorder's own clock instead
            t = self.spans.now()
            self.spans.span("finish", req, t, t, pool=self.pool,
                            reason=reason, tokens=req.done_tokens)
            self.spans.root(
                req, req.t_submit if req.t_submit is not None else t, t,
                "finished", pool=self.pool, reason=reason,
                tokens=req.done_tokens, preemptions=req.preemptions,
            )

    # --- introspection -----------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    @property
    def occupancy(self) -> float:
        return len(self.active) / float(self.slots)

    def decode_slots(self) -> List[int]:
        return sorted(
            s for s, r in self.active.items()
            if r.state is RequestState.DECODE
        )

    def prefill_slots(self) -> List[int]:
        return sorted(
            s for s, r in self.active.items()
            if r.state is RequestState.PREFILL
        )

    @property
    def idle(self) -> bool:
        return self.queue_depth == 0 and not self.active

    def publish_status(self) -> Dict[str, Any]:
        """Build (and retain as ``last_status``) an immutable snapshot
        of the admission ledgers — plain host-side counters, no device
        interaction.  The introspection server reads ``last_status``
        by reference; a reader always sees a complete snapshot."""
        snap = {
            "queue_depth": self.queue_depth,
            "queued_by_tier": {
                t: len(q) for t, q in self._queues.items()
            },
            "active": len(self.active),
            "occupancy": self.occupancy,
            "finished_total": len(self.finished),
            "rejected_total": len(self.rejected),
            "expired_total": self.expired,
            "shed_total": self.shed,
            "preemptions_total": self.preemptions,
        }
        self.last_status = snap
        return snap

    def tenant_summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant fairness aggregates over everything this
        scheduler has seen (the serve report / metrics vocabulary)."""
        out: Dict[str, Dict[str, Any]] = {}

        def row(tenant: str) -> Dict[str, Any]:
            return out.setdefault(tenant, {
                "finished": 0, "rejected": 0, "expired": 0, "active": 0,
                "queued": 0, "preemptions": 0, "tokens": 0, "ttft_ms": [],
                "tier": None,
            })

        for r in self.finished:
            d = row(r.tenant)
            d["finished"] += 1
            d["tokens"] += r.done_tokens
            d["preemptions"] += r.preemptions
            d["tier"] = r.tier
            ttft = r.latency_ms()["ttft_ms"]
            if ttft is not None:
                d["ttft_ms"].append(ttft)
        for r in self.rejected:
            d = row(r.tenant)
            d["rejected"] += 1
            if (r.finish_reason or "").startswith("rejected: deadline"):
                d["expired"] += 1
            d["tier"] = r.tier
        for r in self.active.values():
            d = row(r.tenant)
            d["active"] += 1
            d["tier"] = r.tier
        for r in self.queue:
            d = row(r.tenant)
            d["queued"] += 1
            d["tier"] = r.tier
        return out
