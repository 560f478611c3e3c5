"""Seeded traffic for the serving cells: one general generator, data in.

A traffic mix is a data file under ``traffic_mixes/``, named by the
cell's ``traffic``; this module turns it and ``--seed`` into a list of
:class:`Arrival`.  Two modes:

``"mode": "legacy"`` is a copy of ``flexflow_tpu/serve/traffic.py``
(``synthetic_requests`` / ``multi_tenant_requests``, PR 11/13/18): the
same draws in the same order, so for one seed the prompts, lengths and
arrival times are equal to the program's own generator, value for value
(``tests/benchmark`` pins that).  It is kept because the yardstick may
not change when the program does.

``"mode": "fixed_set"`` (what the cells use) makes every seed do the
same work in another order: the multiset of (prompt length, generation
length) pairs and of inter-arrival gaps is drawn once from
``shape_seed``, ``block`` of them, and every consecutive block of
``block`` requests is a fresh permutation of that multiset under the run
seed.  Token ids come from the run seed.  Without this, two seeds differ
by the luck of their lengths and a run-to-run spread measures the
generator.

Open loop: arrivals never wait for completions.  ``rate_rps <= 0`` puts
every request at t = 0 (a saturating backlog).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

_FLIP_P = 0.25  # per-arrival on/off flip probability of the bursty clock


@dataclasses.dataclass(frozen=True)
class TrafficSpec:
    n_requests: int = 16
    seed: int = 0
    rate_rps: float = 0.0
    prompt_len: Tuple[int, int] = (4, 12)  # inclusive
    max_new: Tuple[int, int] = (4, 24)  # inclusive
    vocab: int = 256
    tenants: int = 1
    shared_prefix: int = 0
    interactive_frac: float = 0.0
    burst_factor: float = 1.0
    session_turns: int = 1
    # fixed_set mode
    mode: str = "legacy"
    shape_seed: int = 0
    block: int = 256
    # stop once arrivals pass this many seconds (0 = n_requests decides)
    duration_s: float = 0.0


@dataclasses.dataclass
class Arrival:
    id: int
    arrival_s: float
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    tenant: str = "default"
    tier: str = "batch"
    session: Optional[str] = None


class _ArrivalClock:
    def __init__(self, spec: TrafficSpec, rng: np.random.Generator) -> None:
        assert spec.burst_factor > 0, spec.burst_factor
        self._spec, self._rng = spec, rng
        self._t = 0.0
        self._on = True

    def gap(self) -> float:
        spec, rng = self._spec, self._rng
        if spec.burst_factor == 1.0:
            return float(rng.exponential(1.0 / spec.rate_rps))
        if rng.random() < _FLIP_P:
            self._on = not self._on
        rate = spec.rate_rps * (
            spec.burst_factor if self._on else 1.0 / spec.burst_factor
        )
        return float(rng.exponential(1.0 / rate))

    def next(self) -> float:
        if self._spec.rate_rps <= 0:
            return self._t
        self._t += self.gap()
        return self._t


def _legacy_single(spec: TrafficSpec) -> List[Arrival]:
    rng = np.random.default_rng(spec.seed)
    clock = _ArrivalClock(spec, rng)
    out: List[Arrival] = []
    for i in range(spec.n_requests):
        t = clock.next()
        plen = int(rng.integers(spec.prompt_len[0], spec.prompt_len[1] + 1))
        gen = int(rng.integers(spec.max_new[0], spec.max_new[1] + 1))
        prompt = rng.integers(0, spec.vocab, size=(plen,)).astype(np.int32)
        out.append(Arrival(i, t, prompt, gen))
    return out


def _legacy_tenants(spec: TrafficSpec) -> List[Arrival]:
    rng = np.random.default_rng(spec.seed)
    nt = max(1, int(spec.tenants))
    n_inter = 0
    if spec.interactive_frac > 0:
        n_inter = min(nt, max(1, int(np.ceil(nt * spec.interactive_frac))))
    sys_prompts = [
        rng.integers(0, spec.vocab, size=(spec.shared_prefix,)).astype(np.int32)
        for _ in range(nt)
    ]
    clock = _ArrivalClock(spec, rng)
    out: List[Arrival] = []
    turns = max(1, int(spec.session_turns))
    n_turn = [0] * nt
    prev_prompt = list(sys_prompts)
    for i in range(spec.n_requests):
        t = clock.next()
        j = i % nt
        plen = int(rng.integers(spec.prompt_len[0], spec.prompt_len[1] + 1))
        gen = int(rng.integers(spec.max_new[0], spec.max_new[1] + 1))
        tail = rng.integers(0, spec.vocab, size=(plen,)).astype(np.int32)
        session = None
        if turns > 1:
            s_idx, turn = divmod(n_turn[j], turns)
            session = f"tenant{j}:s{s_idx}"
            base = sys_prompts[j] if turn == 0 else prev_prompt[j]
            prompt = np.concatenate([base, tail])
            prev_prompt[j] = prompt
            n_turn[j] += 1
        else:
            prompt = np.concatenate([sys_prompts[j], tail])
        out.append(Arrival(
            i, t, prompt, gen, tenant=f"tenant{j}",
            tier="interactive" if j < n_inter else "batch", session=session,
        ))
    return out


def _fixed_set(spec: TrafficSpec) -> List[Arrival]:
    """Every block of ``spec.block`` requests carries the same multiset
    of lengths and gaps, permuted by the run seed."""
    shape = np.random.default_rng(spec.shape_seed)
    K = int(spec.block)
    plens = shape.integers(spec.prompt_len[0], spec.prompt_len[1] + 1, size=K)
    gens = shape.integers(spec.max_new[0], spec.max_new[1] + 1, size=K)
    if spec.rate_rps > 0:
        clock = _ArrivalClock(spec, shape)
        gaps = np.array([clock.gap() for _ in range(K)])
        # hold the block's mean gap to 1/rate exactly, so the offered
        # rate is the stated one in every block
        gaps *= (K / spec.rate_rps) / gaps.sum()
    else:
        gaps = np.zeros(K)
    rng = np.random.default_rng(spec.seed)
    nt = max(1, int(spec.tenants))
    sys_prompts = [
        rng.integers(0, spec.vocab, size=(spec.shared_prefix,)).astype(np.int32)
        for _ in range(nt)
    ]
    out: List[Arrival] = []
    t = 0.0
    i = 0
    while True:
        order = rng.permutation(K)
        gap_order = rng.permutation(K)
        for a, b in zip(order, gap_order):
            t += float(gaps[b])
            if spec.duration_s > 0:
                # a whole block ends on the window's last instant up to
                # rounding: that request is due in the window on every seed
                if t > spec.duration_s + 1e-6:
                    return out
            elif i >= spec.n_requests:
                return out
            j = i % nt
            tail = rng.integers(
                0, spec.vocab, size=(int(plens[a]),)
            ).astype(np.int32)
            prompt = np.concatenate([sys_prompts[j], tail]) if spec.shared_prefix else tail
            out.append(Arrival(
                i, t, prompt, int(gens[a]),
                tenant=f"tenant{j}" if nt > 1 else "default",
            ))
            i += 1
        if spec.duration_s <= 0 and i >= spec.n_requests:
            return out


def generate(spec: TrafficSpec) -> List[Arrival]:
    if spec.mode == "fixed_set":
        return _fixed_set(spec)
    if spec.mode != "legacy":
        raise ValueError(f"traffic mode {spec.mode!r}: legacy | fixed_set")
    if (spec.tenants != 1 or spec.shared_prefix or spec.interactive_frac
            or spec.session_turns != 1):
        return _legacy_tenants(spec)
    return _legacy_single(spec)


def spec_from_cell(traffic: dict, *, seed: int, seconds: float, vocab: int) -> TrafficSpec:
    """A traffic mix's data -> a spec.  ``backlog_requests_per_s`` sizes a
    t = 0 backlog from the window's length (so it never empties);
    ``"duration": "window"`` makes open-loop arrivals cover the window."""
    t = dict(traffic)
    kw = {}
    for k in ("rate_rps", "tenants", "shared_prefix", "interactive_frac",
              "burst_factor", "session_turns", "mode", "shape_seed", "block"):
        if k in t:
            kw[k] = t[k]
    kw["prompt_len"] = tuple(t["prompt_len"])
    kw["max_new"] = tuple(t["max_new"])
    if t.get("duration") == "window":
        kw["duration_s"] = float(seconds)
        kw["n_requests"] = 0
    elif "backlog_requests_per_s" in t:
        kw["n_requests"] = int(
            t.get("backlog_min", 0) + t["backlog_requests_per_s"] * seconds
        )
    else:
        kw["n_requests"] = int(t["n_requests"])
    return TrafficSpec(seed=int(seed), vocab=int(vocab), **kw)
