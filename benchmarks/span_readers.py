"""Readers of the program's own spans in a profiler trace.

The program writes ``ff.<cat>.<name>`` annotations into the profiler's
``/host:CPU`` plane (``flexflow_tpu/obs/trace.py``;
``docs/OBSERVABILITY.md`` has the vocabulary), on the same clock as the
device's ``XLA Ops``.  These readers lay the two over each other:

* how long a phase took per unit of work (``span_ms_per_unit``),
* which phase the host was in while the device sat idle
  (``idle_under_spans_ms_per_unit``) and how much of the idle time no
  span covers (``idle_unattributed_share``),
* how many marks of a kind the slice holds (``span_count``: compiles),
* what the host was doing in each of the device's longest idle gaps
  (``idle_gaps``: the result line's ``breakdown.idle_gaps``).

Each is a metric of its own file under ``layer_metrics/`` (``reader``:
``benchmarks.span_readers:<function>``), listed by the cells it is read
in like any other.

A unit is one turn of the program's loop, marked by a ``unit`` span
(``ff.serve.window``, ``ff.fit.step_dispatch``).  Two shapes of slice:

* the profiler starts and stops in the middle of a running loop (the
  serve cells): a whole unit is the stretch from the start of one
  ``unit`` span to the start of the next, and the slice's ragged edges
  (what lies before the first one's start, and from the last one's start
  on) are dropped;
* the profiler brackets whole calls (the training cell: one ``fit``,
  whose host runs far ahead of the device): the reader is given a
  ``frame`` span (``ff.fit``), the stretch is from the first frame's
  start to the last one's end, and the units are the ``unit`` spans that
  start in it.

Each reader divides by its own count of units.  Device idle time is
what the union of the ``XLA Ops`` intervals on the first chip leaves of
the stretch, as ``device_idle_share`` takes it of the slice; each instant
of it goes to the innermost ``ff.`` span that covers it (the one that
started last).

All take ``run.trace["events"]`` (``trace_reduce.load``'s
``{plane: {line: [(name, start_s, duration_s)]}}``) and return None,
never raise, where the trace holds no ``ff.`` span: a program from
before the spans has nothing to read.

By hand, on a trace kept with ``run.py --trace 1 --keep-trace``:

    python3 -m benchmarks.span_readers .bench_trace/<cell> <cell>
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as TR

HOST_PLANE = "/host:CPU"
PREFIX = "ff."
Span = Tuple[str, float, float]  # name, start_s, end_s


def ff_spans(events: dict) -> List[Span]:
    """Every ``ff.`` event of every host line, by start."""
    out = [
        (name, start, start + dur)
        for evs in events.get(HOST_PLANE, {}).values()
        for name, start, dur in evs
        if name.startswith(PREFIX)
    ]
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def whole_units(spans: Sequence[Span], unit: str, frame: Optional[str] = None,
                ) -> Optional[Tuple[float, float, int]]:
    """``(from, to, count)``: the stretch of whole units and how many."""
    starts = [s for name, s, _ in spans if name == unit]
    if frame is None:
        if len(starts) < 2:
            return None
        return starts[0], starts[-1], len(starts) - 1
    frames = [(s, e) for name, s, e in spans if name == frame]
    if not frames:
        return None
    lo, hi = frames[0][0], max(e for _, e in frames)
    units = sum(1 for s in starts if lo <= s <= hi)
    return (lo, hi, units) if units else None


def _clipped(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def first_chip_ops(events: dict) -> List[TR.Event]:
    """The first chip's ``XLA Ops`` events by start ([] without a device)."""
    planes = TR.device_planes(events)
    if not planes:
        return []
    return sorted(events[planes[0]][TR.OPS_LINE], key=lambda e: e[1])


def device_gaps(events: dict, lo: float, hi: float) -> List[Tuple[float, float]]:
    """``(start, end)`` of each stretch of [lo, hi] in which no operation
    ran on the first chip.  A stretch with no operation before it counts:
    [lo, hi] lies inside the session (its spans were recorded), and an
    operation that ran there would have been recorded as well."""
    gaps, end = [], lo
    for _, start, dur in first_chip_ops(events):
        if start >= hi:
            break
        if start > end:
            gaps.append((end, start))
        end = max(end, start + dur)
    if hi > end:
        gaps.append((end, hi))
    return gaps


def innermost_cover(spans: Sequence[Span]) -> List[Span]:
    """Disjoint ``(name, start, end)`` pieces, by start: at each instant
    the covering span that started last (a child, inside its parent)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out: List[Span] = []
    live: List[Span] = []  # spans that have started, by start
    nxt = 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt][1] <= a:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[2] > a]
        if live:
            name = live[-1][0]
            if out and out[-1][0] == name and out[-1][2] == a:
                out[-1] = (name, out[-1][1], b)
            else:
                out.append((name, a, b))
    return out


def _under(cover: Sequence[Span], starts: Sequence[float], g0: float, g1: float,
           ) -> Dict[str, float]:
    """Seconds of [g0, g1] under each name of ``cover`` (the disjoint
    pieces of ``innermost_cover``; ``starts`` their starts)."""
    by: Dict[str, float] = {}
    i = max(0, bisect.bisect_right(starts, g0) - 1)
    while i < len(cover) and cover[i][1] < g1:
        name, s, e = cover[i]
        ov = _clipped(s, e, g0, g1)
        if ov > 0:
            by[name] = by.get(name, 0.0) + ov
        i += 1
    return by


def idle_by_span(events: dict, unit: str, frame: Optional[str] = None,
                 ) -> Optional[Tuple[Dict[Optional[str], float], int]]:
    """Seconds of device idle time in the whole units by the innermost
    ``ff.`` span covering them (key None: no span), and the units.  None
    without whole units or without a device in the trace."""
    spans = ff_spans(events)
    rng = whole_units(spans, unit, frame)
    if rng is None or not TR.device_planes(events):
        return None
    lo, hi, units = rng
    cover = innermost_cover(spans)
    starts = [c[1] for c in cover]
    by: Dict[Optional[str], float] = {}
    for g0, g1 in device_gaps(events, lo, hi):
        under = _under(cover, starts, g0, g1)
        for name, ov in under.items():
            by[name] = by.get(name, 0.0) + ov
        rest = (g1 - g0) - sum(under.values())
        if rest > 1e-12:
            by[None] = by.get(None, 0.0) + rest
    return by, units


# ---- readers: fn(run, **args) -> float | None ---------------------------

def _events(run) -> Optional[dict]:
    return (run.trace or {}).get("events") or None


def span_ms_per_unit(run, *, span, unit, frame=None, minus=None):
    """Milliseconds of ``span`` (less those of ``minus``) per whole unit."""
    events = _events(run)
    if events is None:
        return None
    spans = ff_spans(events)
    rng = whole_units(spans, unit, frame)
    if rng is None:
        return None
    lo, hi, units = rng
    tot = 0.0
    for name, s, e in spans:
        if name == span:
            tot += _clipped(s, e, lo, hi)
        elif name == minus:
            tot -= _clipped(s, e, lo, hi)
    return 1e3 * tot / units


def idle_under_spans_ms_per_unit(run, *, unit, frame=None, exclude=()):
    """Milliseconds of device idle time per whole unit that fall under an
    ``ff.`` span other than those in ``exclude`` (the waits that are not
    the loop's doing: the sync, the sleep until the next arrival)."""
    events = _events(run)
    got = events and idle_by_span(events, unit, frame)
    if not got:
        return None
    by, units = got
    return 1e3 * sum(
        s for name, s in by.items() if name is not None and name not in exclude
    ) / units


def idle_unattributed_share(run, *, unit, frame=None):
    """Share of the device's idle time in the whole units that no ``ff.``
    span covers.  None when the device was never idle there."""
    events = _events(run)
    got = events and idle_by_span(events, unit, frame)
    if not got:
        return None
    by, _ = got
    total = sum(by.values())
    if total <= 0:
        return None
    return 100.0 * by.get(None, 0.0) / total


def span_count(run, *, span):
    """How many ``span`` events the slice holds.  0 is a reading; None
    when the trace holds no ``ff.`` span at all, so nothing can be said."""
    events = _events(run)
    spans = events and ff_spans(events)
    if not spans:
        return None
    return float(sum(1 for name, _, _ in spans if name == span))


# ---- the result line's breakdown ------------------------------------------

def idle_gaps(events: dict, k: int = 10, longest: int = 200) -> List[List]:
    """The ``longest`` gaps between device operations on the first chip,
    summed by what the host was doing, the ``k`` largest sums.  A gap goes
    whole to the innermost ``ff.`` span that covers most of it (a child
    beats the parent around it, and any ``ff.`` span beats another host
    event however long: a Python frame that always waits covers every
    gap and explains none); where no ``ff.`` span touches it, to the
    runtime's own host event that covers most of it; to ``unattributed``
    where nothing does."""
    ops = first_chip_ops(events)
    if not ops:
        return []
    gaps = device_gaps(events, ops[0][1], max(s + d for _, s, d in ops))
    gaps.sort(key=lambda g: g[0] - g[1])
    cover = innermost_cover(ff_spans(events))
    starts = [c[1] for c in cover]
    other = [
        (name, start, start + dur)
        for evs in events.get(HOST_PLANE, {}).values() for name, start, dur in evs
        if dur > 1e-4 and not name.startswith(PREFIX)  # shorter ones name no gap worth listing
    ]
    by_name: Dict[str, float] = {}
    for g0, g1 in gaps[:longest]:
        under = _under(cover, starts, g0, g1)
        if not under:
            for name, s, e in other:
                ov = _clipped(s, e, g0, g1)
                if ov > under.get(name, 0.0):
                    under[name] = ov
        best = max(under, key=under.get) if under else "unattributed"
        by_name[best] = by_name.get(best, 0.0) + (g1 - g0)
    return [[n, sec] for n, sec in sorted(by_name.items(), key=lambda kv: -kv[1])[:k]]


# ---- by hand ------------------------------------------------------------

def sync_after_device_ms(events: dict, sync: str = "ff.serve.sync") -> List[float]:
    """For each ``sync`` span, its end minus the end of the last device
    operation that started before it: the two clocks agree when the sync
    returns just after the work it waited for."""
    ops = first_chip_ops(events)
    starts = [e[1] for e in ops]
    ends, hi = [], float("-inf")
    for _, s, d in ops:  # running maximum: containers end after their leaves start
        hi = max(hi, s + d)
        ends.append(hi)
    out = []
    for name, _, end in ff_spans(events):
        i = bisect.bisect_left(starts, end)
        if name == sync and i:
            out.append(1e3 * (end - ends[i - 1]))
    return out


def op_after_dispatch_ms(events: dict, sync: str = "ff.serve.sync",
                         window: str = "ff.serve.window") -> List[float]:
    """For each ``sync`` span, the start of the first device operation
    after its end minus the start of the next ``window`` span: the device
    cannot start on a window the host has not begun.  With
    ``sync_after_device_ms`` this brackets the two clocks' offset."""
    op_starts = [e[1] for e in first_chip_ops(events)]
    spans = ff_spans(events)
    win_starts = [s for name, s, _ in spans if name == window]
    out = []
    for name, _, end in spans:
        i, j = bisect.bisect_left(op_starts, end), bisect.bisect_left(win_starts, end)
        if name == sync and i < len(op_starts) and j < len(win_starts):
            out.append(1e3 * (op_starts[i] - win_starts[j]))
    return out


def describe(events: dict, cell: str) -> dict:
    """Every metric of this module that ``cell`` lists (its own files:
    ``workloads/<cell>.json``, ``layer_metrics/<name>.json``), the idle
    time per unit by span, and the clocks' agreement."""
    import statistics
    import types

    here = os.path.dirname(os.path.abspath(__file__))

    def load(*parts):
        with open(os.path.join(here, *parts)) as f:
            return json.load(f)

    run = types.SimpleNamespace(trace={"events": events}, facts={})
    out: dict = {"metrics": {}}
    unit = frame = None
    for name in load("workloads", f"{cell}.json")["layer_metrics"]:
        spec = load("layer_metrics", f"{name}.json")
        mod, _, fn = spec["reader"].partition(":")
        if mod != "benchmarks.span_readers":
            continue
        out["metrics"][name] = globals()[fn](run, **spec["args"])
        unit, frame = spec["args"].get("unit", unit), spec["args"].get("frame", frame)
    spans = ff_spans(events)
    rng = unit and whole_units(spans, unit, frame)
    if rng:
        out["units"] = rng[2]
        out["stretch_ms_per_unit"] = 1e3 * (rng[1] - rng[0]) / rng[2]
    got = unit and idle_by_span(events, unit, frame)
    if got:
        by, units = got
        out["idle_ms_per_unit_by_span"] = {
            str(k): 1e3 * v / units for k, v in sorted(by.items(), key=lambda kv: -kv[1])
        }
    durs: Dict[str, List[float]] = {}
    for name, s, e in spans:
        durs.setdefault(name, []).append(1e3 * (e - s))
    out["spans"] = {
        k: {"count": len(v), "total_ms": sum(v), "median_ms": statistics.median(v),
            "max_ms": max(v)}
        for k, v in sorted(durs.items())
    }
    for key, lag in (("sync_end_after_last_device_op_ms", sync_after_device_ms(events)),
                     ("first_device_op_after_dispatch_start_ms", op_after_dispatch_ms(events))):
        if lag:
            out[key] = {"n": len(lag), "median": statistics.median(lag),
                        "min": min(lag), "max": max(lag)}
    return out


if __name__ == "__main__":
    import sys

    print(json.dumps(describe(TR.load(TR.find_xplane(sys.argv[1])), sys.argv[2]), indent=1))
