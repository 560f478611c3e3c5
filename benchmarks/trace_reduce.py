"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  On a
TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event per executed HLO operation (fusions, custom calls, copies),
``XLA Modules`` one per executed program, ``Steps`` one per step.  Busy
time is the union of the ``XLA Ops`` intervals; the idle share is what
is left of the traced window.  Events nest (a ``while`` holds its body's
operations), so sums are taken over leaves only: an event that contains
another on the same line is a container and its time is its children's.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start_trace(trace_dir: str) -> None:
    """``jax.profiler.start_trace`` with the Python-frame tracer off.  On
    by default, it records every Python call of every thread, which slows
    the host the traced slice is meant to show (a serve window by 2-5 %,
    admission 2.5 x: PERF.md) and names idle gaps by frames such as
    ``_threading.py:637_wait``.  Host TraceMe events stay on: they carry
    the program's ``ff.*`` annotations and the runtime's own."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_s, duration_s)]}}`` for the device
    planes and the host's ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == "/host:CPU"):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def device_planes(trace: dict) -> List[str]:
    return sorted(p for p in trace if DEVICE_PLANE.match(p) and trace[p].get(OPS_LINE))


def union_seconds(events: List[Event]) -> float:
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def leaves(events: List[Event]) -> List[Event]:
    """Events that contain no other event of the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < start + dur and nxt[1] + nxt[2] <= start + dur + 1e-12:
            continue  # the next event starts and ends inside this one
        out.append((name, start, dur))
    return out


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips used."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(union_seconds(trace[p][OPS_LINE]) for p in planes) / len(planes)


def time_by_regex(trace: dict, pattern: str, line: str = OPS_LINE) -> Tuple[float, int]:
    """Summed device seconds and count of the leaf events whose name
    matches, averaged over the chips used."""
    planes = device_planes(trace)
    rx = re.compile(pattern)
    tot, n = 0.0, 0
    for p in planes:
        evs = trace[p].get(line, [])
        for name, _, dur in (leaves(evs) if line == OPS_LINE else evs):
            if rx.search(name):
                tot += dur
                n += 1
    k = max(1, len(planes))
    return tot / k, n // k


def op_name(event_name: str) -> str:
    """``%fusion.338 = (bf16[...]) fusion(...)`` -> ``fusion.338``: the
    TPU's ``XLA Ops`` events are named by their whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: dict, k: int = 10) -> List[List]:
    """The leaf operations that took most device time, by HLO name."""
    planes = device_planes(trace)
    agg: Dict[str, float] = {}
    for p in planes:
        for name, _, dur in leaves(trace[p][OPS_LINE]):
            key = op_name(name)
            agg[key] = agg.get(key, 0.0) + dur
    n = max(1, len(planes))
    return [[name, s / n] for name, s in sorted(agg.items(), key=lambda kv: -kv[1])[:k]]


def traced_calls_share(trace: dict, pattern: str, calls: int):
    """``XLA Modules`` events matching ``pattern`` on the first chip over
    the program calls the host counted while the profiler ran.  A sound
    trace reads about 1 (the slice's ragged edges put it a call or two
    off); a device line that came back truncated reads far under it, and
    the idle share beside it is then not to be believed.  None where the
    host counted no call or the trace holds no device."""
    planes = device_planes(trace)
    if not planes or not calls:
        return None
    rx = re.compile(pattern)
    seen = sum(1 for name, _, _ in trace[planes[0]].get(MODULES_LINE, []) if rx.search(name))
    return seen / calls


def describe(trace: dict, k: int = 80) -> dict:
    """What a trace holds, for the look by hand before a regex is written."""
    out = {}
    for p, lines in trace.items():
        out[p] = {}
        for line, evs in lines.items():
            agg: Dict[str, List[float]] = {}
            for name, _, dur in evs:
                a = agg.setdefault(name, [0, 0.0])
                a[0] += 1
                a[1] += dur
            top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:k]
            out[p][line] = {
                "events": len(evs),
                "union_s": union_seconds(evs),
                "top": [[n, c, s] for n, (c, s) in top],
            }
    return out


def save_recorded(trace: dict, path: str, seconds: float, name_chars: int = 160) -> None:
    """Keep the first ``seconds`` of each device line (names cut to
    ``name_chars``) as gzipped JSON: a recorded trace small enough to
    live beside the tests of this file."""
    import gzip
    import json

    out = {}
    for p in device_planes(trace):
        t0 = min(e[1] for e in trace[p][OPS_LINE])
        out[p] = {
            line: [[n[:name_chars], round(s - t0, 9), round(d, 9)]
                   for n, s, d in evs if s - t0 < seconds]
            for line, evs in trace[p].items()
        }
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def load_recorded(path: str) -> Dict[str, Dict[str, List[Event]]]:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


if __name__ == "__main__":
    import json
    import sys

    # python benchmarks/trace_reduce.py <trace dir>: what the trace holds
    # python benchmarks/trace_reduce.py <trace dir> --record <out.json.gz> <seconds>
    _trace = load(find_xplane(sys.argv[1]))
    if len(sys.argv) > 2 and sys.argv[2] == "--record":
        save_recorded(_trace, sys.argv[3], float(sys.argv[4]))
    else:
        print(json.dumps(describe(_trace), indent=1))
