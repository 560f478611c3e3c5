"""Process-wide tracer: nestable spans, counters, Chrome-trace export.

The reference leans on observability to make auto-parallelization
debuggable — per-op ``--profiling`` timing printouts
(``src/runtime/model.cc:3650-3653``), Legion Prof/Spy tracing, and the
``log_measure``/``log_sim``/``log_dp`` logger categories.  This module is
the TPU-native analog: ONE process-wide :class:`Tracer` that the runtime
(``runtime/executor.py``), the search (``search/``), and the fit/eval
loops (``model.py``) all record into, emitting standard
Chrome-trace-format JSON (loadable in ``chrome://tracing`` / Perfetto,
https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU)
plus a machine-readable ``summary()`` dict that ``bench.py`` consumers
and ``tools/trace_report.py`` read.

Design constraints:
  * One span call, two sinks.  A ``step``-level span always enters a
    ``jax.profiler.TraceAnnotation`` named ``ff.<cat>.<name>`` (``ff.<cat>``
    when the two are equal): inert without a profiler session (one
    activity check, no clock read), and under one
    (``jax.profiler.start_trace``) an event in the ``/host:CPU`` plane
    on the profiler's clock, beside the device's ``XLA Ops``.  When the
    process tracer is on, the same object also records the Chrome event.
    The program runs the same either way: nothing reads the tracer's
    state to pick a path at these sites.  The NAME is the contract with
    readers (docs/OBSERVABILITY.md lists the vocabulary); ``args`` go to
    the Chrome event only.
  * Near-zero overhead when disabled: counters, samples, instants and
    ``op``-level spans check ``tracer.enabled`` (one attr read) or
    receive the shared ``_NULL_SPAN`` singleton — no allocation, no
    clock read, no event.
  * Levels: ``off`` (default) < ``step`` (step/compile/search/epoch
    spans) < ``op`` (adds per-op / per-frontier detail).  A span or
    sample declared at ``level="op"`` is dropped unless the tracer runs
    at ``op``.
  * Spans nest: events are "X" (complete) records stamped at span EXIT
    with the entry timestamp, so a child (which closes first) always
    lies inside its parent's [ts, ts+dur] window on the same tid.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

LEVELS = ("off", "step", "op")

# counter glossary (documented in docs/OBSERVABILITY.md): pre-registered
# at 0 so a trace/summary always carries the full vocabulary — a consumer
# can distinguish "no OOM rejections happened" from "this build doesn't
# count them".
CORE_COUNTERS = (
    "jit.cache_hit",
    "jit.cache_miss",
    "executor.host_syncs",
    "fit.metric_flushes",
    "recompile.count",
    "search.candidates_explored",
    "search.rewrites_considered",
    "search.rewrites_applied",
    "search.oom_rejections",
    "profiler.cache_hit",
    "profiler.cache_miss",
    "checkpoint.bytes_written",
    "network.ring_collectives",
    "network.hierarchical_collectives",
    # --verify-compiled ffcheck pass (docs/ANALYSIS.md): violation count
    # from the last analyzed program (0 after a clean verify)
    "analysis.violations",
)


class _NullSpan:
    """Shared no-op context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ANNOTATION = None  # the profiler-only span class, built at the first span


def _annotation(name: str, cat: str):
    """The profiler's sink of one span: a ``jax.profiler.TraceAnnotation``
    that also answers ``.set()``.  jax is imported here, at the first
    span, so ``obs`` imports without a backend; the same moment registers
    the one listener that marks compiles."""
    global _ANNOTATION
    if _ANNOTATION is None:
        import jax

        class _Annotation(jax.profiler.TraceAnnotation):
            __slots__ = ()

            def set(self, **args) -> None:
                pass  # annotation arguments are event stats no reader keeps

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _ANNOTATION = _Annotation
    return _ANNOTATION(f"ff.{cat}" if name == cat else f"ff.{cat}.{name}")


def _on_jax_duration(event: str, duration_s: float, **_kw) -> None:
    """A zero-length ``ff.compile`` mark whenever XLA builds (or loads
    from the persistent cache) a program: a compile inside a measured
    window shows in the trace at the end of the gap it caused.  Runs
    only when something compiles — nothing on a warm call."""
    if event == COMPILE_EVENT:
        with _annotation("compile", "compile"):
            pass
        _TRACER.instant("compile", cat="compile", seconds=duration_s)


class _Span:
    """One live span of an enabled tracer: the profiler annotation for
    its whole life, and an 'X' event at exit."""

    __slots__ = ("tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._ann = _annotation(name, cat)

    def set(self, **args) -> None:
        """Attach/override args mid-span (e.g. a result computed inside)."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.tracer._record_span(
            self.name, self.cat, self._t0, time.perf_counter(), self.args
        )
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Nestable spans + counters with Chrome-trace JSON export.

    All mutation is lock-guarded (the native dataloader and multi-host
    helpers touch the runtime from worker threads); reads for export
    happen under the same lock.
    """

    def __init__(self, level: str = "off", out_path: Optional[str] = None):
        assert level in LEVELS, f"trace level must be one of {LEVELS}, got {level!r}"
        self.level = level
        self.enabled = level != "off"
        self.op_level = level == "op"
        self.out_path = out_path
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[str, float] = (
            {k: 0.0 for k in CORE_COUNTERS} if self.enabled else {}
        )
        # per-(cat, name) span aggregates for summary(): [count, total_s]
        self._span_agg: Dict[tuple, List[float]] = {}
        self._samples: Dict[str, Dict[str, float]] = {}
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    # --- recording ---------------------------------------------------------
    def span(self, name: str, cat: str = "step", level: str = "step", **args):
        """Context manager timing one phase.  ``cat`` is the Chrome-trace
        category AND the summary phase bucket; ``level='op'`` spans exist
        only when the tracer runs at op level.  Every other span is a
        profiler annotation ``ff.<cat>.<name>`` whether or not the tracer
        is on, and a Chrome event besides when it is."""
        if level == "op" and not self.op_level:
            return _NULL_SPAN
        if not self.enabled:
            return _annotation(name, cat)
        return _Span(self, name, cat, args)

    def _record_span(self, name, cat, t0, t1, args) -> None:
        with self._lock:
            self.events.append({
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts": (t0 - self._t0) * 1e6,
                "dur": (t1 - t0) * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            })
            agg = self._span_agg.setdefault((cat, name), [0, 0.0])
            agg[0] += 1
            agg[1] += t1 - t0

    def counter(self, name: str, value: float = 1.0) -> None:
        """Accumulate a named counter (cheap: no event per increment; the
        cumulative values are emitted as 'C' events at export time)."""
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def sample(self, name: str, value: float, level: str = "op") -> None:
        """Record an instantaneous gauge (e.g. frontier beam width): one
        'C' event per call plus min/max/last aggregates in the summary."""
        if not self.enabled or (level == "op" and not self.op_level):
            return
        with self._lock:
            self.events.append({
                "name": name,
                "ph": "C",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": os.getpid(),
                "args": {name.rsplit(".", 1)[-1]: value},
            })
            s = self._samples.setdefault(
                name, {"count": 0, "min": value, "max": value, "last": value}
            )
            s["count"] += 1
            s["min"] = min(s["min"], value)
            s["max"] = max(s["max"], value)
            s["last"] = value

    def instant(self, name: str, cat: str = "step", **args) -> None:
        """Zero-duration marker event (e.g. a recompile trigger firing)."""
        if not self.enabled:
            return
        with self._lock:
            self.events.append({
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            })

    # --- export ------------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """Machine-readable rollup: per-phase (category) and per-span-name
        time totals, counter values, gauge aggregates.  This is the shared
        measurement vocabulary ``bench.py`` consumers read — see
        docs/OBSERVABILITY.md for the field glossary."""
        with self._lock:
            phases: Dict[str, Dict[str, float]] = {}
            spans: Dict[str, Dict[str, float]] = {}
            for (cat, name), (n, tot) in self._span_agg.items():
                ph = phases.setdefault(cat, {"count": 0, "total_s": 0.0})
                ph["count"] += n
                ph["total_s"] += tot
                spans[name] = {
                    "cat": cat,
                    "count": n,
                    "total_s": tot,
                    "mean_s": tot / n if n else 0.0,
                }
            return {
                "level": self.level,
                "wall_s": time.perf_counter() - self._t0,
                "phases": phases,
                "spans": spans,
                "counters": dict(self.counters),
                "samples": {k: dict(v) for k, v in self._samples.items()},
            }

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace JSON Object Format: ``traceEvents`` plus the
        summary under a vendor key (extra top-level keys are legal and
        ignored by chrome://tracing / Perfetto)."""
        summ = self.summary()
        with self._lock:
            events = list(self.events)
            # final cumulative counter values as 'C' events so the
            # counter track exists in the timeline UIs
            ts = (time.perf_counter() - self._t0) * 1e6
            pid = os.getpid()
            for k, v in self.counters.items():
                events.append({
                    "name": k, "ph": "C", "ts": ts, "pid": pid,
                    "args": {k.rsplit(".", 1)[-1]: v},
                })
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "ts": 0,
                "args": {"name": "flexflow_tpu"},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "flexflow_tpu": {"summary": summ},
        }

    def save(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome-trace file; returns the path written (None when
        no path is configured).  Safe to call repeatedly — later calls
        overwrite with the fuller trace."""
        path = path or self.out_path
        if not path or not self.enabled:
            return None
        doc = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# --- process-wide singleton -------------------------------------------------
_TRACER = Tracer()  # disabled: every site sees the null fast path


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    _TRACER = tracer
    return _TRACER


def configure(level: str = "step", out_path: Optional[str] = None) -> Tracer:
    """Install a fresh enabled tracer as the process tracer."""
    return set_tracer(Tracer(level=level, out_path=out_path))


def configure_from_config(cfg) -> Tracer:
    """Wire the process tracer to ``FFConfig`` (``--trace-out`` /
    ``--trace-level``).  ``--trace-out`` alone implies level ``step``.
    A config with tracing off leaves the current tracer untouched, so an
    explicitly configured tracer survives auxiliary FFModel constructions
    (e.g. a search probe model)."""
    level = getattr(cfg, "trace_level", "off")
    out = getattr(cfg, "trace_out", None)
    if level == "off" and out:
        level = "step"
    if level == "off":
        return _TRACER
    return configure(level=level, out_path=out)
