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
  * Set-up is timed always.  A span of category ``setup``
    (``ff.setup.<name>``: the model's compile, the step program's first
    build, the serve engine's build and warm-up) is timed at every level,
    ``off`` included, into one process-wide tally that ``set_tracer``
    does not replace; jax's own trace, lowering, compile-or-load and
    persistent-cache events are counted under the innermost open one.
    ``setup_summary()`` reads it.  Nothing of it runs in a serve window
    or a step of ``fit``.
"""

from __future__ import annotations

import functools
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
    "fit.batches_viewed",
    "fit.batches_gathered",
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
# jax's compile phases, by the name the set-up tally counts them under
PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    COMPILE_EVENT: "compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_ANNOTATION = None  # the profiler-only span class, built at the first span


def _annotation(name: str, cat: str):
    """The profiler's sink of one span: a ``jax.profiler.TraceAnnotation``
    that also answers ``.set()``.  jax is imported here, at the first
    span, so ``obs`` imports without a backend; the same moment registers
    the listeners for jax's compile phases and persistent-cache events."""
    global _ANNOTATION
    if _ANNOTATION is None:
        import jax

        class _Annotation(jax.profiler.TraceAnnotation):
            __slots__ = ()

            def set(self, **args) -> None:
                pass  # annotation arguments are event stats no reader keeps

        jax.monitoring.register_event_time_span_listener(_on_jax_phase)
        jax.monitoring.register_event_listener(_on_jax_event)
        _ANNOTATION = _Annotation
    return _ANNOTATION(f"ff.{cat}" if name == cat else f"ff.{cat}.{name}")


def _on_jax_phase(event: str, t0: float, t1: float, fun_name: str = "", **_kw) -> None:
    """jax's trace, lowering and compile-or-load of a program, counted in
    the set-up tally; and a zero-length ``ff.compile`` mark whenever XLA
    builds (or loads from the persistent cache) a program: a compile
    inside a measured window shows in the trace at the end of the gap it
    caused.  Runs only when something compiles — nothing on a warm call."""
    phase = PHASE_EVENTS.get(event)
    if phase is None:
        return
    _SETUP.phase(phase, t0, t1, fun_name)
    if phase == "compile":
        with _annotation("compile", "compile"):
            pass
        _TRACER.instant("compile", cat="compile", seconds=t1 - t0)


def _on_jax_event(event: str, **_kw) -> None:
    """A persistent-cache hit or miss: jax records it inside the compile
    it belongs to, before that compile's own event."""
    field = CACHE_EVENTS.get(event)
    if field is not None:
        _SETUP.cache(field)


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


class _SetupSpan(_Span):
    """A span of category ``setup``: timed into the process-wide tally
    whatever the tracer's level, and into the tracer too when it is on
    (``tracer`` is None when it is off)."""

    __slots__ = ()

    def __enter__(self) -> "_SetupSpan":
        self._ann.__enter__()
        _SETUP.open(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        _SETUP.close(self, t1 - self._t0)
        if self.tracer is not None:
            self.tracer._record_span(self.name, self.cat, self._t0, t1, self.args)
        self._ann.__exit__(*exc)
        return False


_PHASE_FIELDS = {"trace": ("traces", "trace_s"), "lower": ("lowerings", "lower_s"),
                 "compile": ("compiles", "compile_s")}


class _SetupTally:
    """What set-up cost, by ``ff.setup`` span and by program.  jax's
    phase events land under the innermost open ``ff.setup`` span and the
    function's name; outside every such span they are not counted, so
    a serve window or a step of ``fit`` adds nothing here even where it
    compiles.  A jaxpr traced inside another's trace (a jitted function
    called by a jitted function) counts once, and its seconds once."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open: List[_SetupSpan] = []
        self._local = threading.local()  # cache events awaiting their compile
        self.spans: Dict[str, List[float]] = {}  # name -> [count, seconds]
        self.outer_s = 0.0
        self.programs: Dict[tuple, Dict[str, float]] = {}
        self.persistent_hits = 0  # every hit of the process, in a span or not

    def open(self, span: _SetupSpan) -> None:
        with self._lock:
            self._open.append(span)

    def close(self, span: _SetupSpan, seconds: float) -> None:
        with self._lock:
            if span in self._open:
                self._open.remove(span)
            agg = self.spans.setdefault(span.name, [0, 0.0])
            agg[0] += 1
            agg[1] += seconds
            if not self._open:
                self.outer_s += seconds
                self._local.traced = []

    def _program(self, fun_name: str) -> Optional[Dict[str, float]]:
        if not self._open:
            return None
        key = (self._open[-1].name, fun_name)
        rec = self.programs.get(key)
        if rec is None:
            rec = self.programs[key] = {
                "traces": 0, "trace_s": 0.0, "lowerings": 0, "lower_s": 0.0,
                "compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
            }
        return rec

    def phase(self, phase: str, t0: float, t1: float, fun_name: str) -> None:
        count, secs = _PHASE_FIELDS[phase]
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]  # a module's name: the function's under jit
        with self._lock:
            rec = self._program(fun_name)
            if rec is None:
                self._local.pending = None
                return
            seconds = t1 - t0
            if phase == "trace":
                # events arrive as traces end, inner ones first: those
                # that began inside this one are the last on the list
                done = getattr(self._local, "traced", None) or []
                while done and done[-1][0] >= t0:
                    s0, s1 = done.pop()
                    seconds -= s1 - s0
                done.append((t0, t1))
                self._local.traced = done
            rec[count] += 1
            rec[secs] += seconds
            if phase == "compile":
                for field, n in (getattr(self._local, "pending", None) or {}).items():
                    rec[field] += n
                self._local.pending = None

    def cache(self, field: str) -> None:
        with self._lock:
            if field == "cache_hits":
                self.persistent_hits += 1
            pending = getattr(self._local, "pending", None) or {}
            pending[field] = pending.get(field, 0) + 1
            self._local.pending = pending

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            programs: Dict[str, Dict[str, Dict[str, float]]] = {}
            for (span, fun), rec in self.programs.items():
                programs.setdefault(span, {})[fun] = dict(rec)
            return {
                "spans": {n: {"count": int(c), "seconds": s}
                          for n, (c, s) in self.spans.items()},
                "outer_s": self.outer_s,
                "programs": programs,
            }


_SETUP = _SetupTally()


def setup_summary() -> Dict[str, Any]:
    """The process's set-up, from its start: ``spans`` (per ``ff.setup``
    span name, ``count`` and ``seconds``), ``outer_s`` (seconds of the
    outermost ``ff.setup`` spans: what the program owns of the time to
    ready) and ``programs`` (span name -> the function's name -> jax's
    ``traces``, ``lowerings``, ``compiles`` — a compile or a load from
    the persistent cache — with their seconds ``trace_s``, ``lower_s``,
    ``compile_s``, and ``cache_hits`` / ``cache_misses`` of the
    persistent cache).  docs/OBSERVABILITY.md has the vocabulary."""
    return _SETUP.summary()


def persistent_cache_hits() -> int:
    """Programs the persistent compilation cache has served this process
    (jax's own ``cache_hits`` event), in a set-up span or not."""
    return _SETUP.persistent_hits


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
        if cat == "setup":
            return _SetupSpan(self if self.enabled else None, name, cat, args)
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


def setup_span(name: str):
    """Decorator: each call of the function is one ``ff.setup.<name>``
    span of the process tracer (the whole of ``FFModel.compile``, of
    ``ServeEngine.__init__``)."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _TRACER.span(name, cat="setup"):
                return fn(*args, **kwargs)

        return inner

    return wrap


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
