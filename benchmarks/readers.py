"""Readers: the few pieces of code that turn a run's raw material into a
per-layer metric.  Each metric is data (``layer_metrics/<name>.json``:
``layer``, ``unit``, ``moves``, ``source``, ``reader`` and the reader's
arguments); a reader is ``fn(run, **args) -> float | None``.

``run`` carries ``facts`` (what the job counted and timed: counters,
spans, samples, work computed from shapes), ``trace`` (the reduced
profiler trace with the traced slice's ``window_s`` and unit counts, or
None), ``peaks`` (this chip's row of ``peaks.json``) and ``chips``.

A reader that finds nothing to read returns None and the metric is left
out of the line.  None of them returns 0 for a share of a peak.
A later PR that needs another reader adds a module and names it by its
dotted path (``"reader": "benchmarks.more_readers:fn"``), as the readers
of the program's ``ff.*`` spans are named (``benchmarks/span_readers.py``).
"""

from __future__ import annotations

import statistics

from benchmarks import trace_reduce as TR
from benchmarks import work


def fact(run, *, name, scale=1.0):
    v = run.facts.get(name)
    return None if v is None else float(v) * scale


def span_seconds(run, *, span):
    return run.facts.get("spans", {}).get(span)


def ratio(run, *, num, den, scale=1.0):
    n, d = run.facts.get(num), run.facts.get(den)
    if n is None or not d:
        return None
    return scale * float(n) / float(d)


def percentile(run, *, samples, q):
    vals = [v for v in run.facts.get("samples", {}).get(samples, []) if v is not None]
    if len(vals) < 2:
        return None
    return statistics.quantiles(vals, n=100, method="inclusive")[int(q) - 1]


def device_idle_share(run):
    if not run.trace:
        return None
    busy = run.trace["busy_s"]
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.trace["window_s"])


def device_ms_per_unit(run, *, unit):
    """Device busy time of the traced slice over its steps (or windows)."""
    if not run.trace or not run.trace.get(unit):
        return None
    return 1e3 * run.trace["busy_s"] / run.trace[unit]


def mfu_of_traced_steps(run, *, flops_per_step):
    """Forward + backward operations of a step over the mean step period
    in the traced slice times the chips' peak."""
    if not run.trace or not run.trace.get("steps"):
        return None
    period = run.trace["window_s"] / run.trace["steps"]
    return 100.0 * run.facts[flops_per_step] / (
        period * run.peaks["flops_bf16"] * run.chips
    )


def mfu_of_window(run, *, flops):
    """Model operations of everything the window processed over the
    window's wall time times the chips' peak."""
    f, w = run.facts.get(flops), run.facts.get("window_s")
    if not f or not w:
        return None
    return 100.0 * f / (w * run.peaks["flops_bf16"] * run.chips)


def kernel_roofline(run, *, regex, bytes_per_call, flops_per_call):
    """Share of the roofline a kernel reached in the traced slice: the
    least time the chip could take for its calls there (mean work per
    call over the whole window, from shapes, times the calls the trace
    holds) over the kernel's summed device time."""
    if not run.trace:
        return None
    seconds, calls = TR.time_by_regex(run.trace["events"], regex)
    b, f = run.facts.get(bytes_per_call), run.facts.get(flops_per_call)
    if not calls or seconds <= 0 or not b:
        return None
    least, _ = work.roofline_seconds(f * calls, b * calls, run.peaks)
    return 100.0 * least / seconds


def op_time_share(run, *, regex):
    """The share of the device's busy time in the traced slice that the
    operations matching ``regex`` took.  0 when the trace holds none:
    this is a share of time, not of a peak, and "none" is a reading."""
    if not run.trace or run.trace["busy_s"] <= 0:
        return None
    seconds, _ = TR.time_by_regex(run.trace["events"], regex)
    return 100.0 * seconds / run.trace["busy_s"]


def _module_time(run, regex):
    if not run.trace:
        return None
    seconds, calls = TR.time_by_regex(run.trace["events"], regex, line=TR.MODULES_LINE)
    return (seconds, calls) if calls and seconds > 0 else None


def module_time_share(run, *, regex):
    """The share of the device's busy time in the traced slice that the
    programs matching ``regex`` took (one ``XLA Modules`` event an
    executed program, named ``jit_<function>``).  A program's event
    spans its operations and the gaps between them, so the shares of all
    programs can sum to a little over 100.  None where the line holds no
    such program: a trace from before the program had that name has
    nothing to read."""
    got = _module_time(run, regex)
    if got is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * got[0] / run.trace["busy_s"]


def module_ms_per_call(run, *, regex):
    """Mean device milliseconds a call of the programs matching ``regex``
    in the traced slice: summed ``XLA Modules`` time over its events."""
    got = _module_time(run, regex)
    return None if got is None else 1e3 * got[0] / got[1]
