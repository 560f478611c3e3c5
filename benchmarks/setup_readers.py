"""Readers of the program's set-up tally (``flexflow_tpu.obs.setup_summary``):
what of ``setup_s`` the program's ``ff.setup.*`` spans hold, and how much of
it jax spent tracing and lowering, compiling or loading from the persistent
cache, and how many programs that cache could not serve.

``run.py`` calls readers in the job's process, after the job, so the tally
they read is the run's own; every ``ff.setup`` span of a job closes before
its window.  Each reader returns None where the tally holds no ``ff.setup``
span — as on a tree from before the spans, whose ``obs`` has no
``setup_summary`` at all.  Named ``benchmarks.setup_readers:<fn>`` in a
metric's file (``README.md``).
"""

from __future__ import annotations


def summary():
    """The process's tally, or None where it holds no ``ff.setup`` span."""
    from flexflow_tpu import obs

    read = getattr(obs, "setup_summary", None)
    got = read() if read is not None else None
    return got if got and got.get("spans") else None


def _programs(tally):
    return [p for by_fun in tally["programs"].values() for p in by_fun.values()]


def setup_unattributed_s(run):
    """``setup_s`` less the outermost ``ff.setup`` spans: what the program
    does not own (imports, the runtime's start, the harness's weights,
    traffic, probes and warm-up calls)."""
    tally, setup = summary(), run.facts.get("setup_s")
    if tally is None or setup is None:
        return None
    return float(setup) - tally["outer_s"]


def setup_lower_s(run):
    """Seconds jax spent tracing jaxprs and lowering them to MLIR inside
    ``ff.setup`` spans."""
    tally = summary()
    if tally is None:
        return None
    return sum(p["trace_s"] + p["lower_s"] for p in _programs(tally))


def setup_compile_load_s(run):
    """Seconds of XLA compiles or persistent-cache loads inside
    ``ff.setup`` spans."""
    tally = summary()
    if tally is None:
        return None
    return sum(p["compile_s"] for p in _programs(tally))


def setup_count(run, *, field):
    """A count summed over the programs built inside ``ff.setup`` spans:
    ``lowerings`` (the init programs, a program lowered again) or
    ``cache_misses`` (programs the persistent cache could not serve)."""
    tally = summary()
    if tally is None:
        return None
    return float(sum(p[field] for p in _programs(tally)))
