"""One run of one cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name (``README.md`` has the rules): the cell's
file under ``workloads/``, its traffic mix under ``traffic_mixes/``, its
configuration under ``configs/``, the
configuration's plain reference under ``reference/``, the cell's job
under ``jobs/``, each per-layer metric under ``layer_metrics/`` and its
reader in ``readers.py`` or, named ``module:function``, in a module of
its own (``span_readers.py``).  This file holds no table of cells, models or
metrics.

Needs a TPU whose ``device_kind`` is in ``peaks.json`` and as many chips
as the cell asks for; otherwise it exits non-zero and prints no result.
The last line of standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    cell = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{cell['config']}.json")
    cell["mix"] = load_json("traffic_mixes", f"{cell['traffic']}.json")
    metrics = {m: load_json("layer_metrics", f"{m}.json") for m in cell["layer_metrics"]}
    reported = set(cell["end_to_end"]) | {"setup_s"}
    for m, spec in metrics.items():
        if spec["moves"] not in reported:
            raise SystemExit(
                f"cell {name} lists per-layer metric {m}, which moves "
                f"{spec['moves']}; the cell does not report that"
            )
    return cell, config, metrics


def resolve_reader(name: str):
    if ":" in name:
        mod, _, fn = name.partition(":")
        return getattr(importlib.import_module(mod), fn)
    from benchmarks import readers

    return getattr(readers, name)


def device_or_exit(chips: int):
    """The device as JAX reports it, and this chip's peaks."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"benchmark: no TPU (jax.default_backend() is {backend!r}); "
            "it measures on the chip and falls back to nothing",
            file=sys.stderr,
        )
        raise SystemExit(3)
    devs = jax.devices()
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    kind = devs[0].device_kind
    peaks = load_json("peaks.json")
    if kind not in peaks:
        print(f"benchmark: device_kind {kind!r} is not in peaks.json", file=sys.stderr)
        raise SystemExit(3)
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs)}
    return device, peaks[kind]


def memory_stats() -> dict:
    """The first device's allocator statistics, numbers only."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: v for k, v in stats.items() if isinstance(v, (int, float))}


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip.  On a TPU the allocator
    keeps two pools apart: ``peak_bytes_in_use`` counts the arrays the
    process holds (weights, optimizer state, KV pool, batches) and
    ``peak_bytes_reserved`` the scratch a running program reserves (its
    temporaries: the activations kept for backward are there).  A step
    needs both at once, so the peak is their sum; either alone would
    call a cell that fills the chip small."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def reduce_trace(trace_dir: str, slice_facts: dict) -> dict:
    from benchmarks import span_readers as SR
    from benchmarks import trace_reduce as TR

    events = TR.load(TR.find_xplane(trace_dir))
    out = dict(slice_facts)
    out["events"] = events
    out["busy_s"] = TR.busy_seconds(events)
    out["top_ops"] = TR.top_ops(events)
    out["idle_gaps"] = SR.idle_gaps(events)
    out["traced_calls_share"] = TR.traced_calls_share(
        events, slice_facts.get("programs", "^$"), slice_facts.get("program_calls", 0)
    )
    return out


def prepare(workload: str):
    """What a run and ``prove.py`` share: the checkout on the path, the
    one fixed compile cache inside it (for the program, which takes the
    variable when it is set, and the reference alike), the cell's files,
    the look for the chip."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell, config, metric_specs = load_cell(workload)
    device, peaks = device_or_exit(int(cell["chips"]))
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cell, config, metric_specs, device, peaks


def make_ctx(cell, config, peaks, *, seed, seconds, trace=False, trace_dir=None):
    from benchmarks import work

    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds, trace=trace,
        trace_dir=trace_dir, peaks=peaks, chips=int(cell["chips"]), work=work,
        memory_peak_bytes=memory_peak_bytes, memory_stats=memory_stats,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .bench_trace/ for a look by hand")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "flexflow_tpu")):
        print("benchmark: the flexflow_tpu package is not beside benchmarks/; "
              "run from the root of a checkout", file=sys.stderr)
        return 3
    cell, config, metric_specs, device, peaks = prepare(args.workload)
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = make_ctx(cell, config, peaks, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), trace_dir=trace_dir)
    job = importlib.import_module(f"benchmarks.jobs.{cell['job']}")
    res = job.run(ctx)

    setup_s = res["t_window_start"] - _T_PROCESS
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    checks = res["checks"]
    correct = bool(checks) and all(
        v == v and v <= limit for _, v, limit in checks
    ) and res["failed"] == 0
    out = {
        "correct": correct,
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
    }
    facts = dict(res["facts"], setup_s=setup_s)
    if args.trace:
        if not res.get("trace"):
            print("benchmark: the job traced nothing", file=sys.stderr)
            return 4
        trace = reduce_trace(trace_dir, res["trace"])
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run = types.SimpleNamespace(
            facts=facts, trace=trace, peaks=peaks, chips=ctx.chips,
        )
        metrics = {}
        for name, spec in metric_specs.items():
            v = resolve_reader(spec["reader"])(run, **spec.get("args", {}))
            if v is not None:
                metrics[name] = {"value": v, "unit": spec["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        if trace["traced_calls_share"] is not None:
            device["traced_calls_share"] = trace["traced_calls_share"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {
            "device_ops": trace["top_ops"], "idle_gaps": trace["idle_gaps"],
        }
    else:
        e2e = dict(res["metrics"], setup_s=setup_s)
        units = dict(cell["end_to_end"], setup_s="s")
        out["metrics"] = {k: {"value": e2e[k], "unit": u} for k, u in units.items()}
        out["device"] = device
    out["facts"] = {
        k: v for k, v in facts.items()
        if isinstance(v, (int, float, str, dict, list)) and k not in ("samples",)
    }
    out["checks"] = {name: {"value": v, "limit": limit} for name, v, limit in checks}
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    for name, v, limit in checks:
        print(f"check {name}: {v!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
