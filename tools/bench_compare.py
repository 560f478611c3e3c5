#!/usr/bin/env python
"""Bench regression gate: diff a bench/metrics JSON against the
``BENCH_r0*.json`` trajectory and exit non-zero past a threshold.

The round artifacts record the throughput of record per round; this tool
makes "is this build getting slower" a CI-checkable question instead of
a judge's eyeball pass.  It understands three input shapes:

  * a raw ``bench.py`` output record (``{"metric": ..., "value": ...}``)
  * a round artifact wrapper (``{"n": 5, "parsed": {...}}``)
  * a ``--metrics-out`` JSONL stream (``ffmetrics/1`` records; the last
    record with a ``samples_per_s`` becomes the headline)

Comparisons are backend-matched ONLY: a CPU-fallback run is never gated
against a TPU baseline (different hardware, not a regression).  They are
also machine-model-matched when both records carry a ``machine_model``
identity (``preset:<chip>`` / ``file:<sha256/12>`` from the priced
``--machine-model-file``): a run priced against a different topology is
a different experiment, not a regression — the gate refuses to compare.
Records predating the identity field (no ``machine_model`` key) compare
as before.

``metrics_sync_every`` (the async-fit flush cadence, new in r06 records)
is COMPARABLE metadata, not an identity: a sync-mode and an async-mode
run measure the same hardware doing the same math, so they still gate
against each other — a differing value is printed as a note, never a
refusal, and legacy records without the field gate unchanged.

The measured metrics on both sides:

  * headline ``value`` (samples/s, higher is better)
  * ``secondary.dlrm.samples_per_sec``, ``secondary.bert_large.samples_per_sec``
  * ``secondary.gpt_decode.cached_tok_per_s``

Usage:
  python tools/bench_compare.py CURRENT.json                 # vs newest same-backend BENCH_r0*.json
  python tools/bench_compare.py CURRENT.json --baseline BENCH_r05.json
  python tools/bench_compare.py CURRENT.json --threshold 0.2
  python tools/bench_compare.py CURRENT.json --strict        # missing baseline is a failure

Exit codes: 0 = within threshold (or no comparable baseline, unless
--strict), 1 = regression past threshold, 2 = input error.

The default threshold (15%) sits above the window-to-window spread the
BENCH artifacts show (±10%) — tighten with --threshold once a
benchmark PR has measured the spread on the chip.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_THRESHOLD = 0.15

# record keys that may legitimately differ between comparable runs —
# noted in the output, but never a reason to refuse the comparison
# (contrast: a machine_model mismatch is a different experiment).
# serve_traffic (the traffic generator's seed/shape identity, new in
# r08) rides the same rule: a different synthetic workload shifts the
# serving numbers for benign reasons, so the gate prints the change
# and still compares.
# cost_model_tier (which cost-model tier produced the record's
# prediction — analytic/measured/calibrated, new in r09) also rides this
# rule: the tier changes prediction accuracy for benign reasons, so the
# gate prints the change and still compares.
# pipeline (the headline run's --pipeline config, new in r09) rides
# the same rule: a pipelined and a non-pipelined run of the same model
# are still the same experiment — the schedule shifts step time for
# architectural reasons the gate should surface, not refuse over.
COMPARABLE_METADATA = (
    "metrics_sync_every", "stack_blocks", "serve_traffic", "cost_model_tier",
    "pipeline",
    # serve_spec_k (r11, docs/SERVING.md): the speculative draft depth of
    # the serve A/B — runs at different k are still the same experiment,
    # but the gate surfaces the change because k shifts decode tokens/s
    # for configuration (not regression) reasons
    "serve_spec_k",
    # fault_plan (r12, docs/RESILIENCE.md): the recovery A/B's injected
    # fault spec — a different plan kills the run at a different step,
    # shifting recovery_s for configuration (not regression) reasons
    "fault_plan",
    # serve_handoff_ms / serve_disagg_split (r13, docs/SERVING.md
    # "Disaggregated prefill/decode"): the disagg A/B's priced KV
    # handoff p99 and its pool split — a different split or a
    # re-priced DCN shifts the handoff for topology (not regression)
    # reasons, so the gate surfaces the change and still compares
    "serve_handoff_ms",
    "serve_disagg_split",
    # serve_attn (r14, docs/PERF.md "Paged decode attention"): which
    # decode-attention kernel the paged A/B's paged arm resolved to —
    # runs measured under different kernels are still the same
    # experiment (the bit-identity fact rides the A/B itself), but the
    # gate surfaces the change because the kernel shifts peak bytes
    # and tok/s for configuration (not regression) reasons
    "serve_attn",
    # grad_overlap (r15, docs/PERF.md "Overlapped gradient sync"):
    # whether the overlap A/B's ring arm actually engaged (a 1-device
    # host declines at data extent 1) — runs with and without the ring
    # are the same experiment, but the gate surfaces the change because
    # exposed_comm_frac only moves when the ring engages
    "grad_overlap",
    # serve_ttft_queue_ms_p99 / serve_handoff_observed_ms (r16,
    # docs/OBSERVABILITY.md): wall-clock waits read off the traced
    # disagg arm's ffspan/1 stream — the queue leg is load-shaped and
    # the measured transit is host-scheduling-shaped, so both are
    # surfaced for drift visibility, never gated
    "serve_ttft_queue_ms_p99",
    "serve_handoff_observed_ms",
    # serve_slo_availability / serve_alerts_fired (r17,
    # docs/OBSERVABILITY.md "SLOs, alerts, and live introspection"):
    # the headline serve run evaluated under the default SLOPolicy —
    # availability and burn alerts are load/host-speed shaped on a
    # smoke box, so both surface for drift visibility, never gated
    "serve_slo_availability",
    "serve_alerts_fired",
    # fleet_replicas / fleet_routing (r18, docs/SERVING.md "Fleet
    # tier"): the fleet A/B's replica count and winning routing policy
    # — runs at different fleet shapes are the same experiment, but the
    # gate surfaces the change because both shift pooled hit rate and
    # p99 for configuration (not regression) reasons
    "fleet_replicas",
    "fleet_routing",
    # kv_dtype / weight_dtype (r19, docs/SERVING.md "Quantized KV cache
    # and weight-only decode"): the quantized A/B arm's storage formats
    # — runs at different quantization arms are the same experiment,
    # but the gate surfaces the change because serve_kv_bytes_per_tok
    # moves with the format, not with code quality
    "kv_dtype",
    "weight_dtype",
)

# (label, path into the record, higher_is_better) — the gated metrics.
# jit_compile_s gates LOWER-is-better: a compile-time regression fails
# like a throughput regression (the scan-stacked block work of r07 made
# compile a first-class budget — see docs/PERF.md).  The serving pair
# (r08, docs/SERVING.md): serve_tok_s higher-is-better, serve_p99_ms
# LOWER-is-better — a latency regression fails even when aggregate
# throughput held.
# cost_model_mape (r09, docs/OBSERVABILITY.md "Calibration loop") gates
# LOWER-is-better: predicted-vs-measured step-time error growing past
# threshold means the cost model drifted from the hardware — the search
# quality regression the calibration loop exists to prevent.
GATED = (
    ("throughput", ("value",), True),
    ("compile", ("jit_compile_s",), False),
    ("cost_model_mape", ("cost_model_mape",), False),
    # pipeline_bubble_frac (r09, docs/PIPELINE.md) gates LOWER-is-better:
    # the 1F1B A/B's measured warmup/drain bubble growing means the
    # schedule degraded (fewer microbatches fitting, a stage imbalance)
    ("pipeline_bubble_frac", ("pipeline_bubble_frac",), False),
    ("serve_tok_s", ("serve_tok_s",), True),
    ("serve_p99_ms", ("serve_p99_ms",), False),
    # serve_prefix_hit_rate (r11, docs/SERVING.md "Prefix sharing"):
    # the shared-prefix A/B's prefix-cache hit rate gates
    # higher-is-better — a drop means requests stopped re-attaching
    # registered blocks (hash keying or CoW regression), which silently
    # halves admissible concurrency long before throughput notices
    ("serve_prefix_hit_rate", ("serve_prefix_hit_rate",), True),
    # serve_disagg_p99_tpot_ms (r13, docs/SERVING.md "Disaggregated
    # prefill/decode") gates LOWER-is-better: the decode pool's p99
    # per-token window latency under bursty traffic — the number the
    # split-pool topology exists to protect; it growing means prefill
    # work leaked back into decode windows or the handoff got slower
    ("serve_disagg_p99_tpot_ms", ("serve_disagg_p99_tpot_ms",), False),
    # serve_paged_attn_peak_mb (r14, docs/PERF.md "Paged decode
    # attention") gates LOWER-is-better: the paged decode program's
    # peak live temp bytes from XLA's memory_analysis() — the number
    # the block-table-native kernel exists to shrink; it growing means
    # a pool-sized gather/materialization crept back into the decode
    # step (the ffcheck ``paged_attn`` audit is the structural twin of
    # this measured gate)
    ("serve_paged_attn_peak_mb", ("serve_paged_attn_peak_mb",), False),
    # serve_prefill_peak_mb (r20, docs/SERVING.md "Chunked prefill on
    # the paged pool") gates LOWER-is-better: the fp32 paged PREFILL
    # program's peak live temp bytes on the long-prompt undersized-pool
    # A/B — the number chunked paged prefill exists to shrink; it
    # growing means the full-virtual-length K/V gather crept back into
    # the prefill phase (the O(S^2) long-context TTFT tax), which the
    # decode-side gate above cannot see
    ("serve_prefill_peak_mb", ("serve_prefill_peak_mb",), False),
    # exposed_comm_frac (r15, docs/PERF.md "Overlapped gradient sync")
    # gates LOWER-is-better: the share of the fused grad sync the ring
    # decomposition could NOT hide under backward compute on the priced
    # BERT-Large dp=8 placement — it growing means the overlap model
    # lost hiding capacity (a link-class regression or an overlap-
    # fraction drift), the search-quality regression the ring axis
    # exists to prevent
    ("exposed_comm_frac", ("exposed_comm_frac",), False),
    # serve_fleet_prefix_hit_rate (r18, docs/SERVING.md "Fleet tier")
    # gates higher-is-better: the prefix-routed fleet's POOLED hit rate
    # (sum hits / sum lookups across replicas) — a drop means the
    # router stopped placing repeats on the replica holding their
    # blocks (digest export or scoring regression), which forfeits the
    # fleet's cross-request KV reuse long before throughput notices
    ("serve_fleet_prefix_hit_rate", ("serve_fleet_prefix_hit_rate",),
     True),
    # serve_fleet_p99_tpot_ms gates LOWER-is-better: the prefix-routed
    # fleet's p99 per-token latency under the bursty multi-tenant
    # shape — routing quality must not buy hit rate with tail latency
    ("serve_fleet_p99_tpot_ms", ("serve_fleet_p99_tpot_ms",), False),
    # serve_kv_bytes_per_tok (r19, docs/SERVING.md "Quantized KV cache
    # and weight-only decode") gates LOWER-is-better: the int8 arm's
    # per-token pool bytes (element pools + per-position scale stream,
    # PagedKVCache.bytes_per_token) — it growing means the quantized
    # pool silently fattened (a full-precision pool or a scale-layout
    # regression sneaking back), which halves admissible concurrency
    # before any throughput gate notices
    ("serve_kv_bytes_per_tok", ("serve_kv_bytes_per_tok",), False),
    ("dlrm", ("secondary", "dlrm", "samples_per_sec"), True),
    ("bert_large", ("secondary", "bert_large", "samples_per_sec"), True),
    ("gpt_decode_cached", ("secondary", "gpt_decode", "cached_tok_per_s"), True),
)

# (label, path) — metrics gated AT ZERO: any non-zero current value is a
# failure, regardless of the baseline (the ratio machinery in GATED
# would skip a 0-or-missing baseline, silently passing a 0 -> N
# regression).  analysis_violations (r10, docs/ANALYSIS.md) is the
# --verify-compiled ffcheck violation count for the headline step: the
# compiled program drifting from its priced strategy is a correctness
# regression at ANY threshold.  A null/missing current value (record
# predates the field, or verify_compiled=off) is not gated.
ZERO_GATED = (
    ("analysis_violations", ("analysis_violations",)),
)

# (label, path) — metrics gated AT TRUE: the current value must be
# exactly 1.0 (True) whenever present, regardless of the baseline.
# resume_replay_exact (r12, docs/RESILIENCE.md) is the kill-and-resume
# bit-identity bit from bench.py's recovery A/B: a resumed run drifting
# from the uninterrupted run by even one bit is a determinism
# regression at ANY threshold.  A null/missing current value (record
# predates the field, or the A/B errored) is not gated.
TRUE_GATED = (
    ("resume_replay_exact", ("resume_replay_exact",)),
)


def _dig(d: Any, path: Tuple[str, ...]) -> Optional[float]:
    for k in path:
        if not isinstance(d, dict) or d.get(k) is None:
            return None
        d = d[k]
    return float(d) if isinstance(d, (int, float)) else None


def load_record(path: str) -> Optional[Dict[str, Any]]:
    """Normalize any of the three input shapes into a bench record."""
    text = open(path).read().strip()
    # JSONL metrics stream: last record carrying a throughput
    if "\n" in text or text.startswith('{"schema"'):
        best = None
        for line in text.splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and rec.get("schema", "").startswith("ffmetrics/"):
                if rec.get("samples_per_s") is not None:
                    best = rec
        if best is not None:
            return {
                "metric": "metrics_stream",
                "value": best["samples_per_s"],
                "backend": best.get("metrics", {}).get("backend", "unknown"),
            }
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        return None
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc:  # round artifact wrapper
        doc = doc["parsed"]
    if isinstance(doc, dict) and "value" in doc:
        return doc
    return None


def find_baselines(root: str) -> List[Tuple[str, Dict[str, Any]]]:
    """Every parseable BENCH_r0*.json, oldest→newest."""
    out = []
    for p in sorted(glob.glob(os.path.join(root, "BENCH_r[0-9]*.json"))):
        rec = load_record(p)
        if rec is not None and rec.get("value"):
            out.append((p, rec))
    return out


def compare(
    current: Dict[str, Any],
    baseline: Dict[str, Any],
    threshold: float,
) -> List[Dict[str, Any]]:
    """Per-metric comparison rows; a row regresses when the current
    value falls more than ``threshold`` below the baseline."""
    rows = []
    for label, path, higher in GATED:
        base = _dig(baseline, path)
        cur = _dig(current, path)
        if base is None or cur is None or base <= 0:
            continue
        ratio = cur / base
        rows.append({
            "metric": label,
            "baseline": base,
            "current": cur,
            "ratio": ratio,
            # higher-is-better regresses by dropping below 1-threshold;
            # lower-is-better (compile time) by rising above 1+threshold
            "regressed": (
                ratio < (1.0 - threshold)
                if higher
                else ratio > (1.0 + threshold)
            ),
        })
    for label, path in ZERO_GATED:
        cur = _dig(current, path)
        if cur is None:
            continue
        base = _dig(baseline, path) or 0.0
        rows.append({
            "metric": label,
            "baseline": base,
            "current": cur,
            "ratio": (
                cur / base if base > 0
                else (1.0 if cur == 0 else float("inf"))
            ),
            # zero-gate: threshold-free — any non-zero count fails even
            # when the baseline predates the field (base treated as 0)
            "regressed": cur > 0,
        })
    for label, path in TRUE_GATED:
        cur = _dig(current, path)
        if cur is None:
            continue
        base = _dig(baseline, path)
        rows.append({
            "metric": label,
            "baseline": base if base is not None else 1.0,
            "current": cur,
            "ratio": cur,
            # true-gate: threshold-free — the bit must hold at 1.0 even
            # when the baseline predates the field
            "regressed": cur != 1.0,
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("current", help="bench record / round artifact / metrics JSONL")
    ap.add_argument("--baseline", action="append", default=None,
                    help="baseline file(s); default: BENCH_r0*.json in --repo-root")
    ap.add_argument("--repo-root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help=f"max tolerated fractional drop (default {DEFAULT_THRESHOLD})")
    ap.add_argument("--strict", action="store_true",
                    help="fail when no comparable (same-backend) baseline exists")
    args = ap.parse_args(argv)

    current = load_record(args.current)
    if current is None:
        print(f"bench_compare: cannot parse {args.current}", file=sys.stderr)
        return 2
    backend = current.get("backend", "unknown")

    if args.baseline:
        baselines = []
        for p in args.baseline:
            rec = load_record(p)
            if rec is None:
                print(f"bench_compare: cannot parse baseline {p}", file=sys.stderr)
                return 2
            baselines.append((p, rec))
    else:
        baselines = find_baselines(args.repo_root)

    # backend-matched only — newest matching artifact is the gate
    matched = [(p, r) for p, r in baselines if r.get("backend") == backend]
    if not matched:
        msg = (f"bench_compare: no {backend!r}-backend baseline among "
               f"{len(baselines)} candidate(s); nothing to gate against")
        print(msg)
        return 1 if args.strict else 0
    # machine-model-matched when BOTH sides carry the identity: a run
    # priced against a different topology (other machine-model file /
    # chip preset) is a different experiment, never a regression
    mm = current.get("machine_model")
    if mm is not None:
        dropped = [
            (p, r) for p, r in matched
            if r.get("machine_model") not in (None, mm)
        ]
        matched = [
            (p, r) for p, r in matched
            if r.get("machine_model") in (None, mm)
        ]
        if dropped and not matched:
            print(f"bench_compare: refusing to compare — every "
                  f"{backend!r}-backend baseline was priced against a "
                  f"different machine model "
                  f"({dropped[-1][1].get('machine_model')!r} vs {mm!r})")
            return 1 if args.strict else 0
        for p, _r in dropped:
            print(f"bench_compare: skipping {p} (different machine model)")
    base_path, base = matched[-1]
    for key in COMPARABLE_METADATA:
        if key in (current.keys() | base.keys()) and (
            current.get(key) != base.get(key)
        ):
            print(f"bench_compare: note — {key} differs "
                  f"({base.get(key)!r} -> {current.get(key)!r}); comparable "
                  f"metadata, still gating")

    rows = compare(current, base, args.threshold)
    if not rows:
        print(f"bench_compare: no shared metrics between {args.current} "
              f"and {base_path}")
        return 1 if args.strict else 0

    print(f"bench_compare: current={args.current} baseline={base_path} "
          f"backend={backend} threshold={args.threshold:.0%}")
    bad = 0
    for r in rows:
        verdict = "REGRESSED" if r["regressed"] else "ok"
        bad += r["regressed"]
        print(f"  {r['metric']:<20} {r['baseline']:>12.2f} -> "
              f"{r['current']:>12.2f}  ({r['ratio']:.2%} of baseline)  {verdict}")
    if bad:
        print(f"bench_compare: {bad} metric(s) regressed more than "
              f"{args.threshold:.0%} — FAIL")
        return 1
    print("bench_compare: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
