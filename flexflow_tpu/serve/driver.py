"""``python -m flexflow_tpu --serve`` — the serving demo driver.

Builds a :func:`~flexflow_tpu.models.transformer.gpt_decoder`, compiles
it (Unity-searched when ``--search-budget`` is set — with
``--objective serve`` the search prices the ServeObjective), stands up
the continuous-batching :class:`~flexflow_tpu.serve.engine.ServeEngine`,
replays a seeded synthetic open-loop workload against it, and prints
ONE JSON summary line (plus the ``--metrics-out`` ffmetrics/1 stream
that ``tools/serve_report.py`` renders).  ``--serve-spans-out F`` adds
the per-request ffspan/1 timeline stream (``tools/serve_report.py
--timeline F`` decomposes TTFT from it); ``--metrics-max-mb M`` rotates
both JSONL streams at M megabytes (docs/OBSERVABILITY.md).

Defaults are CPU-smoke sized; pass model flags for anything real.

    python -m flexflow_tpu --serve --requests 32 --rate 50 \\
        --serve-slots 4 --serve-sync-every 4 --metrics-out serve.jsonl

Multi-tenant shapes: ``--tenants N --shared-prefix P
--interactive-frac F`` generate per-tenant system prompts (prefix
sharing traffic) and SLO tiers; ``--serve-prefix-sharing off``,
``--serve-spec-k K`` and ``--serve-spec-draft-layers D`` control the
allocator and speculative decoding.  The JSON summary then carries
``prefix_hit_rate``, ``preemptions``, per-tier TTFT percentiles, and
the speculative accept rate.

Disaggregated serving (docs/SERVING.md "Disaggregated prefill/decode"):
``--disagg`` serves through a
:class:`~flexflow_tpu.serve.disagg.DisaggregatedCluster` — a
prefill-only pool (``--serve-slots`` wide) feeding a decode-only pool
(``--disagg-decode-slots``, default the same width) over the priced
ffkv/1 handoff; ``--machine-model-file`` prices the DCN hop, and
``--burst-factor F`` makes the synthetic arrivals bursty (the traffic
shape the split-pool topology exists for).  The summary line then
carries the migration/handoff facts (``migrated``, ``handoff_p99_ms``,
``split``).

Fleet tier (docs/SERVING.md "Fleet tier"): ``--serve-replicas N`` (N>1)
serves through a :class:`~flexflow_tpu.serve.fleet.FleetRouter` over N
replica engines — ``--serve-routing prefix|round_robin|least_loaded``
picks the placement policy, ``--session-turns K`` makes the synthetic
traffic multi-turn (session affinity + live KV migration traffic),
``--fleet-out F`` records every routing/migration/scaling decision as
an ``fffleet/1`` JSONL stream (``tools/serve_report.py --fleet F``),
and ``--fleet-autoscale`` closes the loop: the router tails its own
fleet metrics rollup and adds/drains replicas per the SLO policy.

Resilience (docs/RESILIENCE.md): ``--deadline-ms D`` stamps every
synthetic request with a queue deadline (expired requests are rejected
truthfully and counted); ``--serve-drain-file F`` + SIGTERM drains
in-flight work to an ffdrain/1 payload, and ``--resume-drain F``
re-queues it on the next run; ``--serve-watchdog-s`` /
``--serve-shed-windows`` arm the window watchdog and batch-tier
shedding.
"""

from __future__ import annotations

import json
import sys
from typing import List, Optional

__all__ = ["main"]


def _int_pair(s: str) -> tuple:
    lo, _, hi = s.partition(":")
    return (int(lo), int(hi or lo))


def main(argv: Optional[List[str]] = None) -> int:
    from flexflow_tpu.config import FFConfig

    cfg = FFConfig()
    rest = cfg.parse_args(list(argv if argv is not None else sys.argv[1:]))

    # driver-local flags
    opts = dict(
        requests=16, rate=0.0, prompt_len=(4, 12), gen_len=(4, 24),
        hidden=64, heads=4, ff_dim=128, num_layers=2, vocab=256, seq=64,
        traffic_seed=0, tenants=1, shared_prefix=0, interactive_frac=0.0,
        deadline_ms=0.0, resume_drain=None,
        disagg=False, disagg_decode_slots=0, burst_factor=1.0,
        session_turns=1, fleet_out=None, fleet_autoscale=False,
    )
    i = 0
    while i < len(rest):
        a = rest[i]

        def take():
            nonlocal i
            i += 1
            return rest[i]

        if a == "--requests":
            opts["requests"] = int(take())
        elif a == "--rate":
            opts["rate"] = float(take())
        elif a == "--prompt-len":
            opts["prompt_len"] = _int_pair(take())
        elif a == "--gen-len":
            opts["gen_len"] = _int_pair(take())
        elif a == "--hidden":
            opts["hidden"] = int(take())
        elif a == "--heads":
            opts["heads"] = int(take())
        elif a == "--ff-dim":
            opts["ff_dim"] = int(take())
        elif a == "--num-layers":
            opts["num_layers"] = int(take())
        elif a == "--vocab":
            opts["vocab"] = int(take())
        elif a == "--seq":
            opts["seq"] = int(take())
        elif a == "--traffic-seed":
            opts["traffic_seed"] = int(take())
        elif a == "--tenants":
            opts["tenants"] = int(take())
        elif a == "--shared-prefix":
            opts["shared_prefix"] = int(take())
        elif a == "--interactive-frac":
            opts["interactive_frac"] = float(take())
        elif a == "--deadline-ms":
            opts["deadline_ms"] = float(take())
        elif a == "--resume-drain":
            opts["resume_drain"] = take()
        elif a == "--disagg":
            opts["disagg"] = True
        elif a == "--disagg-decode-slots":
            opts["disagg_decode_slots"] = int(take())
        elif a == "--burst-factor":
            opts["burst_factor"] = float(take())
        elif a == "--session-turns":
            opts["session_turns"] = int(take())
        elif a == "--fleet-out":
            opts["fleet_out"] = take()
        elif a == "--fleet-autoscale":
            opts["fleet_autoscale"] = True
        elif a in ("-h", "--help"):
            print(__doc__, file=sys.stderr)
            return 0
        else:
            print(f"--serve: unknown flag {a!r}", file=sys.stderr)
            return 2
        i += 1

    if opts["disagg"] and opts["resume_drain"]:
        print("--serve: --resume-drain is a colocated-engine flag "
              "(incompatible with --disagg)", file=sys.stderr)
        return 2
    fleet = cfg.serve_replicas > 1
    if fleet and opts["disagg"]:
        print("--serve: --serve-replicas > 1 replicates whole engines "
              "(incompatible with --disagg; each replica is colocated)",
              file=sys.stderr)
        return 2
    if fleet and opts["resume_drain"]:
        print("--serve: --resume-drain is a single-engine flag "
              "(incompatible with --serve-replicas > 1)", file=sys.stderr)
        return 2

    # --- SLO ops plane (docs/OBSERVABILITY.md "SLOs, alerts, and live
    # introspection") — set up BEFORE the model build so a bad policy
    # file or an already-bound status port fails fast and truthfully
    # (no compile, no silent fallback port)
    slo = None
    if (cfg.serve_slo_policy or cfg.serve_alerts_out
            or cfg.serve_status_port):
        from flexflow_tpu.obs.slo import SLOEngine, SLOPolicy

        try:
            policy = (
                SLOPolicy.from_file(cfg.serve_slo_policy)
                if cfg.serve_slo_policy else SLOPolicy()
            )
        except (OSError, ValueError) as e:
            print(
                f"--serve: cannot load SLO policy "
                f"{cfg.serve_slo_policy!r}: {e}",
                file=sys.stderr,
            )
            return 1
        slo = SLOEngine(
            policy, alerts_out=cfg.serve_alerts_out,
            max_mb=cfg.metrics_max_mb,
        )
    status = None
    if cfg.serve_status_port:
        from flexflow_tpu.serve.introspect import StatusServer

        try:
            status = StatusServer(cfg.serve_status_port)
        except OSError as e:
            print(
                f"--serve: cannot bind status port "
                f"{cfg.serve_status_port}: {e} — the port is in use; "
                f"pick another with --serve-status-port",
                file=sys.stderr,
            )
            return 1

    from flexflow_tpu import FFModel
    from flexflow_tpu.models.transformer import gpt_decoder
    from flexflow_tpu.serve import ServeEngine, TrafficSpec, synthetic_requests

    slots = cfg.serve_slots or 4
    cfg.batch_size = slots
    model = FFModel(cfg)
    gpt_decoder(
        model, slots, opts["seq"], hidden=opts["hidden"],
        heads=opts["heads"], ff_dim=opts["ff_dim"],
        num_layers=opts["num_layers"], vocab=opts["vocab"],
        use_flash=False,
    )
    # one engine runs on one device.  Unless a mesh or a search was asked
    # for, compile on the one-device mesh it actually uses: the default
    # all-devices mesh would replicate the weights and the KV pool over
    # every chip of the host and do the same work on each
    mesh = None
    if cfg.mesh_shape is None and cfg.search_budget <= 0:
        from flexflow_tpu import MachineMesh

        mesh = MachineMesh((1, 1), ("data", "model"))
    model.compile(seed=cfg.rng_seed, mesh=mesh)

    if fleet:
        from flexflow_tpu.serve import FleetRouter

        machine = None
        if cfg.machine_model_file:
            from flexflow_tpu.parallel.network import load_machine_model

            machine = load_machine_model(cfg.machine_model_file)
        engine = FleetRouter(
            model,
            replicas=cfg.serve_replicas,
            routing=cfg.serve_routing,
            slots=slots,
            block_size=cfg.serve_block_size,
            num_blocks=cfg.serve_num_blocks or None,
            prefill_chunk=cfg.serve_prefill_chunk,
            sync_every=cfg.serve_sync_every,
            metrics_out=cfg.metrics_out,
            fleet_out=opts["fleet_out"],
            prefix_sharing=cfg.serve_prefix_sharing,
            slo_ms=cfg.serve_slo_ms,
            attn=cfg.serve_attn,
            kv_dtype=cfg.serve_kv_dtype,
            weight_dtype=cfg.serve_weight_dtype,
            machine=machine,
            metrics_max_mb=cfg.metrics_max_mb,
            slo=slo,
            autoscale=opts["fleet_autoscale"],
        )
    elif opts["disagg"]:
        from flexflow_tpu.serve import DisaggregatedCluster

        machine = None
        if cfg.machine_model_file:
            from flexflow_tpu.parallel.network import load_machine_model

            machine = load_machine_model(cfg.machine_model_file)
        engine = DisaggregatedCluster(
            model,
            prefill_slots=slots,
            decode_slots=opts["disagg_decode_slots"] or slots,
            prefill_block_size=cfg.serve_block_size,
            decode_block_size=cfg.serve_block_size,
            prefill_num_blocks=cfg.serve_num_blocks or None,
            decode_num_blocks=cfg.serve_num_blocks or None,
            prefill_chunk=cfg.serve_prefill_chunk,
            sync_every=cfg.serve_sync_every,
            metrics_out=cfg.metrics_out,
            prefix_sharing=cfg.serve_prefix_sharing,
            slo_ms=cfg.serve_slo_ms,
            attn=cfg.serve_attn,
            kv_dtype=cfg.serve_kv_dtype,
            weight_dtype=cfg.serve_weight_dtype,
            machine=machine,
            spans_out=cfg.serve_spans_out,
            metrics_max_mb=cfg.metrics_max_mb,
            slo=slo,
        )
    else:
        engine = ServeEngine(
            model,
            slots=slots,
            block_size=cfg.serve_block_size,
            num_blocks=cfg.serve_num_blocks or None,
            prefill_chunk=cfg.serve_prefill_chunk,
            sync_every=cfg.serve_sync_every,
            metrics_out=cfg.metrics_out,
            prefix_sharing=cfg.serve_prefix_sharing,
            attn=cfg.serve_attn,
            kv_dtype=cfg.serve_kv_dtype,
            weight_dtype=cfg.serve_weight_dtype,
            spec_k=cfg.serve_spec_k,
            spec_draft_layers=cfg.serve_spec_draft_layers,
            watchdog_s=cfg.serve_watchdog_s,
            shed_after_windows=cfg.serve_shed_windows,
            slo_ms=cfg.serve_slo_ms,
            drain_path=cfg.serve_drain_file,
            spans_out=cfg.serve_spans_out,
            metrics_max_mb=cfg.metrics_max_mb,
            slo=slo,
        )
        if opts["resume_drain"]:
            from flexflow_tpu.serve.engine import load_drain

            engine.resume_from_drain(load_drain(opts["resume_drain"]))
    spec = TrafficSpec(
        n_requests=opts["requests"], seed=opts["traffic_seed"],
        rate_rps=opts["rate"], prompt_len=opts["prompt_len"],
        max_new=opts["gen_len"], vocab=opts["vocab"],
        tenants=opts["tenants"], shared_prefix=opts["shared_prefix"],
        interactive_frac=opts["interactive_frac"],
        burst_factor=opts["burst_factor"],
        session_turns=opts["session_turns"],
    )
    # clamp generated budgets to the compiled position range
    reqs = synthetic_requests(spec)
    for r in reqs:
        # a budget past the compiled range would be (gracefully)
        # rejected; the demo clamps instead so every request serves
        r.max_new_tokens = max(
            1, min(r.max_new_tokens, opts["seq"] - r.prompt_len)
        )
        if opts["deadline_ms"] > 0:
            r.deadline_ms = opts["deadline_ms"]
    model_desc = (
        f"gpt L{opts['num_layers']} h{opts['hidden']} "
        f"v{opts['vocab']} s{opts['seq']}"
    )
    if status is not None:
        status.attach(
            # the fleet's first replica stands in for /statusz — the
            # status server introspects one engine's scheduler
            (next(iter(engine.replicas.values())).engine
             if fleet else engine),
            slo=slo,
            metrics_path=cfg.metrics_out,
            spans_path=cfg.serve_spans_out,
            meta={
                "traffic": spec.identity,
                "model": model_desc,
                "disagg": opts["disagg"],
                "fleet": (
                    {"replicas": cfg.serve_replicas,
                     "routing": cfg.serve_routing}
                    if fleet else None
                ),
                "strategy": {
                    "grad_overlap": model.strategy.grad_overlap,
                    "pipeline": model.strategy.pipeline is not None,
                    "serve_price": getattr(
                        model.strategy, "serve_price", None,
                    ),
                },
            },
        )
        status.start()
    try:
        report = engine.run(reqs)
    finally:
        if status is not None:
            status.close()
        if slo is not None:
            slo.close()

    if fleet:
        # the summary's geometry fields come from any replica (they are
        # identical by construction — one KV geometry fleet-wide)
        geo = next(iter(engine.replicas.values())).engine
    elif opts["disagg"]:
        geo = engine.decode
    else:
        geo = engine
    out = {
        "metric": "serve_demo",
        "serve_traffic": spec.identity,
        "model": model_desc,
        "slots": slots,
        "block_size": geo.kv.block_size,
        "num_blocks": geo.kv.num_blocks,
        "sync_every": geo.sync_every,
        "attn_kernel": geo.attn_kernel,
        "attn_interpret": geo.attn_interpret,
        "device": geo.device_info(),
        "kv_dtype": geo.kv.kv_dtype,
        "weight_dtype": geo.weight_dtype,
        "kv_bytes_per_token": geo.kv.bytes_per_token,
        **report.to_dict(),
    }
    sp = getattr(model.strategy, "serve_price", None)
    if sp is not None:
        out["serve_price"] = {
            k: sp[k] for k in ("tok_s", "p99_ms", "feasible")
        }
    if slo is not None:
        from flexflow_tpu.obs.aggregate import MetricsAggregator
        from flexflow_tpu.obs.slo import (
            fleet_from_serve_report,
            scaling_recommendation,
        )

        # the autoscaler signal (ROADMAP #2), from the recorded stream
        # when there is one (per-window fleet view) else from the run
        # report (end-of-run view — queue drained by definition)
        if fleet:
            # the router already aggregated every replica's windows
            fleet_report = engine.agg.aggregate_report()
        elif cfg.metrics_out:
            from flexflow_tpu.obs.metrics import read_metrics

            agg = MetricsAggregator()
            for rec in read_metrics(cfg.metrics_out):
                src = (
                    ((rec.get("metrics") or {}).get("serve") or {})
                    .get("phase") or "serve"
                )
                agg.ingest(src, rec)
            fleet_report = agg.aggregate_report()
        else:
            fleet_report = fleet_from_serve_report(out)
        out["slo"] = slo.summary()
        out["scaling"] = scaling_recommendation(fleet_report, slo.policy)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
