"""Runtime configuration and CLI flag parsing.

TPU-native analog of the reference's ``FFConfig``
(``include/flexflow/config.h:92-160``) and ``FFModel::parse_args``
(``src/runtime/model.cc:3566-3730``).  Flag spellings are kept compatible
where they still make sense on TPU; Legion ``-ll:*`` flags are replaced by
mesh-shape flags.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import List, Optional, Sequence, Tuple

import jax


@dataclasses.dataclass
class FFConfig:
    """Global runtime config.

    Reference field map (``include/flexflow/config.h:92-160``):
      * ``batchSize``       -> :attr:`batch_size`
      * ``workersPerNode``  -> derived from the mesh (devices per host)
      * ``numNodes``        -> ``jax.process_count()``
      * ``epochs``          -> :attr:`epochs`
      * ``learningRate / weightDecay`` -> :attr:`learning_rate` / :attr:`weight_decay`
      * search flags (``search_budget``, ``search_alpha``, ``only_data_parallel``,
        ``enable_parameter_parallel`` ...) -> same names, ``model.cc:3566-3730``.
    """

    batch_size: int = 64
    epochs: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # --- search / strategy flags (reference model.cc:3596-3680) ---
    search_budget: int = -1
    search_alpha: float = 1.2
    only_data_parallel: bool = False
    # NOTE: defaults True (reference defaults these off, model.cc:3620-3630,
    # because its parameter/attribute parallel paths were experimental; here
    # they are first-class tested candidates). --disable-* flags opt out.
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    export_strategy_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    # TASO-style JSON substitution rules (reference substitution_loader.cc,
    # substitutions/graph_subst_3_v2.json); "default" loads the bundled set
    substitution_json_file: Optional[str] = None
    # algebraic graph-rewrite tier of the search (reference GraphXfer
    # structure rewrites, substitution.cc:1726-1868); --disable-graph-rewrites
    # restricts the search to placements only
    enable_graph_rewrites: bool = True
    # NOTE deliberately absent vs the reference FFConfig: perform_fusion /
    # enable_inplace_optimizations / search_overlap_backward_update (XLA
    # fuses, in-places, and overlaps inside the single jitted step program),
    # simulator_work_space_size (no simulator workspace exists — op timing
    # compiles real sub-programs), machine_model_version (one TPU machine
    # model, parameterized via --machine-model-file).
    # --- observability (reference model.cc:3650-3670) ---
    # per-step timing printouts in fit() (the reference's --profiling
    # per-op ELAPSED prints) + compile-time cost table
    profiling: bool = False
    export_strategy_computation_graph_file: Optional[str] = None
    taskgraph_file: Optional[str] = None
    # unified tracing (docs/OBSERVABILITY.md): Chrome-trace JSON output
    # path and granularity.  --trace-out alone implies level "step".
    trace_out: Optional[str] = None
    trace_level: str = "off"  # off | step | op
    # --- run-health monitor (docs/OBSERVABILITY.md) ---
    # per-step JSONL metrics stream (loss/grad-norm/throughput/counter
    # deltas, one schema-versioned record per step)
    metrics_out: Optional[str] = None
    # ffspan/1 per-request span stream for serve runs (--serve-spans-out,
    # docs/OBSERVABILITY.md "Request timelines"); None = tracing off,
    # which keeps metrics streams byte-identical to untraced builds
    serve_spans_out: Optional[str] = None
    # size-based rotation for JSONL streams (metrics + spans): when a
    # stream file crosses this many MB it is rotated to .1, .2, ... and
    # read_metrics reads the rotated set back in order.  0 = unbounded.
    metrics_max_mb: float = 0.0
    # anomaly policy: non-finite loss/grad + EMA loss-spike detectors.
    # "dump"/"raise" write a debug bundle (config, strategy, last-N step
    # records, Chrome trace, memory snapshot) on the first anomaly.
    health: str = "off"  # off | warn | dump | raise | restore
    health_dir: str = "health_bundles"  # bundle output directory
    health_window: int = 64  # flight-recorder ring size (last-N records)
    health_spike_factor: float = 4.0  # loss > factor * EMA(loss) => spike
    health_ema_decay: float = 0.9
    health_warmup_steps: int = 5  # finite losses seeding the EMA baseline
    # prediction-drift watchdog (docs/OBSERVABILITY.md "Calibration
    # loop"): EMA of observed/predicted step-time ratio, fires ONCE per
    # run when it leaves [1/factor, factor]; "dump" reuses the one-bundle
    # flight-recorder machinery
    drift: str = "off"  # off | warn | dump
    drift_factor: float = 2.0  # ratio band half-width (multiplicative)
    # --- async training pipeline (docs/OBSERVABILITY.md "Sync points") ---
    # fetch device-accumulated step metrics to host every K steps
    # (plus at epoch end).  0 = auto: 1 when --health/--metrics-out/
    # --profiling demand per-step host observation, else
    # DEFAULT_METRICS_SYNC_EVERY.  1 = the fully synchronous reference
    # behavior (one forced device round-trip per step).  An enabled
    # health monitor / --profiling always forces the effective value to
    # 1 — their whole point is per-step observation.
    metrics_sync_every: int = 0
    # input-pipeline look-ahead: how many batches the loader producer
    # thread (native ffdl.cc ring or the pure-Python fallback) and the
    # device placement stage each run ahead of the step loop
    prefetch_depth: int = 3
    # --- simulator (reference config.h:127-136) ---
    # v1 flat scalars or the v2 multi-slice schema (slices, per-axis ICI
    # link classes, DCN uplinks/contention) — docs/MACHINE_MODEL.md; the
    # loader dispatches on the file's "version" key
    machine_model_file: Optional[str] = None
    # measured cost tier: search candidates costed by compiling-and-timing
    # ops on device (the reference's default behavior,
    # ``src/runtime/simulator.cc:537-577``); off by default here because
    # the analytic tier is free while measuring costs a jit compile per
    # distinct (op, local shape)
    use_measured_cost: bool = False
    cost_cache_file: Optional[str] = None
    # cost-model tier (docs/OBSERVABILITY.md "Calibration loop"):
    # "analytic" = the roofline machine model; "measured" = compile-and-
    # time per-op (same as --measured-cost); "calibrated" = per-op-class
    # + per-objective corrections from a CalibrationStore applied ON TOP
    # of whichever base tier is active (calibrated + --measured-cost
    # composes: corrections scale the measured leaf times)
    cost_model: str = "analytic"  # analytic | measured | calibrated
    # versioned calibration-store JSON (tools/calibration_report.py);
    # load REFUSES a store fit for a different machine-model identity,
    # backend, or compute dtype
    calibration_store_file: Optional[str] = None
    # --- TPU-specific (replaces Legion -ll:gpu etc.) ---
    mesh_shape: Optional[Tuple[int, ...]] = None  # e.g. (2, 4)
    mesh_axis_names: Tuple[str, ...] = ("data", "model")
    # --- multi-host (reference MULTI-NODE.md: GASNet/MPI launcher) ---
    coordinator_address: Optional[str] = None  # host:port of process 0
    num_nodes_cli: Optional[int] = None  # process count (None = env/auto)
    node_id: Optional[int] = None  # this process's index
    dcn_axis: str = "data"  # mesh axis that spans hosts
    compute_dtype: str = "float32"  # params/compute dtype; "bfloat16" for perf
    # dtype the executor HOLDS floating-point parameters in.  "float32"
    # (default): master weights, cast to compute_dtype at use.
    # "bfloat16": weights at rest in bfloat16 -- half the bytes held and
    # no cast inside a serve program; for serving a model whose float32
    # weights pass the chip (an op's fp32_weights stay float32).  Not for
    # training: the optimizer would update bfloat16 masters.
    param_dtype: str = "float32"
    # ZeRO-1: shard optimizer moments over the data axis (memory /dp at the
    # cost of an all-gathered param delta per step).  Beyond the reference,
    # whose optimizer state is replicated per device (optimizer_kernel.cu).
    enable_zero1: bool = False
    # rematerialization policy for the backward pass: "none" (XLA default
    # saves every residual), "attention" (checkpoint attention cores — the
    # S^2-shaped residuals), or "all" (checkpoint every op).  The TPU form
    # of trading FLOPs for HBM (jax.checkpoint).
    remat_policy: str = "none"
    # scan-stacked repeated blocks (docs/PERF.md): execute maximal chains
    # of structurally identical layer blocks as ONE jax.lax.scan over
    # depth-stacked parameters, making trace/compile cost per unique
    # block instead of per layer.  "auto" stacks chains of depth >= 4,
    # "on" stacks any detected chain (depth >= 2), "off" is byte-identical
    # to the unrolled path.
    stack_blocks: str = "auto"  # on | off | auto
    # pipeline parallelism (docs/PIPELINE.md): "off" | "auto" | a stage
    # count S.  "auto" lets the Unity search price a 1F1B pipelined
    # variant of every mesh candidate (stage submesh solve + the
    # (S x M) sweep) and win on cost; a numeric S forces that stage
    # count — through the search when --budget is set, else attached
    # directly to the default/imported strategy when a repeated-block
    # chain divides into S stages.
    pipeline: str = "off"  # off | auto | <stages>
    # microbatches per 1F1B step (0 = auto: the search sweeps divisors
    # of the global batch; non-search strategies default to min(4, B))
    microbatches: int = 0
    # overlapped gradient sync (docs/PERF.md "Overlapped gradient sync"):
    # ring the scan-stacked chains' weight-grad sync into the backward
    # scan body (reduce-scatter + ppermute all-gather over the data axis)
    # so block i's grad traffic overlaps block i-1's backward compute.
    # "auto" rings a chain when the overlap pricing says the exposed time
    # beats the fused tail all-reduce; "ring" forces it on every eligible
    # chain; "off" is byte-identical to today's fused path.  Non-chain
    # weights always keep the fused path; pipelined chains and data-axis
    # extent 1 decline.
    grad_overlap: str = "off"  # off | auto | ring
    # post-compile static analysis (docs/ANALYSIS.md): run the ffcheck
    # registry over every compiled program.  "warn" records violations
    # (ffmetrics `analysis_violations` + the analysis.violations tracer
    # counter), "strict" raises AnalysisError — compile-time enforcement
    # of the collective / transfer / donation / dtype / replication
    # invariants the placement priced.
    verify_compiled: str = "off"  # off | warn | strict
    rng_seed: int = 0
    memory_search_budget: int = -1  # lambda search iterations (graph.cc:2075)
    device_memory_gb: float = -1.0  # per-device HBM budget for λ mem search
    # --- serving (docs/SERVING.md) ---
    # search objective: "train" minimizes the training step estimate,
    # "serve" prices forward-only + the ServeObjective (steady-state
    # decode tokens/s under the --serve-slo-ms p99 per-token bound)
    search_objective: str = "train"  # train | serve
    serve_slots: int = 0  # decode lanes (0 = the model's compiled batch)
    serve_block_size: int = 16  # KV positions per paged block
    serve_num_blocks: int = 0  # KV pool size (0 = full provisioning)
    serve_prefill_chunk: int = 32  # prompt positions per prefill call
    serve_sync_every: int = 4  # decode steps per flush window
    serve_slo_ms: float = 50.0  # p99 per-token latency SLO (objective)
    serve_prefix_sharing: bool = True  # CoW prefix-block sharing
    # decode-attention kernel: "auto" = fused Pallas paged attention
    # where it can run (TPU / interpret), dense gather otherwise
    serve_attn: str = "auto"  # auto | gather | paged
    # quantized serving (docs/SERVING.md "Quantized KV cache and
    # weight-only decode"): KV pool storage format (per-block symmetric
    # scales, in-kernel dequant) and decode weight storage format
    # (per-channel int8, dequantized at the matmul edge)
    serve_kv_dtype: str = "fp32"  # fp32 | bf16 | int8 | fp8
    serve_weight_dtype: str = "fp32"  # fp32 | int8
    serve_spec_k: int = 0  # speculative draft depth (0 = off)
    serve_spec_draft_layers: int = 0  # draft slice depth (0 = half)
    serve_spec_accept: float = 0.7  # priced per-draft acceptance prob.
    # --- resilience (docs/RESILIENCE.md) ---
    # deterministic fault injection: a spec string ([site:]kind@step[:arg],
    # comma-separated) or a JSON plan file; None = no plan, zero overhead
    fault_plan: Optional[str] = None
    checkpoint_every: int = 0  # snapshot every K optimizer steps (0 = off)
    checkpoint_path: Optional[str] = None  # target .npz for --checkpoint-every
    resume_from: Optional[str] = None  # checkpoint to restore before fit
    max_restores: int = 1  # --health restore rewind budget per fit
    coordinator_retries: int = 0  # transient connect retries (distributed)
    coordinator_backoff_s: float = 1.0  # base backoff, doubles per attempt
    serve_watchdog_s: float = 0.0  # flag windows slower than this (0 = off)
    serve_shed_windows: int = 0  # shed batch tier after N SLO-breach windows
    serve_drain_file: Optional[str] = None  # SIGTERM drain payload target
    # --- SLO ops plane (docs/OBSERVABILITY.md "SLOs, alerts, and live
    # introspection") ---
    serve_slo_policy: Optional[str] = None  # SLOPolicy JSON file
    serve_alerts_out: Optional[str] = None  # ffalert/1 fire/resolve JSONL
    serve_status_port: int = 0  # /healthz /statusz /spanz /metricz (0 = off)
    # --- fleet tier (docs/SERVING.md "Fleet tier") ---
    serve_replicas: int = 1  # replica engines behind the fleet router
    serve_routing: str = "prefix"  # prefix | round_robin | least_loaded

    def __post_init__(self) -> None:
        self._devices = None

    # --- device/mesh topology ---------------------------------------------
    @property
    def devices(self):
        if self._devices is None:
            self._devices = jax.devices()
        return self._devices

    @property
    def num_devices(self) -> int:
        """Reference ``workersPerNode * numNodes``."""
        return len(self.devices)

    @property
    def num_nodes(self) -> int:
        return jax.process_count()

    @property
    def workers_per_node(self) -> int:
        return max(1, self.num_devices // max(1, self.num_nodes))

    def build_mesh(self):
        """The MachineMesh this config's ``--mesh-shape`` describes, or
        None — the ONE cfg-to-mesh rule, shared by ``FFModel.compile`` and
        the examples (a second copy silently diverging from compile's was
        a round-4 review finding)."""
        if self.mesh_shape is None:
            return None
        from flexflow_tpu.parallel.machine import MachineMesh

        return MachineMesh(
            self.mesh_shape, self.mesh_axis_names[: len(self.mesh_shape)]
        )

    def parse_args(self, argv: Optional[Sequence[str]] = None) -> List[str]:
        """Parse reference-compatible CLI flags (``model.cc:3566-3730``).

        Returns unconsumed args (the reference silently ignores unknown
        flags; we hand them back for app-level parsing).
        """
        if argv is None:
            argv = sys.argv[1:]
        rest: List[str] = []
        it = iter(range(len(argv)))
        args = list(argv)
        i = 0

        def take() -> str:
            nonlocal i
            i += 1
            return args[i]

        while i < len(args):
            a = args[i]
            if a in ("-b", "--batch-size"):
                self.batch_size = int(take())
            elif a in ("-e", "--epochs"):
                self.epochs = int(take())
            elif a in ("--lr", "--learning-rate"):
                self.learning_rate = float(take())
            elif a in ("--wd", "--weight-decay"):
                self.weight_decay = float(take())
            elif a == "--budget" or a == "--search-budget":
                self.search_budget = int(take())
            elif a == "--alpha" or a == "--search-alpha":
                self.search_alpha = float(take())
            elif a == "--only-data-parallel":
                self.only_data_parallel = True
            elif a == "--remat":
                self.remat_policy = take()
            elif a == "--stack-blocks":
                self.stack_blocks = take()
            elif a == "--pipeline":
                self.pipeline = take()
            elif a == "--microbatches":
                self.microbatches = int(take())
            elif a == "--grad-overlap":
                self.grad_overlap = take()
            elif a == "--verify-compiled":
                self.verify_compiled = take()
            elif a == "--enable-parameter-parallel":
                self.enable_parameter_parallel = True
            elif a == "--disable-parameter-parallel":
                self.enable_parameter_parallel = False
            elif a == "--enable-attribute-parallel":
                self.enable_attribute_parallel = True
            elif a == "--disable-attribute-parallel":
                self.enable_attribute_parallel = False
            elif a == "--profiling":
                self.profiling = True
            elif a == "--trace-out":
                self.trace_out = take()
            elif a == "--trace-level":
                self.trace_level = take()
            elif a == "--metrics-out":
                self.metrics_out = take()
            elif a == "--serve-spans-out":
                self.serve_spans_out = take()
            elif a == "--metrics-max-mb":
                self.metrics_max_mb = float(take())
            elif a == "--health":
                self.health = take()
            elif a == "--health-dir":
                self.health_dir = take()
            elif a == "--health-window":
                self.health_window = int(take())
            elif a == "--health-spike-factor":
                self.health_spike_factor = float(take())
            elif a == "--metrics-sync-every":
                self.metrics_sync_every = int(take())
            elif a == "--prefetch-depth":
                self.prefetch_depth = int(take())
            elif a == "--export-strategy" or a == "--export":
                self.export_strategy_file = take()
            elif a == "--import-strategy" or a == "--import":
                self.import_strategy_file = take()
            elif a == "--substitution-json":
                self.substitution_json_file = take()
            elif a == "--disable-graph-rewrites":
                self.enable_graph_rewrites = False
            elif a == "--taskgraph":
                self.taskgraph_file = take()
            elif a == "--compgraph":
                self.export_strategy_computation_graph_file = take()
            elif a == "--machine-model-file":
                self.machine_model_file = take()
            elif a == "--measured-cost":
                self.use_measured_cost = True
            elif a == "--cost-cache":
                self.cost_cache_file = take()
            elif a == "--cost-model":
                self.cost_model = take()
            elif a == "--calibration-store":
                self.calibration_store_file = take()
            elif a == "--drift":
                self.drift = take()
            elif a == "--drift-factor":
                self.drift_factor = float(take())
            elif a == "--mesh-shape":
                self.mesh_shape = tuple(int(x) for x in take().split("x"))
            elif a == "--dtype":
                self.compute_dtype = take()
            elif a == "--zero1":
                self.enable_zero1 = True
            elif a == "--seed":
                self.rng_seed = int(take())
            elif a == "--device-memory-gb":
                self.device_memory_gb = float(take())
            elif a == "--memory-search-budget":
                self.memory_search_budget = int(take())
            elif a == "--coordinator-address":
                self.coordinator_address = take()
            elif a == "--num-nodes":
                self.num_nodes_cli = int(take())
            elif a == "--node-id":
                self.node_id = int(take())
            elif a == "--dcn-axis":
                self.dcn_axis = take()
            elif a == "--objective":
                self.search_objective = take()
            elif a == "--serve-slots":
                self.serve_slots = int(take())
            elif a == "--serve-block-size":
                self.serve_block_size = int(take())
            elif a == "--serve-num-blocks":
                self.serve_num_blocks = int(take())
            elif a == "--serve-prefill-chunk":
                self.serve_prefill_chunk = int(take())
            elif a == "--serve-sync-every":
                self.serve_sync_every = int(take())
            elif a == "--serve-slo-ms":
                self.serve_slo_ms = float(take())
            elif a == "--serve-prefix-sharing":
                self.serve_prefix_sharing = take().lower() in (
                    "1", "true", "on", "yes",
                )
            elif a == "--serve-attn":
                self.serve_attn = take()
            elif a == "--serve-kv-dtype":
                self.serve_kv_dtype = take()
            elif a == "--serve-weight-dtype":
                self.serve_weight_dtype = take()
            elif a == "--serve-spec-k":
                self.serve_spec_k = int(take())
            elif a == "--serve-spec-draft-layers":
                self.serve_spec_draft_layers = int(take())
            elif a == "--serve-spec-accept":
                self.serve_spec_accept = float(take())
            elif a == "--fault-plan":
                self.fault_plan = take()
            elif a == "--checkpoint-every":
                self.checkpoint_every = int(take())
            elif a == "--checkpoint-path":
                self.checkpoint_path = take()
            elif a == "--resume":
                self.resume_from = take()
            elif a == "--max-restores":
                self.max_restores = int(take())
            elif a == "--coordinator-retries":
                self.coordinator_retries = int(take())
            elif a == "--coordinator-backoff-s":
                self.coordinator_backoff_s = float(take())
            elif a == "--serve-watchdog-s":
                self.serve_watchdog_s = float(take())
            elif a == "--serve-shed-windows":
                self.serve_shed_windows = int(take())
            elif a == "--serve-drain-file":
                self.serve_drain_file = take()
            elif a == "--serve-slo-policy":
                self.serve_slo_policy = take()
            elif a == "--serve-alerts-out":
                self.serve_alerts_out = take()
            elif a == "--serve-status-port":
                self.serve_status_port = int(take())
            elif a == "--serve-replicas":
                self.serve_replicas = int(take())
            elif a == "--serve-routing":
                self.serve_routing = take()
            else:
                rest.append(a)
            i += 1
        return rest


# where compiled programs persist when JAX_COMPILATION_CACHE_DIR does not
# say: one fixed directory in the checkout (the path is part of the cache
# key, so a temp name, pid or timestamp would never hit)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def apply_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR`` places it from
    outside (jax reads the variable itself; nothing here overrides it);
    otherwise it lives at :data:`DEFAULT_COMPILE_CACHE_DIR`.  The
    min-size/min-time gates are zeroed so every program caches — the
    serve programs unroll depth and a cold call pays the whole compile.
    Called by every entry point before its first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def cpu_mesh_env(n: int = 8) -> None:
    """Force an ``n``-device CPU platform for sharding tests.

    Must run before jax initializes its backends (used by tests/conftest.py).
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + opt).strip()
