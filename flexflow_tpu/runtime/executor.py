"""Step-program builder and executor.

This is the TPU-native replacement for the reference's execution stack:
``FFModel::forward/backward/update/zero_gradients`` driving one Legion index
launch per op per iteration (``src/runtime/model.cc:2409-2474``), the
FFMapper routing tasks to devices (``src/mapper/mapper.cc``), and Legion
tracing for replay efficiency (``flexflow_cffi.py:2090-2104``).

Design: the whole training step — forward, loss, backward (autodiff),
metrics, optimizer update, gradient sync — is ONE jitted SPMD program over
the strategy's mesh.  Per-op "launches" exist only at trace time; XLA fuses
and schedules everything (subsuming the reference's ``apply_fusion`` pass,
``model.cc:2495``, and overlap flags).  Tracing happens once per shape —
the jit cache is the analog of Legion's trace replay.

Gradient synchronization: none explicit.  Sharded batch + replicated (or
sharded) weights make GSPMD emit the all-reduce (or reduce-scatter) that
the reference's NCCL optimizer tasks performed
(``src/runtime/optimizer_kernel.cu:85-140``).

Mixed precision (``compute_dtype="bfloat16"``): master params, optimizer
state, BN running stats, loss and metrics stay float32; activations and
op compute run in bfloat16 (params cast at use, inputs cast at graph
entry, logits cast back before the loss).  The cast-at-use VJP yields
float32 gradients, so update math is exact.  The reference runs fp32 on
GPUs (no analog); on TPU bf16 doubles MXU throughput, which the search
cost model already assumes (``search/cost.py``).
"""

from __future__ import annotations

import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from flexflow_tpu.blocks import BlockChain, detect_block_chains
from flexflow_tpu.fftype import LossType, OperatorType
from flexflow_tpu.loss import get_loss_fn
from flexflow_tpu.metrics import COUNTER_PREFIX, GAUGE_PREFIX, Metrics
from flexflow_tpu.obs import get_monitor, get_tracer, persistent_cache_hits
from flexflow_tpu.ops.base import OpContext, get_op_def
from flexflow_tpu.ops.parallel_ops import resolve_parallel_sharding
from flexflow_tpu.optimizer import Optimizer
from flexflow_tpu.parallel.spec import TensorSharding
from flexflow_tpu.parallel.strategy import Strategy
from flexflow_tpu.runtime.faults import get_fault_plan
from flexflow_tpu.tensor import Layer, Tensor


class Executor:
    """Compiles (layers, strategy, optimizer, loss) into jitted step fns."""

    def __init__(
        self,
        layers: List[Layer],
        graph_inputs: List[Tensor],
        logits: Tensor,
        strategy: Strategy,
        optimizer: Optimizer,
        loss_type: LossType,
        metrics: Metrics,
        seed: int = 0,
        remat_policy: str = "none",
        compute_dtype: str = "float32",
        param_dtype: str = "float32",
        dcn_axis: str = "data",
        zero1: bool = False,
        profiling: bool = False,
        stack_blocks: str = "off",
        verify_compiled: str = "off",
        grad_overlap: str = "off",
    ) -> None:
        self.layers = layers
        self.graph_inputs = graph_inputs
        self.logits = logits
        self.strategy = strategy
        self.optimizer = optimizer
        self.loss_type = loss_type
        self.loss_fn = get_loss_fn(loss_type)
        self.metrics = metrics
        self.seed = seed
        assert remat_policy in ("none", "attention", "all"), (
            f"unknown remat policy {remat_policy!r}"
        )
        self.remat_policy = remat_policy
        self.compute_dtype = jnp.dtype(compute_dtype)
        self._mixed = self.compute_dtype != jnp.float32
        # what init_params makes float32-declared weights in
        # (FFConfig.param_dtype: weights at rest)
        self.param_dtype = jnp.dtype(param_dtype)
        # the serve programs' once-cast copies of float32 leaves, by the
        # source array's identity (serve/programs.py::weights_as_consumed):
        # every engine over this executor shares them
        self.serve_cast: Dict[Tuple[str, str], Tuple[Any, Any]] = {}
        # ZeRO-1: optimizer moments sharded over the data axis (memory /dp);
        # GSPMD turns the update into slice-update + all-gather of the
        # param delta — a capability the reference lacks entirely (its
        # optimizer state is replicated per GPU, optimizer_kernel.cu)
        self.zero1 = zero1 and strategy.mesh.axis_size("data") > 1

        self.mesh: Optional[Mesh] = None
        if strategy.mesh.size > 1:
            if jax.process_count() > 1:
                # multi-host: the dcn axis spans processes so its
                # collectives ride DCN, everything else stays on ICI
                # (replaces the reference's GASNet+NCCL split,
                # MULTI-NODE.md / model.cc:3129-3167)
                self.mesh = strategy.mesh.build_hybrid(dcn_axis=dcn_axis)
            else:
                self.mesh = strategy.mesh.build()

        # split weight declarations into trainable params vs state
        self._wspecs: Dict[int, List] = {}
        for layer in layers:
            self._wspecs[int(layer.layer_guid)] = get_op_def(layer.op_type).weights(layer)

        # --- scan-stacked repeated blocks (--stack-blocks, docs/PERF.md):
        # maximal chains of structurally identical blocks execute as ONE
        # jax.lax.scan over depth-stacked parameters, so trace/compile
        # cost is per unique block instead of per layer.  "off" keeps the
        # unrolled path untouched; "auto" stacks chains of depth >= 4;
        # "on" stacks any chain (depth >= 2).  Chains the scan cannot
        # express (stateful ops, aux losses, non-uniform per-depth
        # shardings) are declined — see _chain_executable.
        assert stack_blocks in ("off", "on", "auto"), (
            f"unknown --stack-blocks value {stack_blocks!r}"
        )
        self.stack_blocks = stack_blocks
        self._block_chains: List[BlockChain] = []
        # member layer name -> (stacked bucket = template layer name,
        # depth index): the per-layer view over stacked param storage
        # (checkpoints and get/set_weights always speak per-layer)
        self._stacked_slices: Dict[str, Tuple[str, int]] = {}
        # bucket name -> member layer names ordered by depth
        self._bucket_members: Dict[str, List[str]] = {}
        if stack_blocks != "off":
            min_depth = 4 if stack_blocks == "auto" else 2
            for c in detect_block_chains(layers, min_depth=min_depth):
                if not self._chain_executable(c):
                    continue
                self._register_chain(c)
        # --- pipeline parallelism (docs/PIPELINE.md): when the strategy
        # carries a PipelineSpec, ONE chain runs the microbatched 1F1B
        # schedule — a lax.scan over M + S - 1 ticks whose activation
        # handoff between stage submeshes is a ppermute over the stage
        # axis.  The pipelined chain rides the stacked-param machinery
        # (checkpoints stay per-layer either way), so pipelining forces
        # stacking for THAT chain even under --stack-blocks off.
        self.pipeline = None
        self._pipeline_chain: Optional[BlockChain] = None
        spec = getattr(strategy, "pipeline", None)
        if spec is not None:
            reason = self._setup_pipeline(spec)
            if reason is not None and jax.process_index() == 0:
                print(f"[pipeline] declined at executor: {reason}")
            if self.pipeline is not None and self.pipeline.stage_axis == "data":
                # the stage axis is consumed by the schedule: batch rows
                # are not data-sharded over it, so ZeRO-1's "shard
                # moments over every data replica" premise is gone
                self.zero1 = False
        # execution plan: plain layers interleaved with BlockChain segments
        if self._block_chains:
            chain_at = {c.start: c for c in self._block_chains}
            segs: List[Any] = []
            idx = 0
            while idx < len(layers):
                c = chain_at.get(idx)
                if c is not None:
                    segs.append(c)
                    idx = c.end
                else:
                    segs.append(layers[idx])
                    idx += 1
            self._segments: List[Any] = segs
        else:
            self._segments = list(layers)

        # --- overlapped gradient sync (--grad-overlap, docs/PERF.md
        # "Overlapped gradient sync"): ring each eligible scan-stacked
        # chain's weight-grad sync INTO the backward scan body — a
        # sharding-constraint-forced reduce-scatter over the data axis
        # plus an explicit (n−1)-hop ppermute ring all-gather
        # (_ring_all_gather, the PR-8 shard_map idiom) — so block i's
        # grad traffic overlaps block i−1's backward compute instead of
        # queueing in the fused tail sync.  "off" leaves the trace
        # byte-identical; "auto" arrives here already resolved by
        # FFModel.compile's overlap pricing (an explicit auto on a bare
        # Executor rings every eligible chain, like "ring").  Non-chain
        # weights always keep the fused path; declines mirror
        # docs/PERF.md (data axis extent 1, pipelined chains, weights
        # already data-sharded or with no n-divisible unsharded dim).
        assert grad_overlap in ("off", "auto", "ring"), (
            f"unknown --grad-overlap value {grad_overlap!r}"
        )
        self.grad_overlap = grad_overlap
        # chain start -> {bucket name -> {weight name -> (scatter dim,
        # per-layer base spec)}}; member layer names feed the analyzer's
        # :grad-sync-ring implied entries (analysis/capture.py)
        self._grad_ring: Dict[int, Dict[str, Dict[str, Tuple[int, Tuple]]]] = {}
        self._grad_ring_layers: frozenset = frozenset()
        if grad_overlap != "off":
            self._setup_grad_ring(grad_overlap)

        self._step_jit = None
        self._fwd_jit = None
        self._input_pspec_cache: Dict[int, PartitionSpec] = {}
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self.opt_state: Any = None
        self._step_count = 0
        # observability: --profiling per-step timing, last_step_stats API,
        # trace spans (docs/OBSERVABILITY.md).  The untraced train_step
        # path is untouched when both are off.
        self.profiling = profiling
        self.last_step_stats: Optional[Dict[str, Any]] = None
        # host-sync ledger: every DELIBERATE host-side result fetch the
        # training/eval loops issue (per-step scalar fetches in sync mode,
        # K-step metric flushes in async mode) increments host_syncs via
        # count_host_sync, and the blocking wall time lands in
        # host_stall_s.  Plain attributes, always on (one int add) — the
        # tests' zero-per-step-sync guard reads them without a tracer;
        # count_host_sync mirrors into the tracer counter when enabled.
        # The instrumented path's block_until_ready is NOT in host_syncs
        # (it is the documented profiling sync, reported per step as
        # last_step_stats["host_stall_s"]) but its stall does accumulate.
        self.host_syncs = 0
        self.host_stall_s = 0.0
        self._step_compiled = None  # AOT executable (traced path only)
        # times an AOT step was dropped for the jit wrapper because the
        # params came back under other shardings (see _run_step)
        self.aot_sharding_drifts = 0
        # --verify-compiled (docs/ANALYSIS.md): run the ffcheck registry
        # over the step program once per compile.  "warn" records the
        # count (analysis.violations counter + last_analysis report),
        # "strict" raises AnalysisError before the first step executes.
        assert verify_compiled in ("off", "warn", "strict"), (
            f"unknown --verify-compiled value {verify_compiled!r}"
        )
        self.verify_compiled = verify_compiled
        self.last_analysis = None  # AnalysisReport from the last verify
        self.analysis_violations: Optional[int] = None  # None = never ran
        self._verified_step = False
        self._fwd_seqs_seen: set = set()  # fwd jit-cache hit/miss tracking
        # run-health monitor vocabulary: samples (and tokens when the
        # first input carries a sequence dim) consumed per step — the
        # numerators of the stream's samples_per_s / tokens_per_s
        b = graph_inputs[0].shape[0] if graph_inputs else None
        self._samples_per_step = b
        self._tokens_per_step = (
            b * graph_inputs[0].shape[1]
            if graph_inputs and graph_inputs[0].ndim >= 2
            else None
        )

    # --- sharding helpers --------------------------------------------------
    def _constrain(self, x: jax.Array, pspec: PartitionSpec) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, pspec))

    def _input_pspec(self, t: Tensor) -> PartitionSpec:
        """Inputs take the strategy's declared input sharding of their first
        consumer when one exists (e.g. seq-parallel strategies declare
        graph inputs seq-sharded so layer-0 attention sees a sharded seq
        dim); otherwise they follow the default batch sharding.  Labels are
        co-sharded with the final op (reference label-tensor creation,
        ``model.cc:3086-3124``).  Cached per tensor: the consumer scan is
        O(layers) and this runs on every train_step call."""
        cached = self._input_pspec_cache.get(t.guid)
        if cached is not None:
            return cached
        ps = self._input_pspec_uncached(t)
        self._input_pspec_cache[t.guid] = ps
        return ps

    def _data_shard_ok(self) -> bool:
        """May the batch dim default-shard over 'data'?  Not when a
        pipeline consumes it as the stage axis — microbatches flow
        THROUGH the stage submeshes, they are not split across them —
        and not when the per-microbatch row count B/M no longer divides
        the axis (each microbatch travels the schedule as its own batch
        dim; a non-dividing shard would reshard every tick)."""
        dp = self.strategy.mesh.axis_size("data")
        if self.pipeline is not None:
            if self.pipeline.stage_axis == "data":
                return False
            b = self.graph_inputs[0].shape[0] if self.graph_inputs else 0
            if (b // self.pipeline.microbatches) % dp != 0:
                return False
        return dp > 1

    def _input_pspec_uncached(self, t: Tensor) -> PartitionSpec:
        declared = self._declared_input_sharding(t)
        if declared is not None:
            return declared.partition_spec()
        if self._data_shard_ok() and t.shape[0] % self.strategy.mesh.axis_size("data") == 0:
            return PartitionSpec("data")
        return PartitionSpec()

    def _declared_input_sharding(self, t: Tensor) -> Optional[TensorSharding]:
        """First consumer's strategy-declared sharding for tensor ``t``
        (None when no consumer declares one)."""
        for layer in self.layers:
            for j, it in enumerate(layer.inputs):
                if it.guid == t.guid:
                    op_sh = self.strategy.op_sharding(layer)
                    if op_sh is not None and j < len(op_sh.inputs):
                        return op_sh.inputs[j]
                    return None  # first consumer decides
        return None

    def _cast_compute(self, x: jax.Array) -> jax.Array:
        """float32 -> compute dtype (identity when not mixed; never touches
        integer/bool tensors or already-low-precision arrays)."""
        if self._mixed and hasattr(x, "dtype") and x.dtype == jnp.float32:
            return x.astype(self.compute_dtype)
        return x

    # --- forward trace -----------------------------------------------------
    def _forward(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        state: Dict[str, Dict[str, jax.Array]],
        inputs: Sequence[jax.Array],
        training: bool,
        rng: Optional[jax.Array],
        seq_length: Optional[int] = None,
    ):
        """Trace the PCG in layer order (layers are appended
        topologically by the builder API, mirroring
        ``create_operators_from_layers`` order, ``model.cc:2785``)."""
        values: Dict[int, jax.Array] = {}
        shardings: Dict[int, TensorSharding] = {}
        for t, x in zip(self.graph_inputs, inputs):
            ps = self._input_pspec(t)
            values[t.guid] = self._constrain(self._cast_compute(x), ps)
            spec = tuple(ps)
            shardings[t.guid] = TensorSharding(
                spec=spec + (None,) * (t.ndim - len(spec))
            )

        aux_losses: List[jax.Array] = []
        counters: Dict[str, List[jax.Array]] = {}
        new_state: Dict[str, Dict[str, jax.Array]] = {}
        for seg in self._segments:
            if isinstance(seg, BlockChain):
                if seg is self._pipeline_chain:
                    self._trace_pipeline_scan(
                        seg, values, shardings, params, training, rng,
                        seq_length,
                    )
                else:
                    self._trace_block_scan(
                        seg, values, shardings, params, training, rng,
                        seq_length,
                    )
                continue
            self._trace_layer(
                seg, values, shardings, params, state, training, rng,
                seq_length, new_state, aux_losses, counters=counters,
            )
        # carry over unchanged state
        for name, s in state.items():
            if name not in new_state:
                new_state[name] = s
        logits = values[self.logits.guid]
        if self._mixed and logits.dtype == self.compute_dtype:
            logits = logits.astype(jnp.float32)  # loss/metrics in fp32
        # what the ops counted: counters summed, gauges averaged over the
        # layers that reported them (they join the step's metrics)
        counted = {
            k: (sum(v) / len(v) if k.startswith(GAUGE_PREFIX) else sum(v))
            for k, v in counters.items()
        }
        return logits, new_state, aux_losses, counted

    def _trace_layer(
        self,
        layer: Layer,
        values: Dict[int, jax.Array],
        shardings: Dict[int, TensorSharding],
        params: Dict[str, Dict[str, jax.Array]],
        state: Dict[str, Dict[str, jax.Array]],
        training: bool,
        rng: Optional[jax.Array],
        seq_length: Optional[int],
        new_state: Dict[str, Dict[str, jax.Array]],
        aux_losses: List[jax.Array],
        rng_key: Optional[jax.Array] = None,
        counters: Optional[Dict[str, List[jax.Array]]] = None,
    ) -> None:
        """Trace ONE layer into ``values``/``shardings`` — the loop body
        of the unrolled path, also reused per template position inside a
        ``block_scan`` body (``rng_key`` then carries the per-depth key
        derived from the scan's xs instead of the layer-name fold)."""
        opdef = get_op_def(layer.op_type)
        ins = [values[t.guid] for t in layer.inputs]
        lp32 = dict(params.get(layer.name, {}))
        lp32.update(state.get(layer.name, {}))
        lp = {
            k: v if k in opdef.fp32_weights else self._cast_compute(v)
            for k, v in lp32.items()
        }
        if rng_key is None and rng is not None:
            rng_key = jax.random.fold_in(
                rng, zlib.crc32(layer.name.encode()) % (2**31)
            )
        ctx = OpContext(
            training=training,
            rng=rng_key,
            mesh=self.mesh,
            input_shardings=[shardings.get(t.guid) for t in layer.inputs],
            op_sharding=self.strategy.op_sharding(layer),
            seq_length=seq_length,
        )
        if self.remat_policy == "all" or (
            self.remat_policy == "attention" and layer.op_type in _REMAT_OPS
        ):
            outs = jax.checkpoint(
                lambda p, i, _l=layer, _c=ctx: get_op_def(_l.op_type).forward(_l, p, i, _c)
            )(lp, ins)
        else:
            outs = opdef.forward(layer, lp, ins, ctx)
        # apply sharding constraints on outputs.  Parallel ops derive
        # their outgoing distribution from the incoming one + attrs (the
        # resharding vocabulary, SURVEY §2.4); other ops take the
        # strategy's assignment when one exists.
        if layer.op_type.is_parallel_op:
            src = layer.inputs[0]
            in_sh = shardings.get(src.guid, TensorSharding.replicated(src.ndim))
            out_sh = resolve_parallel_sharding(layer, in_sh, self.strategy.mesh)
            t = layer.outputs[0]
            values[t.guid] = self._constrain(outs[0], out_sh.partition_spec())
            shardings[t.guid] = out_sh
            return
        op_sh = self.strategy.op_sharding(layer)
        for i, (t, y) in enumerate(zip(layer.outputs, outs)):
            if op_sh is not None and i < len(op_sh.output):
                ts = op_sh.output[i]
                y = self._constrain(y, ts.partition_spec())
                shardings[t.guid] = ts
            else:
                shardings[t.guid] = TensorSharding.replicated(t.ndim)
            values[t.guid] = y
        if counters is not None:
            names = [COUNTER_PREFIX + n for n in opdef.step_counters] + [
                GAUGE_PREFIX + n for n in opdef.step_gauges
            ]
            for n, v in zip(names, outs[len(layer.outputs):]):
                counters.setdefault(n, []).append(v.astype(jnp.float32))
        # stateful ops (BN running stats) — accumulated in float32 even
        # under bf16 compute, like the reference's fp32 cudnn stats
        if training and hasattr(opdef, "state_update") and state.get(layer.name):
            ins32 = [
                x.astype(jnp.float32) if x.dtype == self.compute_dtype else x
                for x in ins
            ] if self._mixed else ins
            new_state[layer.name] = opdef.state_update(layer, lp32, ins32)
        # MoE aux (load-balance) loss — reference lambda_bal in aggregate
        if (
            layer.op_type
            in (OperatorType.AGGREGATE, OperatorType.AGGREGATE_SPEC, OperatorType.EXPERTS)
            and layer.attrs.get("lambda_bal", 0.0) > 0.0
        ):
            from flexflow_tpu.ops.moe import Aggregate

            # inputs[3] is the full softmax gate (t, n) — see Aggregate
            # docstring; inputs[0] of aggregate is only the top-k slice.
            gate_probs = values[layer.inputs[3].guid]
            assign = values[layer.inputs[1].guid]
            n = layer.attrs.get("n", layer.attrs.get("n_experts"))
            aux_losses.append(
                layer.attrs["lambda_bal"]
                * Aggregate.aux_loss(gate_probs, assign, n)
            )

    # --- scan-stacked repeated blocks --------------------------------------
    def _chain_executable(self, chain: BlockChain) -> bool:
        """Can this detected chain run as a single scan?  Declined when a
        member op is stateful (BN running stats / Cache — their per-layer
        state cannot ride the carry), carries an aux loss (MoE
        load-balance terms must sum per layer), or when the strategy
        assigns DIFFERENT shardings to corresponding layers of different
        depths (the scan body is traced once, so per-depth layouts must
        agree — the block-collapsed search guarantees this)."""
        for block in chain.layers:
            for l in block:
                opdef = get_op_def(l.op_type)
                if hasattr(opdef, "state_update"):
                    return False
                if opdef.step_counters or opdef.step_gauges:
                    return False  # the scan body has no way out for them
                if any(not w.trainable for w in self._wspecs[int(l.layer_guid)]):
                    return False
                if (
                    l.op_type in (
                        OperatorType.AGGREGATE,
                        OperatorType.AGGREGATE_SPEC,
                        OperatorType.EXPERTS,
                    )
                    and l.attrs.get("lambda_bal", 0.0) > 0.0
                ):
                    return False
        for j in range(chain.block_len):
            # sharding_key: per-depth pipeline stage tags are NOT a
            # sharding difference (the scan body is stage-agnostic)
            keys = {
                (
                    None
                    if self.strategy.op_sharding(chain.layers[d][j]) is None
                    else self.strategy.op_sharding(
                        chain.layers[d][j]
                    ).sharding_key()
                )
                for d in range(chain.depth)
            }
            if len(keys) != 1:
                return False
        return True

    def _register_chain(self, c: BlockChain) -> None:
        """Adopt one chain into the scan-stacked execution plan: record
        it and route its member weights into depth-stacked buckets."""
        if any(x.start == c.start for x in self._block_chains):
            return
        self._block_chains.append(c)
        for j, tl in enumerate(c.template):
            if not self._wspecs[int(tl.layer_guid)]:
                continue
            members = [c.layers[d][j].name for d in range(c.depth)]
            self._bucket_members[tl.name] = members
            for d, m in enumerate(members):
                self._stacked_slices[m] = (tl.name, d)

    def _setup_pipeline(self, spec) -> Optional[str]:
        """Adopt the strategy's PipelineSpec: find the chain it runs
        over, force that chain into the stacked plan, and record the
        spec.  Returns the decline reason (the run then falls back to
        the non-pipelined step) or None on success.  The legality rules
        mirror ``parallel.pipeline.validate_pipeline`` plus the
        executor-only constraints (stage axis unused by the chain's
        shardings, executable scan body)."""
        from flexflow_tpu.parallel.pipeline import select_pipeline_chain

        mm = self.strategy.mesh
        axis_size = mm.axis_size(spec.stage_axis)
        if axis_size not in (1, spec.stages):
            return (
                f"stage axis {spec.stage_axis!r} extent {axis_size} "
                f"matches neither {spec.stages} (real submeshes) nor 1 "
                f"(virtual stages)"
            )
        batch = self.graph_inputs[0].shape[0] if self.graph_inputs else 0
        if batch <= 0 or batch % spec.microbatches:
            return (
                f"global batch {batch} not divisible into "
                f"{spec.microbatches} microbatches"
            )
        chain = select_pipeline_chain(self.layers, spec.stages)
        if chain is None:
            return (
                f"no repeated-block chain divides into {spec.stages} "
                f"stages"
            )
        if not self._chain_executable(chain):
            return "chain not scan-executable (stateful/aux-loss/non-uniform)"
        # shared operands must be batch-invariant: a (B, ...) operand
        # would have to travel the schedule with its microbatch
        guid_t = {
            t.guid: t
            for block in chain.layers for l in block for t in l.inputs
        }
        for g in chain.shared_guids:
            t = guid_t.get(g)
            if t is not None and t.ndim >= 1 and t.shape[0] == batch:
                return f"chain shared operand {t.name!r} is batch-shaped"
        # the stage axis is consumed by the schedule: the chain's own
        # shardings (and its carry activation) must not also use it
        if axis_size > 1:
            for block in chain.layers:
                for l in block:
                    s = self.strategy.op_sharding(l)
                    if s is None:
                        continue
                    used = set()
                    for ts in list(s.output) + [
                        v for v in s.weights.values()
                    ] + [t for t in s.inputs if t is not None]:
                        used |= set(ts.used_axes())
                        used |= set(ts.partial_axes)
                    if spec.stage_axis in used:
                        return (
                            f"layer {l.name!r} shards over the stage "
                            f"axis {spec.stage_axis!r}"
                        )
        # reuse the already-registered chain object when --stack-blocks
        # detected the same run (segments are keyed by object identity);
        # a DIFFERENT overlapping chain would double-register layers
        existing = next(
            (
                x for x in self._block_chains
                if x.start < chain.end and chain.start < x.end
            ),
            None,
        )
        if existing is not None:
            if (
                existing.start != chain.start
                or existing.block_len != chain.block_len
                or existing.depth != chain.depth
            ):
                return "pipeline chain overlaps a differently-stacked chain"
            chain = existing
        else:
            self._register_chain(chain)
        self.pipeline = spec
        self._pipeline_chain = chain
        return None

    def _trace_block_scan(
        self,
        chain: BlockChain,
        values: Dict[int, jax.Array],
        shardings: Dict[int, TensorSharding],
        params: Dict[str, Dict[str, jax.Array]],
        training: bool,
        rng: Optional[jax.Array],
        seq_length: Optional[int],
    ) -> None:
        """Trace one repeated-block chain as ``jax.lax.scan`` over its
        depth-stacked parameters.  The body traces the TEMPLATE block
        once (via :meth:`_trace_layer`, so remat / mixed precision /
        sharding constraints are applied exactly as on the unrolled
        path); per-depth parameters arrive as scan xs, and per-depth rng
        keys are derived inside the body from the member layer names'
        crc32 values (also scan xs) so dropout streams match the
        unrolled path bit for bit."""
        tmpl = chain.template
        depth, L = chain.depth, chain.block_len
        # member-name crc32 per (depth, position): the unrolled path's
        # per-layer rng fold targets, fed through xs so iteration d
        # reproduces layer d's stream
        crcs = np.asarray(
            [
                [
                    zlib.crc32(chain.layers[d][j].name.encode()) % (2**31)
                    for j in range(L)
                ]
                for d in range(depth)
            ],
            np.uint32,
        )
        xs_params = {
            tl.name: params[tl.name] for tl in tmpl if tl.name in params
        }
        carry0 = values[chain.carry_in_guid]
        out_sh_box: Dict[int, TensorSharding] = {}
        ring_plan = (
            self._grad_ring.get(chain.start) if training else None
        )
        grad_sync = None
        if ring_plan:
            n = self.strategy.mesh.axis_size("data")
            grad_sync = self._make_chain_grad_sync(ring_plan, n)
        body = self._chain_scan_body(
            chain, values, shardings, training, rng, seq_length, out_sh_box,
            grad_sync=grad_sync,
        )

        with get_tracer().span(
            "block_scan", cat="step", level="op", depth=depth, layers=L,
        ):
            if grad_sync is not None:
                # ring traffic per bucket: full stacked bytes, (n-1) hops
                # per leaf; exposed_ms from the compile-time overlap
                # pricing when one was attached (observability only)
                from flexflow_tpu.ops.base import _dtype_bytes

                ring_bytes = depth * sum(
                    int(np.prod(w.shape)) * _dtype_bytes(w.dtype)
                    for tl in tmpl
                    for w in self._wspecs[int(tl.layer_guid)]
                    if w.name in ring_plan.get(tl.name, {})
                )
                price = getattr(self.strategy, "grad_overlap_price", None)
                span_kw = dict(
                    depth=depth, hops=n - 1, bytes=int(ring_bytes),
                )
                if price and price.get("exposed_s") is not None:
                    span_kw["exposed_ms"] = float(price["exposed_s"]) * 1e3
                with get_tracer().span(
                    "grad_ring", cat="step", level="op", **span_kw
                ):
                    carry, _ = jax.lax.scan(body, carry0, (crcs, xs_params))
            else:
                carry, _ = jax.lax.scan(body, carry0, (crcs, xs_params))
        values[chain.out_guid] = carry
        out_t = chain.layers[-1][-1].outputs[0]
        shardings[chain.out_guid] = out_sh_box.get(
            chain.template_out_guid, TensorSharding.replicated(out_t.ndim)
        )

    def _chain_scan_body(
        self,
        chain: BlockChain,
        values: Dict[int, jax.Array],
        shardings: Dict[int, TensorSharding],
        training: bool,
        rng: Optional[jax.Array],
        seq_length: Optional[int],
        out_sh_box: Dict[int, TensorSharding],
        grad_sync=None,
    ):
        """The ONE-block scan body shared by ``_trace_block_scan`` and the
        pipelined ``_trace_pipeline_scan``: trace the TEMPLATE block over
        ``(carry, (crc_row, per-depth params))``, with shared operands
        closure-captured from ``values`` and per-depth dropout keys
        derived from the member-name crc32 xs (bit-parity with the
        unrolled per-layer ``fold_in``).

        ``grad_sync`` (an identity with a ring-sync VJP from
        ``_make_chain_grad_sync``) wraps each depth slice's params so the
        weight-grad sync runs INSIDE the backward scan body
        (--grad-overlap); ``None`` — always the case on the pipeline
        path — leaves the body byte-identical to today's."""
        tmpl = chain.template

        def body(carry, x):
            crc_row, p_d = x
            if grad_sync is not None:
                p_d = grad_sync(p_d)
            vals: Dict[int, jax.Array] = {chain.carry_in_guid: carry}
            shs: Dict[int, TensorSharding] = {}
            if chain.carry_in_guid in shardings:
                shs[chain.carry_in_guid] = shardings[chain.carry_in_guid]
            for g in chain.shared_guids:
                vals[g] = values[g]  # closure capture: scan-invariant
                if g in shardings:
                    shs[g] = shardings[g]
            for j, tl in enumerate(tmpl):
                self._trace_layer(
                    tl, vals, shs, p_d, {}, training, None, seq_length,
                    {}, [],
                    rng_key=(
                        jax.random.fold_in(rng, crc_row[j])
                        if rng is not None
                        else None
                    ),
                )
            out_sh_box.update(shs)
            return vals[chain.template_out_guid], None

        return body

    # --- overlapped gradient sync (--grad-overlap, docs/PERF.md) -----------
    def _setup_grad_ring(self, mode: str) -> None:
        """Build the per-chain ring plans: which stacked buckets' weight
        grads leave the fused tail sync and ring inside the backward scan
        body instead.  Eligibility mirrors the search side
        (``search/cost.py grad_ring_chain_layers``): scan-stacked chains
        whose grads are partial over the data axis, on a data axis of
        extent > 1, with no pipeline; per weight, the ring needs an
        unsharded dim divisible by the data extent to chunk over."""
        from flexflow_tpu.search.cost import (
            default_op_sharding, node_grad_sync_rows,
        )

        mm = self.strategy.mesh
        n = mm.axis_size("data")

        def decline(reason: str) -> None:
            if mode == "ring" and jax.process_index() == 0:
                print(f"[grad-overlap] declined at executor: {reason}")

        if n <= 1:
            decline("data axis extent 1")
            return
        if self.pipeline is not None:
            decline(
                "pipelined chain "
                f'(stage_axis=="{self.pipeline.stage_axis}")'
            )
            return
        members: set = set()
        for c in self._block_chains:
            plan: Dict[str, Dict[str, Tuple[int, Tuple]]] = {}
            for tl in c.template:
                os_ = self.strategy.op_sharding(tl) or default_op_sharding(tl)
                synced = {
                    wn for wn, _b, _n, _a in node_grad_sync_rows(tl, os_, mm)
                }
                if not synced:
                    continue
                lplan: Dict[str, Tuple[int, Tuple]] = {}
                for w in self._wspecs[int(tl.layer_guid)]:
                    if not w.trainable or w.name not in synced:
                        continue
                    ps = tuple(
                        self.strategy.weight_pspec(tl, w.name, len(w.shape))
                    )
                    base = list(ps) + [None] * (len(w.shape) - len(ps))
                    for d in range(len(w.shape)):
                        if base[d] is None and w.shape[d] % n == 0:
                            lplan[w.name] = (d, tuple(base))
                            break
                if lplan:
                    plan[tl.name] = lplan
            if plan:
                self._grad_ring[c.start] = plan
                for blk in c.layers:
                    for l in blk:
                        members.add(l.name)
        if not self._grad_ring:
            decline(
                "no eligible scan-stacked chain (non-chain weights keep "
                "the fused path)"
            )
        self._grad_ring_layers = frozenset(members)

    def _ring_all_gather(self, g, scat_spec, base_spec, dim: int, n: int):
        """Explicit ring all-gather of ``g`` (sharded ``scat_spec``, with
        the data axis chunking ``dim``) back to ``base_spec`` via (n−1)
        ``ppermute`` hops inside ``shard_map`` — the PR-8 handoff idiom
        (``_trace_pipeline_scan._shift``): each hop forwards the chunk
        around the data ring while the receiving device writes it into
        place, so XLA can schedule hop h beside the surrounding backward
        compute instead of fusing one monolithic tail collective."""
        def local(gl):
            shard = gl.shape[dim]
            idx = jax.lax.axis_index("data")
            full = jnp.zeros(
                gl.shape[:dim] + (shard * n,) + gl.shape[dim + 1:], gl.dtype
            )
            full = jax.lax.dynamic_update_slice_in_dim(
                full, gl, idx * shard, dim
            )
            cur = gl
            perm = [(i, (i + 1) % n) for i in range(n)]
            for h in range(1, n):
                cur = jax.lax.ppermute(cur, "data", perm)
                full = jax.lax.dynamic_update_slice_in_dim(
                    full, cur, ((idx - h) % n) * shard, dim
                )
            return full

        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(PartitionSpec(*scat_spec),),
            out_specs=PartitionSpec(*base_spec),
            check_vma=False,
        )(g)

    def _make_chain_grad_sync(self, plan, n: int):
        """The identity "grad-sync point" wrapped around each depth
        slice's params inside the backward scan body: forward is the
        identity; the custom VJP replaces GSPMD's deferred fused tail
        all-reduce with, per planned leaf, (a) a sharding constraint that
        scatters the cotangent over the data axis — forcing the pending
        partial-sum resolution to materialize HERE, inside the scan
        body, as a reduce-scatter — and (b) the explicit ppermute ring
        all-gather back to the weight's own layout.  Net effect: a ring
        all-reduce decomposition of the exact same reduction, placed
        where block i−1's backward compute can hide it.  Unplanned
        leaves pass through untouched (fused path)."""

        def ring_leaf(g, dim, base_spec):
            scat = list(base_spec)
            scat[dim] = "data"
            g = self._constrain(g, PartitionSpec(*scat))
            return self._ring_all_gather(g, tuple(scat), base_spec, dim, n)

        @jax.custom_vjp
        def sync(tree):
            return tree

        def fwd(tree):
            return tree, None

        def bwd(_, ct):
            out = {}
            for lname, leaves in ct.items():
                lplan = plan.get(lname, {})
                out[lname] = {
                    wn: (
                        ring_leaf(g, *lplan[wn]) if wn in lplan else g
                    )
                    for wn, g in leaves.items()
                }
            return (out,)

        sync.defvjp(fwd, bwd)
        return sync

    def _zero1_ring_gather(self, new_params):
        """ZeRO-1 param unshard, ring-pipelined against the optimizer
        update (--grad-overlap): scatter-constrain each ring bucket's
        updated stack over the data axis — GSPMD then computes that
        bucket's update on 1/n of the weights, free to overlap with the
        other buckets' updates — and reassemble with the explicit
        ppermute ring instead of one fused tail all-gather.  Math
        identity; non-ring buckets keep GSPMD's fused delta gather."""
        n = self.strategy.mesh.axis_size("data")
        out = dict(new_params)
        for plan in self._grad_ring.values():
            for lname, lplan in plan.items():
                ws = out.get(lname)
                if not ws:
                    continue
                ws = dict(ws)
                for wn, (dim, base) in lplan.items():
                    if wn not in ws:
                        continue
                    # stacked storage carries a leading depth dim
                    sbase = (None,) + tuple(base)
                    scat = list(sbase)
                    scat[dim + 1] = "data"
                    g = self._constrain(ws[wn], PartitionSpec(*scat))
                    ws[wn] = self._ring_all_gather(
                        g, tuple(scat), sbase, dim + 1, n
                    )
                out[lname] = ws
        return out

    def _trace_pipeline_scan(
        self,
        chain: BlockChain,
        values: Dict[int, jax.Array],
        shardings: Dict[int, TensorSharding],
        params: Dict[str, Dict[str, jax.Array]],
        training: bool,
        rng: Optional[jax.Array],
        seq_length: Optional[int],
    ) -> None:
        """Trace the pipelined chain as the microbatched 1F1B schedule
        (docs/PIPELINE.md).  The realization is GSPMD-native: one
        ``lax.scan`` over the ``M + S - 1`` schedule ticks whose carry is
        the per-stage activation buffer ``(S, b, ...)`` with dim 0
        sharded over the stage axis.  Each tick

          1. hands activations off — ``concat(mb_t, buf[:-1])`` shifts
             every stage's output to its successor, which XLA lowers to
             a collective-permute across the stage submeshes (the
             microbatch-sized point-to-point transfer the cost model's
             ``_stage_handoff_time`` prices); the new microbatch enters
             at stage 0;
          2. computes ALL stages at once — a ``vmap`` over the stage dim
             applies stage ``s``'s ``depth/S`` blocks (an inner scan
             over the per-stage slice of the depth-stacked params) to
             its current microbatch; because buffer and params are both
             stage-sharded on dim 0, every submesh computes only its own
             stage (SPMD realizes MPMD, the praxis pipelining idiom);
          3. emits the last stage's output — valid from tick ``S - 1``.

        Microbatch ``m``'s logits surface at tick ``m + S - 1``; the
        discarded warmup/drain outputs are the ``(S-1)/(M+S-1)`` bubble.
        Reverse-mode autodiff runs the scan backward, so gradients
        accumulate on device across microbatches — no host syncs are
        added anywhere.  Warmup/drain lanes carry zeros whose outputs
        (and therefore cotangents) are discarded.

        Virtual stages (stage axis extent 1, e.g. single device) run the
        exact same program without the collective — the schedule is then
        a pure microbatching transform, which is what the parity tests
        pin against the non-pipelined step."""
        spec = self.pipeline
        S, M = spec.stages, spec.microbatches
        depth, L = chain.depth, chain.block_len
        per = depth // S
        real = self.strategy.mesh.axis_size(spec.stage_axis) == S
        stage_ps = spec.stage_axis if real else None

        carry0 = values[chain.carry_in_guid]
        B = carry0.shape[0]
        b = B // M
        carry_sh = shardings.get(
            chain.carry_in_guid, TensorSharding.replicated(carry0.ndim)
        )
        buf_spec = PartitionSpec(stage_ps, *carry_sh.spec)

        out_sh_box: Dict[int, TensorSharding] = {}
        body = self._chain_scan_body(
            chain, values, shardings, training, rng, seq_length, out_sh_box
        )

        # per-(depth, position) member-name crc32 rows (the unrolled
        # path's dropout-key fold targets), regrouped per stage
        crcs = np.asarray(
            [
                [
                    zlib.crc32(chain.layers[d][j].name.encode()) % (2**31)
                    for j in range(L)
                ]
                for d in range(depth)
            ],
            np.uint32,
        ).reshape(S, per, L)
        # depth-stacked params regrouped (depth, ...) -> (S, per, ...):
        # dim 0 was stage-sharded by _stack_param_buckets, so the reshape
        # is layout-local (each submesh keeps its own depth slice)
        xs_params = {
            tl.name: params[tl.name]
            for tl in chain.template
            if tl.name in params
        }
        stage_params = jax.tree.map(
            lambda a: a.reshape((S, per) + tuple(a.shape[1:])), xs_params
        )

        def stage_fn(p_stage, crc_stage, x):
            y, _ = jax.lax.scan(body, x, (crc_stage, p_stage))
            return y

        vstages = jax.vmap(stage_fn, in_axes=(0, 0, 0))

        # microbatch stream padded with S-1 drain ticks
        mbs = carry0.reshape((M, b) + tuple(carry0.shape[1:]))
        pad = jnp.zeros((S - 1, b) + tuple(carry0.shape[1:]), carry0.dtype)
        xs_mb = jnp.concatenate([mbs, pad], axis=0)
        buf0 = self._constrain(
            jnp.zeros((S, b) + tuple(carry0.shape[1:]), carry0.dtype),
            buf_spec,
        )

        if real and self.mesh is not None:
            # activation handoff between REAL stage submeshes: an
            # explicit ppermute inside shard_map over the stage axis —
            # stage s's buffer moves to stage s+1, the fresh microbatch
            # enters at stage 0.  Explicit because it is the semantic
            # (ISSUE 8 / ROADMAP #2: "collective permutes between stage
            # meshes") and because GSPMD's lowering of the equivalent
            # concat(mb[None], buf[:-1]) shift produces WRONG VALUES on
            # the CPU backend when the mesh carries further axes
            # (verified miscompile; the ppermute path is exact).
            mesh_ = self.mesh
            axis_ = spec.stage_axis
            mb_spec = PartitionSpec(*carry_sh.spec)

            def _shift(buf, mb_t):
                def local(bl, ml):
                    moved = jax.lax.ppermute(
                        bl, axis_, [(i, i + 1) for i in range(S - 1)]
                    )
                    idx = jax.lax.axis_index(axis_)
                    return jnp.where(idx == 0, ml[None], moved)

                return jax.shard_map(
                    local, mesh=mesh_,
                    in_specs=(buf_spec, mb_spec), out_specs=buf_spec,
                    check_vma=False,
                )(buf, mb_t)
        else:
            def _shift(buf, mb_t):
                return self._constrain(
                    jnp.concatenate([mb_t[None], buf[:-1]], axis=0),
                    buf_spec,
                )

        def tick(buf, mb):
            # stage s's input <- stage s-1's output; microbatch enters
            # at stage 0 (the 1F1B handoff)
            shifted = _shift(buf, mb)
            out = self._constrain(vstages(stage_params, crcs, shifted), buf_spec)
            return out, out[-1]

        with get_tracer().span(
            "pipeline_scan", cat="step", level="op",
            stages=S, microbatches=M, depth=depth, layers=L,
        ):
            _, ys = jax.lax.scan(tick, buf0, xs_mb)
        # microbatch m's output surfaces at tick m + S - 1; reassemble
        # the global batch in row order
        out = ys[S - 1:].reshape((B,) + tuple(ys.shape[2:]))
        out_t = chain.layers[-1][-1].outputs[0]
        out_sh = out_sh_box.get(
            chain.template_out_guid, TensorSharding.replicated(out_t.ndim)
        )
        values[chain.out_guid] = self._constrain(out, out_sh.partition_spec())
        shardings[chain.out_guid] = out_sh

    # --- param init --------------------------------------------------------
    def init_params(self, key: Optional[jax.Array] = None) -> None:
        """Sharded on-device init (replaces per-weight init tasks,
        ``include/flexflow/initializer.h``; weights are born with their
        final sharding — no host staging)."""
        if key is None:
            key = jax.random.PRNGKey(self.seed)

        def make_init(layer, w):
            pspec = self.strategy.weight_pspec(layer, w.name, len(w.shape))
            dtype = w.dtype.to_jnp()
            if (
                dtype == jnp.float32
                and w.name not in get_op_def(layer.op_type).fp32_weights
            ):
                dtype = self.param_dtype

            def init_fn(k):
                return w.initializer(k, w.shape, dtype)

            if self.mesh is not None:
                return jax.jit(
                    init_fn, out_shardings=NamedSharding(self.mesh, pspec)
                )
            return jax.jit(init_fn)

        params: Dict[str, Dict[str, jax.Array]] = {}
        state: Dict[str, Dict[str, jax.Array]] = {}
        i = 0
        for layer in self.layers:
            for w in self._wspecs[int(layer.layer_guid)]:
                sub = jax.random.fold_in(key, i)
                i += 1
                arr = make_init(layer, w)(sub)
                bucket = params if w.trainable else state
                bucket.setdefault(layer.name, {})[w.name] = arr
        self.params = params
        self.state = state
        # stacked init: each member layer drew its weights with exactly
        # the per-layer keys above (bit-parity with the unrolled path);
        # chains then collapse into ONE (depth, ...) array per template
        # weight, sharded (None, *per-layer spec) on the mesh
        self._stack_param_buckets()
        self.opt_state = self.optimizer.init_state(self.params)
        if self.zero1:
            self._zero1_axes = self._zero1_token_axes()
            self._zero1_specs = jax.tree.map(self._zero1_pspec, self.opt_state)
            self.opt_state = jax.tree.map(
                self._zero1_place, self.opt_state, self._zero1_specs
            )

    def _stack_param_buckets(self) -> None:
        """Collapse per-member param buckets into (depth, ...) stacked
        arrays keyed by the template layer name (no-op without chains)."""
        for c in self._block_chains:
            # pipelined chain: the depth dim is ALSO the stage dim —
            # stage s's params live on stage submesh s, so dim 0 of the
            # (depth, ...) stack shards over the stage axis (depth is a
            # multiple of S by stage-partition legality)
            stage_axis = None
            if (
                c is self._pipeline_chain
                and self.pipeline is not None
                and self.strategy.mesh.axis_size(self.pipeline.stage_axis)
                == self.pipeline.stages
            ):
                stage_axis = self.pipeline.stage_axis
            for j, tl in enumerate(c.template):
                ws = self._wspecs[int(tl.layer_guid)]
                if not ws:
                    continue
                members = self._bucket_members[tl.name]
                stacked: Dict[str, jax.Array] = {}
                for w in ws:
                    arrs = [self.params[m][w.name] for m in members]
                    s = jnp.stack(arrs)
                    if self.mesh is not None:
                        ps = self.strategy.weight_pspec(
                            tl, w.name, len(w.shape)
                        )
                        s = jax.device_put(
                            s,
                            NamedSharding(
                                self.mesh,
                                PartitionSpec(stage_axis, *tuple(ps)),
                            ),
                        )
                    stacked[w.name] = s
                for m in members:
                    self.params.pop(m, None)
                self.params[tl.name] = stacked

    # --- per-layer weight view over stacked storage ------------------------
    def unstack_tree(
        self, tree: Dict[str, Dict[str, Any]]
    ) -> Dict[str, Dict[str, Any]]:
        """Per-layer view of a ``{bucket: {weight: array}}`` tree: stacked
        buckets expand to one entry per member layer (depth slices);
        plain buckets pass through.  Checkpoints and ``get_weights``
        always present THIS layout, so artifacts written by stacked and
        unrolled executors are interchangeable."""
        out: Dict[str, Dict[str, Any]] = {}
        for lname, ws in tree.items():
            members = self._bucket_members.get(lname)
            if members is None:
                out[lname] = dict(ws)
            else:
                for d, m in enumerate(members):
                    out[m] = {wn: arr[d] for wn, arr in ws.items()}
        return out

    def locate_weight(
        self, lname: str, wname: str
    ) -> Optional[Tuple[Dict, str, Optional[int]]]:
        """(store, bucket name, depth index) for a PER-LAYER weight name;
        depth index is None for unstacked weights, and the store is
        ``self.params`` or ``self.state``.  None when unknown."""
        route = self._stacked_slices.get(lname)
        if route is not None:
            bname, d = route
            if bname in self.params and wname in self.params[bname]:
                return self.params, bname, d
            return None
        for store in (self.params, self.state):
            if lname in store and wname in store[lname]:
                return store, lname, None
        return None

    def weight_global_shape(
        self, lname: str, wname: str
    ) -> Optional[Tuple[int, ...]]:
        """Per-layer logical shape of one weight (stacked buckets report
        the slice shape, not the (depth, ...) storage shape)."""
        loc = self.locate_weight(lname, wname)
        if loc is None:
            return None
        store, bname, d = loc
        shp = store[bname][wname].shape
        return tuple(int(s) for s in (shp[1:] if d is not None else shp))

    def assign_weight_entries(
        self,
        entries: Dict[str, Dict[str, np.ndarray]],
        strict: bool = True,
        shape_skip: bool = False,
    ) -> None:
        """Write per-layer ``{layer: {weight: array}}`` entries into the
        stores, routing members of stacked chains into depth slices.  A
        bucket whose every slice arrives is written with ONE device_put;
        partial updates read-modify-write the stacked array.  ``strict``
        errors on unknown names; ``shape_skip`` silently skips
        shape-mismatched entries (the recompile weight-carry
        semantics)."""
        pending: Dict[Tuple[int, str, str], Dict[int, np.ndarray]] = {}
        stores: Dict[int, Dict] = {}
        for lname, ws in entries.items():
            for wname, arr in ws.items():
                loc = self.locate_weight(lname, wname)
                if loc is None:
                    if strict:
                        raise KeyError(f"unknown weight {lname}/{wname}")
                    continue
                store, bname, d = loc
                cur = store[bname][wname]
                a = np.asarray(arr)
                if d is None or a.shape == tuple(cur.shape):
                    if a.shape != tuple(cur.shape):
                        if shape_skip:
                            continue
                        raise ValueError(
                            f"weight {lname}/{wname}: got shape {a.shape}, "
                            f"expected {tuple(cur.shape)}"
                        )
                    store[bname][wname] = jax.device_put(
                        np.asarray(a, cur.dtype), cur.sharding
                    )
                    continue
                if a.shape != tuple(cur.shape[1:]):
                    if shape_skip:
                        continue
                    raise ValueError(
                        f"weight {lname}/{wname}: got shape {a.shape}, "
                        f"expected {tuple(cur.shape[1:])} (slice of stacked "
                        f"{tuple(cur.shape)})"
                    )
                key = (id(store), bname, wname)
                stores[id(store)] = store
                pending.setdefault(key, {})[d] = np.asarray(a, cur.dtype)
        for (sid, bname, wname), slices in pending.items():
            store = stores[sid]
            cur = store[bname][wname]
            depth = int(cur.shape[0])
            if len(slices) == depth:
                full = np.stack([slices[d] for d in range(depth)])
            else:
                full = np.array(np.asarray(cur))
                for d, a in slices.items():
                    full[d] = a
            store[bname][wname] = jax.device_put(
                full.astype(cur.dtype), cur.sharding
            )

    def assign_opt_entries(
        self,
        okey: str,
        entries: Dict[str, Dict[str, np.ndarray]],
        shape_skip: bool = False,
    ) -> None:
        """Per-layer restore into ``opt_state[okey]`` (moments mirror the
        param tree, so stacked buckets route identically)."""
        tree = self.opt_state.get(okey)
        if not isinstance(tree, dict):
            raise KeyError(f"no optimizer slot {okey!r}")
        pending: Dict[Tuple[str, str], Dict[int, np.ndarray]] = {}
        for lname, ws in entries.items():
            for wname, arr in ws.items():
                route = self._stacked_slices.get(lname)
                bname, d = route if route is not None else (lname, None)
                cur = tree.get(bname, {}).get(wname)
                if cur is None:
                    if shape_skip:
                        continue
                    raise KeyError(f"unknown opt entry {okey}/{lname}/{wname}")
                a = np.asarray(arr)
                if d is None or a.shape == tuple(cur.shape):
                    if a.shape != tuple(cur.shape):
                        if shape_skip:
                            continue
                        raise ValueError(
                            f"opt {okey}/{lname}/{wname}: shape {a.shape} "
                            f"!= {tuple(cur.shape)}"
                        )
                    tree[bname][wname] = jax.device_put(
                        np.asarray(a, cur.dtype), cur.sharding
                    )
                    continue
                if a.shape != tuple(cur.shape[1:]):
                    if shape_skip:
                        continue
                    raise ValueError(
                        f"opt {okey}/{lname}/{wname}: shape {a.shape} != "
                        f"slice {tuple(cur.shape[1:])}"
                    )
                pending.setdefault((bname, wname), {})[d] = np.asarray(
                    a, cur.dtype
                )
        for (bname, wname), slices in pending.items():
            cur = tree[bname][wname]
            depth = int(cur.shape[0])
            if len(slices) == depth:
                full = np.stack([slices[d] for d in range(depth)])
            else:
                full = np.array(np.asarray(cur))
                for d, a in slices.items():
                    full[d] = a
            tree[bname][wname] = jax.device_put(
                full.astype(cur.dtype), cur.sharding
            )

    # --- ZeRO-1 helpers ----------------------------------------------------
    def _zero1_pspec(self, x) -> Optional[PartitionSpec]:
        """Merged sharding spec for one moment leaf: keep whatever sharding
        it inherited from its param (e.g. a TP 'model' axis — discarding it
        would INCREASE memory) and add the token-sharded mesh axes to the
        first unsharded dim that divides them.  Computed once at init from
        concrete arrays; reused as a constraint inside the jitted step
        (tracers carry no sharding).

        Both 'data' and 'expert' split the token batch, so gradients of
        params not already sharded on them are full sums replicated across
        both — ZeRO-1's "shard over every data-parallel replica" means the
        combined dp*ep degree.  Sharding over the combined axes (one dim,
        one tuple) also keeps the weight-grad reshard expressible as an
        all-to-all: with 'data' alone on a dp*ep mesh the grad of a dense
        fed by an (('data','expert'),None)-sharded activation needs an
        8-way-dim0 -> 4-way-dim1 transition, which GSPMD can only do by
        full rematerialization (observed in MULTICHIP_r03: "Involuntary
        full rematerialization" on the moe+zero1 phase)."""
        mm = self.strategy.mesh
        if not hasattr(x, "ndim") or x.ndim < 1:
            return None
        cur = getattr(x, "sharding", None)
        spec: List = (
            list(cur.spec) if isinstance(cur, NamedSharding) else []
        )
        spec += [None] * (x.ndim - len(spec))
        used = {
            a
            for e in spec
            if e
            for a in ((e,) if isinstance(e, str) else tuple(e))
        }
        axes = tuple(a for a in self._zero1_axes if a not in used)
        if not axes:
            return None
        deg = 1
        for a in axes:
            deg *= mm.axis_size(a)
        for i in range(x.ndim):
            if spec[i] is None and x.shape[i] % deg == 0:
                spec[i] = axes if len(axes) > 1 else axes[0]
                return PartitionSpec(*spec)
        # no single dim fits the combined degree — place axes greedily on
        # separate free dims, largest degree first (keeps the biggest
        # memory win; any sharding of a replicated moment is valid)
        placed = False
        for a in sorted(axes, key=mm.axis_size, reverse=True):
            for i in range(x.ndim):
                if spec[i] is None and x.shape[i] % mm.axis_size(a) == 0:
                    spec[i] = a
                    placed = True
                    break
        return PartitionSpec(*spec) if placed else None

    def _zero1_token_axes(self) -> Tuple[str, ...]:
        """Mesh axes that split the token batch: 'data' plus every EP axis
        any strategy entry declares (the strategy layer parameterizes the
        axis name via ``expert_parallel_strategy(..., ep_axis=...)``, so it
        must not be hardcoded here).  Gradients of params unsharded on
        these axes are full sums replicated across them, so ZeRO-1 may
        shard moments over their combined degree."""
        axes = ["data"]
        for s in self.strategy.ops.values():
            a = (getattr(s, "extras", None) or {}).get("ep_axis")
            if a and a not in axes:
                axes.append(a)
        return tuple(a for a in axes if self.strategy.mesh.axis_size(a) > 1)

    def _zero1_place(self, x, ps):
        if ps is None or self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, ps))

    def _zero1_constrain(self, x, ps):
        if ps is None:
            return x
        return self._constrain(x, ps)

    # --- step building -----------------------------------------------------
    def _build_step(self):
        metrics = self.metrics
        loss_fn = self.loss_fn

        # per-step rng derived INSIDE the program from the optimizer step
        # counter when one exists — the eager PRNGKey+fold_in pair used to
        # cost two host->device dispatches per step.  Custom optimizers
        # without a "step" entry fall back to a host-passed counter so
        # the rng stream still advances.
        opt_has_step = isinstance(self.opt_state, dict) and "step" in self.opt_state
        self._opt_has_step = opt_has_step
        # run-health diagnostics: global grad/param L2 norms computed
        # INSIDE the step program (two scalar outputs fused into the
        # existing metrics fetch — near-zero marginal device cost, zero
        # cost when the monitor is off).  Captured at build time; the
        # LR-scheduler's `_step_jit = None` retrace picks up changes.
        diagnostics = get_monitor().wants_diagnostics

        def global_norm(tree):
            sq = sum(
                jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                for leaf in jax.tree.leaves(tree)
                if hasattr(leaf, "dtype")
                and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
            )
            return jnp.sqrt(sq)

        def step(params, state, opt_state, inputs, labels, host_step):
            cnt = opt_state["step"] if opt_has_step else host_step
            rng = jax.random.fold_in(jax.random.PRNGKey(self.seed), cnt)

            def objective(p):
                logits, new_state, aux, counted = self._forward(
                    p, state, inputs, True, rng
                )
                loss = loss_fn(logits, labels)
                for a in aux:
                    loss = loss + a
                return loss, (logits, new_state, counted)

            (loss, (logits, new_state, counted)), grads = jax.value_and_grad(
                objective, has_aux=True
            )(params)
            new_params, new_opt = self.optimizer.update(params, grads, opt_state)
            if self.zero1:
                # keep moments sharded in steady state; GSPMD then updates
                # each device's shard and all-gathers only the param delta
                new_opt = jax.tree.map(
                    self._zero1_constrain, new_opt, self._zero1_specs
                )
                if self._grad_ring:
                    # --grad-overlap: ring the ZeRO-1 param unshard of the
                    # ring buckets per bucket, pipelined against the other
                    # buckets' optimizer updates (math identity)
                    new_params = self._zero1_ring_gather(new_params)
            m = metrics.compute(logits, labels) if metrics else {}
            if counted:
                m = {**m, **counted}
            if diagnostics:
                m = dict(m)
                m["grad_norm"] = global_norm(grads)
                m["param_norm"] = global_norm(new_params)
            return new_params, new_state, new_opt, loss, m

        donate = (0, 1, 2)
        return jax.jit(step, donate_argnums=donate)

    def _build_fwd(self):
        def fwd(params, state, inputs, seq_length):
            logits, *_ = self._forward(
                params, state, inputs, False, None, seq_length
            )
            return logits

        # static seq_length: each distinct value is its own trace, matching
        # the reference's per-seq_length forward (model.cc:2415-2420)
        return jax.jit(fwd, static_argnums=(3,))

    # --- public API --------------------------------------------------------
    def count_host_sync(self, n: int = 1, stall_s: float = 0.0) -> None:
        """Record ``n`` deliberate host syncs (forced device round-trips
        issued by a training/eval loop) and the wall time the host spent
        blocked in them.  Mirrors into the ``executor.host_syncs`` tracer
        counter when tracing is on, so the trace summary shows the sync
        cadence (docs/OBSERVABILITY.md, "Sync points")."""
        self.host_syncs += n
        self.host_stall_s += stall_s
        tracer = get_tracer()
        if tracer.enabled:
            tracer.counter("executor.host_syncs", float(n))

    def place_batch(self, batch: Sequence[Any]) -> Tuple[List[Any], Any]:
        """Stage one ``(x0..xk, y)`` batch onto devices: the placement leg
        of the input pipeline, shared by ``fit``/``eval`` through
        :class:`flexflow_tpu.dataloader.DevicePrefetcher` so H2D transfer
        of batch i+1 dispatches while step i runs.  ``train_step`` /
        ``forward`` re-run ``_place`` on the results, which short-circuits
        already-committed arrays."""
        *bx, by = batch
        inputs = [
            self._place(x, self._input_pspec(t), t.shape[0])
            for x, t in zip(bx, self.graph_inputs)
        ]
        labels = self._place(
            by, self._label_pspec(), self.graph_inputs[0].shape[0]
        )
        return inputs, labels

    def _maybe_verify_compiled(self, args) -> None:
        """--verify-compiled hook: run the ffcheck registry over the
        compiled step program ONCE per compile (docs/ANALYSIS.md).  Warn
        mode records the violation count (``analysis.violations`` tracer
        counter, ``last_analysis`` report, the ``analysis_violations``
        ffmetrics field); strict mode raises AnalysisError before the
        first step executes on device."""
        if self.verify_compiled == "off" or self._verified_step:
            return
        self._verified_step = True
        from flexflow_tpu.analysis import (
            AnalysisError,
            AnalysisReport,
            analyze_program,
            artifact_from_executor_step,
        )

        if self._step_compiled is None:
            # fast path never AOT-compiles on its own: do it here and
            # keep the executable (the step reuses it — no double
            # compile, and the analysis sees exactly what will run)
            self._step_compiled = self._step_jit.lower(*args).compile()
        compiled = (
            None if self._step_compiled is self._step_jit
            else self._step_compiled
        )
        art = artifact_from_executor_step(self, args, compiled)
        report = AnalysisReport()
        report.add_program(art.name)
        report.extend(analyze_program(art))
        self.last_analysis = report
        self.analysis_violations = len(report.violations)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.counter(
                "analysis.violations", float(self.analysis_violations)
            )
        if not report.ok:
            if self.verify_compiled == "strict":
                raise AnalysisError(report)
            print(report.format_human())

    def _run_step(self, fn, args, tracer):
        """Call the step program.  An AOT executable pins the input
        shardings it was compiled with, while GSPMD is free to return
        the updated params under different ones; that one mismatch —
        jax raises it as a ValueError BEFORE running or donating
        anything — swaps in the jit wrapper (which recompiles for the
        new shardings and stays the step from here on) and is counted.
        Anything else, a device OOM included, propagates."""
        try:
            return fn(*args)
        except ValueError as e:
            if fn is self._step_jit or "input shardings" not in str(e):
                raise
        self._step_compiled = self._step_jit
        self.aot_sharding_drifts += 1
        tracer.counter("jit.aot_sharding_drift")
        tracer.counter("jit.cache_miss")
        return self._step_jit(*args)

    def train_step(self, inputs: Sequence[Any], labels: Any) -> Tuple[float, Dict[str, float]]:
        # fault-injection hook (--fault-plan, docs/RESILIENCE.md): one
        # call + None check when no plan is installed — the same cost
        # class as the get_monitor() probe below, ledger-pinned
        plan = get_fault_plan()
        if plan is not None:
            plan.on_train_step(self)
        tracer = get_tracer()
        if not (tracer.enabled or self.profiling or get_monitor().enabled):
            if self._step_jit is None:
                # the first build, lowering and compile (or cache load)
                # of the step program happen in this call
                with tracer.span("step_program", cat="setup"):
                    return self._train_step_fast(tracer, inputs, labels)
            return self._train_step_fast(tracer, inputs, labels)
        return self._train_step_instrumented(tracer, inputs, labels)

    def _train_step_fast(
        self, tracer, inputs: Sequence[Any], labels: Any
    ) -> Tuple[float, Dict[str, float]]:
        """No clock reads, no forced device sync (async dispatch stays
        pipelined).  An AOT executable left by an earlier instrumented
        step (e.g. bench.py's compile-capture step) is reused so the
        program never compiles twice."""
        if self._step_jit is None:
            self._step_jit = self._build_step()
            self._step_compiled = None
            self._verified_step = False
        inputs = [
            self._place(x, self._input_pspec(t), t.shape[0])
            for x, t in zip(inputs, self.graph_inputs)
        ]
        labels = self._place(labels, self._label_pspec(), self.graph_inputs[0].shape[0])
        fn = self._step_compiled or self._step_jit
        args = (
            self.params, self.state, self.opt_state, inputs, labels,
            self._step_count,
        )
        if self.verify_compiled != "off":
            self._maybe_verify_compiled(args)
            fn = self._step_compiled or fn
        out = self._run_step(fn, args, tracer)
        self.params, self.state, self.opt_state, loss, m = out
        self._step_count += 1
        return loss, m

    def _train_step_instrumented(
        self, tracer, inputs: Sequence[Any], labels: Any
    ) -> Tuple[float, Dict[str, float]]:
        """Timed step (tracing or --profiling): host placement+dispatch
        vs device wall split, jit-compile events with cache hit/miss, and
        a device-memory snapshot from the compiled program's
        ``memory_analysis()``.  Opt-in because the block_until_ready it
        inserts serializes the async dispatch the fast path relies on.
        The first call compiles AOT (``jit.lower().compile()``) so
        compile time is attributed to its own span instead of hiding
        inside step 0's device time."""
        t_begin = time.perf_counter()
        step_no = self._step_count
        with tracer.span("train_step", cat="step", step=step_no):
            if self._step_jit is None:
                self._step_compiled = None
            with tracer.span("h2d_place", cat="step", level="op"):
                inputs = [
                    self._place(x, self._input_pspec(t), t.shape[0])
                    for x, t in zip(inputs, self.graph_inputs)
                ]
                labels = self._place(
                    labels, self._label_pspec(), self.graph_inputs[0].shape[0]
                )
            args = (
                self.params, self.state, self.opt_state, inputs, labels,
                self._step_count,
            )
            compile_s = 0.0
            if self._step_compiled is None:
                with tracer.span("step_program", cat="setup"):
                    if self._step_jit is None:
                        with tracer.span("build_step", cat="compile"):
                            self._step_jit = self._build_step()
                        self._verified_step = False
                    t0 = time.perf_counter()
                    hits = persistent_cache_hits()
                    with tracer.span("jit_compile", cat="compile", fn="train_step"):
                        self._step_compiled = self._step_jit.lower(*args).compile()
                    compile_s = time.perf_counter() - t0
                tracer.counter("jit.cache_miss")
                # persistent compilation cache: jax's own hit event
                # inside the compile says the executable came from disk
                # (docs/OBSERVABILITY.md)
                if persistent_cache_hits() > hits:
                    tracer.counter("jit_cache.persistent_hit")
                self._record_memory_snapshot(tracer)
            else:
                tracer.counter("jit.cache_hit")
            if self.verify_compiled != "off":
                with tracer.span("verify_compiled", cat="compile"):
                    self._maybe_verify_compiled(args)
            t0 = time.perf_counter()
            out = self._run_step(self._step_compiled, args, tracer)
            dispatch_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            with tracer.span("device_step", cat="step", step=step_no):
                out = jax.block_until_ready(out)
            device_s = time.perf_counter() - t0
        self.params, self.state, self.opt_state, loss, m = out
        self._step_count += 1
        total_s = time.perf_counter() - t_begin
        # host_stall_s: wall time the host spent BLOCKED waiting on the
        # device — here exactly the block_until_ready window, because the
        # instrumented path forces one sync per step by design (that is
        # what makes the wall split measurable; docs/OBSERVABILITY.md
        # "Sync points").  The untraced fast path never stalls, so an
        # async fit loop with instrumentation off accumulates ~0 here.
        self.host_stall_s += device_s
        self.last_step_stats = {
            "step": step_no,
            "total_s": total_s,
            "host_s": total_s - device_s,
            "dispatch_s": dispatch_s,
            "device_s": device_s,
            "host_stall_s": device_s,
            "compile_s": compile_s,
            "jit_cache": "miss" if compile_s else "hit",
        }
        if self.analysis_violations is not None:
            self.last_step_stats["analysis_violations"] = (
                self.analysis_violations
            )
        if self.pipeline is not None:
            # pipeline dimension of this step (ffmetrics/1 nullable
            # fields + the pipeline.bubble_s counter): bubble seconds =
            # measured device wall x the schedule's (S-1)/(M+S-1) idle
            # fraction — the wall-clock the warmup/drain lanes spent on
            # discarded compute (docs/PIPELINE.md, "Bubble math")
            bf = self.pipeline.bubble_frac
            self.last_step_stats.update(
                pipeline_stages=self.pipeline.stages,
                microbatches=self.pipeline.microbatches,
                bubble_frac=bf,
            )
            if tracer.enabled:
                tracer.counter("pipeline.bubble_s", device_s * bf)
        if self._grad_ring:
            # overlapped gradient sync (--grad-overlap): the compile-time
            # overlap pricing's predicted exposed comm per step — an
            # ffmetrics/1 nullable additive field, like bubble_frac; None
            # when no pricing was attached (bare Executor)
            price = getattr(self.strategy, "grad_overlap_price", None)
            self.last_step_stats["exposed_comm_s"] = (
                float(price["exposed_s"])
                if price and price.get("exposed_s") is not None
                else None
            )
        # run-health monitor: feed the flight recorder / detectors.  The
        # float() fetches are the monitor's documented per-step cost (the
        # block_until_ready above already synced, so they are host copies
        # of ready scalars, not fresh device round-trips).  A "raise"
        # policy propagates HealthError out of this call AFTER the step's
        # results were committed above — the bundle captures the state
        # the run died with.
        monitor = get_monitor()
        if monitor.enabled:
            monitor.observe_step(
                self.last_step_stats,
                float(loss),
                {k: float(v) for k, v in m.items()},
                samples=self._samples_per_step,
                tokens=self._tokens_per_step,
                # pair the search's priced cost with this observation
                # (calibration loop, docs/OBSERVABILITY.md); read late
                # off the strategy so a prediction attached after
                # construction (imported/data-parallel strategies priced
                # by FFModel.compile) still lands in every record
                predicted_step_s=getattr(
                    self.strategy, "predicted_step_s", None
                ),
                predicted_tok_s=getattr(
                    self.strategy, "predicted_tok_s", None
                ),
            )
        return loss, m

    def memory_snapshot(self) -> Optional[Dict[str, float]]:
        """Device-memory footprint of the compiled step from XLA's actual
        buffer assignment (``compiled.memory_analysis()`` — the same
        source the search's measured memory tier reads).  None when no
        AOT executable exists yet or the backend reports nothing.  Feeds
        both the tracer gauges and the health monitor's debug bundle."""
        compiled = self._step_compiled
        if compiled is None or compiled is self._step_jit:
            return None
        try:
            ma = compiled.memory_analysis()
        except Exception:
            return None
        if ma is None:
            return None
        out: Dict[str, float] = {}
        for field in (
            "temp_size_in_bytes",
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(ma, field, None)
            if v is not None:
                out[field] = float(v)
        return out or None

    def _record_memory_snapshot(self, tracer) -> None:
        snap = self.memory_snapshot()
        if not snap:
            return
        for field, v in snap.items():
            tracer.sample(
                "memory." + field.replace("_size_in_bytes", "_bytes"),
                v, level="step",
            )

    def forward(
        self, inputs: Sequence[Any], seq_length: Optional[int] = None
    ) -> jax.Array:
        tracer = get_tracer()
        if self._fwd_jit is None:
            self._fwd_jit = self._build_fwd()
            self._fwd_seqs_seen = set()
        if tracer.enabled:
            # static seq_length: each distinct value is its own trace
            # (model.cc:2415-2420), so classify hit/miss per value
            if seq_length in self._fwd_seqs_seen:
                tracer.counter("jit.cache_hit")
                cm = tracer.span("forward", cat="step", level="op")
            else:
                self._fwd_seqs_seen.add(seq_length)
                tracer.counter("jit.cache_miss")
                cm = tracer.span(
                    "jit_compile", cat="compile", fn="forward",
                    seq_length=str(seq_length),
                )
        else:
            cm = tracer.span("forward")  # tracer off: the profiler's sink alone
        with cm:
            inputs = [
                self._place(x, self._input_pspec(t), t.shape[0])
                for x, t in zip(inputs, self.graph_inputs)
            ]
            return self._fwd_jit(self.params, self.state, inputs, seq_length)

    def _label_pspec(self) -> PartitionSpec:
        if self._data_shard_ok():
            return PartitionSpec("data")
        return PartitionSpec()

    def _place(self, x: Any, pspec: PartitionSpec, global_batch: Optional[int] = None):
        """Host->device placement.  Multi-process: every process may feed
        either the full global batch (each process then device_puts only its
        addressable shards, via ``make_array_from_callback``) or just its
        process-local rows (``make_array_from_process_local_data`` — the
        analog of the reference's per-node zero-copy staging,
        ``src/dataloader/dataloader.cc:232-300``).  Which one arrived is
        disambiguated by the leading-dim size against ``global_batch``."""
        # device arrays NEVER round-trip through host numpy (np.asarray on a
        # jax.Array is a D2H fetch and a host sync); device_put reshards
        # on-device when needed and no-ops when not
        if self.mesh is None:
            return x if isinstance(x, jax.Array) else jnp.asarray(np.asarray(x))
        ns = NamedSharding(self.mesh, pspec)
        if isinstance(x, jax.Array):
            return x if x.sharding == ns else jax.device_put(x, ns)
        arr = np.asarray(x)
        if jax.process_count() > 1:
            if (
                global_batch is not None
                and arr.ndim > 0
                and arr.shape[0] != global_batch
            ):
                # only the exact per-process row count is the local case; a
                # short final batch must error here, not be silently glued
                # into a wrongly-sized global array
                local = global_batch // jax.process_count()
                if arr.shape[0] != local:
                    raise ValueError(
                        f"per-process batch has {arr.shape[0]} rows; expected "
                        f"the global batch ({global_batch}) or the "
                        f"process-local share ({local}). Pad or drop the "
                        f"remainder batch."
                    )
                return jax.make_array_from_process_local_data(ns, arr)
            return jax.make_array_from_callback(
                arr.shape, ns, lambda idx: arr[idx]
            )
        return jax.device_put(arr, ns)


_REMAT_OPS = frozenset({
    OperatorType.MULTIHEAD_ATTENTION,
    OperatorType.GATED_ATTENTION,
    OperatorType.GATED_DELTA_NET,
    OperatorType.MAMBA2_MIXER,
})
