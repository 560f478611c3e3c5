"""Analytic cost model (first tier of the simulator, SURVEY §2.2 S3).

The reference costs a strategy by running kernels on device
(``Simulator::measure_operator_cost``, ``src/runtime/simulator.cc:537``) +
analytic transfer estimates (``estimate_xfer_cost``, ``graph.cc:1438``).
This module is the *analytic* tier: roofline per-op compute time from
FLOPs/HBM-bytes and collective time from an ICI machine model.  The
measured tier (compile-and-time sub-programs, the true analog of the
CUDA-event micro-profiler ``model.cu:38``) plugs in via
``flexflow_tpu.search.simulator`` and overrides these numbers when
available.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.fftype import DataType, OperatorType
from flexflow_tpu.ops.base import get_op_def
from flexflow_tpu.parallel.machine import MachineMesh
from flexflow_tpu.parallel.strategy import OpSharding, Strategy
from flexflow_tpu.tensor import Layer


class TPUMachineModel:
    """ICI/DCN analog of the reference's machine models
    (``SimpleMachineModel``/``EnhancedMachineModel``,
    ``include/flexflow/simulator.h:212-605``; config file
    ``machine_config_example``).

    Default numbers approximate a v5p chip; override via constructor for
    other generations (the reference reads a config file —
    ``--machine-model-file`` maps to :func:`from_file`).
    """

    # bf16 peak / HBM / per-link-direction ICI by generation (public specs)
    CHIP_PRESETS = {
        "v4": dict(peak_flops=2.75e14, hbm_bw=1.2e12, ici_bw=9e10),
        "v5e": dict(peak_flops=1.97e14, hbm_bw=8.19e11, ici_bw=4.5e10),
        "v5 lite": dict(peak_flops=1.97e14, hbm_bw=8.19e11, ici_bw=4.5e10),
        "v5p": dict(peak_flops=4.59e14, hbm_bw=2.765e12, ici_bw=9e10),
        "v5": dict(peak_flops=4.59e14, hbm_bw=2.765e12, ici_bw=9e10),
        "v6e": dict(peak_flops=9.18e14, hbm_bw=1.64e12, ici_bw=9e10),
        "v6 lite": dict(peak_flops=9.18e14, hbm_bw=1.64e12, ici_bw=9e10),
    }

    def __init__(
        self,
        peak_flops: float = 4.59e14,  # bf16 FLOP/s per chip
        hbm_bw: float = 2.765e12,  # bytes/s
        ici_bw: float = 9e10,  # bytes/s per link direction
        dcn_bw: float = 6.25e9,  # bytes/s per host
        latency: float = 1e-6,  # per-collective latency (s)
        dcn_latency: float = 1e-5,  # cross-host collective latency (s)
        dcn_axes: Tuple[str, ...] = (),  # mesh axes that span hosts (DCN)
        topology=None,  # PhysicalTopology of the ICI slice (or None: flat)
    ) -> None:
        self.peak_flops = peak_flops
        self.hbm_bw = hbm_bw
        self.ici_bw = ici_bw
        self.dcn_bw = dcn_bw
        self.latency = latency
        self.dcn_latency = dcn_latency
        self.dcn_axes = tuple(dcn_axes)
        self.topology = topology
        # per-axis ring-bandwidth multipliers, set by for_mesh()
        self._axis_mult: Dict[str, float] = {}
        # machine-model identity for bench records / the regression gate
        # (tools/bench_compare.py refuses to diff runs priced against
        # different topologies): "default:...", "preset:<chip>", or
        # "file:<sha256/12>" — set by for_chip/from_file/load_machine_model
        self.source = "default:v5p-class"

    @classmethod
    def for_chip(cls, device_kind: str, **over) -> "TPUMachineModel":
        """Preset for a TPU generation, matched by substring of the JAX
        ``device_kind`` (e.g. ``"TPU v5 lite"``).  A kind no preset
        matches raises: pricing an unknown chip as some other chip is
        how a wrong roofline goes unnoticed."""
        dk = device_kind.lower()
        for key in sorted(cls.CHIP_PRESETS, key=len, reverse=True):
            if key in dk:
                m = cls(**{**cls.CHIP_PRESETS[key], **over})
                m.source = f"preset:{key}"
                return m
        raise ValueError(
            f"no machine-model preset for device kind {device_kind!r} "
            f"(known: {sorted(cls.CHIP_PRESETS)}); add one to "
            "TPUMachineModel.CHIP_PRESETS or pass --machine-model-file"
        )

    @classmethod
    def detect(cls, **over) -> "TPUMachineModel":
        """Model for the chip actually present.  Off-TPU (the CPU test
        meshes) there is no chip to price, so the v5p-class defaults
        stand in — chosen because the backend IS cpu, not because a
        probe failed."""
        import jax as _jax

        if _jax.default_backend() == "tpu":
            return cls.for_chip(_jax.devices()[0].device_kind, **over)
        return cls(**over)

    @staticmethod
    def from_file(path: str) -> "TPUMachineModel":
        """Load a ``--machine-model-file`` of either schema: a v2 file
        (``"version": 2`` — slices/link-classes/DCN uplinks) returns a
        :class:`~flexflow_tpu.parallel.network.NetworkedMachineModel`;
        a legacy v1 flat file returns a plain :class:`TPUMachineModel`."""
        from flexflow_tpu.parallel.network import load_machine_model

        return load_machine_model(path)

    @staticmethod
    def _from_v1_dict(d: dict) -> "TPUMachineModel":
        """The legacy flat-scalar schema (v1): top-level roofline/ICI/DCN
        scalars + optional ``chip`` preset + optional ``topology`` grid."""
        d = dict(d)
        if "dcn_axes" in d:
            d["dcn_axes"] = tuple(d["dcn_axes"])
        chip = d.pop("chip", None)
        if "topology" in d:
            from flexflow_tpu.parallel.machine import PhysicalTopology

            t = d["topology"]
            d["topology"] = PhysicalTopology(
                dims=tuple(t["dims"]), wrap=tuple(t.get("wrap", ()))
            )
        if chip:
            return TPUMachineModel.for_chip(chip, **d)
        return TPUMachineModel(**d)

    # --- physical-topology binding ----------------------------------------
    def _ici_shape(self, mesh: MachineMesh) -> Tuple[int, ...]:
        """Mesh shape with DCN-spanning axes collapsed to 1: the physical
        topology constrains only the per-slice ICI portion; an axis that
        rides DCN is sliced across hosts, and its intra-slice remainder is
        unknown here, so it goes unconstrained rather than falsely
        rejecting every multi-slice mesh."""
        return tuple(
            1 if n in self.dcn_axes else s
            for n, s in zip(mesh.axis_names, mesh.shape)
        )

    def legal_mesh(self, mesh: MachineMesh) -> bool:
        """Is this logical mesh realizable as ICI-contiguous submeshes of
        the declared physical grid?  Always true without a topology (the
        reference's SimpleMachineModel behavior)."""
        if self.topology is None:
            return True
        return self.topology.legal(self._ici_shape(mesh))

    def for_mesh(self, mesh: MachineMesh) -> "TPUMachineModel":
        """Bind per-axis ring-bandwidth multipliers for a concrete logical
        mesh: an axis that closes a torus ring through wraparound links
        prices collectives at 2× link bandwidth; an open line at 1×.
        No-op (returns self) without a topology."""
        if self.topology is None:
            return self
        assign = self.topology.assign(self._ici_shape(mesh))
        bound = TPUMachineModel(
            peak_flops=self.peak_flops, hbm_bw=self.hbm_bw,
            ici_bw=self.ici_bw, dcn_bw=self.dcn_bw, latency=self.latency,
            dcn_latency=self.dcn_latency, dcn_axes=self.dcn_axes,
            topology=self.topology,
        )
        bound.source = self.source
        if assign is not None:
            bound._axis_mult = {
                mesh.axis_names[i]: mult for i, (_, mult) in assign.items()
            }
        return bound

    def _bw(self, axis: Optional[str]) -> float:
        """Link bandwidth for a collective over ``axis``: DCN when the axis
        spans hosts (multi-slice outer axis — the reference's GASNet path,
        ``MULTI-NODE.md``), ICI (scaled by the bound torus-ring multiplier)
        otherwise."""
        if axis in self.dcn_axes:
            return self.dcn_bw
        return self.ici_bw * self._axis_mult.get(axis, 1.0)

    def _lat(self, axis: Optional[str]) -> float:
        return self.dcn_latency if axis in self.dcn_axes else self.latency

    # --- collective time estimates (ring algorithms over ICI/DCN) ---------
    def all_reduce(self, nbytes: float, n: int, axis: Optional[str] = None) -> float:
        if n <= 1:
            return 0.0
        bw = self._bw(axis)
        return self._lat(axis) * math.log2(max(2, n)) + 2 * nbytes * (n - 1) / (n * bw)

    def all_gather(self, nbytes_out: float, n: int, axis: Optional[str] = None) -> float:
        if n <= 1:
            return 0.0
        return self._lat(axis) + nbytes_out * (n - 1) / (n * self._bw(axis))

    def reduce_scatter(self, nbytes_in: float, n: int, axis: Optional[str] = None) -> float:
        if n <= 1:
            return 0.0
        return self._lat(axis) + nbytes_in * (n - 1) / (n * self._bw(axis))

    def all_to_all(self, nbytes: float, n: int, axis: Optional[str] = None) -> float:
        if n <= 1:
            return 0.0
        return self._lat(axis) + nbytes * (n - 1) / (n * self._bw(axis))

    # fraction of a grad-sync ring's time the backward compute stream can
    # hide when the sync is software-pipelined into the backward scan
    # (--grad-overlap, docs/PERF.md "Overlapped gradient sync").  ICI
    # collectives overlap well — the DMA engines run them beside the MXU;
    # DCN collectives barely do — the host-mediated uplink path
    # serializes against the step.
    OVERLAP_ICI = 0.9
    OVERLAP_DCN = 0.15

    def overlap_fraction(self, axis: Optional[str] = None) -> float:
        """How much of a collective over ``axis`` can hide under
        concurrent backward compute (0 = fully exposed, 1 = free)."""
        if axis in self.dcn_axes:
            return self.OVERLAP_DCN
        return self.OVERLAP_ICI


# Zero-flop ops XLA compiles to views or fuses into their consumers'
# loads (a slice feeds each consumer directly; reshape/flat are bitcasts):
# charging them a full HBM round trip would bias the search against
# structural rewrites that introduce them (batched-GEMM + split).
_VIEW_OPS = frozenset({
    OperatorType.SPLIT, OperatorType.RESHAPE, OperatorType.FLAT,
    OperatorType.IDENTITY, OperatorType.NOOP, OperatorType.INPUT,
    OperatorType.WEIGHT,
})


def op_compute_time(
    layer: Layer,
    degree: int,
    machine: TPUMachineModel,
    mxu_util: float = 0.5,
    fwd_only: bool = False,
) -> float:
    """Roofline: max(flops-bound, bandwidth-bound), fwd+bwd (bwd ≈ 2×fwd
    flops for matmul-type ops — the reference measures both separately).
    ``fwd_only`` prices the forward pass alone (inference/serving)."""
    if layer.op_type in _VIEW_OPS:
        return 0.0
    opdef = get_op_def(layer.op_type)
    factor = 1.0 if fwd_only else 3.0
    flops = factor * opdef.flops(layer) / max(1, degree)
    mem = factor * opdef.mem_bytes(layer) / max(1, degree)
    return max(flops / (machine.peak_flops * mxu_util), mem / machine.hbm_bw)


def _dtype_nbytes(dt) -> int:
    from flexflow_tpu.ops.base import _dtype_bytes

    return _dtype_bytes(dt)


def reshard_cost(
    shape,
    elt_bytes: int,
    src: "TensorSharding",
    dst: "TensorSharding",
    mesh: MachineMesh,
    machine: TPUMachineModel,
    with_backward: bool = False,
) -> float:
    """Collective time to move a tensor from distribution ``src`` to ``dst``.

    This is the analytic analog of the reference's
    ``SearchHelper::estimate_xfer_cost`` (``src/runtime/graph.cc:1438``) +
    the parallel-op kernels' implied data movement (§2.4): under GSPMD a
    layout change lowers to
      * all-reduce     — partial axes resolved (``Reduction``)
      * all-gather     — axes removed from a dim (``Combine``)
      * all-to-all     — axes moved between dims (``Repartition`` of an
                         already-sharded tensor)
      * local slice    — axes added to a dim (``Repartition``; ~latency only)
    Deterministic pure function — unit-testable, unlike the reference's
    device-measured xfers (SURVEY §4.7 gap).

    ``with_backward`` additionally charges the transpose collective the
    autodiff of this edge runs in the backward pass — equal bytes for the
    layout transposes (all-gather↔reduce-scatter, all-to-all↔all-to-all)
    and a real all-gather for the cotangent of a forward slice.  Partial
    resolution stays 1× here; its backward half (the column-parallel dx
    all-reduce) is charged input-sized at the consumer node by
    ``node_cost``.  Strategy costing must set it: pricing forward
    reshards only systematically favors activation-sharded hybrids over
    data parallelism (a 2D-sharded MLP "won" by exactly the unpriced
    backward half before round 4).
    """
    from flexflow_tpu.parallel.spec import TensorSharding  # noqa: F401

    total = float(math.prod(shape)) * elt_bytes
    cost = 0.0

    bwd = 2.0 if with_backward else 1.0
    # partial-sum resolution (axes partial in src, not in dst).  Priced 1×
    # even under with_backward: the matching backward collective (the
    # column-parallel dx all-reduce at the paired boundary) is charged
    # where it actually runs — at the consumer node, input-sized — by
    # node_cost's dgrad-sync term, and its bytes differ from this edge's
    # whenever the pair isn't width-symmetric.
    pending = [a for a in src.partial_axes if a not in dst.partial_axes]
    shard_deg = max(1, src.total_degree(mesh))
    for a in pending:
        n = mesh.axis_size(a)
        if n > 1:
            cost += machine.all_reduce(total / shard_deg, n, axis=a)

    src_map = {a: d for d in range(len(src.spec)) for a in src.axes_of(d)}
    dst_map = {a: d for d in range(len(dst.spec)) for a in dst.axes_of(d)}

    # axes kept but moved between dims -> all-to-all
    moved = [a for a in src_map if a in dst_map and src_map[a] != dst_map[a]]
    # axes removed entirely -> all-gather
    removed = [a for a in src_map if a not in dst_map]

    dst_deg = max(1, dst.total_degree(mesh))
    bytes_per_dev_dst = total / dst_deg
    for a in moved:
        n = mesh.axis_size(a)
        if n > 1:
            cost += bwd * machine.all_to_all(bytes_per_dev_dst, n, axis=a)
    gather_factor = 1
    gather_axis = None
    for a in removed:
        gather_factor *= mesh.axis_size(a)
        if a in machine.dcn_axes:
            gather_axis = a  # any DCN participant prices the whole gather
    if gather_factor > 1:
        cost += bwd * machine.all_gather(
            bytes_per_dev_dst, gather_factor, axis=gather_axis
        )
    # axes only in dst: local dynamic-slice, charge latency once
    added = [a for a in dst_map if a not in src_map]
    if added:
        cost += machine.latency
        if with_backward:
            # the cotangent of a forward slice is gathered back across the
            # added axes — a real collective, unlike the forward slice
            added_deg = 1
            add_axis = None
            for a in added:
                added_deg *= mesh.axis_size(a)
                if a in machine.dcn_axes:
                    add_axis = a
            if added_deg > 1:
                cost += machine.all_gather(
                    bytes_per_dev_dst * added_deg, added_deg, axis=add_axis
                )
    return cost


def default_op_sharding(layer: Layer) -> "OpSharding":
    """Fully-replicated OpSharding for a layer with no strategy entry —
    the shared fallback used by the event simulator and profiling table so
    they always agree on unassigned ops."""
    from flexflow_tpu.parallel.spec import TensorSharding

    return OpSharding(
        output=[
            TensorSharding.replicated(len(sh))
            for sh, _ in get_op_def(layer.op_type).infer(layer)
        ]
    )


def node_cost(
    layer: Layer,
    sharding: "OpSharding",
    mesh: MachineMesh,
    machine: Optional[TPUMachineModel] = None,
    lambda_mem: float = 0.0,
    compute_time: Optional[float] = None,
    forward_only: bool = False,
) -> float:
    """Compute + weight-grad-sync time for one op under one sharding choice
    (the DP's leaf cost — reference ``SearchHelper::graph_cost`` leaf at
    ``src/runtime/graph.cc:1586`` + optimizer NCCL allreduce cost).

    ``lambda_mem`` adds a memory pressure term (λ·bytes) — the
    multi-objective combination of the reference's memory-aware search
    (``try_one_lambda``, ``src/runtime/graph.cc:1884``).

    ``forward_only`` prices inference: forward roofline only, and the
    training-only collectives — weight-grad allreduce and the backward
    dgrad partial resolution — are skipped entirely (there IS no
    backward pass to run them in).  The λ memory terms stay: weights and
    activations occupy HBM either way.
    """
    m = machine or TPUMachineModel()
    opdef = get_op_def(layer.op_type)
    out0 = sharding.output[0] if sharding.output else None
    # per-op compute split (output shards, partial axes, and weight-side
    # splits like fused-Experts EP)
    degree = opdef.shard_degree(layer, sharding, mesh)
    # measured tier (simulator.MeasuredCostModel) overrides the roofline
    t = (
        compute_time
        if compute_time is not None
        else op_compute_time(layer, degree, m, fwd_only=forward_only)
    )
    # gradient sync: weight grads are partial over every mesh axis that
    # shards the op's *data* (batch/seq) but not the weight itself
    data_axes = set()
    if out0 is not None:
        for i in range(len(out0.spec)):
            data_axes.update(out0.axes_of(i))
        data_axes -= set(out0.partial_axes)
    for w in opdef.weights(layer):
        if not w.trainable:
            continue
        wb = math.prod(w.shape) * _dtype_nbytes(w.dtype)
        ws = sharding.weights.get(w.name)
        wd = ws.total_degree(mesh) if ws is not None else 1
        waxes = set(ws.used_axes()) if ws is not None else set()
        sync = 1
        sync_axis = None
        for a in data_axes - waxes:
            sync *= mesh.axis_size(a)
            if a in m.dcn_axes:
                sync_axis = a  # DCN participant dominates the ring
        if sync > 1 and not forward_only:
            t += m.all_reduce(wb / wd, sync, axis=sync_axis)
        if lambda_mem > 0.0:
            t += lambda_mem * (wb / wd)
    # backward dgrad sync (Megatron's backward half): a weight-sharding
    # axis the op's input layout doesn't carry means some dgrad
    # contraction runs over a dim sharded by that axis, so the input
    # cotangent comes out partial over it and autodiff resolves it with
    # an input-sized all-reduce before handing it to the producer.
    # Canonical cases: column-parallel linear (dx = dy @ W^T contracts
    # the sharded out-dim); fused TP attention (dx before the sharded
    # QKV projections).  Row-parallel inside a Megatron pair is exempt —
    # its input spec carries the axis.  Integer inputs (embedding ids)
    # are not differentiated, so vocab-sharded embeddings charge nothing.
    part_deg = 1
    if forward_only:
        # no backward pass: the dgrad partial-resolution term below is
        # dead — but forward partial sums (Megatron row-parallel) are
        # still resolved by the EDGE cost, which stays priced
        if lambda_mem > 0.0 and out0 is not None:
            out_b = sum(
                math.prod(s) * _dtype_nbytes(dt)
                for s, dt in opdef.infer(layer)
            )
            t += lambda_mem * (out_b / max(1, out0.total_degree(mesh)))
        return t
    for a in (out0.partial_axes if out0 is not None else ()):
        part_deg *= mesh.axis_size(a)
    out_deg_full = (out0.total_degree(mesh) if out0 is not None else 1) * part_deg
    waxes_all = set()
    # weight-side compute split beyond what the output carries (fused
    # Experts EP): the op partitions its own computation over the weight
    # axis and owns the dispatch collectives (all-to-all in its forward
    # AND backward) — no dgrad partial arises, so no charge
    if degree <= out_deg_full:
        for w in opdef.weights(layer):
            if w.trainable:
                ws = sharding.weights.get(w.name)
                if ws is not None:
                    waxes_all |= set(ws.used_axes())
    if waxes_all:
        in_axes = set()
        for ts in sharding.inputs:
            if ts is not None:
                for d in range(len(ts.spec)):
                    in_axes |= set(ts.axes_of(d))
        seen_guids = set()
        float_in_bytes = 0.0
        for tin in layer.inputs:
            # graph inputs are exempt: grad is taken w.r.t. params only,
            # so a graph input's cotangent (and its partial resolution) is
            # dead code XLA eliminates — only produced activations whose
            # cotangent flows to an upstream layer pay the all-reduce
            if (
                tin.guid in seen_guids
                or tin.owner_layer is None
                or tin.dtype in (
                    DataType.INT32, DataType.INT64, DataType.BOOLEAN,
                )
            ):
                continue
            seen_guids.add(tin.guid)
            float_in_bytes += math.prod(tin.shape) * _dtype_nbytes(tin.dtype)
        for a in sorted(waxes_all - in_axes):
            n = mesh.axis_size(a)
            if n > 1 and float_in_bytes:
                # input shard degree: the op's full compute degree
                # (INCLUDING partial axes — fused TP attention carries the
                # weight axis as an output partial, not an output shard)
                # divided by this axis's own factor
                in_deg = max(1, out_deg_full // n)
                t += m.all_reduce(float_in_bytes / in_deg, n, axis=a)
    if lambda_mem > 0.0 and out0 is not None:
        out_b = sum(
            math.prod(s) * _dtype_nbytes(dt) for s, dt in opdef.infer(layer)
        )
        # memory degree excludes partial axes (partial sums are full-size
        # per device along those axes)
        t += lambda_mem * (out_b / max(1, out0.total_degree(mesh)))
    return t


def node_grad_sync_rows(layer, sharding, mesh, machine=None):
    """The layer's weight-grad sync terms as ``(weight_name,
    bytes_per_device, degree, dcn_axis_or_None)`` rows — EXACTLY the loop
    :func:`node_cost` prices with ``m.all_reduce`` (same DCN-participant
    selection), exposed so the overlap model (:func:`chain_grad_overlap`)
    and the executor's ring eligibility can re-derive the same traffic
    without drifting apart."""
    dcn = machine.dcn_axes if machine is not None else ()
    opdef = get_op_def(layer.op_type)
    out0 = sharding.output[0] if sharding.output else None
    data_axes = set()
    if out0 is not None:
        for i in range(len(out0.spec)):
            data_axes.update(out0.axes_of(i))
        data_axes -= set(out0.partial_axes)
    rows = []
    for w in opdef.weights(layer):
        if not w.trainable:
            continue
        wb = math.prod(w.shape) * _dtype_nbytes(w.dtype)
        ws = sharding.weights.get(w.name)
        wd = ws.total_degree(mesh) if ws is not None else 1
        waxes = set(ws.used_axes()) if ws is not None else set()
        sync = 1
        sync_axis = None
        for a in data_axes - waxes:
            sync *= mesh.axis_size(a)
            if a in dcn:
                sync_axis = a  # DCN participant dominates the ring
        if sync > 1:
            rows.append((w.name, wb / wd, sync, sync_axis))
    return rows


def chain_grad_overlap(chain, strategy, mesh, machine, block_cost):
    """Overlap pricing for one collapsed chain's weight-grad sync
    (--grad-overlap, docs/PERF.md): the fused tail all-reduce vs the same
    traffic as a ring reduce-scatter + all-gather software-pipelined into
    the backward scan, where block *i*'s ring hides under block *i−1*'s
    backward compute.  Per-block exposed comm is
    ``max(0, ring_time − overlap_frac × backward_compute)`` with
    ``overlap_frac`` from the machine model's link classes
    (:meth:`TPUMachineModel.overlap_fraction` — DCN axes barely overlap).
    Returns ``None`` when the chain carries no data-axis grad sync;
    otherwise a dict with ``fused_s``/``ring_s``/``exposed_s``/
    ``overlap_frac``/``saved_s``/``sync_bytes``/``ring_degree``."""
    fused = ring = 0.0
    frac = None
    degree = 1
    sync_bytes = 0.0
    for l in chain.template:
        os_ = strategy.op_sharding(l)
        if os_ is None:
            os_ = default_op_sharding(l)
        for _wn, b, nsync, ax in node_grad_sync_rows(l, os_, mesh, machine):
            fused += machine.all_reduce(b, nsync, axis=ax)
            ring += (
                machine.reduce_scatter(b, nsync, axis=ax)
                + machine.all_gather(b, nsync, axis=ax)
            )
            f = machine.overlap_fraction(ax)
            frac = f if frac is None else min(frac, f)
            degree = max(degree, nsync)
            sync_bytes += b
    if fused <= 0.0 or frac is None:
        return None
    # backward share of the block's compute the ring can hide under:
    # bwd ≈ 2× fwd flops (op_compute_time's 3× factor), so 2/3 of the
    # block cost net of the fused sync itself
    bwd = max(0.0, (2.0 / 3.0) * (block_cost - fused))
    exposed = max(0.0, ring - frac * bwd)
    return {
        "fused_s": fused,
        "ring_s": ring,
        "exposed_s": exposed,
        "overlap_frac": frac,
        "saved_s": fused - exposed,
        "sync_bytes": sync_bytes,
        "ring_degree": degree,
    }


def grad_ring_chain_layers(layers, strategy) -> frozenset:
    """Names of the layers whose weight-grad sync lowers as the explicit
    ring under ``--grad-overlap ring`` — the search-side mirror of the
    executor's eligibility (uniform collapsed chains with data-axis grad
    sync; pipelined strategies decline entirely).  Drives the
    ``:grad-sync-ring`` entries :func:`implied_collectives` emits for a
    winner that carries the choice."""
    from flexflow_tpu.blocks import detect_block_chains

    if strategy.pipeline is not None:
        return frozenset()
    mesh = strategy.mesh
    names = set()
    for ch in detect_block_chains(layers, min_depth=4):
        if not _chain_assignment_uniform(ch, strategy):
            continue
        has_sync = False
        for l in ch.template:
            os_ = strategy.op_sharding(l) or default_op_sharding(l)
            if node_grad_sync_rows(l, os_, mesh):
                has_sync = True
                break
        if has_sync:
            for blk in ch.layers:
                for l in blk:
                    names.add(l.name)
    return frozenset(names)


def grad_overlap_adjustment(layers, strategy, machine, mode: str = "auto"):
    """Whole-strategy overlap pricing: ``(delta_s, price)`` where
    ``delta_s`` is the step-time reduction from ringing every eligible
    chain's grad sync (``auto`` only rings chains it helps; ``ring``
    forces the decomposition and prices it honestly, even when worse)
    and ``price`` aggregates the per-chain terms for
    ``Strategy.grad_overlap_price``.  ``(0.0, None)`` when nothing rings."""
    if mode not in ("auto", "ring") or strategy.pipeline is not None:
        return 0.0, None
    _, parts = estimate_strategy_parts(
        layers, strategy, machine, collapse_blocks=True,
        grad_overlap=mode,
    )
    delta = 0.0
    agg = {"fused_s": 0.0, "ring_s": 0.0, "exposed_s": 0.0,
           "sync_bytes": 0.0, "chains": 0}
    frac = None
    for entry in parts.values():
        ov = entry.get("grad_overlap")
        if ov is None:
            continue
        depth = entry["chain"].depth
        delta += depth * ov["saved_s"]
        for k in ("fused_s", "ring_s", "exposed_s"):
            agg[k] += depth * ov[k]
        agg["sync_bytes"] += depth * ov["sync_bytes"]
        agg["chains"] += 1
        f = ov["overlap_frac"]
        frac = f if frac is None else min(frac, f)
    if agg["chains"] == 0:
        return 0.0, None
    agg["overlap_frac"] = frac
    return delta, agg


def estimate_strategy_cost(
    layers: List[Layer],
    strategy: Strategy,
    machine: Optional[TPUMachineModel] = None,
    lambda_mem: float = 0.0,
    node_time_fn=None,
    cost_cache: Optional[Dict] = None,
    collapse_blocks: bool = True,
    forward_only: bool = False,
    grad_overlap: str = "off",
) -> float:
    """Per-step time estimate for a whole strategy: node costs (compute +
    weight-grad sync) + per-edge reshard collectives.  Pure function of the
    layer graph + strategy — deterministic and unit-testable (the gap
    SURVEY §4.7 notes in the reference's device-measured costing).

    ``forward_only`` prices an inference step (no backward collectives,
    1× forward roofline — see :func:`node_cost`); the serving objective
    (``unity_search --objective serve``) searches under this pricing.

    ``collapse_blocks``: chains of >= 4 structurally identical blocks
    whose strategy assignment is uniform across repeats are priced ONCE
    and multiplied — first application at the chain's real boundary
    sharding, the remaining ``depth - 1`` at the steady-state boundary
    (carry-in = the block's own output layout).  Identical totals to the
    unrolled walk, at per-unique-block instead of per-layer host cost
    (``flexflow_tpu.blocks``, docs/PERF.md)."""
    total, _parts = estimate_strategy_parts(
        layers, strategy, machine, lambda_mem=lambda_mem,
        node_time_fn=node_time_fn, cost_cache=cost_cache,
        collapse_blocks=collapse_blocks, forward_only=forward_only,
        grad_overlap=grad_overlap,
    )
    return total


def estimate_strategy_parts(
    layers: List[Layer],
    strategy: Strategy,
    machine: Optional[TPUMachineModel] = None,
    lambda_mem: float = 0.0,
    node_time_fn=None,
    cost_cache: Optional[Dict] = None,
    collapse_blocks: bool = True,
    forward_only: bool = False,
    grad_overlap: str = "off",
) -> Tuple[float, Dict[int, Dict]]:
    """:func:`estimate_strategy_cost` with the collapsed-chain pricing
    exposed: returns ``(total, parts)`` where ``parts`` maps each
    collapsed chain's start index to ``{"chain", "first", "steady"}`` —
    the chain object, the first block's cost at the real boundary
    sharding, and the steady-state per-block cost.  The pipeline tier
    (``estimate_pipeline_step_time``) reads these so stage enumeration
    re-prices NOTHING per (stage count x microbatch count) — the whole
    (S x M) sweep is arithmetic over one collapsed walk
    (docs/PIPELINE.md, "Pricing").

    ``grad_overlap`` (off|auto|ring) re-prices each chain's weight-grad
    sync as a ring pipelined into the backward scan
    (:func:`chain_grad_overlap`): ``auto`` rings a chain only when the
    exposed time beats the fused sync, ``ring`` forces it.  The per-chain
    terms land in ``parts[start]["grad_overlap"]``; ``first``/``steady``
    stay at fused pricing (the pipeline tier, which reads them, never
    combines with the ring — the executor declines pipelined chains)."""
    from flexflow_tpu.ops.parallel_ops import resolve_parallel_sharding
    from flexflow_tpu.parallel.spec import TensorSharding

    mesh = strategy.mesh
    m = (machine or TPUMachineModel()).for_mesh(mesh)
    total = 0.0
    # track explicit parallel-op distributions (layers are topological)
    pop_out: Dict[int, TensorSharding] = {}  # tensor guid -> sharding

    def producer_sharding(t, override=None) -> Optional[TensorSharding]:
        if override and t.guid in override:
            return override[t.guid]
        if t.guid in pop_out:
            return pop_out[t.guid]
        if t.owner_layer is None:
            return None
        prod = strategy.op_sharding(t.owner_layer)
        if prod is None or t.owner_idx >= len(prod.output):
            return None
        return prod.output[t.owner_idx]

    def layer_cost(layer) -> float:
        """Node + incoming-edge cost of one layer."""
        c_total = 0.0
        if layer.op_type.is_parallel_op:
            # explicit reshard: charge the implied collective (mirrors
            # the DP tier's _transition_cost_parallel)
            t = layer.inputs[0]
            src = producer_sharding(t) or TensorSharding.replicated(t.ndim)
            dst = resolve_parallel_sharding(layer, src, mesh)
            c_total += reshard_cost(
                t.shape, _dtype_nbytes(t.dtype), src, dst, mesh, m,
                # graph inputs have no cotangent — same rule as dp.py, so
                # the DP and this estimator optimize the same objective
                with_backward=t.owner_layer is not None and not forward_only,
            )
            pop_out[layer.outputs[0].guid] = dst
            return c_total
        os_ = strategy.op_sharding(layer)
        if os_ is None:
            os_ = OpSharding(
                output=[
                    TensorSharding.replicated(len(s))
                    for s, _ in get_op_def(layer.op_type).infer(layer)
                ]
            )
        if cost_cache is not None:
            nk = ("n", int(layer.layer_guid), os_.key(), forward_only)
            c = cost_cache.get(nk)
            if c is None:
                c = node_cost(
                    layer, os_, mesh, m, lambda_mem=lambda_mem,
                    compute_time=node_time_fn(layer, os_) if node_time_fn else None,
                    forward_only=forward_only,
                )
                cost_cache[nk] = c
            c_total += c
        else:
            c_total += node_cost(
                layer,
                os_,
                mesh,
                m,
                lambda_mem=lambda_mem,
                compute_time=node_time_fn(layer, os_) if node_time_fn else None,
                forward_only=forward_only,
            )
        for i, t in enumerate(layer.inputs):
            src = producer_sharding(t)
            if src is None:
                continue
            explicit = i < len(os_.inputs) and os_.inputs[i] is not None
            dst = os_.inputs[i] if explicit else TensorSharding.replicated(t.ndim)
            # without an explicit requirement, batch-compatible layouts pass
            # through free (GSPMD keeps them); only charge when src carries
            # partials or channel shards the consumer didn't ask for
            if not explicit and not src.partial_axes and not any(
                "model" in src.axes_of(d) for d in range(len(src.spec))
            ):
                continue
            bwd = t.owner_layer is not None and not forward_only
            if cost_cache is not None:
                ek = ("e", t.guid, src.key(), dst.key(), bwd)
                c = cost_cache.get(ek)
                if c is None:
                    c = reshard_cost(
                        t.shape, _dtype_nbytes(t.dtype), src, dst, mesh, m,
                        with_backward=bwd,
                    )
                    cost_cache[ek] = c
                c_total += c
            else:
                c_total += reshard_cost(
                    t.shape, _dtype_nbytes(t.dtype), src, dst, mesh, m,
                    with_backward=bwd,
                )
        return c_total

    chain_at = {}
    if collapse_blocks:
        from flexflow_tpu.blocks import detect_block_chains

        for ch in detect_block_chains(layers, min_depth=4):
            if _chain_assignment_uniform(ch, strategy):
                chain_at[ch.start] = ch

    parts: Dict[int, Dict] = {}
    idx, n = 0, len(layers)
    while idx < n:
        chain = chain_at.get(idx)
        if chain is None:
            total += layer_cost(layers[idx])
            idx += 1
            continue
        first = sum(layer_cost(l) for l in chain.template)
        # steady state: price BLOCK 1 — a real interior repeat, so its
        # carry is a produced tensor (backward collectives and the dgrad
        # sync of node_cost apply, which a graph-input-fed template would
        # wrongly exempt) and its producers resolve through the strategy
        steady = sum(layer_cost(l) for l in chain.layers[1])
        total += first + (chain.depth - 1) * steady
        parts[chain.start] = {
            "chain": chain, "first": first, "steady": steady,
        }
        if grad_overlap in ("auto", "ring") and not forward_only:
            ov = chain_grad_overlap(chain, strategy, mesh, m, steady)
            if ov is not None and (
                grad_overlap == "ring" or ov["exposed_s"] < ov["fused_s"]
            ):
                total -= chain.depth * ov["saved_s"]
                parts[chain.start]["grad_overlap"] = ov
        if chain.layers[-1][-1].op_type.is_parallel_op:
            # downstream consumers resolve the chain output through
            # pop_out exactly as they would after the unrolled walk;
            # block 1's resolve is the steady-state layout
            out_sh = pop_out.get(chain.layers[1][-1].outputs[0].guid)
            if out_sh is not None:
                pop_out[chain.out_guid] = out_sh
        idx = chain.end
    # multi-slice models tally ring-vs-hierarchical routing choices per
    # collective; surface them as tracer counters once per estimate
    if hasattr(m, "flush_decisions"):
        m.flush_decisions()
    return total, parts


class ImpliedCollective:
    """One collective the cost model expects GSPMD to lower for a strategy
    (``flexflow_tpu.analysis`` reconciles these against the compiled HLO —
    docs/ANALYSIS.md "Collective audit").

    ``kind`` is the HLO instruction family (``all-reduce`` / ``all-gather``
    / ``all-to-all`` / ``reduce-scatter`` / ``collective-permute``);
    ``axes`` the mesh axes the collective runs over; ``required`` marks
    entries whose ABSENCE from the lowering is itself a violation (grad
    sync, the pipeline handoff) — optional entries only widen what the
    lowering is allowed to contain."""

    __slots__ = ("kind", "axes", "reason", "required")

    def __init__(self, kind: str, axes, reason: str, required: bool = False):
        self.kind = kind
        self.axes = frozenset(axes)
        self.reason = reason
        self.required = required

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        req = " required" if self.required else ""
        return (f"ImpliedCollective({self.kind} over "
                f"{sorted(self.axes)}{req}: {self.reason})")


def _transition_implied(src, dst, mesh, with_backward: bool, reason: str):
    """The collectives one ``src -> dst`` layout change lowers to — the
    same taxonomy :func:`reshard_cost` prices (all-reduce for partial
    resolution, all-to-all for moved axes, all-gather for removed axes,
    local slice for added axes), emitted as entries instead of seconds."""
    out = []
    pending = [a for a in src.partial_axes if a not in dst.partial_axes]
    for a in pending:
        if mesh.axis_size(a) > 1:
            out.append(ImpliedCollective("all-reduce", {a}, reason + ":psum"))
    src_map = {a: d for d in range(len(src.spec)) for a in src.axes_of(d)}
    dst_map = {a: d for d in range(len(dst.spec)) for a in dst.axes_of(d)}
    moved = [a for a in src_map if a in dst_map and src_map[a] != dst_map[a]]
    removed = [a for a in src_map if a not in dst_map]
    for a in moved:
        if mesh.axis_size(a) > 1:
            out.append(ImpliedCollective("all-to-all", {a}, reason + ":move"))
    gaxes = {a for a in removed if mesh.axis_size(a) > 1}
    if gaxes:
        out.append(ImpliedCollective("all-gather", gaxes, reason + ":gather"))
        if with_backward:
            # transpose of an all-gather: reduce-scatter (or the
            # partitioner's equivalent all-reduce + slice)
            out.append(ImpliedCollective(
                "reduce-scatter", gaxes, reason + ":gather-bwd"))
            out.append(ImpliedCollective(
                "all-reduce", gaxes, reason + ":gather-bwd"))
    added = {a for a in dst_map if a not in src_map if mesh.axis_size(a) > 1}
    if added and with_backward:
        # forward is a local slice; the cotangent is gathered back
        out.append(ImpliedCollective(
            "all-gather", added, reason + ":slice-bwd"))
    return out


def implied_collectives(
    layers: List[Layer],
    strategy: Strategy,
    forward_only: bool = False,
    extra_axes: Tuple[str, ...] = (),
    grad_ring_layers=(),
) -> List["ImpliedCollective"]:
    """The multiset of collectives ``strategy`` implies for the compiled
    program — the reconciliation source for the analyzer's collective
    audit (a placement that PRICES collective X but LOWERS collective Y
    is flagged at compile time instead of in a bench regression).

    Mirrors :func:`estimate_strategy_parts`'s walk exactly — parallel-op
    reshards, implicit edge reshards, weight-grad sync, backward dgrad
    sync — but collects (kind, axes) entries instead of seconds, so the
    pricing model and the verification model can never drift apart.
    Chains are walked unrolled (no costs are computed, so collapse buys
    nothing; the entry SET is identical either way).

    ``extra_axes`` admits optional all-gather/reduce-scatter over axes
    the runtime adds outside the strategy walk (the executor's ZeRO-1
    moment sharding gathers the param delta over its shard axes).

    ``grad_ring_layers`` names layers whose weight-grad sync lowers as
    the explicit ring decomposition under ``--grad-overlap`` (the
    executor's actual ring set, or :func:`grad_ring_chain_layers` for a
    search winner): their grad-sync entries gain ``:grad-sync-ring``
    reduce-scatter + collective-permute companions so the audit tolerates
    the (n−1)-hop ppermute chain the ring all-gather lowers to.  The
    fused ``:grad-sync`` all-reduce entry stays required — the ring's
    scatter leg satisfies it through ALLOWED_LOWERINGS; the ring's own
    presence is pinned by the ffcheck ``overlap`` check, not here."""
    from flexflow_tpu.ops.parallel_ops import resolve_parallel_sharding
    from flexflow_tpu.parallel.spec import TensorSharding

    mesh = strategy.mesh
    out: List[ImpliedCollective] = []
    pop_out: Dict[int, "TensorSharding"] = {}

    def producer_sharding(t):
        if t.guid in pop_out:
            return pop_out[t.guid]
        if t.owner_layer is None:
            return None
        prod = strategy.op_sharding(t.owner_layer)
        if prod is None or t.owner_idx >= len(prod.output):
            return None
        return prod.output[t.owner_idx]

    for layer in layers:
        if layer.op_type.is_parallel_op:
            t = layer.inputs[0]
            src = producer_sharding(t) or TensorSharding.replicated(t.ndim)
            dst = resolve_parallel_sharding(layer, src, mesh)
            out.extend(_transition_implied(
                src, dst, mesh,
                with_backward=t.owner_layer is not None and not forward_only,
                reason=layer.name,
            ))
            pop_out[layer.outputs[0].guid] = dst
            continue
        os_ = strategy.op_sharding(layer)
        if os_ is None:
            os_ = default_op_sharding(layer)
        opdef = get_op_def(layer.op_type)
        out0 = os_.output[0] if os_.output else None
        # --- implicit edge reshards (same skip rule as the estimator) ---
        for i, t in enumerate(layer.inputs):
            src = producer_sharding(t)
            if src is None:
                continue
            explicit = i < len(os_.inputs) and os_.inputs[i] is not None
            dst = os_.inputs[i] if explicit else TensorSharding.replicated(t.ndim)
            if not explicit and not src.partial_axes and not any(
                "model" in src.axes_of(d) for d in range(len(src.spec))
            ):
                continue
            out.extend(_transition_implied(
                src, dst, mesh,
                with_backward=t.owner_layer is not None and not forward_only,
                reason=layer.name,
            ))
        # --- node collectives (same terms node_cost prices) ---
        data_axes = set()
        if out0 is not None:
            for d in range(len(out0.spec)):
                data_axes.update(out0.axes_of(d))
            data_axes -= set(out0.partial_axes)
            # forward partial sums a consumer resolves implicitly
            for a in out0.partial_axes:
                if mesh.axis_size(a) > 1:
                    out.append(ImpliedCollective(
                        "all-reduce", {a}, layer.name + ":partial"))
        out_axes_all = set()
        if out0 is not None:
            for d in range(len(out0.spec)):
                out_axes_all.update(out0.axes_of(d))
            out_axes_all |= set(out0.partial_axes)
        waxes_all = set()
        for w in opdef.weights(layer):
            if not w.trainable:
                continue
            ws = os_.weights.get(w.name)
            waxes = set(ws.used_axes()) if ws is not None else set()
            waxes_all |= waxes
            # forward contraction over a weight-sharded axis the output
            # does not carry (vocab-sharded embedding lookup, matmul
            # contracting dim): each shard holds a partial sum the
            # lowering resolves with a forward all-reduce
            wpsum = {
                a for a in waxes - out_axes_all if mesh.axis_size(a) > 1
            }
            if wpsum:
                out.append(ImpliedCollective(
                    "all-reduce", wpsum, f"{layer.name}.{w.name}:wpsum"))
            sync_axes = {
                a for a in data_axes - waxes if mesh.axis_size(a) > 1
            }
            if sync_axes and not forward_only:
                # the one collective every training step MUST contain:
                # weight grads partial over the data axes are resolved by
                # an all-reduce (or a ZeRO reduce-scatter)
                out.append(ImpliedCollective(
                    "all-reduce", sync_axes,
                    f"{layer.name}.{w.name}:grad-sync", required=True,
                ))
                if layer.name in grad_ring_layers:
                    out.append(ImpliedCollective(
                        "reduce-scatter", sync_axes,
                        f"{layer.name}.{w.name}:grad-sync-ring"))
                    out.append(ImpliedCollective(
                        "collective-permute", sync_axes,
                        f"{layer.name}.{w.name}:grad-sync-ring"))
        if waxes_all and not forward_only:
            in_axes = set()
            for ts in os_.inputs:
                if ts is not None:
                    for d in range(len(ts.spec)):
                        in_axes |= set(ts.axes_of(d))
            for a in sorted(waxes_all - in_axes):
                if mesh.axis_size(a) > 1:
                    out.append(ImpliedCollective(
                        "all-reduce", {a}, layer.name + ":dgrad-sync"))
        if forward_only and data_axes:
            # inference programs still reduce metrics/logits summaries
            # across the data shards (loss mean, argmax agreement)
            axes = {a for a in data_axes if mesh.axis_size(a) > 1}
            if axes:
                out.append(ImpliedCollective(
                    "all-reduce", axes, layer.name + ":eval-reduce"))
    # loss/metrics means cross every data-sharding axis of the step
    all_data_axes = set()
    for e in out:
        if e.required:
            all_data_axes |= e.axes
    if all_data_axes:
        out.append(ImpliedCollective(
            "all-reduce", all_data_axes, "loss-mean"))
    # runtime-added sharding axes (executor ZeRO-1): param delta
    # all-gather + grad reduce-scatter over the shard axes
    ex_axes = {a for a in extra_axes if mesh.axis_size(a) > 1}
    if ex_axes:
        out.append(ImpliedCollective("all-gather", ex_axes, "zero1:unshard"))
        out.append(ImpliedCollective(
            "reduce-scatter", ex_axes, "zero1:scatter"))
        out.append(ImpliedCollective("all-reduce", ex_axes, "zero1"))
    # pipeline handoff: the 1F1B stage boundary is an explicit ppermute
    # (docs/PIPELINE.md — GSPMD's concat-shift alternative miscompiles,
    # so the analyzer REQUIRES the permute form)
    spec = strategy.pipeline
    if spec is not None and mesh.axis_size(spec.stage_axis) == spec.stages:
        out.append(ImpliedCollective(
            "collective-permute", {spec.stage_axis}, "pipeline:handoff",
            required=not forward_only,
        ))
        # the schedule's other traffic: output reassembly (last stage's
        # rows -> global batch) over the stage axis, and the shard_map
        # transpose's psums — differentiating the stage body inserts an
        # all-reduce over every axis a captured operand is replicated
        # along (check_vma is off inside shard_map).  Priced as xfer_s /
        # epsilon by estimate_pipeline_step_time, tolerated here by kind.
        out.append(ImpliedCollective(
            "all-gather", {spec.stage_axis}, "pipeline:reassemble"))
        for ax in mesh.axis_names:
            out.append(ImpliedCollective(
                "all-reduce", {ax}, "pipeline:grad"))
    return out


def stage_contended_machine(machine, stages: int):
    """Machine view for pricing a stage SUBMESH whose collectives still
    cross DCN while ``stages`` stages execute concurrently
    (docs/PIPELINE.md, "Pricing").

    A pipeline whose stage axis is NOT a ``dcn_axes`` member keeps the
    slice-crossing factor inside every stage — so each tick, all S
    stages issue their weight-grad / reshard collectives over the SAME
    shared per-host uplinks.  The uplink is a physical resource: S
    concurrent users divide its rate by S (the same ``dcn_contention``
    semantics PR 3 introduced for concurrent slice-crossing
    collectives).  A ``dcn_axes`` stage axis needs no clone — collapsing
    it removed every DCN collective from the submesh, which is exactly
    why slices-become-stages wins on cost.

    Returns ``machine`` unchanged when there is nothing to contend."""
    if machine is None or stages <= 1 or not getattr(machine, "dcn_axes", ()):
        return machine
    try:
        from flexflow_tpu.parallel.network import NetworkedMachineModel
    except ImportError:  # pragma: no cover - network module always ships
        NetworkedMachineModel = ()
    if NetworkedMachineModel and isinstance(machine, NetworkedMachineModel):
        clone = NetworkedMachineModel(
            slice_topology=machine.slice_topology,
            num_slices=machine.num_slices,
            hosts_per_slice=machine.hosts_per_slice,
            peak_flops=machine.peak_flops,
            hbm_bw=machine.hbm_bw,
            dcn_bw_per_uplink=machine.dcn_bw_per_uplink,
            dcn_uplinks_per_host=machine.dcn_uplinks_per_host,
            dcn_latency=machine.dcn_latency,
            dcn_contention=machine.dcn_contention * stages,
            dcn_axes=machine.dcn_axes,
            latency=machine.latency,
        )
        clone.source = machine.source
        # share the routing tallies like for_mesh clones do
        clone.decision_stats = machine.decision_stats
        clone._flushed = machine._flushed
        return clone
    import copy

    clone = copy.copy(machine)
    clone.dcn_bw = machine.dcn_bw / stages
    return clone


def _stage_handoff_time(
    machine: TPUMachineModel, nbytes_per_dev: float, axis: str, parallel: int
) -> float:
    """One inter-stage activation handoff: a ``ppermute`` moving each
    device's microbatch shard to its peer in the next stage submesh —
    point-to-point, NOT a collective, which is the whole reason
    slices-become-stages wins on a multi-slice machine: the only bytes
    crossing ``axis`` are microbatch-sized and every chip pair moves in
    parallel.  ``parallel`` is the per-chip flow count crossing the
    boundary (the stage submesh size)."""
    if axis in machine.dcn_axes:
        lat = machine.dcn_latency
        agg = getattr(machine, "_slice_dcn_bw", None)
        if agg is not None:
            # NetworkedMachineModel: m parallel flows engage up to
            # hosts_per_slice uplink sets (same routing the hierarchical
            # collective's DCN phase uses)
            return lat + nbytes_per_dev * max(1, parallel) / agg(parallel)
        return lat + nbytes_per_dev / machine.dcn_bw
    return machine.latency + nbytes_per_dev / machine.ici_bw


def estimate_pipeline_step_time(
    layers: List[Layer],
    strategy: Strategy,
    machine: Optional[TPUMachineModel],
    *,
    chain,
    stages: int,
    microbatches: int,
    stage_axis: str,
    sub_total: Optional[float] = None,
    sub_parts: Optional[Dict[int, Dict]] = None,
    lambda_mem: float = 0.0,
    node_time_fn=None,
    cost_cache: Optional[Dict] = None,
) -> Optional[Dict[str, float]]:
    """1F1B pipelined step-time estimate (docs/PIPELINE.md, "Pricing").

    ``strategy`` is the STAGE-SUBMESH assignment (the stage axis has
    extent 1 in ``strategy.mesh``) — weight-grad allreduces and reshard
    collectives are therefore priced intra-stage only, which is exactly
    what pipelining buys: params live on one stage, so no gradient ever
    crosses the stage axis.  The chain portion of the submesh estimate
    is replaced by the schedule:

      ``(M + S - 1) x (per-microbatch stage time + handoff)``

    with per-microbatch stage time ``(depth/S) x block_cost / M`` (the
    roofline is byte/flop-linear, so a 1/M microbatch prices at 1/M —
    the latency floor is absorbed by the handoff term) and the
    warmup/drain bubble ``(S-1)/(M+S-1)`` falling out of the tick count.
    Non-chain prologue/epilogue layers run per-step at full batch,
    replicated over the stage axis, and keep their submesh price.

    ``sub_total``/``sub_parts`` short-circuit the collapsed walk when
    the caller already ran :func:`estimate_strategy_parts` — the (S x M)
    sweep then re-prices nothing.  Returns None when the chain was not
    collapsed under this strategy (non-uniform assignment — no legal
    scan, no legal pipeline)."""
    if sub_total is None or sub_parts is None:
        sub_total, sub_parts = estimate_strategy_parts(
            layers, strategy, machine, lambda_mem=lambda_mem,
            node_time_fn=node_time_fn, cost_cache=cost_cache,
            collapse_blocks=True,
        )
    part = sub_parts.get(chain.start)
    if part is None:
        return None
    depth = part["chain"].depth
    chain_cost = part["first"] + (depth - 1) * part["steady"]
    remainder = max(0.0, sub_total - chain_cost)
    avg_block = chain_cost / depth
    ticks = microbatches + stages - 1
    m = machine or TPUMachineModel()
    # per-microbatch stage time: the roofline is byte/flop-linear so a
    # 1/M microbatch prices at 1/M — DOWN TO the dispatch floor of one
    # kernel latency per op per tick.  Without the floor the degenerate
    # S=depth, M=batch corner (single-row microbatches through
    # single-block stages) prices as free and wins every sweep.
    per_stage_ops = (depth // stages) * part["chain"].block_len
    stage_s = max(
        (depth // stages) * avg_block / microbatches,
        per_stage_ops * m.latency,
    )
    # handoff bytes: the carry tensor's per-device microbatch shard
    out_t = part["chain"].layers[0][-1].outputs[0]
    sh = None
    os_ = strategy.op_sharding(part["chain"].layers[0][-1])
    if os_ is not None and os_.output:
        sh = os_.output[0]
    shard_deg = max(1, sh.total_degree(strategy.mesh)) if sh is not None else 1
    nbytes = (
        float(math.prod(out_t.shape)) * _dtype_nbytes(out_t.dtype)
        / microbatches / shard_deg
    )
    xfer_s = _stage_handoff_time(m, nbytes, stage_axis, strategy.mesh.size)
    # the handoff is point-to-point and OVERLAPS the next tick's stage
    # compute (the PipeDream/GPipe steady-state assumption — while stage
    # s computes microbatch i, microbatch i+1's activation is already in
    # flight), so a tick costs max(compute, transfer), not the sum; one
    # unoverlapped handoff remains at the schedule head.  This is what
    # makes slices-become-stages rational on a multi-slice machine: a
    # DCN handoff hidden under a fat intra-slice stage is free, while a
    # DCN COLLECTIVE inside a stage is paid every block.
    tick_s = max(stage_s, xfer_s)
    pipe_s = ticks * tick_s + xfer_s
    step_s = remainder + pipe_s
    return {
        "step_s": step_s,
        "bubble_frac": (stages - 1) / ticks,
        "bubble_s": (stages - 1) * tick_s,
        "stage_s": stage_s,
        "xfer_s": xfer_s,
        "pipe_s": pipe_s,
        "remainder_s": remainder,
        "chain_s_unpipelined": chain_cost,
        "stages": float(stages),
        "microbatches": float(microbatches),
    }


def estimate_decode_step_time(
    layers: List[Layer],
    strategy: Strategy,
    machine: Optional[TPUMachineModel] = None,
    *,
    slots: int,
    kv_len: int,
    train_tokens: int,
    mxu_util: float = 0.5,
    attn_kernel: str = "paged",
    kv_dtype: str = "fp32",
    weight_dtype: str = "fp32",
) -> Dict[str, float]:
    """Analytic ONE-token decode step time under a strategy — the
    serving analog of :func:`estimate_strategy_cost` (docs/SERVING.md,
    "The SLO objective").

    Decode is a different roofline regime from training: per step every
    weight streams from HBM once while only ``slots`` activation rows
    flow through it, so dense layers are weight-bandwidth-bound; the
    attention term reads each slot's ``kv_len``-deep K/V pages; and
    tensor-parallel shardings buy weight-stream time with one partial-sum
    allreduce per sharded layer at decode-activation size (tiny bytes —
    latency-dominated, which is exactly why a DCN-crossing model axis is
    poison for serving and the 2-slice golden pins that the objective
    knows it).

    Activation/collective bytes scale from the graph's training shapes
    by ``slots / train_tokens`` (the graph carries (B, S, H) tensors;
    a decode step moves one token per slot).  Pure host math —
    deterministic, golden-testable, no TPU required.

    ``attn_kernel`` prices the engine's decode-attention path
    (docs/PERF.md "Paged decode attention"): ``"paged"`` (default, the
    fused Pallas kernel) reads each K/V page exactly once, so the
    attention term is the bare ``2 * slots * kv_len * e`` byte stream;
    ``"gather"`` (the dense fallback) additionally materializes the
    per-lane page gather every layer — one extra read of the pool
    pages plus one write of the dense virtual-length buffer before the
    attention re-reads it, i.e. 3x the K/V bytes.

    ``kv_dtype``/``weight_dtype`` price the quantized serving arms
    (docs/SERVING.md "Quantized KV cache and weight-only decode") the
    same way ``attn_kernel`` prices the kernel: per-element bytes in
    the K/V stream and the weight stream drop to the storage format's
    (int8/fp8 = 1, bf16 = 2), a quantized pool additionally streams
    its float32 per-position scales, and the FLOPs terms are untouched
    (dequant rides the same mul units the contraction uses).  The
    ``"fp32"`` defaults mean "the model's own dtypes" and reproduce
    the pre-quantization numbers exactly, so every existing serve
    golden is byte-identical with the arms off.

    Returns ``{"step_s", "mem_s", "flops_s", "coll_s"}``.
    """
    _QBYTES = {"fp32": None, "bf16": 2, "int8": 1, "fp8": 1}
    if kv_dtype not in _QBYTES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r}: expected one of {tuple(_QBYTES)}"
        )
    if weight_dtype not in ("fp32", "int8"):
        raise ValueError(
            f"weight_dtype {weight_dtype!r}: expected fp32 | int8"
        )
    kv_nb = _QBYTES[kv_dtype]  # None = use the graph dtype
    w_nb = 1 if weight_dtype == "int8" else None
    mesh = strategy.mesh
    m = (machine or TPUMachineModel()).for_mesh(mesh)
    mem_s = flops_s = coll_s = 0.0
    for layer in layers:
        if layer.op_type.is_parallel_op or layer.op_type in _VIEW_OPS:
            continue
        opdef = get_op_def(layer.op_type)
        os_ = strategy.op_sharding(layer) or default_op_sharding(layer)
        out0 = os_.output[0] if os_.output else None
        # slot parallelism: mesh axes sharding the output's batch dim
        slot_deg = 1
        if out0 is not None and len(out0.spec):
            for a in out0.axes_of(0):
                slot_deg *= mesh.axis_size(a)
        local_slots = max(1.0, slots / max(1, slot_deg))
        lmem = lflops = 0.0
        for w in opdef.weights(layer):
            wd = 1
            ws = os_.weights.get(w.name)
            if ws is not None:
                wd = max(1, ws.total_degree(mesh))
            elems = math.prod(w.shape)
            lmem += elems * (
                w_nb if w_nb is not None else _dtype_nbytes(w.dtype)
            ) / wd
            lflops += 2.0 * elems / wd * local_slots
        if layer.op_type == OperatorType.MULTIHEAD_ATTENTION:
            e = layer.attrs.get("embed_dim", 0)
            tp = 1
            ws = os_.weights.get("wq")
            if ws is not None:
                tp = max(1, ws.total_degree(mesh))
            nb = (
                kv_nb if kv_nb is not None
                else _dtype_nbytes(layer.outputs[0].dtype)
            )
            kv_bytes = 2.0 * local_slots * kv_len * e * nb / tp
            if kv_nb is not None and kv_dtype in ("int8", "fp8"):
                # the per-position float32 scale stream (2 pools x
                # kv_len positions per slot, scales shared over heads)
                kv_bytes += 2.0 * local_slots * kv_len * 4.0 / tp
            lmem += kv_bytes
            if attn_kernel == "gather":
                # dense gather materialization: pool pages read once
                # more + the virtual-length buffer written before the
                # attention contraction re-reads it
                lmem += 2.0 * kv_bytes
            lflops += 2.0 * 2.0 * local_slots * kv_len * e / tp
        mem_s += lmem / m.hbm_bw
        flops_s += lflops / (m.peak_flops * mxu_util)
        # partial-sum resolution per step (the TP allreduce), at
        # decode-activation bytes
        if out0 is not None and out0.partial_axes:
            out_b = sum(
                math.prod(s) * _dtype_nbytes(dt)
                for s, dt in opdef.infer(layer)
            )
            per_tok = out_b / max(1, train_tokens)
            shard_deg = max(1, out0.total_degree(mesh))
            for a in out0.partial_axes:
                n = mesh.axis_size(a)
                if n > 1:
                    coll_s += m.all_reduce(
                        per_tok * local_slots / shard_deg, n, axis=a
                    )
    if hasattr(m, "flush_decisions"):
        m.flush_decisions()
    # dense compute and weight streaming overlap on real hardware only
    # partially; the roofline takes the max per step, serialized with
    # the collectives (same convention as op_compute_time)
    return {
        "step_s": max(mem_s, flops_s) + coll_s,
        "mem_s": mem_s,
        "flops_s": flops_s,
        "coll_s": coll_s,
    }


def estimate_prefill_chunk_time(
    layers: List[Layer],
    strategy: Strategy,
    machine: Optional[TPUMachineModel] = None,
    *,
    chunk: int,
    kv_len: int,
    train_tokens: int,
    slots: int = 1,
    mxu_util: float = 0.5,
    attn_kernel: str = "paged",
    kv_dtype: str = "fp32",
    weight_dtype: str = "fp32",
) -> Dict[str, float]:
    """Analytic ONE-chunk batched prefill dispatch time under a
    strategy — the prefill analog of :func:`estimate_decode_step_time`
    (docs/SERVING.md "Chunked prefill on the paged pool").

    One dispatch ingests ``chunk`` prompt positions for each of
    ``slots`` lanes (the engine's batched prefill program, r20): the
    decode weights stream from HBM ONCE per chunk-batch while
    ``slots * chunk`` activation rows flow through them — which is the
    whole point of batching prefill across slots; the per-slot loop
    paid that stream once per slot.

    ``attn_kernel`` prices the chunk-attention path, and this is where
    the O(S^2) asymmetry lives:

    * ``"paged"`` — the block-table-native kernel's visible-page DMA
      clamp reads only the chunk's visible prefix, ``kv_len / 2 +
      chunk`` positions for the MEAN chunk of a ``kv_len``-long prompt
      (chunk i sees ``i * chunk + chunk``; the average over a prompt's
      chunks is half the final depth).
    * ``"gather"`` — the dense fallback materializes the FULL virtual
      length every chunk regardless of start: pool pages read once
      more + the (H, SV, D) buffer written and re-read, i.e. 3x
      ``kv_len`` positions of K/V bytes per layer per chunk.

    ``kv_dtype``/``weight_dtype`` reuse the decode estimator's storage
    axes (quantized pools add the float32 per-position scale stream,
    scaled 3x on the gather arm like the pages it rides with).  The
    attention FLOPs term is identical across kernels — the win is
    traffic, not arithmetic.

    The collective term charges BOTH partial-sum resolution (the decode
    estimator's term, at chunk-row bytes) AND the strategy's implied
    activation reshard collectives (:func:`reshard_cost` over the same
    edge walk :func:`implied_collectives` audits), INCLUDING edges into
    view ops — a reshape that demands a replicated input from a
    batch-sharded producer lowers a real all-gather every dispatch.
    Pricing nodes only would make such shardings look collective-free:
    the per-chip row count shrinks while the ~1us-latency-floor
    all-gather they owe per dispatch vanishes from the bill, and the
    prefill pool flips to an activation-sharded hybrid that is slower
    end-to-end.  At serving-sized activations these collectives are
    latency-dominated — exactly why the prefill pool wants the
    collective-free layout and the disagg 2-slice golden pins that the
    pricing knows it.

    Per-prompt-position feed cost (what the disagg split pricing
    amortizes) is ``chunk_s / (slots * chunk)``.  Pure host math —
    deterministic, golden-testable, no TPU required.

    Returns ``{"chunk_s", "mem_s", "flops_s", "coll_s"}``.
    """
    _QBYTES = {"fp32": None, "bf16": 2, "int8": 1, "fp8": 1}
    if kv_dtype not in _QBYTES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r}: expected one of {tuple(_QBYTES)}"
        )
    if weight_dtype not in ("fp32", "int8"):
        raise ValueError(
            f"weight_dtype {weight_dtype!r}: expected fp32 | int8"
        )
    from flexflow_tpu.ops.parallel_ops import resolve_parallel_sharding
    from flexflow_tpu.parallel.spec import TensorSharding

    kv_nb = _QBYTES[kv_dtype]
    w_nb = 1 if weight_dtype == "int8" else None
    chunk = max(1, int(chunk))
    slots = max(1, int(slots))
    # mean visible depth of a chunk while prefilling a kv_len prompt
    # (paged); the gather arm always touches the full virtual length
    visible = kv_len / 2.0 + chunk
    mesh = strategy.mesh
    m = (machine or TPUMachineModel()).for_mesh(mesh)
    mem_s = flops_s = coll_s = 0.0
    # activation bytes scale from the graph's training shapes to one
    # chunk dispatch's slots x chunk rows (the latency floor inside the
    # machine model's collective pricing is byte-independent, so tiny
    # reshards still pay their ~1us — the term that makes a DCN- or
    # even ICI-crossing model axis lose at serving scale)
    act_scale = (slots * chunk) / max(1, train_tokens)
    # lane parallelism: the batched prefill program lane-shards its OWN
    # (slots, chunk) batch over the mesh's non-model axes — the serve
    # batch is ``slots``, not the training graph's batch, so a mesh
    # whose data axis the TRAINING batch cannot divide (forcing the
    # strategy fully replicated) still spreads the serve lanes.  The
    # strategy-derived dim-0 sharding is honored per layer when wider.
    lane_cap = 1
    for _a in mesh.axis_names:
        if _a != "model":
            lane_cap *= mesh.axis_size(_a)
    lane_cap = min(lane_cap, slots)
    pop_out: Dict[int, "TensorSharding"] = {}

    def _producer_sharding(t):
        if t.guid in pop_out:
            return pop_out[t.guid]
        if t.owner_layer is None:
            return None
        prod = strategy.op_sharding(t.owner_layer)
        if prod is None or t.owner_idx >= len(prod.output):
            return None
        return prod.output[t.owner_idx]

    for layer in layers:
        if layer.op_type.is_parallel_op:
            # explicit reshard: the implied collective runs once per
            # chunk dispatch at chunk-row bytes
            t = layer.inputs[0]
            src = _producer_sharding(t) or TensorSharding.replicated(
                t.ndim
            )
            dst = resolve_parallel_sharding(layer, src, mesh)
            coll_s += reshard_cost(
                t.shape, _dtype_nbytes(t.dtype) * act_scale,
                src, dst, mesh, m, with_backward=False,
            )
            pop_out[layer.outputs[0].guid] = dst
            continue
        opdef = get_op_def(layer.op_type)
        os_ = strategy.op_sharding(layer) or default_op_sharding(layer)
        out0 = os_.output[0] if os_.output else None
        # edge reshards the dispatch pays (same skip rule as the
        # training estimator: batch-compatible layouts pass through
        # free) — walked for VIEW ops too: a reshape that demands a
        # replicated input from a sharded producer lowers a real
        # all-gather even though the view itself computes nothing
        for i, t in enumerate(layer.inputs):
            src = _producer_sharding(t)
            if src is None:
                continue
            explicit = i < len(os_.inputs) and os_.inputs[i] is not None
            dst = (
                os_.inputs[i] if explicit
                else TensorSharding.replicated(t.ndim)
            )
            if not explicit and not src.partial_axes and not any(
                "model" in src.axes_of(d) for d in range(len(src.spec))
            ):
                continue
            coll_s += reshard_cost(
                t.shape, _dtype_nbytes(t.dtype) * act_scale,
                src, dst, mesh, m, with_backward=False,
            )
        if layer.op_type in _VIEW_OPS:
            continue
        slot_deg = 1
        if out0 is not None and len(out0.spec):
            for a in out0.axes_of(0):
                slot_deg *= mesh.axis_size(a)
        slot_deg = max(slot_deg, lane_cap)
        local_slots = max(1.0, slots / max(1, slot_deg))
        local_rows = local_slots * chunk
        lmem = lflops = 0.0
        for w in opdef.weights(layer):
            wd = 1
            ws = os_.weights.get(w.name)
            if ws is not None:
                wd = max(1, ws.total_degree(mesh))
            elems = math.prod(w.shape)
            lmem += elems * (
                w_nb if w_nb is not None else _dtype_nbytes(w.dtype)
            ) / wd
            lflops += 2.0 * elems / wd * local_rows
        if layer.op_type == OperatorType.MULTIHEAD_ATTENTION:
            e = layer.attrs.get("embed_dim", 0)
            tp = 1
            ws = os_.weights.get("wq")
            if ws is not None:
                tp = max(1, ws.total_degree(mesh))
            nb = (
                kv_nb if kv_nb is not None
                else _dtype_nbytes(layer.outputs[0].dtype)
            )
            if attn_kernel == "gather":
                # full-SV materialization every chunk: pool read +
                # dense buffer write + attention re-read
                kv_bytes = 3.0 * 2.0 * local_slots * kv_len * e * nb / tp
                if kv_nb is not None and kv_dtype in ("int8", "fp8"):
                    kv_bytes += (
                        3.0 * 2.0 * local_slots * kv_len * 4.0 / tp
                    )
            else:
                # visible pages only — the kernel's DMA clamp
                kv_bytes = 2.0 * local_slots * visible * e * nb / tp
                if kv_nb is not None and kv_dtype in ("int8", "fp8"):
                    kv_bytes += 2.0 * local_slots * visible * 4.0 / tp
            lmem += kv_bytes
            # chunk rows x visible keys, QK^T + PV (kernel-independent)
            lflops += 2.0 * 2.0 * local_rows * visible * e / tp
        mem_s += lmem / m.hbm_bw
        flops_s += lflops / (m.peak_flops * mxu_util)
        if out0 is not None and out0.partial_axes:
            out_b = sum(
                math.prod(s) * _dtype_nbytes(dt)
                for s, dt in opdef.infer(layer)
            )
            per_tok = out_b / max(1, train_tokens)
            shard_deg = max(1, out0.total_degree(mesh))
            for a in out0.partial_axes:
                n = mesh.axis_size(a)
                if n > 1:
                    coll_s += m.all_reduce(
                        per_tok * local_rows / shard_deg, n, axis=a
                    )
    if hasattr(m, "flush_decisions"):
        m.flush_decisions()
    return {
        "chunk_s": max(mem_s, flops_s) + coll_s,
        "mem_s": mem_s,
        "flops_s": flops_s,
        "coll_s": coll_s,
    }


def estimate_speculative_decode(
    step_s: float,
    *,
    k: int,
    accept_rate: float,
    draft_frac: float,
    verify_overhead: float = 1.0,
) -> Dict[str, float]:
    """Accept-rate-weighted macro-step pricing for speculative decoding
    (docs/SERVING.md, "Speculative accept math").

    One macro step = ``k`` draft steps on the shallow slice (each
    ``draft_frac`` of a full decode step — the layer-count fraction, a
    good proxy in the weight-streaming regime where step time is linear
    in layers streamed) + ONE full-depth verify over the k+1 rows.  The
    verify batches k+1 positions through the same weight stream a
    single decode step pays, so its cost is ~one step
    (``verify_overhead`` scales it for the extra attention/FLOPs).

    With per-draft acceptance probability ``a`` (i.i.d. approximation),
    the macro emits the verify row's own token plus a geometric prefix
    of accepted drafts::

        E[tokens] = 1 + a + a^2 + ... + a^k = (1 - a^{k+1}) / (1 - a)

    so the effective per-token step time is ``macro_s / E[tokens]`` and
    the speedup over plain decode is ``step_s / effective``.  At a=1
    the bound is the ideal (k+1) / (k·draft_frac + 1); at a=0 spec is a
    pure loss (macro_s > step_s for one token) — the objective prices
    both arms and only picks spec when it wins.
    """
    k = max(0, int(k))
    a = min(1.0, max(0.0, float(accept_rate)))
    df = min(1.0, max(0.0, float(draft_frac)))
    step_s = max(float(step_s), 1e-12)
    if a >= 1.0:
        expected = float(k + 1)
    else:
        expected = (1.0 - a ** (k + 1)) / (1.0 - a)
    macro_s = k * df * step_s + verify_overhead * step_s
    effective = macro_s / max(expected, 1e-12)
    return {
        "k": float(k),
        "accept_rate": a,
        "draft_frac": df,
        "expected_tokens": expected,
        "macro_s": macro_s,
        "effective_step_s": effective,
        "speedup": step_s / effective,
    }


def estimate_kv_handoff_time(nbytes: float, machine=None) -> float:
    """One prefill→decode KV handoff over DCN (docs/SERVING.md,
    "Disaggregated prefill/decode"): a point-to-point transfer of the
    request's dense spill payload, priced as one DCN phase latency plus
    the bytes over one host's aggregate uplink bandwidth (the handoff
    is a single logical flow, so it rides ``host_dcn_bw`` like the flat
    ring's slice-boundary hop — not the slice-aggregate rate a spread
    collective gets).

    ``machine=None`` prices zero (a colocated cluster has no wire);
    a scalar :class:`TPUMachineModel` falls back to its flat ``dcn_bw``.
    Pure host math — the disagg search arm and the in-process transport
    both inject exactly this number.
    """
    if machine is None:
        return 0.0
    bw = getattr(machine, "host_dcn_bw", None) or getattr(
        machine, "dcn_bw", 0.0
    )
    lat = float(getattr(machine, "dcn_latency", 0.0))
    return lat + (float(nbytes) / bw if bw else 0.0)


def _chain_assignment_uniform(chain, strategy: Strategy) -> bool:
    """Every repeat of the chain carries the same per-position OpSharding
    (the precondition for price-once-multiply).  Compared by
    ``sharding_key()``: per-depth pipeline stage tags price identically
    (stage membership changes WHERE a block runs, not what it costs)."""
    for j in range(chain.block_len):
        keys = set()
        for d in range(chain.depth):
            s = strategy.op_sharding(chain.layers[d][j])
            keys.add(None if s is None else s.sharding_key())
        if len(keys) != 1:
            return False
    return True
