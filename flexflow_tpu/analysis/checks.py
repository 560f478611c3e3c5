"""The non-collective checks: transfer & sync, donation, dtype
promotion, replication (docs/ANALYSIS.md "Check catalog").

Each check is total over :class:`ProgramArtifact` — missing inputs mean
skip, never raise — and reports op/file-level diagnostics via the jaxpr
equation's user source frame where one exists.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from flexflow_tpu.analysis.core import (
    DONATION_BYTES_FLOOR,
    DTYPE_LEAK_MIN_ELEMS,
    H2D_CONST_BYTES_FLOOR,
    ProgramArtifact,
    Violation,
    eqn_where,
    register_check,
    walk_jaxpr_eqns,
)

# jaxpr primitives that force a device->host round trip when they appear
# INSIDE a jitted body (the async-fit / zero-sync-serve killers).
# debug_callback is warn-level: ordered prints stall dispatch but do not
# change results.
HOST_SYNC_PRIMS = {
    "pure_callback": "error",
    "io_callback": "error",
    "callback": "error",
    "infeed": "error",
    "outfeed": "error",
    "debug_callback": "warn",
}
# the HLO-text fallback when no jaxpr was captured
_HOST_CALLBACK_TARGETS = (
    'custom_call_target="xla_python_cpu_callback"',
    'custom_call_target="xla_ffi_python_cpu_callback"',
)


def _dtype_bytes(dtype_str: str) -> int:
    import numpy as np

    try:
        return int(np.dtype(dtype_str).itemsize)
    except TypeError:
        return 4


@register_check("transfer")
def check_transfers(artifact: ProgramArtifact) -> List[Violation]:
    """Statically find device-to-host transfers (host callbacks, infeed/
    outfeed) and un-prefetched H2D copies (large host constants closed
    over by the jitted body) — the static form of the ``host_syncs``
    ledger guarantee."""
    out: List[Violation] = []
    if artifact.jaxpr is not None:
        for eqn in walk_jaxpr_eqns(artifact.jaxpr):
            sev = HOST_SYNC_PRIMS.get(eqn.primitive.name)
            if sev is not None:
                out.append(Violation(
                    check="transfer",
                    severity=sev,
                    program=artifact.name,
                    message=(
                        f"host round-trip inside jitted body: "
                        f"{eqn.primitive.name}"
                    ),
                    where=(eqn_where(eqn) or eqn.primitive.name),
                ))
        # closed-over host arrays become per-dispatch H2D copies; device
        # arrays (jax.Array) are already resident
        import numpy as np

        consts = getattr(artifact.jaxpr, "consts", ())
        for c in consts:
            if type(c).__module__.startswith("numpy") and isinstance(
                c, np.ndarray
            ) and c.nbytes >= H2D_CONST_BYTES_FLOOR:
                out.append(Violation(
                    check="transfer",
                    severity="warn",
                    program=artifact.name,
                    message=(
                        f"un-prefetched H2D copy: jitted body closes over "
                        f"a host array of {c.nbytes} bytes "
                        f"(shape {tuple(c.shape)}) — stage it with "
                        f"device_put/place_batch instead"
                    ),
                ))
    elif artifact.hlo:
        for tgt in _HOST_CALLBACK_TARGETS:
            n = artifact.hlo.count(tgt)
            if n:
                out.append(Violation(
                    check="transfer",
                    severity="error",
                    program=artifact.name,
                    message=(
                        f"{n} host-callback custom-call(s) inside the "
                        f"compiled program ({tgt})"
                    ),
                ))
    return out


@register_check("donation")
def check_donation(artifact: ProgramArtifact) -> List[Violation]:
    """Detect buffers eligible for donation but not donated.

    A non-donated input whose (shape, dtype) matches an output left over
    after the donated inputs consumed theirs holds BOTH copies live
    across the step — the double-HBM hazard ``search/memory.py`` budgets
    assume away.  Small buffers (< 1 MiB) are exempt: token ids and
    scalar counters legitimately alias nothing.
    """
    if not artifact.expects_donation or not artifact.inputs:
        return []
    out: List[Violation] = []
    # multiset of output avals, consumed donated-first
    remaining: Dict[tuple, int] = {}
    for shape, dtype in artifact.outputs:
        k = (tuple(shape), dtype)
        remaining[k] = remaining.get(k, 0) + 1
    donated_any = False
    for label, shape, dtype, donated in artifact.inputs:
        if donated:
            donated_any = True
            k = (tuple(shape), dtype)
            if remaining.get(k, 0) > 0:
                remaining[k] -= 1
    for label, shape, dtype, donated in artifact.inputs:
        if donated or not shape:
            continue
        nbytes = math.prod(shape) * _dtype_bytes(dtype)
        if nbytes < DONATION_BYTES_FLOOR:
            continue
        k = (tuple(shape), dtype)
        if remaining.get(k, 0) > 0:
            remaining[k] -= 1
            out.append(Violation(
                check="donation",
                severity="error",
                program=artifact.name,
                message=(
                    f"input {label} ({dtype}{list(shape)}, {nbytes} bytes) "
                    f"matches an undonated output — donate it or both "
                    f"copies stay live across the step (double-HBM)"
                ),
                where=label,
                details={"bytes": nbytes, "shape": list(shape),
                         "dtype": dtype},
            ))
    # donation declared but dropped at lowering: XLA records honored
    # donations in the module header's input_output_alias
    if donated_any and artifact.hlo and "input_output_alias" not in artifact.hlo:
        out.append(Violation(
            check="donation",
            severity="error",
            program=artifact.name,
            message=(
                "donate_argnums declared but the compiled module carries "
                "no input_output_alias — donation was dropped at lowering"
            ),
        ))
    return out


@register_check("dtype")
def check_dtype(artifact: ProgramArtifact) -> List[Violation]:
    """fp32 leaks inside reduced-precision compute regions: a
    dot/conv contracting fp32 operands of non-trivial size inside a
    program whose compute dtype is bf16/fp16 runs at a fraction of the
    MXU rate and doubles the activation bytes.  Deliberate fp32 islands
    (loss scalars, norm denominators, optimizer math on master weights)
    fall under the ``DTYPE_LEAK_MIN_ELEMS`` floor or are not dots."""
    if artifact.compute_dtype not in ("bfloat16", "float16"):
        return []
    if artifact.jaxpr is None:
        return []
    out: List[Violation] = []
    for eqn in walk_jaxpr_eqns(artifact.jaxpr):
        if eqn.primitive.name not in ("dot_general", "conv_general_dilated"):
            continue
        opnds = [
            v.aval for v in eqn.invars if hasattr(getattr(v, "aval", None), "dtype")
        ]
        if not opnds:
            continue
        fp32 = [a for a in opnds if str(a.dtype) == "float32"]
        big = [a for a in fp32 if a.size >= DTYPE_LEAK_MIN_ELEMS]
        if fp32 and big:
            shapes = [tuple(a.shape) for a in opnds]
            out.append(Violation(
                check="dtype",
                severity="error",
                program=artifact.name,
                message=(
                    f"fp32 {eqn.primitive.name} inside a "
                    f"{artifact.compute_dtype} compute region "
                    f"(operands {shapes}) — silent upcast"
                ),
                where=(eqn_where(eqn) or eqn.primitive.name),
                details={"operand_shapes": [list(s) for s in shapes]},
            ))
    return out


@register_check("serve_cow")
def check_serve_cow(artifact: ProgramArtifact) -> List[Violation]:
    """Copy-on-write safety for prefix-shared paged KV caches.  The
    hazard lives in the ALLOCATOR (a shared refcount>1 or prefix-indexed
    block mapped by a slot's writable region), not in any one compiled
    program, so at the artifact level this check is a registered no-op —
    the live scan runs in
    :func:`flexflow_tpu.analysis.capture.analyze_serve_engine`, which
    walks ``PagedKVCache.shared_write_hazards()`` and emits
    ``serve_cow`` violations against the ``serve.kvcache`` program."""
    return []


def _pool_inputs(artifact: ProgramArtifact):
    """The K/V pool inputs of a serve program, ``(label, shape, dtype)``:
    recognised by their label and by the pool's rank, ``(L, num_blocks *
    block_size, H * D)`` (serve/kvcache.py)."""
    return [
        (label, tuple(shape), dtype)
        for label, shape, dtype, _ in artifact.inputs
        if label in ("cache_k", "cache_v") and len(shape) == 3
    ]


def _no_pool_violation(check: str, artifact: ProgramArtifact, claim: str):
    return Violation(
        check=check,
        severity="error",
        program=artifact.name,
        message=(
            f"program claims {claim} but none of its inputs is "
            f"recognised as a K/V pool (label cache_k / cache_v, shape "
            f"(L, num_blocks * block_size, H * D)) — the audit has "
            f"nothing to hold the claim against"
        ),
        where="inputs",
        details={
            "inputs": [
                [label, list(shape)] for label, shape, _, _ in artifact.inputs
            ],
        },
    )


@register_check("paged_attn")
def check_paged_attn(artifact: ProgramArtifact) -> List[Violation]:
    """Structural proof the paged-attention fusion happened: a serve
    program that CLAIMS the fused Pallas kernel (docs/PERF.md "Paged
    decode attention") must lower no pool-sized gather — the dense
    fallback's per-layer ``pool[tables]`` materializes a (B, MB, BS, H,
    D) buffer, so any gather/take whose output is at least ONE lane's
    virtual-length K/V bytes (``MB * BS * H * D * itemsize``) means the
    gather is still in the program.

    Prefill is audited too (r20, "Chunked prefill on the paged pool"):
    the batched chunk program claiming ``paged`` must not lower the
    dense fallback's per-layer ``pool[tables]`` either — its output is
    ``slots`` lanes of virtual-length K/V, the exact O(S^2) hazard the
    prefill kernel extension deletes.

    Only a gather FROM THE POOL counts: its operand holds a layer of
    the pool, ``num_blocks * BS * H * D`` elements under any shape, or
    the whole of it.  A paged program has other gathers that outgrow one
    lane's K/V bytes at some scale and are none of the fallback's: the
    batched token-embedding lookup (a 2-D table), and the page-write
    path's gather of each lane's new rows into page shape
    (``paged_kv_write``: its operand is the chunk's rows, (2, slots,
    chunk, H * D), and its size follows the chunk, not the virtual
    length).

    Total: artifacts without a ``serve_attn: "paged"`` detail (gather
    engines, non-serve programs) or without a jaxpr skip.  One that
    claims ``paged`` and shows no K/V pool input is a violation, not a
    skip.  Small gathers from the pool (per-page dynamic slices from
    the kernel's own lowering) sit far below the threshold and pass."""
    det = artifact.details or {}
    if det.get("serve_attn") != "paged":
        return []
    if artifact.role not in ("decode", "draft", "verify", "prefill"):
        return []
    if artifact.jaxpr is None:
        return []
    # one lane's virtual-length K/V bytes from the pool operand's
    # (L, N * BS, H * D) shape + the table geometry
    mb, bs = det.get("max_blocks_per_seq"), det.get("block_size")
    pool = next(
        (p for p in _pool_inputs(artifact) if p[0] == "cache_k"), None
    )
    if not mb or not bs or pool is None:
        return [_no_pool_violation(
            "paged_attn", artifact, 'serve_attn "paged"'
        )]
    _, (layers, rows, hd), pool_dtype = pool
    lane_bytes = int(mb) * int(bs) * hd * _dtype_bytes(pool_dtype)
    out: List[Violation] = []
    for eqn in walk_jaxpr_eqns(artifact.jaxpr):
        if eqn.primitive.name not in ("gather", "take"):
            continue
        # a gather from the pool only (see docstring)
        aval0 = getattr(eqn.invars[0] if eqn.invars else None, "aval", None)
        if math.prod(getattr(aval0, "shape", (0,))) not in (
            rows * hd, layers * rows * hd
        ):
            continue
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            nbytes = math.prod(aval.shape) * _dtype_bytes(
                str(getattr(aval, "dtype", "float32"))
            )
            if nbytes >= lane_bytes:
                out.append(Violation(
                    check="paged_attn",
                    severity="error",
                    program=artifact.name,
                    message=(
                        f"paged decode program still materializes a "
                        f"pool-sized gather: {eqn.primitive.name} -> "
                        f"{tuple(aval.shape)} ({nbytes} bytes >= "
                        f"{lane_bytes} = one lane's virtual-length "
                        f"K/V) — the dense fallback's page gather "
                        f"survived lowering"
                    ),
                    where=(eqn_where(eqn) or eqn.primitive.name),
                    details={
                        "output_shape": list(aval.shape),
                        "nbytes": nbytes,
                        "lane_kv_bytes": lane_bytes,
                    },
                ))
    return out


@register_check("kv_quant")
def check_kv_quant(artifact: ProgramArtifact) -> List[Violation]:
    """Structural proof the quantized KV pool actually shrank: a serve
    program whose details CLAIM ``kv_dtype: "int8"|"fp8"`` (docs/
    SERVING.md "Quantized KV cache and weight-only decode") must lower
    its ``cache_k`` / ``cache_v`` pool inputs with a 1-byte element
    type.  A config that claims int8 while the traced pool aval is still
    float32/bfloat16 prices and reports an HBM footprint it does not
    have — the exact graft this check exists to catch.

    Total: artifacts without a quantized ``kv_dtype`` claim (fp32/bf16
    engines, non-serve programs) skip; one with the claim and no K/V
    pool input recognised is a violation.  Prefill is included — it
    writes the same pool the decode programs read, so a full-precision
    prefill pool is the same lie."""
    det = artifact.details or {}
    if det.get("kv_dtype") not in ("int8", "fp8"):
        return []
    if artifact.role not in ("decode", "draft", "verify", "prefill"):
        return []
    pools = _pool_inputs(artifact)
    if not pools:
        return [_no_pool_violation(
            "kv_quant", artifact, f"kv_dtype {det.get('kv_dtype')!r}"
        )]
    out: List[Violation] = []
    for label, shape, dtype in pools:
        ds = str(dtype)
        # ml_dtypes float8 names don't round-trip through np.dtype —
        # size the aval by name for the 1-byte families
        if ds == "int8" or "float8" in ds or "uint8" in ds:
            nbytes = 1
        else:
            nbytes = _dtype_bytes(ds)
        if nbytes > 1:
            out.append(Violation(
                check="kv_quant",
                severity="error",
                program=artifact.name,
                message=(
                    f"program claims kv_dtype "
                    f"{det.get('kv_dtype')!r} but lowers pool input "
                    f"{label!r} as {ds} ({nbytes} bytes/elem, shape "
                    f"{tuple(shape)}) — the full-precision pool "
                    f"survived, so the claimed HBM/bandwidth savings "
                    f"are fictional"
                ),
                where=f"inputs[{label}]",
                details={
                    "claimed_kv_dtype": det.get("kv_dtype"),
                    "pool_input": label,
                    "pool_dtype": ds,
                    "pool_shape": list(shape),
                },
            ))
    return out


@register_check("replication")
def check_replication(artifact: ProgramArtifact) -> List[Violation]:
    """Operands lowered fully replicated when the strategy says sharded:
    the weight occupies ``degree``x the HBM the placement priced, and its
    collectives vanish — usually a dropped sharding constraint or an
    executor/strategy keying mismatch."""
    if (
        artifact.param_shardings is None
        or artifact.strategy is None
        or artifact.layers is None
    ):
        return []
    from flexflow_tpu.ops.base import get_op_def

    strategy = artifact.strategy
    mesh = strategy.mesh
    out: List[Violation] = []
    for layer in artifact.layers:
        bucket = artifact.param_shardings.get(layer.name)
        if not isinstance(bucket, dict):
            continue  # stacked members key under their template's name
        for w in get_op_def(layer.op_type).weights(layer):
            actual = bucket.get(w.name)
            if actual is None:
                continue
            pspec = strategy.weight_pspec(layer, w.name, len(w.shape))
            degree = 1
            for entry in pspec:
                axes = entry if isinstance(entry, tuple) else (entry,)
                for a in axes:
                    if a is not None:
                        degree *= mesh.axis_size(a)
            if degree <= 1:
                continue
            replicated = getattr(actual, "is_fully_replicated", False)
            if replicated:
                out.append(Violation(
                    check="replication",
                    severity="error",
                    program=artifact.name,
                    message=(
                        f"weight {layer.name}.{w.name} lowered fully "
                        f"replicated but the strategy shards it "
                        f"{degree}-way ({_fmt_pspec(pspec)}) — "
                        f"{degree}x the priced HBM"
                    ),
                    where=f"params[{layer.name}][{w.name}]",
                    details={"intended": _fmt_pspec(pspec),
                             "degree": degree},
                ))
    return out


def _fmt_pspec(pspec: Any) -> str:
    return "P(" + ", ".join(
        "+".join(e) if isinstance(e, tuple) else (str(e) if e else "None")
        for e in pspec
    ) + ")"


def _result_bytes(text: str, opcode: str) -> int:
    """Largest result-buffer size parsed from the ``dtype[dims]`` shapes
    on an HLO instruction line, restricted to the text BEFORE the opcode
    token (the result side of ``=``) so operand shapes never count."""
    import re

    head = text.split(f" {opcode}", 1)[0]
    best = 0
    for dt, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]*)\]", head):
        elems = math.prod(int(x) for x in dims.split(",") if x) if dims else 1
        best = max(best, elems * _dtype_bytes(dt))
    return best


@register_check("overlap")
def check_overlap(artifact: ProgramArtifact) -> List[Violation]:
    """Structural proof the overlapped gradient sync happened: a fit
    program that CLAIMS the in-scan ring (docs/PERF.md "Overlapped
    gradient sync") must lower the ring's (n−1)-hop ``collective-permute``
    chain per ringed bucket, and must NOT still carry a fused tail
    ``all-reduce`` at the full stacked bucket bytes — either one means
    the ring was claimed (and priced) but the fused sync survived
    lowering.

    Total: artifacts without a ``grad_ring`` detail claiming
    ``"ring"`` with at least one chain, or without compiled HLO, skip.
    Forward/serve programs never carry the detail.  Small all-reduces
    (loss/metric scalars, per-slice reductions inside the scan body —
    at most ``bucket_bytes / depth``) sit below the threshold and
    pass."""
    det = (artifact.details or {}).get("grad_ring") or {}
    chains = det.get("chains") or []
    if det.get("grad_overlap") != "ring" or not chains or not artifact.hlo:
        return []
    from flexflow_tpu.analysis.collectives import extract_collectives

    summary = extract_collectives(artifact.hlo, artifact.mesh)
    out: List[Violation] = []
    # (a) the ring's permute chain must be in the program: at least
    # hops = n−1 collective-permutes attributed to the data axis
    # (unattributed ops — no mesh on the artifact — count permissively)
    need_hops = max(c["hops"] for c in chains)
    n_perm = sum(
        1 for op in summary.ops
        if op.kind == "collective-permute"
        and (op.axes is None or "data" in op.axes)
    )
    if n_perm < need_hops:
        out.append(Violation(
            check="overlap",
            severity="error",
            program=artifact.name,
            message=(
                f"grad-overlap ring claimed but the lowered program has "
                f"{n_perm} data-axis collective-permute(s) — the ring "
                f"all-gather needs at least {need_hops} hops; the fused "
                f"path was priced away but never replaced"
            ),
            details={"permutes": n_perm, "need_hops": need_hops},
        ))
    # (b) no fused tail sync may survive at full stacked bucket bytes:
    # the ring moved the reduction INTO the scan body at per-slice size
    floor = min(c["bucket_bytes"] for c in chains)
    for op in summary.ops:
        if op.kind != "all-reduce":
            continue
        nbytes = _result_bytes(op.text, "all-reduce")
        if nbytes >= floor:
            out.append(Violation(
                check="overlap",
                severity="error",
                program=artifact.name,
                message=(
                    f"grad-overlap ring claimed but a fused all-reduce "
                    f"at {nbytes} bytes survived (HLO line {op.line_no}) "
                    f">= the smallest ringed bucket ({floor} bytes) — "
                    f"the tail sync the ring was priced to eliminate is "
                    f"still in the program"
                ),
                details={"nbytes": nbytes, "bucket_bytes_floor": floor,
                         "line_no": op.line_no},
            ))
    return out
