"""Artifact capture: turn live runtime objects (Executor, ServeEngine,
or any jitted callable) into :class:`ProgramArtifact`\\ s the checks
understand.

Capture is built on ``jitted.trace(*args)`` — abstract evaluation only,
no execution, no donation, no compile — plus the AOT executable the
caller already owns (the executor's ``_step_compiled``, or a fresh
``.lower().compile()`` when none exists).  So ``--verify-compiled``
costs one trace walk on top of the compile the program needed anyway.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

from flexflow_tpu.analysis.core import (
    AnalysisReport,
    ProgramArtifact,
    analyze_program,
    flatten_info,
)


def _labeled_inputs(args_info: Any, arg_names: Sequence[str]):
    """Flatten ``trace(...).args_info`` into labeled rows, naming each
    leaf by its top-level argument (``params[dense1][kernel]``)."""
    top = args_info
    # jax reports ``(positional_args_tuple, kwargs_dict)`` (older
    # versions wrapped the positional tuple alone one level deep)
    if (
        isinstance(top, tuple) and len(top) == 2
        and isinstance(top[0], tuple) and isinstance(top[1], dict)
    ):
        top = top[0] + tuple(top[1].values())
    elif isinstance(top, tuple) and len(top) == 1 and isinstance(top[0], tuple):
        top = top[0]
    if isinstance(top, (tuple, list)) and len(top) == len(arg_names):
        rows = []
        for name, sub in zip(arg_names, top):
            rows.extend(flatten_info(sub, name))
        return rows
    return flatten_info(args_info, "arg")


def capture_jit(
    name: str,
    role: str,
    jitted: Any,
    args: Tuple,
    *,
    compiled: Any = None,
    arg_names: Sequence[str] = (),
    mesh: Any = None,
    strategy: Any = None,
    layers: Any = None,
    compute_dtype: str = "float32",
    implied: Any = None,
    expects_donation: bool = True,
    param_shardings: Any = None,
    details: Any = None,
) -> ProgramArtifact:
    """Build an artifact from one jitted callable + example args.
    ``compiled`` reuses an existing AOT executable; otherwise the
    capture lowers and compiles one itself."""
    tr = jitted.trace(*args)
    if compiled is None:
        compiled = tr.lower().compile()
    hlo = ""
    try:
        hlo = compiled.as_text()
    except Exception:
        pass
    inputs = _labeled_inputs(
        tr.args_info,
        arg_names or tuple(f"arg{i}" for i in range(len(args))),
    )
    outputs = [
        (shape, dtype)
        for _, shape, dtype, _ in flatten_info(tr.out_info, "out")
    ]
    return ProgramArtifact(
        name=name,
        role=role,
        hlo=hlo,
        jaxpr=tr.jaxpr,
        mesh=mesh,
        strategy=strategy,
        layers=layers,
        compute_dtype=compute_dtype,
        inputs=inputs,
        outputs=outputs,
        implied=implied,
        expects_donation=expects_donation,
        param_shardings=param_shardings,
        details=details or {},
    )


def _executor_implied(ex, forward_only: bool):
    from flexflow_tpu.search.cost import implied_collectives

    layers = (
        ex.strategy.rewritten_layers
        if getattr(ex.strategy, "rewritten_layers", None)
        else ex.layers
    )
    implied = implied_collectives(
        layers,
        ex.strategy,
        forward_only=forward_only,
        extra_axes=("data",) if ex.zero1 else (),
        # the executor's EXACT ring plan (not the search's estimate):
        # layers whose weight-grad sync runs as the in-scan ring get
        # optional reduce-scatter/collective-permute companions
        grad_ring_layers=getattr(ex, "_grad_ring_layers", frozenset()),
    )
    if ex.pipeline is None:
        # the executor declined the strategy's pipeline (or none was
        # set): the handoff ppermute is not in this program
        implied = [e for e in implied if not e.reason.startswith("pipeline")]
    return implied


def _param_shardings(compiled) -> Optional[dict]:
    """The params subtree of the executable's input shardings —
    ``layer -> wname -> Sharding`` for the replication audit."""
    try:
        args_shardings, _ = compiled.input_shardings
        tree = args_shardings[0]
        return tree if isinstance(tree, dict) else None
    except Exception:
        return None


def _grad_ring_details(ex) -> dict:
    """The executor's ring claim, for the ``overlap`` check
    (analysis/checks.py): per ringed chain, the data extent (ring
    degree), hop count, and ``bucket_bytes`` — the LARGEST ringed
    leaf's full stacked bytes (depth x weight bytes), i.e. the size of
    the fused tail all-reduce the ring must have eliminated from the
    lowered program.  (The fused path syncs each stacked leaf as its
    own all-reduce, so the largest leaf — not the bucket sum — is what
    a surviving tail sync lowers at.)"""
    plans = getattr(ex, "_grad_ring", None)
    out = {"grad_overlap": getattr(ex, "grad_overlap", "off"), "chains": []}
    if not plans:
        return out
    import numpy as np

    from flexflow_tpu.ops.base import _dtype_bytes

    n = ex.strategy.mesh.axis_size("data")
    for c in ex._block_chains:
        plan = plans.get(c.start)
        if not plan:
            continue
        bucket_bytes = c.depth * max(
            int(np.prod(w.shape)) * _dtype_bytes(w.dtype)
            for tl in c.template
            for w in ex._wspecs[int(tl.layer_guid)]
            if w.name in plan.get(tl.name, {})
        )
        out["chains"].append({
            "start": int(c.start),
            "depth": int(c.depth),
            "ring_degree": int(n),
            "hops": int(n - 1),
            "bucket_bytes": int(bucket_bytes),
        })
    return out


def artifact_from_executor_step(
    ex, args: Tuple, compiled: Any = None
) -> ProgramArtifact:
    """The fit-step artifact: trace ``ex._step_jit`` at the step's real
    args, pair with the AOT executable."""
    return capture_jit(
        "fit",
        "fit",
        ex._step_jit,
        args,
        compiled=compiled,
        arg_names=("params", "state", "opt_state", "inputs", "labels", "step"),
        mesh=ex.mesh,
        strategy=ex.strategy,
        layers=ex.layers,
        compute_dtype=str(ex.compute_dtype),
        implied=_executor_implied(ex, forward_only=False),
        param_shardings=_param_shardings(compiled) if compiled is not None else None,
        details={"grad_ring": _grad_ring_details(ex)},
    )


def _synth_batch(ex):
    """A shape/dtype-correct dummy batch for capture-only compiles."""
    import numpy as np

    from flexflow_tpu.fftype import DataType

    rng = np.random.default_rng(0)
    xs = []
    for t in ex.graph_inputs:
        if t.dtype in (DataType.INT32, DataType.INT64):
            xs.append(np.zeros(t.shape, np.int32))
        elif t.dtype == DataType.BOOLEAN:
            xs.append(np.zeros(t.shape, bool))
        else:
            xs.append(rng.normal(size=t.shape).astype(np.float32))
    if "CROSSENTROPY" in ex.loss_type.name:
        y = np.zeros((ex.graph_inputs[0].shape[0], 1), np.int32)
    else:
        y = np.zeros(ex.logits.shape, np.float32)
    return xs, y


def analyze_executor(
    ex,
    programs: Sequence[str] = ("fit",),
    checks: Optional[Sequence[str]] = None,
) -> AnalysisReport:
    """Analyze an executor's compiled program(s), synthesizing a dummy
    batch when none has run yet.  ``programs``: subset of
    ``("fit", "eval")``."""
    report = AnalysisReport()
    xs_np, y_np = _synth_batch(ex)
    inputs = [
        ex._place(x, ex._input_pspec(t), t.shape[0])
        for x, t in zip(xs_np, ex.graph_inputs)
    ]
    labels = ex._place(y_np, ex._label_pspec(), ex.graph_inputs[0].shape[0])
    if "fit" in programs:
        if ex._step_jit is None:
            ex._step_jit = ex._build_step()
            ex._step_compiled = None
        args = (ex.params, ex.state, ex.opt_state, inputs, labels, 0)
        compiled = ex._step_compiled
        if compiled is None or compiled is ex._step_jit:
            try:
                compiled = ex._step_jit.lower(*args).compile()
                ex._step_compiled = compiled
            except Exception:
                compiled = None
        art = artifact_from_executor_step(ex, args, compiled)
        report.add_program(art.name)
        report.extend(analyze_program(art, checks))
    if "eval" in programs:
        if ex._fwd_jit is None:
            ex._fwd_jit = ex._build_fwd()
        args = (ex.params, ex.state, inputs, None)
        art = capture_jit(
            "eval",
            "eval",
            ex._fwd_jit,
            args,
            arg_names=("params", "state", "inputs", "seq_length"),
            mesh=ex.mesh,
            strategy=ex.strategy,
            layers=ex.layers,
            compute_dtype=str(ex.compute_dtype),
            implied=_executor_implied(ex, forward_only=True),
            expects_donation=False,
        )
        report.add_program(art.name)
        report.extend(analyze_program(art, checks))
    return report


def analyze_serve_engine(
    engine, checks: Optional[Sequence[str]] = None
) -> AnalysisReport:
    """Analyze a ServeEngine's decode + prefill (and, when speculative
    decoding is on, draft + verify) programs.  No strategy
    reconciliation (the decode programs are hand-written, not
    search-placed) — the transfer/donation/dtype audits carry the
    zero-sync-serve and paged-KV-donation guarantees.

    Additionally audits copy-on-write safety (``serve_cow``): every
    serve program DONATES the whole paged K/V pool and writes, in
    place, the pages its tables name (the page-write kernel on a paged
    engine, the XLA scatter on a gather one — ``engine.kv_write``), so
    a block mapped by a slot's writable
    region while still shared (refcount > 1) or prefix-indexed would be
    silently corrupted for every other table that maps it.  The
    allocator's :meth:`PagedKVCache.shared_write_hazards` must therefore
    be empty whenever programs can run — donation of shared blocks is
    never declared."""
    import jax.numpy as jnp

    from flexflow_tpu.analysis.core import Violation

    ex = engine.model.executor
    kv = engine.kv
    B, MB = engine.slots, kv.max_blocks_per_seq
    z = jnp.zeros((B,), jnp.int32)
    bt0 = jnp.zeros((B, MB), jnp.int32)
    dt = str(ex.compute_dtype)
    report = AnalysisReport()
    # quantized pools (r19): the serve programs take the scale pools
    # alongside the element pools, and int8 weight-only decode swaps
    # the params arg for the engine's quantized (qparams, scales) tuple
    # — capture exactly what the engine runs so the kv_quant check sees
    # the truth
    pool_args = (kv.cache_k, kv.cache_v) + (
        (kv.scale_k, kv.scale_v) if kv.quantized else ()
    )
    pool_names = ("cache_k", "cache_v") + (
        ("scale_k", "scale_v") if kv.quantized else ()
    )
    params_arg = getattr(engine, "_params_arg", ex.params)
    programs = [
        (
            "serve.decode",
            engine._decode,
            (params_arg,) + pool_args + (z, z, bt0),
            ("params",) + pool_names + ("tok", "pos", "block_tables"),
        ),
        (
            "serve.prefill",
            engine._prefill,
            (params_arg,) + pool_args + (
                jnp.zeros((B, engine.prefill_chunk), jnp.int32),
                z, jnp.ones((B,), jnp.int32), bt0,
            ),
            ("params",) + pool_names + ("toks", "start", "n_valid",
             "block_tables"),
        ),
    ]
    if getattr(engine, "_draft", None) is not None:
        programs.append((
            "serve.draft",
            engine._draft,
            (params_arg,) + pool_args + (z, z, bt0),
            ("params",) + pool_names + ("tok", "pos", "block_tables"),
        ))
        programs.append((
            "serve.verify",
            engine._verify,
            (params_arg,) + pool_args + (
                jnp.zeros((B, engine.spec_k + 1), jnp.int32), z, bt0,
            ),
            ("params",) + pool_names + ("toks", "pos0",
             "block_tables"),
        ))
    # pool geometry + the engine's resolved attention kernel ride the
    # artifact so the ``paged_attn`` audit can size its materialization
    # threshold (one lane's virtual-length K/V bytes) and knows which
    # programs CLAIM to be gather-free
    serve_details = {
        "serve_attn": getattr(engine, "attn_kernel", "gather"),
        "max_blocks_per_seq": MB,
        "block_size": kv.block_size,
        "slots": B,
        # quantization claims (r19): the kv_quant check cross-examines
        # these against the captured pool avals — a config that CLAIMS
        # int8/fp8 KV while lowering a full-precision cache_k is lying
        # about its HBM footprint
        "kv_dtype": kv.kv_dtype,
        "weight_dtype": getattr(engine, "weight_dtype", "fp32"),
    }
    for name, jitted, args, names in programs:
        art = capture_jit(
            name,
            name.split(".", 1)[1],
            jitted,
            args,
            arg_names=names,
            mesh=ex.mesh,
            compute_dtype=dt,
            details=serve_details,
        )
        report.add_program(art.name)
        report.extend(analyze_program(art, checks))
    # serve_cow: CoW safety as an ffcheck invariant — a live allocator
    # state where a shared/indexed block sits in a slot's writable
    # region means a donated in-place write would corrupt other tables
    if checks is None or "serve_cow" in checks:
        report.add_program("serve.kvcache")
        try:
            hazards = kv.shared_write_hazards()
        except Exception:
            hazards = []  # checks are total: never raise
        report.extend([
            Violation(
                check="serve_cow",
                severity="error",
                program="serve.kvcache",
                message=(
                    f"slot {slot} may write logical block {idx} -> "
                    f"physical {blk} which is shared "
                    f"(refcount {kv.refcount(blk)}) or prefix-indexed; "
                    "donated in-place writes would corrupt every other table "
                    "mapping it (copy-on-write discipline breached)"
                ),
                where=f"slot{slot}/block{idx}",
                details={
                    "slot": slot, "logical_idx": idx, "block": blk,
                    "refcount": kv.refcount(blk),
                },
            )
            for slot, idx, blk in hazards
        ])
    return report


def analyze_disagg_cluster(
    cluster, checks: Optional[Sequence[str]] = None
) -> AnalysisReport:
    """Analyze a :class:`~flexflow_tpu.serve.disagg.DisaggregatedCluster`:
    both pools' serve programs (renamed ``prefill.*`` / ``decode.*``)
    plus the ``serve_handoff`` audit — every delivered or in-flight
    ``ffkv/1`` frame must digest-verify, the pools must not share KV
    device buffers (cross-pool donation would corrupt both), and no
    request may be active in both pools at once.  Per-pool CoW safety
    rides on each pool's own ``serve_cow`` check."""
    import dataclasses as _dc

    from flexflow_tpu.analysis.core import Violation

    report = AnalysisReport()
    for pool, eng in (
        ("prefill", cluster.prefill), ("decode", cluster.decode),
    ):
        sub = analyze_serve_engine(eng, checks)
        for name in sub.programs:
            report.add_program(f"{pool}.{name}")
        report.extend([
            _dc.replace(v, program=f"{pool}.{v.program}")
            for v in sub.violations
        ])
    if checks is None or "serve_handoff" in checks:
        report.add_program("disagg.handoff")
        try:
            rows = list(cluster.handoff_audit())
        except Exception:
            rows = []  # checks are total: never raise
        report.extend([
            Violation(
                check="serve_handoff",
                severity="error",
                program="disagg.handoff",
                message=f"[{r.get('check')}] {r.get('message')}",
                where=str(r.get("check", "")),
                details=dict(r),
            )
            # pool-local CoW rows are already reported by each pool's
            # serve_cow check above — don't double-count them here
            for r in rows
            if r.get("check") != "serve_cow"
        ])
    return report
