"""The zero-per-step-sync serve loop over the paged serve programs.

Execution model (docs/SERVING.md):

* The four jitted programs (``decode``, ``prefill``, ``draft``,
  ``verify``) are one decoder trunk over the donated paged K/V pools,
  built by :func:`flexflow_tpu.serve.programs.build_serve_programs`; the
  engine warms them up in one chain, so that all agree on ONE buffer
  layout.  ONE decode step serves every slot every step, and ONE batched
  chunked prefill ingests ``prefill_chunk`` prompt positions of ALL
  mid-prefill slots per window (static shapes: one compile serves every
  prompt length and every active set).  Chunks are scheduled between
  decode windows so a long prompt never stalls running decodes for its
  whole length.
* The loop runs in **flush windows** (the async-fit discipline of
  ``FFModel.fit`` applied to serving): within a window, decode steps
  chain the next-token array device-to-device — greedy argmax happens
  ON device — and the host fetches nothing.  One host sync per window
  (``Executor.count_host_sync`` ledger, same as training) drains the
  buffered tokens, detects EOS/budget finishes, recycles slots, admits
  queued requests, and emits one ``ffmetrics/1`` record.  Window length
  adapts to ``min(sync_every, tokens remaining)`` so a finishing
  request is recycled the step its budget ends.

The observable-latency consequence is deliberate and documented: a
token becomes visible at its window's flush, so TTFT/TPOT include up to
``sync_every`` steps of batching delay — the same latency/throughput
knob the ServeObjective prices (objective.py).
"""

from __future__ import annotations

import dataclasses
import math
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.dataloader import DevicePrefetcher
from flexflow_tpu.models.gpt_decode import GPTSpec
from flexflow_tpu.obs import (
    MetricsStream,
    SpanRecorder,
    get_tracer,
    step_record,
)
from flexflow_tpu.runtime.faults import get_fault_plan
from flexflow_tpu.serve.kvcache import PagedKVCache, kv_pool_dtype
from flexflow_tpu.serve.programs import (
    build_serve_programs,
    weights_as_consumed,
)
from flexflow_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    RequestState,
)

__all__ = [
    "ServeEngine",
    "ServeReport",
    "UnsupportedServeConfig",
    "count_pool_relayouts",
    "count_weight_casts",
    "load_drain",
    "save_drain",
]

# drain payload schema id (docs/RESILIENCE.md): in-flight KV spills +
# queue contents, written atomically so a killed drain leaves either
# nothing or a complete payload.  The flattening/digest machinery is
# shared with the ffkv/1 handoff wire format (serve/wire.py) — one
# codec, two framings (a whole engine to disk vs one request over a
# pool-to-pool transport).
DRAIN_SCHEMA = "ffdrain/1"


def save_drain(path: str, payload: Dict[str, Any]) -> str:
    """Persist a :meth:`ServeEngine.drain` payload as one atomic,
    digest-checked ``.npz`` (the checkpoint writer's temp + fsync +
    ``os.replace`` discipline).  Returns the path written."""
    from flexflow_tpu.model import _write_checkpoint_atomic
    from flexflow_tpu.serve.wire import flatten_requests

    flat, metas = flatten_requests(payload["requests"])
    return _write_checkpoint_atomic(
        path, flat, {"schema": DRAIN_SCHEMA, "requests": metas},
    )


def load_drain(path: str) -> Dict[str, Any]:
    """Read a :func:`save_drain` file back into the in-memory payload
    shape :meth:`ServeEngine.resume_from_drain` consumes.  Refuses
    torn/corrupt files with the checkpoint loader's truthful errors."""
    import zipfile

    from flexflow_tpu.model import CheckpointError
    from flexflow_tpu.serve.wire import (
        HandoffError,
        unflatten_requests,
        verify_flat,
    )

    try:
        with np.load(path) as z:
            flat = {k: np.asarray(z[k]) for k in z.files}
    except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
        raise CheckpointError(
            f"drain file {path!r} is torn or truncated "
            f"({type(e).__name__}: {e}); refusing to load"
        ) from e
    try:
        manifest = verify_flat(flat, f"drain file {path!r}")
    except HandoffError as e:
        raise CheckpointError(str(e)) from e
    requests = unflatten_requests(flat, manifest["requests"])
    return {"schema": manifest["schema"], "requests": requests}


# one instruction of a compiled module's text: result type, then the
# first `` word(`` after it, which is the operation
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(")
_HLO_ARRAY = re.compile(r"[a-z]+(\d+)\w*\[([\d,]*)\]")


def count_pool_relayouts(hlo_text: str, pool_nbytes: int,
                         itemsize: Optional[int] = None) -> int:
    """How many operations of a compiled program's text (fused ones
    included) ``copy`` or ``transpose`` into an array of ``pool_nbytes``
    bytes, under any shape: a whole K/V pool re-laid out, which is what
    a pool geometry the kernels cannot read at rest costs at every call
    (PERF.md, PR 27 and PR 29).  With ``itemsize``, only arrays of
    elements that wide: a chunk's bfloat16 activations ``(slots, 256,
    4096)`` are as many bytes as a float32 state ``(slots, 64, 64,
    128)`` and are not it.  Not counted: ``copy-start`` /
    ``copy-done``, the compiler staging a small array in faster
    memory."""
    n = 0
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if not m or m.group(2) not in ("copy", "transpose"):
            continue
        made = _HLO_ARRAY.search(m.group(1))
        if made is None:
            continue
        dims = [int(d) for d in made.group(2).split(",") if d]
        bits = int(made.group(1))
        if itemsize is not None and bits != 8 * itemsize:
            continue
        n += math.prod(dims) * bits // 8 == pool_nbytes
    return n


_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) \(.*\{$")
_HLO_INSTR = re.compile(
    r"^\s*(ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w-]+)\(([^)]*)\)(.*)$"
)


def count_weight_casts(hlo_text: str, weight_shapes, dtype) -> int:
    """How many ``convert`` operations of a compiled program's text
    (fused ones included) take a float32 weight -- an entry parameter of
    one of ``weight_shapes``, whole or as any operation over weights
    alone leaves it (a slice, a bitcast, the compiler's staging copies),
    followed into and out of the fusions it is handed to -- and make it
    ``dtype``: the compute-dtype copy that a program handed
    float32 weights writes at EVERY call before it multiplies anything
    (PERF.md, PR 33).  The operand is followed by name, not recognised
    by its shape alone: a chunk's normed rows are float32 ``(slots x
    chunk, hidden)`` too, and are not counted."""
    import jax.numpy as jnp

    to = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}[
        jnp.dtype(dtype).name
    ]
    shapes = {",".join(str(int(d)) for d in s) for s in weight_shapes}
    comps: Dict[str, list] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        m = _HLO_INSTR.match(line)
        if m is not None and cur is not None:
            root, name, made, op, args, attrs = m.groups()
            called = re.search(r"calls=%([\w.-]+)", attrs)
            cur.append((
                bool(root), name, made, op, re.findall(r"%[\w.-]+", args)
                or [args], called and called.group(1),
            ))

    def walk(comp, weights_in):
        # (casts counted in ``comp``, whether its root is a weight still);
        # ``weights_in``: which of its parameters are weights, by number
        n, weight, root_is_weight = 0, set(), False
        for root, name, made, op, args, called in comps.get(comp, ()):
            if op == "parameter" and weights_in is None:
                # the entry computation's: by dtype and shape
                dims = re.match(r"f32\[([\d,]*)\]", made)
                is_w = dims is not None and dims.group(1) in shapes
            elif op == "parameter":
                is_w = int(args[0]) in weights_in
            elif op in ("fusion", "call") and called:
                inner, is_w = walk(
                    called, {i for i, a in enumerate(args) if a in weight}
                )
                n += inner
            elif op == "convert":
                is_w = False
                n += args[0] in weight and made.startswith(to + "[")
            else:
                # made of weights alone: a weight moved, cut, staged in
                # faster memory or put together again (bitcast, slice,
                # copy-start / -done, slice-done, ConcatBitcast, ...)
                is_w = all(a in weight for a in args)
            if is_w:
                weight.add(name)
                root_is_weight |= root
        return n, root_is_weight

    return walk(entry, None)[0] if entry else 0


class UnsupportedServeConfig(ValueError):
    """An engine option the decoder at hand is not served with: raised by
    ``ServeEngine.__init__`` before anything is built, never by an
    assertion deep in a program."""


def _pct(vals: Sequence[float], q: float) -> Optional[float]:
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


@dataclasses.dataclass
class ServeReport:
    """End-of-run aggregate (the bench/driver artifact payload)."""

    wall_s: float
    new_tokens: int
    tok_s: float
    requests_finished: int
    requests_rejected: int
    ttft_p50_ms: Optional[float]
    ttft_p99_ms: Optional[float]
    tpot_p50_ms: Optional[float]
    tpot_p99_ms: Optional[float]
    occupancy_mean: float
    windows: int
    decode_steps: int
    prefill_chunks: int
    host_syncs: int
    per_request: List[Dict[str, Any]]
    # --- multi-tenant scale-out (PR 11) ---
    prefix_hit_rate: Optional[float] = None  # shareable lookups that hit
    preemptions: int = 0  # batch-tier spill events
    per_tier: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict
    )  # tier -> finished / ttft_p50_ms / ttft_p99_ms / tpot_p99_ms
    per_tenant: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict
    )
    spec_k: int = 0  # speculative draft depth (0 = off)
    spec_draft_layers: int = 0
    spec_accept_rate: Optional[float] = None  # accepted / drafted
    spec_drafted: int = 0
    spec_accepted: int = 0
    peak_active: int = 0  # max simultaneously-admitted requests
    # --- resilience (docs/RESILIENCE.md) ---
    requests_expired: int = 0  # deadline_ms expiries while queued
    drained: bool = False  # run ended via SIGTERM drain, not queue-empty
    shed: int = 0  # batch requests shed under sustained SLO pressure
    watchdog_fires: int = 0  # windows slower than --serve-watchdog-s
    # --- batched paged prefill (r20) ---
    # ONE jitted prefill dispatch serves every mid-prefill slot per
    # window, so dispatches == windows-with-prefill-work regardless of
    # slot count (prefill_chunks keeps counting per-slot logical chunks)
    prefill_dispatches: int = 0
    prefill_attn_kernel: Optional[str] = None  # kernel prefill ran on
    kv_write: Optional[str] = None  # "page_kernel" | "xla_scatter"
    # compute blocks the paged kernel's lanes walked over the run's decode
    # and prefill calls, and what walking every lane's whole table would
    # have taken (ServeEngine._attn_blocks; None where it does not apply)
    attn_blocks_walked: Optional[int] = None
    attn_blocks_full_table: Optional[int] = None
    # --- window and full layer groups, routed experts (PR 32) ---
    # K/V rows the attention calls of the finished requests had to read,
    # every layer counted (a window layer reads its window), and what
    # they would read were every layer full
    kv_rows_visible: Optional[int] = None
    kv_rows_context: Optional[int] = None
    # most pages mapped into slots' tables at a window's start, a group
    kv_pages_held_full: int = 0
    kv_pages_held_window: int = 0
    # device-side counters of the expert layers, summed over the run's
    # program calls (they ride the window's one sync): valid rows routed
    # (positions x top-k), distinct experts with >= 1 row a (layer, call),
    # and the mean over (layer, call) of the fullest expert's load over
    # the mean load.  None for a model without routed experts
    moe_rows: Optional[int] = None
    moe_experts_touched: Optional[int] = None
    moe_load_max_over_mean: Optional[float] = None
    moe_layers: int = 0
    # --- the state group (PR 34): its footprint, the most slots whose
    # state belonged to a request at a window's start, spills and
    # restores that carried a state, and valid rows through state layers
    # (positions x state layers, from the finished requests' lengths)
    state_pool_bytes: int = 0
    state_slots_held: int = 0
    state_spills: int = 0
    state_restores: int = 0
    ssm_rows: Optional[int] = None
    # --- what it ran on (ServeEngine.device_info) ---
    attn_interpret: bool = False  # paged kernel ran in the Pallas interpreter
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.pop("per_request")
        return d


class ServeEngine:
    """Continuous-batching serving over one compiled decoder
    (``models/gpt_decode.py::GPTSpec`` says which: ``gpt_decoder``,
    ``afmoe_decoder``, ``nemotron_h_decoder``).  Speculation, int8
    weights, a quantized pool and disaggregated or fleet serving are
    built for ``gpt_decoder``-shaped models and refused for the rest --
    a decoder with window or state layers, routed experts (all held or a
    share), layers of one branch -- by name
    (:class:`UnsupportedServeConfig`).

    ``slots`` defaults to the model's compiled batch; the KV pool
    defaults to full provisioning (``num_blocks`` =
    slots x blocks-per-max-seq + trash) — pass a smaller ``num_blocks``
    to oversubscribe HBM (requests then share the pool and admission
    waits on the free list; see the HBM-sharing test).
    """

    def __init__(
        self,
        model,
        *,
        slots: Optional[int] = None,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefill_chunk: int = 32,
        sync_every: int = 4,
        temperature: float = 0.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        metrics_out: Optional[str] = None,
        prefetch_depth: int = 2,
        prefix_sharing: bool = True,
        attn: str = "auto",
        kv_dtype: str = "fp32",
        weight_dtype: str = "fp32",
        spec_k: int = 0,
        spec_draft_layers: int = 0,
        watchdog_s: float = 0.0,
        shed_after_windows: int = 0,
        slo_ms: float = 50.0,
        drain_path: Optional[str] = None,
        phase: Optional[str] = None,
        spans_out: Optional[str] = None,
        span_recorder: Optional[SpanRecorder] = None,
        metrics_max_mb: float = 0.0,
        slo=None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.model = model
        self.spec = GPTSpec.from_model(model)
        if not self.spec.is_gpt:
            for what, on in (
                (f"speculation (spec_k={spec_k})", int(spec_k) > 0),
                (f"weight_dtype {weight_dtype!r}", str(weight_dtype) != "fp32"),
                (f"a quantized pool (kv_dtype {kv_dtype!r})",
                 str(kv_dtype) in ("int8", "fp8")),
                (f"a disaggregated or fleet pool (phase {phase!r})",
                 phase is not None),
                ("--verify-compiled (its serve audit knows one pool group)",
                 getattr(model.config, "verify_compiled", "off") != "off"),
            ):
                if on:
                    raise UnsupportedServeConfig(
                        f"{what} is built for gpt_decoder-shaped models "
                        "(learned positions, LayerNorm, one head count, GELU "
                        "FFN); this decoder is served without it"
                    )
        self.slots = int(slots or self.spec.batch)
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.sync_every = max(1, int(sync_every))
        self.temperature = float(temperature)
        if self.temperature > 0.0:
            # sampling needs the distribution on host before the next
            # token can be fed — that is a per-step sync by definition
            self.sync_every = 1
        self._rng = np.random.default_rng(seed)
        self.eos_id = eos_id
        # speculative decoding (docs/SERVING.md): draft with the first
        # ``spec_draft_layers`` of the chain, verify ``spec_k`` drafts
        # in one batched step.  Greedy-only: sampling re-introduces a
        # per-step host draw, which defeats both spec and the zero-sync
        # window, so temperature > 0 turns it off.
        self.spec_k = max(0, int(spec_k))
        self.spec_draft_layers = max(0, int(spec_draft_layers))
        if self.spec_k and not (
            0 < self.spec_draft_layers < self.spec.num_layers
        ):
            # a sane default: half-depth draft (at least one layer)
            self.spec_draft_layers = max(1, self.spec.num_layers // 2)
        if self.temperature > 0.0:
            self.spec_k = 0
        # decode-attention kernel (docs/PERF.md "Paged decode
        # attention"): "auto" resolves to the fused Pallas paged kernel
        # wherever it can run (TPU, or interpreter mode forced) and
        # declines to the dense gather otherwise — so a plain CPU run
        # stays byte-identical to the pre-paged engine
        from flexflow_tpu.ops.pallas import paged_attention as _pattn

        dt = model.executor.compute_dtype
        # a page has to be whole sublane tiles of the pool's dtype for
        # the kernels to lower on a TPU: ``auto`` declines to the gather
        # arm where it is not, an explicit ``paged`` says so and raises
        self.attn_kernel = _pattn.resolve_serve_attn(
            attn, block_size, kv_pool_dtype(jnp, str(kv_dtype), fallback=dt)
        )
        # the flag is read when the programs trace — at the warmup below
        self.attn_interpret = (
            self.attn_kernel == "paged" and bool(_pattn.INTERPRET)
        )
        # how the programs write new K/V rows into the pool rides the
        # same decision (``write_kv``, programs.py): the Pallas page-write
        # kernel wherever the paged attention kernel runs, the XLA
        # scatter where Pallas cannot.  It engages on every call or none
        self.kv_write = (
            "page_kernel" if self.attn_kernel == "paged" else "xla_scatter"
        )
        # quantized serving arms (docs/SERVING.md "Quantized KV cache
        # and weight-only decode"): kv_dtype picks the pool element
        # format (fp32 = the engine's compute dtype — the legacy pool),
        # weight_dtype="int8" streams per-channel-scaled int8 decode
        # weights dequantized at the matmul edge
        self.kv_dtype = str(kv_dtype)
        self.weight_dtype = str(weight_dtype)
        if self.weight_dtype not in ("fp32", "int8"):
            raise ValueError(
                f"weight_dtype {self.weight_dtype!r}: expected fp32 | int8"
            )
        # the pool's row is the K/V heads'; window layers live in a
        # group of their own (a ring a slot, kvcache.py), state-space
        # layers' recurrent states in a third, provisioned a slot
        attn = [b for b in self.spec.branches if b.is_attention]
        n_win = sum(1 for b in attn if b.window)
        state = self.spec.state_layers
        state_shapes = {}
        if state:
            from flexflow_tpu.ops.ssm import mamba2_dims

            a = state[0].attrs
            h, p, _, n, _, cw = mamba2_dims(a)
            state_shapes = dict(
                state_conv=(a["conv_kernel"] - 1, cw), state_ssm=(h, p, n),
            )
        self.kv = PagedKVCache(
            len(attn) - n_win, self.spec.kv_heads,
            self.spec.head_dim,
            slots=self.slots, block_size=block_size,
            num_blocks=num_blocks, max_seq_len=self.spec.seq, dtype=dt,
            kv_dtype=self.kv_dtype, prefix_sharing=prefix_sharing,
            window_layers=n_win, window=self.spec.window,
            chunk=self.prefill_chunk,
            state_layers=len(state), **state_shapes,
        )
        self.sched = ContinuousBatchingScheduler(self.slots, self.kv)
        self.metrics = MetricsStream(metrics_out, max_mb=metrics_max_mb)
        # SLO burn-rate engine (obs/slo.py): fed the SAME window record
        # the metrics stream gets, strictly after the window's single
        # host sync — attaching it adds zero syncs and leaves every
        # stream byte-identical.  A disagg cluster passes ONE shared
        # engine to both pools (per-phase counter deltas inside).
        self.slo = slo
        # live introspection (serve/introspect.py): when a StatusServer
        # is attached it flips ``publish_status`` and the window loop
        # publishes an immutable snapshot dict by atomic reference swap
        # — no locks on the hot path, readers see old-or-new, never torn
        self.publish_status = False
        self.status_snapshot: Optional[Dict[str, Any]] = None
        # per-request distributed tracing (ffspan/1, obs/spans.py): a
        # disagg cluster passes ONE shared recorder to both pool engines
        # (shared clock base + unique span ids); a colocated engine owns
        # its own when --serve-spans-out names a path.  None = off, and
        # every emission site below is behind a None check — the serve
        # streams and the host-sync ledger are untouched (pinned).
        if span_recorder is not None:
            self.spans: Optional[SpanRecorder] = span_recorder
            self._owns_spans = False
        elif spans_out:
            self.spans = SpanRecorder(spans_out, max_mb=metrics_max_mb)
            self._owns_spans = True
        else:
            self.spans = None
            self._owns_spans = False
        self.sched.spans = self.spans
        self.sched.pool = phase
        # disaggregated-pool role (docs/SERVING.md): None = colocated
        # (the classic engine, records unchanged); "prefill"/"decode"
        # stamp every window record's serve vocabulary with the pool
        # the window ran on — ADDITIVE ffmetrics/1, old readers ignore
        # it and tools/serve_report.py renders a per-phase section
        self.phase = phase
        self._handoff_ms_w: List[float] = []
        self._handoff_obs_w: List[float] = []
        self._migrated_blocks_w = 0
        self._migrated_bytes_w = 0
        self.prefetch_depth = max(1, int(prefetch_depth))
        # search prediction pairing (calibration loop): a strategy from
        # ``unity_search --objective serve`` carries the ServeObjective's
        # priced one-token decode step time / tokens/s — thread them into
        # every window record so ``CalibrationStore.ingest_serve_metrics``
        # can calibrate the decode roofline from production streams.
        # Nullable: a demo model without a serve search emits None.
        sp = getattr(model.strategy, "serve_price", None) or {}
        self.predicted_step_s = sp.get("step_s")
        self.predicted_tok_s = sp.get("tok_s")

        # --- the compiled programs: one trunk, four programs (programs.py)
        progs = build_serve_programs(
            model, self.kv, attn_kernel=self.attn_kernel,
            weight_dtype=self.weight_dtype, spec_k=self.spec_k,
            spec_draft_layers=self.spec_draft_layers,
            # greedy decoding needs the argmax alone: the distribution
            # stays on the device (and out of the program)
            return_probs=self.temperature > 0.0,
        )
        self._n_head = progs.n_head
        self._moe_layers = sum(1 for b in self.spec.branches if b.kind == "moe")
        # the expert layers' counters, summed on the device call by call
        # and read with the window's tokens
        self._moe_acc = None
        self._params_arg = progs.params_arg
        self._decode, self._prefill = progs.decode, progs.prefill
        self._draft, self._verify = progs.draft, progs.verify
        B, MB, P = self.slots, self.kv.max_blocks_per_seq, self.prefill_chunk

        # warmup both programs once so the cache layout/sharding
        # stabilizes (same rationale as GPTDecodeSession) and steady
        # state replays compiled code only
        idle_decode, idle_prefill = self._idle_args()
        z, _, bt0 = idle_decode
        nh = self._n_head
        res = self._decode(self._params_arg, *self._kvs(), *idle_decode)
        bufs = res[nh:]
        res = self._prefill(self._params_arg, *bufs, *idle_prefill)
        bufs = res[nh:]
        # chain one more decode on the prefill's outputs so BOTH
        # programs have seen the other's cache layout — steady state
        # then replays compiled code regardless of phase interleaving
        res = self._decode(self._params_arg, *bufs, z, z, bt0)
        bufs = res[nh:]
        if self.spec_k:
            # the speculative programs join the same warmup chain so
            # all four agree on ONE buffer layout (a second layout
            # would recompile every donated program once per layout)
            res = self._draft(self._params_arg, *bufs, z, z, bt0)
            bufs = res[1:]
            res = self._verify(
                self._params_arg, *bufs,
                jnp.zeros((B, self.spec_k + 1), jnp.int32), z, bt0,
            )
            bufs = res[4:]
            res = self._decode(self._params_arg, *bufs, z, z, bt0)
            bufs = res[nh:]
        # keep the CHAINED warmup buffers as the live pool: the warmup
        # only ever wrote the trash block (all tables were zero), so
        # every real block still holds zeros — and replacing them with
        # fresh device_put arrays would introduce a second buffer
        # layout, recompiling both donated programs once per layout
        self._store_kvs(bufs)

        # --verify-compiled (docs/ANALYSIS.md): the executor's post-
        # compile ffcheck pass, applied to the serve programs — the
        # transfer/donation/dtype audits carry the zero-sync-serve and
        # paged-KV-donation guarantees at the program level
        self.last_analysis = None
        self.analysis_violations: Optional[int] = None
        vc = getattr(model.config, "verify_compiled", "off")
        if vc != "off":
            from flexflow_tpu.analysis import (
                AnalysisError,
                analyze_serve_engine,
            )

            report = analyze_serve_engine(self)
            self.last_analysis = report
            self.analysis_violations = len(report.violations)
            tracer = get_tracer()
            if tracer.enabled:
                tracer.counter(
                    "analysis.violations", float(self.analysis_violations)
                )
            if not report.ok:
                if vc == "strict":
                    raise AnalysisError(report)
                print(report.format_human())

        # --- loop state ---------------------------------------------------
        self.windows = 0
        self.decode_steps = 0
        self.prefill_chunks = 0
        # batched-prefill ledger (r20): dispatches, not chunks — one
        # per window with any mid-prefill slot, pinned by tests
        self.prefill_dispatches = 0
        # persistent host staging buffers for the batched prefill
        # chunk arrays — refilled per window, never reallocated
        self._pf_toks = np.zeros((B, P), np.int32)
        self._pf_start = np.zeros((B,), np.int32)
        self._pf_n = np.zeros((B,), np.int32)
        self._pf_bt = np.zeros((B, MB), np.int32)
        self._pf_wbt = (
            np.zeros((B, self.kv.ring_blocks), np.int32)
            if self.kv.window_layers else None
        )
        self._moe_tot = np.zeros((4,), np.float64)
        self._pages_peak = {"full": 0, "window": 0}
        self._state_slots_peak = 0
        self._state0 = (0, 0)  # the cache's spills and restores at a run's start
        self.spec_drafted = 0  # draft tokens proposed (spec mode)
        self.spec_accepted = 0  # draft tokens the full model confirmed
        self.peak_active = 0
        self._occ_sum = 0.0
        self._t0: Optional[float] = None
        # --- resilience state (docs/RESILIENCE.md) ------------------------
        # SIGTERM drain: the handler only sets a flag; the loop drains at
        # the next window boundary (inside the window's own sync budget)
        self.watchdog_s = float(watchdog_s)  # 0 = watchdog off
        self.shed_after_windows = int(shed_after_windows)  # 0 = shed off
        self.slo_ms = float(slo_ms)
        self.drain_path = drain_path
        self._drain_requested = False
        self.drained = False
        self.drain_payload: Optional[Dict[str, Any]] = None
        self.watchdog_fires = 0
        self._slo_breach_windows = 0  # consecutive over-SLO windows

    # --- submission --------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        req_id: int = -1,
        eos_id: Optional[int] = None,
        arrival_s: float = 0.0,
        tenant: str = "default",
        tier: str = "batch",
        deadline_ms: Optional[float] = None,
        session: Optional[str] = None,
    ) -> Request:
        req = Request(
            prompt=prompt, max_new_tokens=max_new_tokens, id=req_id,
            eos_id=eos_id if eos_id is not None else self.eos_id,
            arrival_s=arrival_s, tenant=tenant, tier=tier,
            deadline_ms=deadline_ms, session=session,
        )
        # a budget past the compiled position range / pool size comes
        # back REJECTED with a reason (graceful, never a crash)
        return self.sched.submit(req, now=self._now())

    def _now(self) -> float:
        return time.perf_counter()

    def device_info(self) -> Dict[str, Any]:
        """Where this engine's weights and KV pool actually live: the
        platform as JAX reports it, how many devices the process sees,
        how many of them hold the engine's arrays, and the mesh the
        model was compiled under."""
        jax = self._jax
        used = set()
        for leaf in jax.tree.leaves((self._params_arg, self._kvs())):
            used |= leaf.devices()
        d0 = jax.devices()[0]
        mesh = self.model.strategy.mesh
        return {
            "platform": d0.platform,
            "device_kind": d0.device_kind,
            "device_count": len(jax.devices()),
            "devices_used": len(used),
            "mesh": dict(zip(mesh.axis_names, mesh.shape)),
        }

    # --- pool-buffer threading ---------------------------------------------
    def _idle_args(self):
        """What the decode and the prefill program take after the pools
        when no lane is live (all-zero tables: every write lands in the
        trash block): ``(tok, pos, bt)`` and ``(toks, start, n_valid,
        bt)``."""
        jnp = self._jnp
        B, MB = self.slots, self.kv.max_blocks_per_seq
        z = jnp.zeros((B,), jnp.int32)
        bt0 = jnp.zeros((B, MB), jnp.int32)
        if self.kv.window_layers:
            # both groups' tables, as the programs take them
            bt0 = (bt0, jnp.zeros((B, self.kv.ring_blocks), jnp.int32))
        toks = jnp.zeros((B, self.prefill_chunk), jnp.int32)
        return (z, z, bt0), (toks, z, jnp.ones((B,), jnp.int32), bt0)

    def pool_relayouts(self) -> int:
        """Operations in the compiled decode and prefill programs that
        copy or transpose an array of a pool's byte size
        (:func:`count_pool_relayouts`).  The pool's geometry exists to
        make this 0 on a TPU; ``H * D`` off the 128-lane grid or a page
        that is not whole sublane tiles can bring a copy back, correct
        and slow, and this is where it shows.  A state layer's state
        array counts like a pool: its update is in place on the donated
        buffer, not a copy of it (the conv's tail beside it is a shift
        register of a few rows that every call rewrites whole: not
        counted).  Read on demand
        (``chip_smoke.py``, the status server's ``/poolz``), never at
        build: it lowers and compiles both programs a second time."""
        pools = [getattr(self.kv, n) for n in self._pool_names()] + self.kv.state_ssm
        sizes = {(x.size * x.dtype.itemsize, x.dtype.itemsize) for x in pools}
        return sum(
            count_pool_relayouts(t, n, item)
            for t in self._program_texts(self._params_arg) for n, item in sizes
        )

    def weight_casts(self) -> int:
        """Operations in the compiled decode and prefill programs that
        convert a float32 weight to the compute dtype
        (:func:`count_weight_casts`).  The programs are handed their
        weights already cast (``programs.weights_as_consumed``), so this
        is 0 on every engine whose weights are not int8 -- and what the
        same programs read when lowered with ``executor.params`` of a
        float32-at-rest model is the cast of every stack, every call.
        Read on demand, like :meth:`pool_relayouts`."""
        shapes = {
            tuple(x.shape)
            for x in self._jax.tree.leaves(self.model.executor.params)
        }
        dt = self.model.executor.compute_dtype
        return sum(
            count_weight_casts(t, shapes, dt)
            for t in self._program_texts(self._params_arg)
        )

    def _program_texts(self, params) -> List[str]:
        """The compiled decode and prefill programs' text when handed
        ``params`` (lowered and compiled anew: two compiles)."""
        # shapes, not the live buffers: a running window donates those
        pools = [
            self._jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            for x in self._kvs()
        ]
        return [
            prog.lower(params, *pools, *args).compile().as_text()
            for prog, args in zip(
                (self._decode, self._prefill), self._idle_args()
            )
        ]

    def attn_walk(self) -> Optional[Dict[str, Any]]:
        """How the paged kernel of this engine's programs walks a block
        table (``paged_attention.attention_walk``, which the kernel
        builds its grid and buffers from): ``grid`` (one step a lane),
        ``pages_per_block``, ``max_blocks``.  None on the gather arm."""
        if self.attn_kernel != "paged":
            return None
        from flexflow_tpu.ops.pallas.paged_attention import attention_walk

        walk = attention_walk(
            self.slots, self.kv.block_size, self.kv.max_blocks_per_seq
        )
        if self.kv.window_layers:
            # the window group's walk: over a ring, from the first page a
            # row still sees; a chunk's rows in tiles where heads are grouped
            from flexflow_tpu.ops.pallas.paged_attention import rows_tile

            P, sp = self.prefill_chunk, self.spec
            walk["window"] = dict(
                attention_walk(
                    self.slots, self.kv.block_size, self.kv.ring_blocks,
                    P // rows_tile(P, sp.heads, sp.kv_heads),
                ),
                window=self.kv.window, ring_blocks=self.kv.ring_blocks,
            )
        return walk

    def _kv_rows(self, fin) -> Tuple[int, int]:
        """(visible, context): K/V rows the attention calls of the
        finished requests had to read, summed over the layers -- a full
        layer reads a lane's positions up to its last row, a window
        layer those its rows still see -- and the same were every layer
        full.  Reckoned at the end of a run from the requests' lengths,
        like :meth:`_attn_blocks`."""
        W, P = self.kv.window, self.prefill_chunk
        n_win, n_full = self.kv.window_layers, self.kv.num_layers
        full = win = 0
        for r in fin:
            lo = np.arange(r.shared_prefix_pos, r.prompt_len, P)
            hi = np.minimum(lo + P, r.prompt_len)
            at = r.prompt_len + np.arange(max(0, r.done_tokens - 1))
            full += int(hi.sum() + (at + 1).sum())
            if n_win:
                win += int((hi - np.maximum(0, lo - W + 1)).sum()
                           + np.minimum(at + 1, W).sum())
        return n_full * full + n_win * win, (n_full + n_win) * full

    def _attn_blocks(self, fin) -> Tuple[Optional[int], Optional[int]]:
        """(walked, full table): compute blocks the kernel's lanes took
        over the run's decode and prefill calls, a layer, against
        ``max_blocks`` a lane a call.  Reckoned once, at the end of a
        run, from the lengths of the requests that finished — a live lane
        walks the blocks up to its position, every other lane of a call
        one (the trash block) — so the window pays nothing for it.  Not
        reckoned under speculation (a request's lengths do not say what
        its draft and verify calls read) or on the gather arm."""
        walk = self.attn_walk()
        if walk is None or self.spec_k:
            return None, None
        from flexflow_tpu.ops.pallas.paged_attention import lane_blocks

        geom = dict(
            block_size=self.kv.block_size,
            max_blocks_per_seq=self.kv.max_blocks_per_seq,
        )
        P = self.prefill_chunk
        live = walked = 0
        for r in fin:
            chunks = np.arange(r.shared_prefix_pos, r.prompt_len, P)
            steps = r.prompt_len + np.arange(max(0, r.done_tokens - 1))
            live += len(chunks) + len(steps)
            walked += int(lane_blocks(chunks, P, **geom).sum())
            walked += int(lane_blocks(steps, 1, **geom).sum())
        lanes = (self.decode_steps + self.prefill_dispatches) * self.slots
        walked += max(0, lanes - live)
        return walked, lanes * walk["max_blocks"]

    def _refresh_weights(self) -> None:
        """Before a run (never inside a window): serve what
        ``set_weights`` or a training step put into the executor since
        the programs' weights were cast -- ``weights_as_consumed``
        re-casts the leaves whose source array is another object now, an
        identity check a leaf and no device work when none is.  The int8
        arm quantizes once, at build, as it always did."""
        if self.weight_dtype != "int8":
            self._params_arg = weights_as_consumed(
                self.model.executor, self.spec
            )

    def _kvs(self):
        """The live pool buffers in program-argument order: (ck, cv)
        for a full-precision pool, (ck, cv, sk, sv) for a quantized one,
        (ck, cv, wk, wv) with a window group, then every state layer's
        conv state and every state layer's ssm state — every program
        donates and returns exactly this tuple."""
        kv = self.kv
        return (
            tuple(getattr(kv, name) for name in self._pool_names())
            + tuple(kv.state_conv) + tuple(kv.state_ssm)
        )

    def _pool_names(self) -> Tuple[str, ...]:
        kv = self.kv
        names = ("cache_k", "cache_v")
        if kv.quantized:
            names += ("scale_k", "scale_v")
        if kv.window_layers:  # the window group's pools ride last
            names += ("win_k", "win_v")
        return names

    def _store_kvs(self, bufs) -> None:
        """Write a program's returned pool buffers back as the live
        pool (the counterpart of :meth:`_kvs`)."""
        names, n = self._pool_names(), self.kv.state_layers
        assert len(bufs) == len(names) + 2 * n, (len(bufs), names, n)
        for name, buf in zip(names, bufs):
            setattr(self.kv, name, buf)
        self.kv.state_conv = list(bufs[len(names):len(names) + n])
        self.kv.state_ssm = list(bufs[len(names) + n:])

    def _take(self, res):
        """A decode or prefill call's results: keep its pools (and add
        its expert counters to the device-side sum), return ``(nxt,
        probs)``."""
        self._store_kvs(res[self._n_head:])
        if self._n_head == 3:
            self._moe_acc = res[2] if self._moe_acc is None else self._moe_acc + res[2]
        return res[0], res[1]

    # --- the serve loop ----------------------------------------------------
    def run(self, requests: Optional[Sequence[Request]] = None) -> ServeReport:
        """Serve ``requests`` (plus anything already submitted) until
        the queue drains.  Requests carry open-loop ``arrival_s``
        offsets relative to run start; the loop submits each when its
        arrival time passes (and never waits on completions to do so —
        open loop)."""
        ex = self.model.executor
        pending = sorted(requests or (), key=lambda r: (r.arrival_s, r.id))
        self._refresh_weights()
        t0 = self._t0 = self._now()
        if self.spans is not None and self._owns_spans:
            # a shared (cluster-owned) recorder is based by the cluster
            self.spans.set_base(t0)
        syncs0 = ex.host_syncs
        # the engine is reusable across runs; counters and the report
        # are per-run (the compiled programs and the pool persist)
        self.windows = self.decode_steps = self.prefill_chunks = 0
        self.prefill_dispatches = 0
        self.spec_drafted = self.spec_accepted = 0
        self.peak_active = 0
        self._occ_sum = 0.0
        self.watchdog_fires = 0
        self._slo_breach_windows = 0
        self._moe_tot[:] = 0.0
        self._pages_peak = {"full": 0, "window": 0}
        self._state_slots_peak = 0
        self._state0 = (self.kv.state_spills, self.kv.state_restores)
        fin0 = len(self.sched.finished)
        rej0 = len(self.sched.rejected)
        pre0 = self.sched.preemptions
        exp0 = self.sched.expired
        shed0 = self.sched.shed
        # requests queued via submit() before run() count as arriving
        # at run start for TTFT purposes — and their queue-wait clock
        # (deadline_ms) rebases onto the run-relative timeline the loop
        # passes to admit()
        for r in self.sched.queue:
            if r.arrival_abs_s is None:
                r.arrival_abs_s = t0
                r.t_submit = 0.0
                r.t_enqueued = 0.0
        # SIGTERM = drain request (docs/RESILIENCE.md): the handler only
        # sets a flag; the loop drains at the next window BOUNDARY, so
        # the spill happens inside the normal sync discipline.  Restored
        # in finally; install can fail off the main thread (tests,
        # embedding) — drain then remains available via request_drain().
        import signal as _signal

        self.drained = False
        self._drain_requested = False
        old_handler = None
        try:
            old_handler = _signal.signal(
                _signal.SIGTERM, lambda signum, frame: self.request_drain()
            )
        except ValueError:
            pass
        n_sub = 0
        # window-phase spans (docs/OBSERVABILITY.md, "ff.* spans"): they go
        # to the profiler's trace whether or not the process tracer is on
        tracer = get_tracer()
        try:
            while True:
                if self._drain_requested:
                    self.drain_payload = self.drain()
                    if self.drain_path:
                        save_drain(self.drain_path, self.drain_payload)
                    self.drained = True
                    break
                now = self._now() - t0
                with tracer.span("admit", cat="serve"):
                    while (n_sub < len(pending)
                           and pending[n_sub].arrival_s <= now):
                        r = pending[n_sub]
                        self.sched.submit(r, now=now)
                        r.arrival_abs_s = t0 + r.arrival_s
                        n_sub += 1
                    self.sched.admit(now=now)
                if self.sched.idle:
                    if n_sub >= len(pending):
                        break
                    # open loop: idle until the next arrival is due
                    dt_next = pending[n_sub].arrival_s - (self._now() - t0)
                    if dt_next > 0:
                        with tracer.span("idle", cat="serve"):
                            time.sleep(min(dt_next, 0.05))
                    continue
                self._window()
        finally:
            if old_handler is not None:
                try:
                    _signal.signal(_signal.SIGTERM, old_handler)
                except ValueError:
                    pass
        wall = self._now() - t0
        return self._report(
            wall, ex.host_syncs - syncs0,
            self.sched.finished[fin0:], len(self.sched.rejected) - rej0,
            self.sched.preemptions - pre0,
            expired=self.sched.expired - exp0,
            shed=self.sched.shed - shed0,
        )

    def request_drain(self) -> None:
        """Ask the run loop to drain at the next window boundary (what
        the SIGTERM handler calls; also callable directly)."""
        self._drain_requested = True

    def note_handoff(self, ms: float, blocks: int, nbytes: int,
                     observed_ms: Optional[float] = None) -> None:
        """Record one KV migration landing on this pool (the disagg
        router calls this at delivery).  Accumulates into the NEXT
        window record's ``handoff_ms``/``migrated_blocks``/
        ``handoff_bytes`` serve vocabulary — additive ffmetrics/1.
        ``observed_ms`` is the MEASURED send→deliver wall (PR 16) next
        to the priced ``ms``, so predicted-vs-observed DCN error is
        visible per window; None keeps pre-trace records byte-exact."""
        self._handoff_ms_w.append(float(ms))
        self._migrated_blocks_w += int(blocks)
        self._migrated_bytes_w += int(nbytes)
        if observed_ms is not None:
            self._handoff_obs_w.append(float(observed_ms))

    # --- drain / restore (docs/RESILIENCE.md) -------------------------------
    def drain(self) -> Dict[str, Any]:
        """Spill every in-flight slot to host and unload the queues into
        one payload a restarted engine resumes from.  DECODE slots spill
        their live K/V bit-exactly (:meth:`PagedKVCache.spill` — the
        preemption convention); mid-PREFILL slots drop their partial KV
        and re-ingest on resume (deterministic, so the output stream is
        unchanged).  Greedy decode + bit-exact restore ⇒ the combined
        pre-drain + post-restart token streams equal an uninterrupted
        run's, which the drain/restart test pins byte for byte."""
        sched = self.sched
        tracer = get_tracer()
        with tracer.span("drain", cat="serve"):
            reqs: List[Request] = []
            spilled = 0
            for slot in sorted(sched.active):
                req = sched.active.pop(slot)
                if req.state is RequestState.DECODE and req.done_tokens > 0:
                    # positions with live KV: the full prompt + one write per
                    # decode step taken (the latest token is the next step's
                    # input — no KV yet); same arithmetic as _preempt_one
                    live = req.prompt_len + max(0, req.done_tokens - 1)
                    req.kv_spill = self.kv.spill(slot, live)
                    req.state = RequestState.PREEMPTED
                    spilled += 1
                else:
                    self.kv.release(slot)
                    req.kv_spill = None
                    req.prefill_pos = 0
                    req.state = RequestState.QUEUED
                sched.free_slots.append(slot)
                req.slot = -1
                reqs.append(req)
            reqs.extend(sched.queue)  # admission order, interactive first
            for q in sched._queues.values():
                q.clear()
            if tracer.enabled:
                tracer.instant(
                    "serve_drain", cat="health",
                    requests=len(reqs), spilled=spilled,
                )
                tracer.counter("serve.drains")
            return {
                "schema": DRAIN_SCHEMA,
                "requests": [
                    {
                        "id": int(r.id),
                        "prompt": np.asarray(r.prompt, np.int32),
                        "max_new_tokens": int(r.max_new_tokens),
                        "eos_id": r.eos_id,
                        "tenant": r.tenant,
                        "tier": r.tier,
                        "deadline_ms": r.deadline_ms,
                        "session": r.session,
                        "preemptions": int(r.preemptions),
                        "tokens": list(r.tokens),
                        "kv_spill": r.kv_spill,
                    }
                    for r in reqs
                ],
            }

    def resume_from_drain(self, payload: Dict[str, Any]) -> List[Request]:
        """Reload a :meth:`drain` payload into this engine's queues.
        Spilled requests re-enter as PREEMPTED — the scheduler's
        ``_place`` restores their K/V bit-exactly and they rejoin decode
        mid-stream; the rest re-queue for normal admission.  Call before
        :meth:`run`."""
        schema = payload.get("schema")
        assert schema == DRAIN_SCHEMA, (
            f"drain payload schema {schema!r} != {DRAIN_SCHEMA!r}"
        )
        out: List[Request] = []
        for d in payload["requests"]:
            req = Request(
                prompt=d["prompt"],
                max_new_tokens=int(d["max_new_tokens"]),
                id=int(d["id"]),
                eos_id=d.get("eos_id"),
                tenant=d.get("tenant", "default"),
                tier=d.get("tier", "batch"),
                deadline_ms=d.get("deadline_ms"),
                session=d.get("session"),
            )
            req.tokens = [int(t) for t in d.get("tokens", ())]
            req.preemptions = int(d.get("preemptions", 0))
            kv = d.get("kv_spill")
            if kv is not None:
                req.kv_spill = kv
                req.state = RequestState.PREEMPTED
            else:
                req.state = RequestState.QUEUED
            # bypass submit(): admissibility was proven before the drain
            # and re-checking would re-run the shared-prefix arithmetic
            # against a cold index
            self.sched._queues[req.tier].append(req)
            self.sched._next_id = max(self.sched._next_id, req.id) + 1
            out.append(req)
        return out

    # --- one flush window ---------------------------------------------------
    def _window(self) -> None:
        # fault-injection hook (--fault-plan serve:..., docs/RESILIENCE.md):
        # one call + None check when no plan is installed, ledger-pinned
        plan = get_fault_plan()
        if plan is not None:
            plan.on_serve_window(self)
        jnp = self._jnp
        ex = self.model.executor
        tracer = get_tracer()
        spans = self.spans
        with tracer.span("window", cat="serve"):
            t_win = self._now()
            B, MB = self.slots, self.kv.max_blocks_per_seq
            fin_before = len(self.sched.finished)
            # admission happened just before this window — sample the high-
            # water mark now, before any in-window finishes release slots
            self.peak_active = max(self.peak_active, len(self.sched.active))
            for g, n in self.kv.pages_held().items():
                self._pages_peak[g] = max(self._pages_peak[g], n)
            self._state_slots_peak = max(self._state_slots_peak, self.kv.state_slots_held)
            wbt_pf = self._pf_wbt

            # 1) prefill: ONE batched dispatch covers every mid-prefill
            #    slot (r20) — per-lane block tables/start/n_valid, idle
            #    lanes ride with zero rows and write the trash block, so
            #    the window streams the decode weights once per chunk-batch
            #    instead of once per slot.  Chunk arrays are assembled into
            #    the engine's persistent host buffers (no per-slot np.zeros
            #    churn) and staged H2D once per window through the shared
            #    DevicePrefetcher.
            prefill_done: List[Any] = []  # (req, slot) — lanes read at flush
            chunks = []  # (slot, lo, hi) — per-slot logical chunks
            pf_nxt = pf_probs = None
            for slot in self.sched.prefill_slots():
                req = self.sched.active[slot]
                lo = req.prefill_pos
                hi = min(lo + self.prefill_chunk, req.prompt_len)
                chunks.append((slot, lo, hi))
            if chunks:
                with tracer.span("prefill_dispatch", cat="serve"):
                    toks, start, n_valid, bt_pf = (
                        self._pf_toks, self._pf_start, self._pf_n, self._pf_bt,
                    )
                    toks.fill(0)
                    start.fill(0)
                    n_valid.fill(0)
                    bt_pf.fill(0)
                    if wbt_pf is not None:
                        wbt_pf.fill(0)
                    for slot, lo, hi in chunks:
                        req = self.sched.active[slot]
                        toks[slot, : hi - lo] = req.prompt[lo:hi]
                        start[slot] = lo
                        n_valid[slot] = hi - lo
                        bt_pf[slot] = self.kv.table_row(slot)
                        if wbt_pf is not None:
                            wbt_pf[slot] = self.kv.win_tables[slot]

                    def place(arrs):
                        # the dispatch gets its OWN copy of each staging buffer:
                        # they are refilled next window while this window's
                        # program may still be queued, and the CPU backend
                        # aliases an aligned numpy buffer instead of copying it
                        return tuple(
                            self._jax.device_put(jnp.asarray(a.copy())) for a in arrs
                        )

                    host = (toks, start, n_valid, bt_pf)
                    if wbt_pf is not None:
                        host += (wbt_pf,)
                    (staged,) = list(DevicePrefetcher(
                        [host], place, depth=self.prefetch_depth,
                    ))
                    if wbt_pf is not None:  # both groups' tables, one argument
                        staged = staged[:3] + (staged[3:],)
                    t_c0 = spans.now() if spans is not None else 0.0
                    pf_nxt, pf_probs = self._take(
                        self._prefill(self._params_arg, *self._kvs(), *staged)
                    )
                    self.prefill_chunks += len(chunks)
                    self.prefill_dispatches += 1
                    t_c1 = spans.now() if spans is not None else 0.0
                    for slot, lo, hi in chunks:
                        req = self.sched.active[slot]
                        req.prefill_pos = hi
                        if spans is not None:
                            # host dispatch wall of the batched chunk (device
                            # completion is async by design — no fetch, no
                            # added sync); buffered
                            spans.span(
                                "prefill", req, t_c0, t_c1, pool=self.phase,
                                slot=slot, lo=lo, n=hi - lo,
                            )
                        # register the chunk's fully-written prompt blocks in
                        # the prefix index NOW (not at prefill end): a request
                        # arriving in the next admit round with the same system
                        # prompt re-attaches them instead of allocating —
                        # concurrent sharing, not just warm-cache sharing
                        self.kv.commit_prefix(
                            req.slot, req.prompt, req.prefill_pos
                        )
                        if req.prefill_pos >= req.prompt_len:
                            prefill_done.append((req, slot))

            # 2) decode: chain device tokens for an adaptive window
            dec_slots = self.sched.decode_slots()
            # span bookkeeping: request refs + token counts BEFORE the
            # window, so per-request decode_window/spec spans can be emitted
            # after the flush without touching the dispatch path
            dec_reqs = (
                [(s, self.sched.active[s]) for s in dec_slots]
                if spans is not None else []
            )
            done_before = {s: r.done_tokens for s, r in dec_reqs}
            spec_w: Dict[int, List[int]] = {}
            t_dec0 = spans.now() if spans is not None else 0.0
            buffered: List[Any] = []  # per-step (B,) next-token device arrays
            spec_buf: List[Any] = []  # per-macro (n (B,W), acc (B,)) pairs
            probs_last = None
            steps = 0
            if dec_slots:
                with tracer.span("decode_dispatch", cat="serve"):
                    remaining = [
                        self.sched.active[s].max_new_tokens
                        - self.sched.active[s].done_tokens
                        for s in dec_slots
                    ]
                    cur = np.zeros((B,), np.int32)
                    pos = np.zeros((B,), np.int32)
                    bt = np.zeros((B, MB), np.int32)
                    wbt = None
                    if self.kv.window_layers:
                        wbt = np.zeros_like(self.kv.win_tables)
                    for s in dec_slots:
                        r = self.sched.active[s]
                        cur[s] = r.tokens[-1]
                        pos[s] = r.prompt_len + r.done_tokens - 1
                        bt[s] = self.kv.tables[s]
                        if wbt is not None:
                            wbt[s] = self.kv.win_tables[s]
                    bt_d = self._jax.device_put(jnp.asarray(bt))
                    if wbt is not None:
                        bt_d = (bt_d, self._jax.device_put(jnp.asarray(wbt)))
                    cur_d = self._jax.device_put(jnp.asarray(cur))
                    if self.spec_k:
                        # speculative macro steps: k chained draft calls on the
                        # shallow slice, ONE full-depth verify over the k+1 rows.
                        # verify returns the next macro's (token, position) as
                        # device arrays, so macros chain with NO host fetch —
                        # still one sync per window
                        k = self.spec_k
                        W = k + 1
                        macros = max(
                            1, min(self.sync_every, -(-min(remaining) // W))
                        )
                        pos_d = self._jax.device_put(jnp.asarray(pos))
                        for _ in range(macros):
                            cur_j, pos_j = cur_d, pos_d
                            drafts = []
                            for _j in range(k):
                                res = self._draft(
                                    self._params_arg, *self._kvs(),
                                    cur_j, pos_j, bt_d,
                                )
                                dn = res[0]
                                self._store_kvs(res[1:])
                                drafts.append(dn)
                                cur_j, pos_j = dn, pos_j + 1
                            toks = jnp.stack([cur_d] + drafts, axis=1)  # (B, W)
                            res = self._verify(
                                self._params_arg, *self._kvs(),
                                toks, pos_d, bt_d,
                            )
                            n, acc, cur_d, pos_d = res[:4]
                            self._store_kvs(res[4:])
                            spec_buf.append((n, acc))
                        steps = macros * W  # program invocations this window
                    else:
                        steps = max(1, min(self.sync_every, min(remaining)))
                        for _ in range(steps):
                            # a copy of pos: it is advanced in place below while
                            # this step may still be queued (see place() above)
                            nxt, probs_last = self._take(self._decode(
                                self._params_arg, *self._kvs(),
                                cur_d, jnp.asarray(pos.copy()), bt_d,
                            ))
                            buffered.append(nxt)
                            cur_d = nxt  # device-to-device chain: NO host fetch
                            for s in dec_slots:
                                pos[s] += 1
                    self.decode_steps += steps

            # 3) flush: the window's ONE deliberate host sync
            with tracer.span("sync", cat="serve"):
                t_sync = self._now()
                host_tok = [np.asarray(b) for b in buffered]
                host_spec = [
                    (np.asarray(n), np.asarray(a)) for n, a in spec_buf
                ]
                if prefill_done:
                    # ONE fetch of the batched dispatch's lanes, inside the
                    # window's single sync — indexed per finishing slot.
                    # The distribution comes along only where sampling asks
                    # (greedy programs do not return one: (slots, vocab)
                    # float32 is 0.8 MB a slot at 200 k entries)
                    pf_nxt_h = np.asarray(pf_nxt)
                    pf_probs_h = None if pf_probs is None else np.asarray(pf_probs)
                    host_pre = [
                        (req, int(pf_nxt_h[slot]),
                         None if pf_probs_h is None else pf_probs_h[slot])
                        for req, slot in prefill_done
                    ]
                else:
                    host_pre = []
                if self._moe_acc is not None and (buffered or prefill_done):
                    # the expert layers' counters ride this same sync (a
                    # window that fetches nothing leaves them on the device
                    # for the next: it does not wait for its programs)
                    self._moe_tot += np.asarray(self._moe_acc, np.float64)
                    self._moe_acc = None
                stall = self._now() - t_sync
            with tracer.span("flush", cat="serve"):
                ex.count_host_sync(1, stall)
                flushed_tokens = 0
                spec_drafted_w = spec_accepted_w = 0

                # decode lanes: assign buffered tokens in step order
                for ki in range(len(host_tok)):
                    for s in dec_slots:
                        req = self.sched.active.get(s)
                        if req is None or req.state is not RequestState.DECODE:
                            continue  # finished earlier in this flush (EOS)
                        if self.temperature > 0.0 and probs_last is not None:
                            # sampling mode runs 1-step windows; draw on host
                            from flexflow_tpu.models.transformer import sample_next

                            tok = int(sample_next(
                                np.asarray(probs_last)[s][None],
                                self.temperature, self._rng,
                            )[0])
                        else:
                            tok = int(host_tok[ki][s])
                        req.tokens.append(tok)
                        flushed_tokens += 1
                        self._finish_if_done(req, tok)

                # speculative lanes: each macro contributes its accepted prefix
                # (acc drafts + the verify row's own argmax); tokens past an
                # EOS/budget finish are overshoot and are discarded exactly like
                # the plain-decode overshoot above
                for n_h, acc_h in host_spec:
                    for s in dec_slots:
                        req = self.sched.active.get(s)
                        if req is None or req.state is not RequestState.DECODE:
                            continue
                        a = int(acc_h[s])
                        spec_drafted_w += self.spec_k
                        spec_accepted_w += a
                        if spans is not None:
                            e = spec_w.setdefault(s, [0, 0])
                            e[0] += self.spec_k
                            e[1] += a
                        for j in range(a + 1):
                            tok = int(n_h[s, j])
                            req.tokens.append(tok)
                            flushed_tokens += 1
                            self._finish_if_done(req, tok)
                            if req.state is not RequestState.DECODE:
                                break
                self.spec_drafted += spec_drafted_w
                self.spec_accepted += spec_accepted_w

                # prefill completions: first generated token becomes visible now
                for req, tok, probs in host_pre:
                    if self.temperature > 0.0:
                        from flexflow_tpu.models.transformer import sample_next

                        tok = int(sample_next(
                            probs[None], self.temperature, self._rng,
                        )[0])
                    req.state = RequestState.DECODE
                    req.tokens.append(int(tok))
                    flushed_tokens += 1
                    req.t_first_token = self._now()
                    if spans is not None:
                        tt = spans.rel(req.t_first_token)
                        spans.span("first_token", req, tt, tt, pool=self.phase)
                    self._finish_if_done(req, int(tok))

                # per-request decode/spec spans for this window — emitted after
                # the flush (post-sync), from counts the flush already computed
                if spans is not None and dec_reqs:
                    t_dec1 = spans.now()
                    for s, r in dec_reqs:
                        spans.span(
                            "decode_window", r, t_dec0, t_dec1, pool=self.phase,
                            window=self.windows, steps=steps, slot=s,
                            tokens=r.done_tokens - done_before[s],
                        )
                        sw = spec_w.get(s)
                        if sw is not None:
                            spans.span(
                                "spec", r, t_dec0, t_dec1, pool=self.phase,
                                k=self.spec_k, drafted=sw[0], accepted=sw[1],
                            )

                self.windows += 1
                self._occ_sum += self.sched.occupancy
                win_wall = self._now() - t_win
                # window watchdog (--serve-watchdog-s): a window slower than the
                # budget is flagged loudly — a stalled loader, a GC pause, or a
                # degraded DCN link shows up here long before SLO percentiles do
                if self.watchdog_s and win_wall > self.watchdog_s:
                    self.watchdog_fires += 1
                    if tracer.enabled:
                        tracer.counter("serve.watchdog_fires")
                        tracer.instant(
                            "serve_watchdog", cat="health",
                            window=self.windows - 1,
                            wall_s=round(win_wall, 6),
                            budget_s=self.watchdog_s,
                        )
                # graceful shedding (--serve-shed-windows): after N CONSECUTIVE
                # windows over the per-token SLO, reject the queued batch tier
                # with a truthful reason — shrinking the backlog instead of
                # letting every tier's latency collapse together
                if self.shed_after_windows and flushed_tokens:
                    per_tok_ms = win_wall / flushed_tokens * 1e3
                    if per_tok_ms > self.slo_ms:
                        self._slo_breach_windows += 1
                    else:
                        self._slo_breach_windows = 0
                    if self._slo_breach_windows >= self.shed_after_windows:
                        now_rel = self._now() - (self._t0 or 0.0)
                        n = self.sched.shed_batch_queue(
                            now_rel,
                            f"sustained SLO pressure: per-token "
                            f"{per_tok_ms:.1f} ms > {self.slo_ms:.1f} ms SLO "
                            f"for {self._slo_breach_windows} consecutive windows",
                        )
                        self._slo_breach_windows = 0
                        if n and tracer.enabled:
                            tracer.counter("serve.shed", float(n))
                # the window record is built once and fanned out: the metrics
                # stream (when recording), the SLO engine, and the status
                # snapshot all see the IDENTICAL dict — what the file says is
                # what the alerts and endpoints say
                if (self.metrics.enabled or self.slo is not None
                        or self.publish_status):
                    fin = [
                        {
                            "id": r.id, "tokens": r.done_tokens,
                            "reason": r.finish_reason, "tenant": r.tenant,
                            "tier": r.tier, "preempted": r.preemptions,
                            **r.latency_ms(),
                        }
                        for r in self.sched.finished[fin_before:]
                    ]
                    # per-tenant fairness snapshot: occupancy share + progress
                    # (ADDITIVE ffmetrics/1 vocabulary — old readers ignore it)
                    tenants: Dict[str, Dict[str, Any]] = {}
                    for r in list(self.sched.active.values()) + self.sched.queue:
                        d = tenants.setdefault(r.tenant, {
                            "tier": r.tier, "active": 0, "queued": 0,
                        })
                        d["active" if r.slot >= 0 else "queued"] += 1
                    serve_m: Dict[str, Any] = {
                        "queue_depth": self.sched.queue_depth,
                        "occupancy": self.sched.occupancy,
                        "decode_steps": steps,
                        "prefill_chunks": len(chunks),
                        "active": len(self.sched.active),
                        "finished": fin,
                        "rejected_total": len(self.sched.rejected),
                        "expired_total": self.sched.expired,
                        "shed_total": self.sched.shed,
                        "prefix_hit_rate": self.kv.prefix_hit_rate,
                        "cached_blocks": self.kv.cached_blocks,
                        "preemptions_total": self.sched.preemptions,
                        "tenants": tenants,
                        # which decode-attention kernel served this window
                        # (ADDITIVE ffmetrics/1 vocabulary — r14, old readers
                        # ignore it, old streams simply lack it)
                        "attn_kernel": self.attn_kernel,
                        # which kernel CHUNKED PREFILL ran on + how many
                        # batched dispatches this window issued (ADDITIVE —
                        # r20; pre-r20 streams simply lack both and
                        # tools/serve_report.py stays silent)
                        "prefill_attn_kernel": self.attn_kernel,
                        "prefill_dispatches": 1 if chunks else 0,
                        # how the programs wrote this window's new K/V rows
                        # into the pool (ADDITIVE, as the two above)
                        "kv_write": self.kv_write,
                        # quantized-serving vocabulary (ADDITIVE — r19): the
                        # pool/weight formats and the per-position HBM cost
                        "kv_dtype": self.kv_dtype,
                        "weight_dtype": self.weight_dtype,
                        "kv_bytes_per_token": self.kv.bytes_per_token,
                    }
                    # disaggregated-pool vocabulary (ADDITIVE — absent on
                    # colocated engines, so pre-r13 streams are unchanged)
                    if self.phase is not None:
                        serve_m["phase"] = self.phase
                    if self._handoff_ms_w:
                        serve_m["handoff_ms"] = [
                            round(x, 4) for x in self._handoff_ms_w
                        ]
                        serve_m["migrated_blocks"] = self._migrated_blocks_w
                        serve_m["handoff_bytes"] = self._migrated_bytes_w
                        # measured send→deliver transit beside the priced value
                        # (PR 16, ADDITIVE — absent unless the router measured)
                        if self._handoff_obs_w:
                            serve_m["handoff_observed_ms"] = [
                                round(x, 4) for x in self._handoff_obs_w
                            ]
                    if self.spec_k:
                        serve_m["spec"] = {
                            "k": self.spec_k,
                            "draft_layers": self.spec_draft_layers,
                            "drafted": spec_drafted_w,
                            "accepted": spec_accepted_w,
                        }
                    rec = step_record(
                        step=self.windows - 1,
                        t=time.time(),
                        step_wall_s=win_wall,
                        host_stall_s=stall,
                        tokens=flushed_tokens,
                        samples=len(dec_slots),
                        predicted_step_s=self.predicted_step_s,
                        predicted_tok_s=self.predicted_tok_s,
                        metrics={"serve": serve_m},
                    )
                    if self.metrics.enabled:
                        self.metrics.append(rec)
                    if self.slo is not None:
                        self.slo.observe_record(rec)
                    if self.publish_status:
                        # immutable snapshot, published by atomic reference
                        # swap — the introspection server reads it lock-free
                        self.status_snapshot = self._status_snapshot(rec)
                # handoff accumulators are per-window whether or not a metrics
                # stream is attached
                self._handoff_ms_w = []
                self._handoff_obs_w = []
                self._migrated_blocks_w = 0
                self._migrated_bytes_w = 0
                # batched span flush — strictly after the window's one host
                # sync, so tracing adds file writes but never a device wait
                if spans is not None:
                    spans.flush()

    def _status_snapshot(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        """One immutable per-window snapshot for the introspection
        server: the window record itself plus the scheduler ledgers and
        the engine's drain/health flags.  Built strictly after the
        window's single host sync from values already on the host —
        publishing it costs a dict build, never a device wait."""
        return {
            "t": rec.get("t"),
            "window": self.windows - 1,
            "phase": self.phase,
            "record": rec,
            "sched": self.sched.publish_status(),
            "drain_requested": self._drain_requested,
            "drained": self.drained,
            "watchdog_fires": self.watchdog_fires,
            "attn_kernel": self.attn_kernel,
            "kv_write": self.kv_write,
            "kv_dtype": self.kv_dtype,
            "weight_dtype": self.weight_dtype,
            "kv_pages_held": self.kv.pages_held(),
            "state": self._state_report(),
            "moe": self._moe_report(),
        }

    def _state_report(self) -> Optional[Dict[str, Any]]:
        """The state group as it stands (None without state layers)."""
        kv = self.kv
        if not kv.state_layers:
            return None
        return {
            "layers": kv.state_layers, "pool_bytes": kv.state_bytes(),
            "bytes_per_slot": kv.state_bytes_per_slot,
            "slots_held": kv.state_slots_held,
            "spills": kv.state_spills, "restores": kv.state_restores,
        }

    def _moe_report(self) -> Optional[Dict[str, Any]]:
        """The expert layers' counters so far this run (``moe.rows``,
        ``moe.experts_touched``, ``moe.load_max_over_mean``), from what
        the windows' syncs brought; None without routed experts."""
        if not self._moe_layers:
            return None
        rows, touched, lmm, calls = self._moe_tot
        return {
            "rows": int(rows), "experts_touched": int(touched),
            "load_max_over_mean": float(lmm / calls) if calls else None,
            "layers": self._moe_layers,
        }

    def _finish_if_done(self, req: Request, tok: int) -> None:
        if req.eos_id is not None and tok == req.eos_id:
            self.sched.finish(req, self._now(), "eos")
        elif req.done_tokens >= req.max_new_tokens:
            self.sched.finish(req, self._now(), "length")

    # --- report -------------------------------------------------------------
    def _report(
        self, wall: float, host_syncs: int, fin=None, rejected=None,
        preemptions: Optional[int] = None,
        expired: Optional[int] = None, shed: Optional[int] = None,
    ) -> ServeReport:
        fin = self.sched.finished if fin is None else fin
        lat = [r.latency_ms() for r in fin]
        ttft = [d["ttft_ms"] for d in lat]
        tpot = [d["tpot_ms"] for d in lat]
        new_tokens = sum(r.done_tokens for r in fin)
        per_tier: Dict[str, Dict[str, Any]] = {}
        for tier in sorted({r.tier for r in fin}):
            rs = [r for r in fin if r.tier == tier]
            tl = [r.latency_ms() for r in rs]
            per_tier[tier] = {
                "finished": len(rs),
                "preemptions": sum(r.preemptions for r in rs),
                "ttft_p50_ms": _pct([d["ttft_ms"] for d in tl], 50),
                "ttft_p99_ms": _pct([d["ttft_ms"] for d in tl], 99),
                "tpot_p99_ms": _pct([d["tpot_ms"] for d in tl], 99),
            }
        per_tenant: Dict[str, Dict[str, Any]] = {}
        for tenant, d in sorted(self.sched.tenant_summary().items()):
            ttfts = d.pop("ttft_ms")
            d["ttft_p50_ms"] = _pct(ttfts, 50)
            d["ttft_p99_ms"] = _pct(ttfts, 99)
            per_tenant[tenant] = d
        blocks_walked, blocks_full_table = self._attn_blocks(fin)
        rows_visible, rows_context = self._kv_rows(fin)
        if self._moe_acc is not None:  # what the last windows left behind
            self._moe_tot += np.asarray(self._moe_acc, np.float64)
            self._moe_acc = None
        moe = self._moe_report() or {}
        n_state = self.kv.state_layers
        ssm_rows = n_state * sum(
            r.prompt_len - r.shared_prefix_pos + max(0, r.done_tokens - 1) for r in fin
        ) if n_state else None
        tracer = get_tracer()
        if tracer.enabled:
            if n_state:
                tracer.counter("ssm.rows", float(ssm_rows))
                tracer.counter("kv.state_pool_bytes", float(self.kv.state_bytes()))
            tracer.counter("kv.rows_visible", float(rows_visible))
            tracer.counter("kv.rows_context", float(rows_context))
            for g, n in self._pages_peak.items():
                tracer.counter(f"kv.pages_held.{g}", float(n))
            for k in ("rows", "experts_touched", "load_max_over_mean"):
                if moe.get(k) is not None:
                    tracer.counter(f"moe.{k}", float(moe[k]))
        rep = ServeReport(
            wall_s=wall,
            new_tokens=new_tokens,
            tok_s=new_tokens / wall if wall > 0 else 0.0,
            requests_finished=len(fin),
            requests_rejected=(
                len(self.sched.rejected) if rejected is None else rejected
            ),
            ttft_p50_ms=_pct(ttft, 50),
            ttft_p99_ms=_pct(ttft, 99),
            tpot_p50_ms=_pct(tpot, 50),
            tpot_p99_ms=_pct(tpot, 99),
            occupancy_mean=(
                self._occ_sum / self.windows if self.windows else 0.0
            ),
            windows=self.windows,
            decode_steps=self.decode_steps,
            prefill_chunks=self.prefill_chunks,
            host_syncs=host_syncs,
            per_request=[
                {
                    "id": r.id, "prompt_len": r.prompt_len,
                    "tokens": list(r.tokens), "reason": r.finish_reason,
                    "tenant": r.tenant, "tier": r.tier,
                    "preemptions": r.preemptions,
                    "shared_prefix_pos": r.shared_prefix_pos,
                    **r.latency_ms(),
                }
                for r in fin
            ],
            prefix_hit_rate=self.kv.prefix_hit_rate,
            preemptions=(
                self.sched.preemptions if preemptions is None
                else preemptions
            ),
            per_tier=per_tier,
            per_tenant=per_tenant,
            spec_k=self.spec_k,
            spec_draft_layers=self.spec_draft_layers if self.spec_k else 0,
            spec_accept_rate=(
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted else None
            ),
            spec_drafted=self.spec_drafted,
            spec_accepted=self.spec_accepted,
            peak_active=self.peak_active,
            requests_expired=(
                self.sched.expired if expired is None else expired
            ),
            drained=self.drained,
            shed=self.sched.shed if shed is None else shed,
            watchdog_fires=self.watchdog_fires,
            prefill_dispatches=self.prefill_dispatches,
            prefill_attn_kernel=self.attn_kernel,
            kv_write=self.kv_write,
            attn_blocks_walked=blocks_walked,
            attn_blocks_full_table=blocks_full_table,
            attn_interpret=self.attn_interpret,
            device=self.device_info(),
            kv_rows_visible=rows_visible,
            kv_rows_context=rows_context,
            kv_pages_held_full=self._pages_peak["full"],
            kv_pages_held_window=self._pages_peak["window"],
            moe_rows=moe.get("rows"),
            moe_experts_touched=moe.get("experts_touched"),
            moe_load_max_over_mean=moe.get("load_max_over_mean"),
            moe_layers=self._moe_layers,
            state_pool_bytes=self.kv.state_bytes(),
            state_slots_held=self._state_slots_peak,
            state_spills=self.kv.state_spills - self._state0[0],
            state_restores=self.kv.state_restores - self._state0[1],
            ssm_rows=ssm_rows,
        )
        self.metrics.close()
        if self.spans is not None and self._owns_spans:
            # cluster-shared recorders are closed by the cluster
            self.spans.close()
        return rep
