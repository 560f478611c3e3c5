"""KV-cache decode for the GPT family (round-5 verdict #9).

The reference's only incremental-decoding machinery is seq_length
masking (``FFIterationConfig::seq_length``,
``include/flexflow/config.h:162-167``) — every step re-runs the full
forward over the whole prefix, so step time grows with prefix length.
:func:`flexflow_tpu.models.transformer.gpt_generate` reproduces that
behavior for parity.  This module goes beyond it the TPU way: ONE jitted
single-token step whose inputs are static-shape K/V caches
``(L, B, heads, S_max, head_dim)``; each step projects q/k/v for one
position, ``dynamic_update_slice``s the caches at ``t`` (donated, so XLA
updates in place), and attends the single query row against the cache
under an ``iota <= t`` mask.  Per step that is O(S_max·hidden) attention
reads + O(1-token) FFN work — independent of how long the prefix is —
and the trace is position-independent, so the whole generation runs on
one compiled program (the parity/no-retrace tests pin both properties).

Prompt ingestion is phase-separated (docs/SERVING.md): :meth:`
GPTDecodeSession.prefill` feeds the WHOLE prompt in one batched call —
P query rows against the same cache, causal-masked — instead of the
token-at-a-time warmup loop.  Per row the math is element-for-element
the per-token step's (same cache layout, same mask width, same cast
rules), so the cache contents and next-token probs are bit-identical to
the loop (pinned by tests/test_serve.py for fp32 and bf16); the win is
P positions per dispatch instead of P dispatches.

Works on any model built by
:func:`flexflow_tpu.models.transformer.gpt_decoder` (the layer names are
the contract).  Under a sharded strategy the step jit inherits the
executor's parameter shardings and GSPMD inserts the collectives, same
as the full forward.  The production serving layer
(:mod:`flexflow_tpu.serve`) reuses :class:`GPTSpec` and the same math
over a paged/block cache.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = ["GPTSpec", "LayerSpec", "BranchSpec", "GPTDecodeSession", "gpt_generate_cached"]


ATTENTION_KINDS = ("mha", "gated", "diff", "sparse")
FFN_KINDS = ("gelu", "gated_ffn", "moe")
STATE_KINDS = ("mamba2", "mamba1", "lightning")


@dataclasses.dataclass(frozen=True)
class BranchSpec:
    """One residual branch ``x + [norm](mixer(norm(x)))`` as the serve
    programs run it: the names of the layers whose parameters it reads,
    and what the mixer is."""

    # "mha" | "gated" | "diff" | "sparse" (attention) | "gelu" |
    # "gated_ffn" | "moe" (FFN) | "mamba2" | "mamba1" | "lightning" (state)
    # | "gmu" (a gated memory unit)
    kind: str
    norm_in: str
    mixer: Tuple[str, ...]  # (ff0, ff1) for "gelu", one layer otherwise
    norm_post: Optional[str] = None  # sandwich norm on the mixer's output
    # "moe", "mamba*", "lightning", "gmu", "sparse" (its sizes): the op's attrs
    attrs: Optional[Dict[str, Any]] = None
    scale: float = 1.0  # the branch's output times this before the residual add
    # --- attention ("mha": q/k/v/o, biases optional, K/V heads grouped or not)
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    has_bias: bool = False
    qk_norm_zero_centered: bool = False  # gated: the per-head norm's weight from 0
    qk_eps: float = 0.0  # gated: the per-head norm's eps
    rotary_dim: int = 0  # 0: the layer carries no positions of its own
    rope_theta: float = 0.0
    window: int = 0  # 0: every earlier key; else the last ``window`` keys
    # --- differential ("diff"): heads, kv_heads and head_dim are the
    # pool's geometry -- a K/V PAIR one head of 2 x the published head
    # size, a query two heads of that size (``[q1 | 0]``, ``[0 | q2]``);
    # qk_eps is the per-pair norm's eps
    lambda_init: float = 0.0
    lambda_scale: float = 1.0
    # the layer whose K/V this attention reads (a cross layer: it writes
    # none); -1: its own
    kv_source: int = -1

    @property
    def is_attention(self) -> bool:
        return self.kind in ATTENTION_KINDS

    @property
    def differential(self) -> bool:
        return self.kind == "diff"

    @property
    def pool_layers(self) -> int:
        """Layers of the pool an attention branch with K/V of its own
        writes: one, or, for a sparse layer, one a K/V head (each head's
        selected pages are walked on their own, so a pool row is one
        head's)."""
        return self.kv_heads if self.kind == "sparse" else 1

    @property
    def owns_memory(self) -> bool:
        """Whether the branch writes a pool: an attention with K/V of its
        own, or a state layer."""
        return (self.is_attention and self.kv_source < 0) or self.kind in STATE_KINDS


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer: a sequence of residual branches.  GPT-2's and
    Trinity's layers have two (an attention and an FFN), Nemotron-H's
    one.  The properties read the attention branch (``norm_in``,
    ``attn``, ``attn_kind``, ``heads`` ..., ``window``) or the FFN
    branch (``norm_pre_ffn``, ``ffn``, ``ffn_kind``, ``moe``) of a
    layer that has one."""

    branches: Tuple[BranchSpec, ...]

    def _one(self, kinds) -> Optional[BranchSpec]:
        return next((b for b in self.branches if b.kind in kinds), None)

    @property
    def attention(self) -> Optional[BranchSpec]:
        return self._one(ATTENTION_KINDS)

    @property
    def ffn_branch(self) -> Optional[BranchSpec]:
        return self._one(FFN_KINDS)

    @property
    def window(self) -> int:
        at = self.attention
        return at.window if at else 0

    @property
    def ffn_kind(self) -> Optional[str]:
        f = self.ffn_branch
        return None if f is None else {"gated_ffn": "gated"}.get(f.kind, f.kind)

    @property
    def ffn(self) -> Optional[Tuple[str, ...]]:
        f = self.ffn_branch
        return f.mixer if f else None

    @property
    def moe(self) -> Optional[Dict[str, Any]]:
        f = self.ffn_branch
        return f.attrs if f is not None and f.kind == "moe" else None

    @property
    def norm_pre_ffn(self) -> Optional[str]:
        f = self.ffn_branch
        return f.norm_in if f else None

    @property
    def norm_post_ffn(self) -> Optional[str]:
        f = self.ffn_branch
        return f.norm_post if f else None

    @property
    def norm_in(self) -> str:
        return self.branches[0].norm_in

    @property
    def attn(self) -> Optional[str]:
        at = self.attention
        return at.mixer[0] if at else None

    @property
    def attn_kind(self) -> Optional[str]:
        at = self.attention
        return at.kind if at else None

    @property
    def norm_post_attn(self) -> Optional[str]:
        at = self.attention
        return at.norm_post if at else None

    def __getattr__(self, name):
        # heads, kv_heads, head_dim, has_bias, rotary_dim, ...: the
        # attention branch's
        if name in ("heads", "kv_heads", "head_dim", "has_bias", "rotary_dim",
                    "rope_theta", "qk_eps", "qk_norm_zero_centered"):
            at = self.attention
            if at is not None:
                return getattr(at, name)
        raise AttributeError(name)


@dataclasses.dataclass(frozen=True)
class GPTSpec:
    """What a compiled decoder implies for decoding -- the ONE
    extraction rule, shared by the dense session here (``gpt_decoder``
    models) and the paged serving programs
    (:mod:`flexflow_tpu.serve.programs`).  Read from the model's layers
    and their attrs, whatever builder named them: an embedding (plus a
    learned position table, or times a constant), then a chain of
    residual branches ``x + [norm](mixer(norm(x)))`` -- the mixer an
    attention op (:class:`MultiHeadAttention`, K/V heads grouped or not,
    :class:`GatedAttention`, :class:`DifferentialAttention` over its
    own K/V or, a cross layer, another layer's, or
    :class:`SparseBlockAttention`), an FFN (two dense layers around a
    GELU, a :class:`GatedFFN`, or :class:`RoutedExperts` holding all or a
    share of its experts), a :class:`Mamba2Mixer`, :class:`Mamba1Mixer`
    or :class:`LightningAttention`, or a :class:`GatedMemoryUnit` over
    the memory a Mamba-1 layer exports; a scalar multiply before the add
    is the branch's ``scale`` -- and a final norm, a scalar multiply
    (``head_scale``) or not, and a bias-free head, its own or tied to the
    embedding's table.  A mixer branch and the FFN
    branch that follows it under the same name prefix are one layer
    (GPT-2, Trinity, phi4flash); every other branch is a layer of its
    own (Nemotron-H)."""

    num_layers: int
    heads: int
    head_dim: int
    hidden: int
    has_bias: bool
    eps: float
    batch: int
    seq: int
    kv_heads: int = 0
    vocab: int = 0
    embed: str = "tok_embed"
    pos_embed: Optional[str] = "pos_embed"  # learned position table
    embed_scale: float = 1.0
    norm: str = "layer"  # "layer" | "rms" | "rms_zero_centered"
    final_norm: str = "final_ln"
    head: str = "lm_head"
    layers: Tuple[LayerSpec, ...] = ()
    head_tied: bool = False  # the head reads the embedding's table
    head_scale: float = 1.0  # the final norm's output times this before the head

    @property
    def branches(self) -> Tuple[BranchSpec, ...]:
        return tuple(b for l in self.layers for b in l.branches)

    @property
    def is_gpt(self) -> bool:
        """The shape the dense session and the speculative, int8 and
        quantized-pool serve arms are written for: learned positions,
        LayerNorm, one head count, GELU FFN, no window."""
        return self.pos_embed is not None and self.norm == "layer" and all(
            [b.kind for b in l.branches] == ["mha", "gelu"] and not l.window
            and l.kv_heads == l.heads for l in self.layers
        )

    @property
    def window(self) -> int:
        """The window of the layers that have one (0: none has)."""
        ws = {l.window for l in self.layers if l.window}
        if len(ws) > 1:
            raise ValueError(f"layers with different windows {sorted(ws)} are not served")
        return ws.pop() if ws else 0

    @property
    def has_moe(self) -> bool:
        return any(b.kind == "moe" for b in self.branches)

    @property
    def state_layers(self) -> Tuple[BranchSpec, ...]:
        """The branches that carry a recurrent state a slot."""
        return tuple(b for b in self.branches if b.kind in STATE_KINDS)

    @property
    def self_layers(self) -> int:
        """Layers a prefill runs on every row of a chunk: up to the last
        layer that writes a pool.  The layers after it (a YOCO-style
        cross-decoder: memory units, cross-attention over another
        layer's K/V, FFNs) write nothing a later row reads, so a prefill
        runs them on the rows whose logits are needed only."""
        own = [i for i, l in enumerate(self.layers) if any(b.owns_memory for b in l.branches)]
        return own[-1] + 1 if own else self.num_layers

    @classmethod
    def from_model(cls, model) -> "GPTSpec":
        assert model.executor is not None, "call compile() first"
        from flexflow_tpu.fftype import ActiMode, OperatorType as T

        layers = model.layers
        producer = {t.guid: l for l in layers for t in l.outputs}
        consumers: Dict[int, list] = {}
        for l in layers:
            for t in l.inputs:
                consumers.setdefault(t.guid, []).append(l)
        NORMS = (T.LAYERNORM, T.RMS_NORM)
        ATTENTIONS = (T.MULTIHEAD_ATTENTION, T.GATED_ATTENTION, T.DIFF_ATTENTION,
                      T.SPARSE_ATTENTION)
        MIXERS = ATTENTIONS + (T.LINEAR, T.GATED_FFN, T.ROUTED_EXPERTS, T.MAMBA2_MIXER,
                               T.MAMBA1_MIXER, T.LIGHTNING_ATTENTION, T.GATED_MEMORY)

        def refuse(why):
            raise ValueError(
                "not a decoder the serve programs know (an embedding, then "
                "residual branches norm -> MultiHeadAttention | GatedAttention "
                "| DifferentialAttention | SparseBlockAttention | Mamba2Mixer | "
                "Mamba1Mixer | LightningAttention | GatedMemoryUnit | "
                "dense+GELU+dense | GatedFFN | RoutedExperts -> [norm] -> "
                f"[scalar multiply] -> add, a final norm [times a scalar], a head): {why}"
            )

        def after(layer, *kinds):
            """The one consumer of ``layer``'s output among ``kinds``
            (a residual add also reads a block's input: not asked for
            unless named)."""
            hits = []
            for c in consumers.get(layer.outputs[0].guid, ()):
                if c.op_type in kinds and c not in hits:  # q, k, v: one reader
                    hits.append(c)
            return hits[0] if len(hits) == 1 else None

        def norm_kind(l):
            if l.op_type == T.LAYERNORM:
                return "layer"
            return "rms_zero_centered" if l.attrs.get("zero_centered") else "rms"

        embeds = [l for l in layers if l.op_type == T.EMBEDDING]
        heads_ = [l for l in layers if l.op_type in (T.LINEAR, T.TIED_HEAD)]
        if len(embeds) != 1 or not heads_:
            refuse("it needs one token embedding and an output head")
        embed, head = embeds[0], heads_[-1]
        tied = head.op_type == T.TIED_HEAD
        if tied and head.attrs["tied_to"] != embed.name:
            refuse(f"the head {head.name!r} is tied to another table than the embedding's")
        if not tied and head.attrs.get("use_bias", True):
            refuse(f"the head {head.name!r} has a bias")
        final = producer.get(head.inputs[0].guid)
        head_scale = 1.0
        if final is not None and final.op_type == T.SCALAR_MULTIPLY:
            head_scale = float(final.attrs["scalar"])
            final = producer.get(final.inputs[0].guid)
        if final is None or final.op_type not in NORMS:
            refuse(f"the head {head.name!r} does not read a norm")
        pos_embed, embed_scale = None, 1.0
        stream = embed  # the layer whose output is the residual stream
        nxt = after(embed, T.EW_ADD, T.SCALAR_MULTIPLY)
        if nxt is not None and nxt.op_type == T.EW_ADD:
            other = [producer.get(t.guid) for t in nxt.inputs
                     if producer.get(t.guid) is not embed]
            # a position table -- or the first branch's residual add, of
            # a decoder whose embedding is the stream as it is
            if len(other) == 1 and other[0] is not None and other[0].op_type == T.WEIGHT:
                pos_embed, stream = other[0].name, nxt
        elif nxt is not None:
            embed_scale, stream = float(nxt.attrs["scalar"]), nxt

        cross_source = {}  # a cross layer's name -> the layer whose K/V it reads

        def attention_of(at):
            a = at.attrs
            if at.op_type == T.DIFF_ATTENTION:
                # the pool's geometry: a K/V pair one head of 2 d, a query
                # pair two heads of 2 d (BranchSpec)
                from flexflow_tpu.ops.attention import DifferentialAttention

                d = dict(kind="diff", heads=a["num_heads"], kv_heads=a["num_kv_heads"] // 2,
                         head_dim=2 * a["head_dim"], qk_eps=float(a["eps"]),
                         window=int(a.get("window", 0)),
                         lambda_init=DifferentialAttention.lambda_init(a),
                         lambda_scale=float(a.get("lambda_scale", 1.0)))
                if a.get("cross"):
                    src = producer.get(at.inputs[1].guid)
                    if src is None or src.op_type != T.DIFF_ATTENTION or not src.attrs.get("exports_kv"):
                        refuse(f"{at.name!r} reads K/V no attention layer exported")
                    if src.attrs.get("window"):
                        refuse(f"{at.name!r} reads a window layer's K/V (cross layers read the full group)")
                    cross_source[at.name] = src.name
                elif len(at.inputs) != 1:
                    refuse(f"{at.name!r} is not self-attention")
                return d
            if any(t.guid != at.inputs[0].guid for t in at.inputs):
                refuse(f"{at.name!r} is not self-attention")
            if at.op_type == T.SPARSE_ATTENTION:
                sizes = ("kernel", "stride", "block", "topk", "window", "dense_len")
                return dict(kind="sparse", heads=a["num_heads"], kv_heads=a["num_kv_heads"],
                            head_dim=a["head_dim"], qk_eps=float(a["eps"]),
                            attrs={n: int(a[n]) for n in sizes})
            if at.op_type == T.MULTIHEAD_ATTENTION:
                if not a.get("causal"):
                    refuse(f"{at.name!r} is not causal")
                h = a["num_heads"]
                return dict(kind="mha", heads=h, kv_heads=a.get("num_kv_heads") or h,
                            head_dim=a.get("kdim") or a["embed_dim"] // h,
                            has_bias=bool(a.get("bias")))
            return dict(kind="gated", heads=a["num_heads"],
                        kv_heads=a["num_kv_heads"], head_dim=a["head_dim"],
                        qk_norm_zero_centered=bool(a.get("zero_centered", True)),
                        qk_eps=float(a.get("eps", 1e-6)),
                        rotary_dim=int(a["rotary_dim"]),
                        rope_theta=float(a["rope_theta"]),
                        window=int(a.get("window", 0)))

        # walk the residual stream: each step is norm -> mixer -> [norm]
        # -> add back onto the stream, until the norm is the head's
        branches = []
        while True:
            n_in = after(stream, *NORMS)
            if n_in is None:
                refuse(f"no one norm reads the residual stream after {stream.name!r}")
            if n_in is final:
                break
            mix = after(n_in, *MIXERS)
            if mix is None:
                refuse(f"no mixer the serve programs know reads {n_in.name!r}")
            names, kind = (mix.name,), {}
            if mix.op_type in ATTENTIONS:
                kind = attention_of(mix)
            elif mix.op_type == T.LINEAR:
                f1 = after(mix, T.LINEAR)
                if f1 is None or mix.attrs.get("activation") != ActiMode.GELU:
                    refuse(f"{mix.name!r} is not dense + GELU + dense")
                names, kind, mix = (mix.name, f1.name), dict(kind="gelu"), f1
            elif mix.op_type == T.GATED_FFN:
                kind = dict(kind="gated_ffn")
            elif mix.op_type == T.ROUTED_EXPERTS:
                kind = dict(kind="moe", attrs=dict(mix.attrs))
            elif mix.op_type == T.GATED_MEMORY:
                src = producer.get(mix.inputs[1].guid)
                if src is None or src.op_type != T.MAMBA1_MIXER:
                    refuse(f"{mix.name!r} reads a memory no state-space layer exported")
                kind = dict(kind="gmu", attrs=dict(mix.attrs))
            elif mix.op_type == T.MAMBA1_MIXER:
                kind = dict(kind="mamba1", attrs=dict(mix.attrs))
            elif mix.op_type == T.LIGHTNING_ATTENTION:
                kind = dict(kind="lightning", attrs=dict(mix.attrs))
            else:
                kind = dict(kind="mamba2", attrs=dict(mix.attrs))
            post = after(mix, *NORMS)
            scaled = after(post or mix, T.SCALAR_MULTIPLY)
            if scaled is not None:
                kind["scale"] = float(scaled.attrs["scalar"])
            res = after(scaled or post or mix, T.EW_ADD)
            if res is None or stream.outputs[0].guid not in [t.guid for t in res.inputs]:
                refuse(f"no residual add after {mix.name!r}")
            branches.append(BranchSpec(
                norm_in=n_in.name, mixer=names,
                norm_post=post.name if post else None, **kind,
            ))
            stream = res

        def prefix(b):
            return b.norm_in.split("_")[0]

        specs = []
        for b in branches:
            last = specs[-1] if specs else None
            if (b.kind in FFN_KINDS and last is not None and len(last) == 1
                    and last[0].kind not in FFN_KINDS and prefix(last[0]) == prefix(b)):
                last.append(b)
            else:
                specs.append([b])
        # a cross layer's K/V source, by the index of the layer that owns them
        at_layer = {b.mixer[0]: i for i, bs in enumerate(specs) for b in bs}
        specs = [LayerSpec(tuple(
            dataclasses.replace(b, kv_source=at_layer[cross_source[b.mixer[0]]])
            if b.mixer[0] in cross_source else b for b in bs
        )) for bs in specs]
        attn = [b for l in specs for b in l.branches if b.is_attention]
        if not attn:
            refuse("no attention layer")
        l0 = attn[0]
        if len({(l.heads, l.kv_heads, l.head_dim) for l in attn}) != 1:
            refuse("layers differ in their heads (one K/V pool geometry is served)")
        batch, seq = model.graph_inputs[0].shape
        return cls(
            num_layers=len(specs),
            heads=l0.heads,
            head_dim=l0.head_dim,
            hidden=embed.attrs["out_dim"],
            has_bias=l0.has_bias,
            eps=final.attrs.get("eps", 1e-5),
            batch=batch,
            seq=seq,
            kv_heads=l0.kv_heads,
            vocab=embed.attrs["num_entries"],
            embed=embed.name,
            pos_embed=pos_embed,
            embed_scale=embed_scale,
            norm=norm_kind(final),
            final_norm=final.name,
            head=head.name,
            layers=tuple(specs),
            head_tied=tied,
            head_scale=head_scale,
        )


def make_cast(jnp, dt):
    """Mixed-precision rule shared by every decode/prefill program
    (mirrors ``FFConfig.compute_dtype`` in the executor): float32 master
    params become the compute dtype, caches/activations are in the
    compute dtype, probabilities back in float32.  WHERE the rule runs:
    the serve programs' weights are cast with it once, when the programs
    are built (``serve/programs.py::weights_as_consumed``), and inside
    a program it finds nothing left to cast; :class:`GPTDecodeSession`
    below still applies it at use, inside every call (ROADMAP S4)."""
    mixed = dt != jnp.float32

    def cast(x):
        if mixed and x.dtype == jnp.float32:
            return x.astype(dt)
        return x

    return cast


def quantize_weights_int8(jnp, params):
    """Weight-only int8 for the weight-streaming-bound decode roofline
    (``ServeSpec.weight_dtype`` — docs/SERVING.md): every float leaf
    with >= 2 axes is stored int8 with a per-output-channel (last axis)
    symmetric float32 scale; 1-D leaves (biases, layer-norm params) and
    integer leaves stay as-is with scale 1.  Returns ``(qparams,
    scales)`` — two trees of identical structure that
    :func:`dequantize_weights_int8` folds back at the matmul edge, so
    HBM streams 1-byte elements and the dequant happens in-register."""
    import numpy as np

    def q(x):
        xa = np.asarray(x)
        if xa.ndim < 2 or not np.issubdtype(xa.dtype, np.floating):
            return x, jnp.asarray(1.0, jnp.float32)
        xf = xa.astype(np.float32)
        amax = np.max(np.abs(xf), axis=tuple(range(xa.ndim - 1)))
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        qx = np.clip(np.round(xf / scale), -127, 127).astype(np.int8)
        return jnp.asarray(qx), jnp.asarray(scale)

    import jax

    pairs = jax.tree.map(q, params)
    qparams = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    scales = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return qparams, scales


def dequantize_weights_int8(jax, jnp, qparams, scales):
    """The read-side rule of :func:`quantize_weights_int8`: int8 leaves
    become ``w.astype(f32) * scale`` (scale broadcasts on the last
    axis); everything else passes through.  Traced inside each serve
    program, so the lowered HLO reads int8 from HBM and widens next to
    the consuming matmul."""
    return jax.tree.map(
        lambda w, s: w.astype(jnp.float32) * s
        if w.dtype == jnp.int8 else w,
        qparams, scales,
    )


def layer_norm(jax, jnp, p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


class GPTDecodeSession:
    """Compiled single-token decode step + cache state for one model."""

    def __init__(self, model) -> None:
        import jax
        import jax.numpy as jnp

        self.model = model
        spec = GPTSpec.from_model(model)
        if not spec.is_gpt:
            raise ValueError(
                "GPTDecodeSession decodes gpt_decoder-shaped models (learned "
                "positions, LayerNorm, GELU FFN); this decoder is served by "
                "flexflow_tpu.serve.ServeEngine"
            )
        self.spec = spec
        self.num_layers = spec.num_layers
        self.heads = spec.heads
        self.kd = spec.head_dim
        self.hidden = spec.hidden
        self.has_bias = spec.has_bias
        self.batch, self.seq = spec.batch, spec.seq
        self.eps = spec.eps
        self._trace_count = 0  # exposed for the no-retrace test

        L, B, H, S, D = (
            self.num_layers, self.batch, self.heads, self.seq, self.kd,
        )
        eps = self.eps
        has_bias = self.has_bias
        scale = 1.0 / math.sqrt(D)
        # mirror the executor's mixed-precision rule (FFConfig.compute_dtype)
        dt = model.executor.compute_dtype
        cast = make_cast(jnp, dt)
        unstack = model.executor.unstack_tree

        def ln(p, x):
            return layer_norm(jax, jnp, p, x, eps)

        def step(params, cache_k, cache_v, tok, t):
            # tok (B,) int32; t () int32; caches (L, B, H, S, D)
            self._trace_count += 1  # traced once; calls replay the jit
            # per-layer view of scan-stacked chains, then cast-at-use
            params = jax.tree.map(cast, unstack(params))
            x = params["tok_embed"]["kernel"][tok]  # (B, hidden)
            x = x + params["pos_embed"]["value"][t]
            mask = (jnp.arange(S) <= t)[None, None, :]
            for i in range(L):
                p_at = params[f"dec{i}_attn"]
                h = ln(params[f"dec{i}_ln0"], x)
                q = h @ p_at["wq"]
                k = h @ p_at["wk"]
                v = h @ p_at["wv"]
                if has_bias:
                    q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
                q = q.reshape(B, H, D)
                k = k.reshape(B, H, 1, D)
                v = v.reshape(B, H, 1, D)
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k[None], (i, 0, 0, t, 0)
                )
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v[None], (i, 0, 0, t, 0)
                )
                # scores as multiply+reduce, NOT dot_general: the batched
                # prefill computes the same contraction with a P dim in
                # the operands, and XLA's dot kernels accumulate
                # differently across those shapes (1-ulp drift) while the
                # fused mul+sum lowers identically — this is what makes
                # prefill-vs-step bit-identity hold (tests/test_serve.py)
                scores = (q[:, :, None, :] * cache_k[i]).sum(-1) * scale
                scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
                w = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("bhs,bhsd->bhd", w, cache_v[i])
                o = o.reshape(B, H * D) @ p_at["wo"]
                if has_bias:
                    o = o + p_at["bo"]
                x = x + o
                h = ln(params[f"dec{i}_ln1"], x)
                p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
                f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
                f = f @ p1["kernel"] + p1["bias"]
                x = x + f
            # barrier before the head: pins the SAME fusion boundary in
            # step and prefill, so the trailing ln+head+softmax (identical
            # shapes in both) compiles identically — without it XLA fuses
            # the last FFN into the head differently per program and bf16
            # probs drift by an ulp (the prefill parity tests pin this)
            x = jax.lax.optimization_barrier(x)
            x = ln(params["final_ln"], x)
            logits = x @ params["lm_head"]["kernel"]
            # probabilities in float32, like the executor's fp32 loss head
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return probs, cache_k, cache_v

        def prefill(params, cache_k, cache_v, toks, start):
            # toks (B, P) int32, start () int32 — ALL P rows in one call.
            # Per row this is exactly ``step`` at t = start + p: same
            # cache layout, same S-wide ``iota <= t`` mask (masked lanes
            # get weight exactly 0.0, and 0.0 * v sums are exact), same
            # cast points — so cache contents and the last row's probs
            # are bit-identical to the per-token loop (pinned in tests).
            P = toks.shape[1]
            params = jax.tree.map(cast, unstack(params))
            pos = start + jnp.arange(P)  # (P,)
            x = params["tok_embed"]["kernel"][toks]  # (B, P, hidden)
            x = x + params["pos_embed"]["value"][pos]
            # mask[p, s]: key position s visible to query row p, shaped
            # (1, P, 1, S) against the (B, P, H, S) score tensor
            mask = (jnp.arange(S)[None, :] <= pos[:, None])[None, :, None, :]
            for i in range(L):
                p_at = params[f"dec{i}_attn"]
                h = ln(params[f"dec{i}_ln0"], x)
                q = h @ p_at["wq"]
                k = h @ p_at["wk"]
                v = h @ p_at["wv"]
                if has_bias:
                    q, k, v = q + p_at["bq"], k + p_at["bk"], v + p_at["bv"]
                q = q.reshape(B, P, H, D)
                # cache layout (L, B, H, S, D): one contiguous P-wide write
                k = k.reshape(B, P, H, D).transpose(0, 2, 1, 3)
                v = v.reshape(B, P, H, D).transpose(0, 2, 1, 3)
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k[None], (i, 0, 0, start, 0)
                )
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v[None], (i, 0, 0, start, 0)
                )
                # same mul+reduce contraction as ``step`` (see note there):
                # (B,P,H,1,D)*(B,1,H,S,D) -> sum over D -> (B,P,H,S)
                scores = (
                    q[:, :, :, None, :] * cache_k[i][:, None]
                ).sum(-1) * scale
                scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
                w = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("bphs,bhsd->bphd", w, cache_v[i])
                o = o.reshape(B, P, H * D) @ p_at["wo"]
                if has_bias:
                    o = o + p_at["bo"]
                x = x + o
                h = ln(params[f"dec{i}_ln1"], x)
                p0, p1 = params[f"dec{i}_ff0"], params[f"dec{i}_ff1"]
                f = jax.nn.gelu(h @ p0["kernel"] + p0["bias"])
                f = f @ p1["kernel"] + p1["bias"]
                x = x + f
            # only the LAST prompt row's distribution feeds generation —
            # skip the (P-1) dead vocab matmuls.  The barrier (see step)
            # also keeps the row slice from back-fusing into the decoder
            # stack, which would regroup the last FFN's accumulation.
            x = jax.lax.optimization_barrier(x)
            x = ln(params["final_ln"], x[:, -1])
            logits = x @ params["lm_head"]["kernel"]
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return probs, cache_k, cache_v

        # donate the caches: XLA reuses their buffers for the in-place
        # dynamic_update_slice instead of copying (L*B*H*S*D*2 floats)
        self._step = jax.jit(step, donate_argnums=(1, 2))
        # one compiled prefill per distinct prompt length P (static shape)
        self._prefill = jax.jit(prefill, donate_argnums=(1, 2))
        self._dtype = dt
        self._cache_shape = (L, B, H, S, D)
        ck = jnp.zeros(self._cache_shape, dt)
        cv = jnp.zeros(self._cache_shape, dt)
        # warmup: the step's OUTPUT cache layout/sharding can differ from
        # a fresh jnp.zeros (params may be mesh-sharded), which would cost
        # one extra trace on the second call — stabilize it here and pin
        # the sharding so every real step replays ONE compiled program
        tok0 = jnp.zeros((B,), jnp.int32)
        _, ck, cv = self._step(
            model.executor.params, ck, cv, tok0, jnp.asarray(0, jnp.int32)
        )
        _, ck, cv = self._step(
            model.executor.params, ck, cv, tok0, jnp.asarray(0, jnp.int32)
        )
        self._cache_sharding = (ck.sharding, cv.sharding)
        self._jax = jax
        self._jnp = jnp
        self.reset()
        self._trace_count = 0  # warmup traces don't count

    def reset(self) -> None:
        jax, jnp = self._jax, self._jnp
        sk, sv = self._cache_sharding
        self.cache_k = jax.device_put(
            jnp.zeros(self._cache_shape, self._dtype), sk
        )
        self.cache_v = jax.device_put(
            jnp.zeros(self._cache_shape, self._dtype), sv
        )

    def step(self, tok: np.ndarray, t: int) -> np.ndarray:
        """Feed token ``tok`` (B,) at position ``t``; returns next-token
        probabilities (B, vocab).  O(S_max) per call, prefix-independent."""
        import jax.numpy as jnp

        # dynamic_update_slice CLAMPS out-of-range starts — an oversized t
        # would silently overwrite position seq-1 instead of erroring
        assert 0 <= int(t) < self.seq, (
            f"position {t} outside the compiled sequence length {self.seq}"
        )
        probs, self.cache_k, self.cache_v = self._step(
            self.model.executor.params, self.cache_k, self.cache_v,
            jnp.asarray(tok, jnp.int32), jnp.asarray(t, jnp.int32),
        )
        return probs

    def prefill(self, toks: np.ndarray, start: int = 0) -> np.ndarray:
        """Feed ``toks`` (B, P) at positions ``start..start+P-1`` in ONE
        batched call (the phase-separated prompt ingestion — replaces P
        :meth:`step` dispatches); returns next-token probabilities
        (B, vocab) after the last row.  Each distinct P compiles once;
        the caches come back pinned to the session's sharding so the
        decode step's no-retrace guarantee survives a prefill."""
        import jax.numpy as jnp

        toks = jnp.asarray(toks, jnp.int32)
        assert toks.ndim == 2 and toks.shape[0] == self.batch, toks.shape
        P = toks.shape[1]
        assert P >= 1 and 0 <= int(start) and int(start) + P <= self.seq, (
            f"prefill [{start}, {start + P}) outside the compiled "
            f"sequence length {self.seq}"
        )
        probs, ck, cv = self._prefill(
            self.model.executor.params, self.cache_k, self.cache_v,
            toks, jnp.asarray(start, jnp.int32),
        )
        sk, sv = self._cache_sharding
        self.cache_k = self._jax.device_put(ck, sk)
        self.cache_v = self._jax.device_put(cv, sv)
        return probs


def gpt_generate_cached(
    model,
    prompt_ids,
    max_new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
    session: GPTDecodeSession | None = None,
    top_k: int = 0,
    top_p: float = 1.0,
    batched_prefill: bool = True,
) -> Tuple[np.ndarray, GPTDecodeSession]:
    """Cache-carrying generation — same contract as
    :func:`flexflow_tpu.models.transformer.gpt_generate` (greedy at
    temperature 0, softmax sampling otherwise) but each step costs
    O(S_max), not a full-prefix forward.  Returns ``(ids, session)``;
    pass ``session`` back in to reuse the compiled step across calls.

    ``batched_prefill=True`` (default) ingests the whole prompt in ONE
    :meth:`GPTDecodeSession.prefill` call; ``False`` keeps the original
    token-at-a-time warmup loop (the two are bit-identical — pinned by
    tests/test_serve.py — so the flag exists for that pin and for
    A/B-ing dispatch counts, not because outputs differ).
    """
    assert session is None or session.model is model, (
        "session was built for a different model"
    )
    sess = session or GPTDecodeSession(model)
    sess.reset()
    p = np.asarray(prompt_ids, np.int32)
    batch, start = p.shape
    assert batch == sess.batch, (batch, sess.batch)
    end = start + max_new_tokens
    assert 1 <= start and end <= sess.seq, (
        f"prompt_len + max_new_tokens = {end} exceeds the compiled "
        f"sequence length {sess.seq}"
    )
    out = np.zeros((batch, end), np.int32)
    out[:, :start] = p
    rng = np.random.default_rng(seed)
    if batched_prefill:
        probs = sess.prefill(p, 0)
    else:
        probs = None
        for t in range(start):  # prefill: feed prompt tokens one at a time
            probs = sess.step(out[:, t], t)
    from flexflow_tpu.models.transformer import sample_next

    for t in range(start, end):
        nxt = sample_next(
            np.asarray(probs), temperature, rng, top_k=top_k, top_p=top_p
        )
        out[:, t] = nxt
        if t + 1 < end:
            probs = sess.step(nxt, t)
    return out, sess
