"""Paged/block KV-cache allocator with copy-on-write prefix sharing
(docs/SERVING.md).

The dense decode session reserves a monolithic ``(L, B, H, S_max, D)``
cache — every slot pays max-S HBM whether its conversation is 8 tokens
or 8000.  This module carves the same capacity into fixed-size blocks
(``block_size`` positions each, all layers and heads of one slot's
position range together) with a free list and per-request block tables:
physically the cache is position-major, ``(L, num_blocks * block_size,
H * D)`` — a row holds every head of one position, physical block ``n``
is the ``block_size`` rows from ``n * block_size`` — and a request's
logical position ``p`` lives in physical block ``table[p //
block_size]`` at offset ``p % block_size``.  The minor dimension is the
whole ``H * D`` row (768 at GPT-2-small width), so the layout the TPU
keeps the pool in at rest is the plain row-major tiled one the Pallas
kernels read: no serve program re-lays a pool out (PERF.md, PR 29; a
(..., block_size, D) page with D = 64 is kept in a compact layout of
the runtime's own and cost four whole-pool copies a call).  Short and
long requests then share HBM — the pool only needs to cover the sum of
*actual* reserved lengths, not slots x max-S (the admission test in
tests/test_serve.py pins a workload whose summed max-lengths exceed the
monolithic footprint).

**Prefix sharing (PR 11).**  Physical blocks are ref-counted and keyed
by the cumulative hash of the prompt tokens they hold: block ``b`` of a
prompt is registered under ``sha1(prompt[0:(b+1)*block_size])`` once its
positions are fully written, so the key identifies both content AND
position — two requests whose prompts agree on the first
``(b+1)*block_size`` tokens provably hold bit-identical K/V there (the
prefill program is deterministic and causal).  A later reservation that
matches the index maps the existing physical block into its table and
bumps the refcount instead of allocating; admission then charges only
*unshared* blocks.  Registered blocks whose refcount drops to zero are
RETAINED in an LRU cache (still indexed — a second wave of requests with
the same system prompt hits warm) and evicted lazily when the free list
runs dry.  Shared blocks are read-only by discipline: the engine only
ever writes positions past the shared prefix, and
:meth:`PagedKVCache.ensure_private` provides the copy-on-write escape
hatch (allocate a fresh block, copy the device contents, drop the
refcount) for any path that must write inside one —
:meth:`shared_write_hazards` is the auditable invariant ffcheck pins.

Allocation policy: blocks for a request's full declared budget
(``prompt_len + max_new_tokens``) are reserved at admission, so
mid-flight exhaustion cannot happen — a request that fits is never
killed for blocks.  The trade-off (vs vLLM-style lazy growth +
preemption) is documented in docs/SERVING.md; reservation keeps the
zero-sync decode windows free of allocation faults.  Exhaustion
surfaces in exactly two graceful forms: :meth:`PagedKVCache.can_reserve`
= False (scheduler keeps the request queued, FIFO) and
:exc:`KVCacheOOM` on a reserve that was not pre-checked.

**Spill/restore (SLO preemption).**  :meth:`spill` drains one slot's
live K/V to host as a per-layer payload (the per-layer checkpoint
convention: one ``layer{i} -> {k, v}`` entry per decoder layer, dtype
preserved bit-for-bit) and releases its blocks; :meth:`restore` reserves
fresh blocks (re-attaching any prefix blocks still in the index) and
scatters the private positions back.  Because gather/scatter preserve
bytes, a preempted request resumes the exact token stream.

Physical block 0 is the TRASH block: never allocated, never registered,
it absorbs the writes of inactive decode lanes and padded prefill rows
(their block tables are all-zero), so the jitted step needs no masking.

**Two layer groups (PR 32, docs/SERVING.md "Window and full layers").**
A model whose layers mix full and sliding-window attention keeps the two
kinds apart.  Everything above is the FULL group: its layers keep every
page of a request.  A WINDOW layer never looks further back than
``window`` positions, so the window group (``window_layers`` > 0) is a
RING a slot: ``ring_blocks = ceil((window + chunk) / block_size) + 1``
pages in a pool of its own (``win_k`` / ``win_v``, ``(window_layers,
(slots * ring_blocks + 1) * block_size, H * D)``), logical page ``j``
of the slot at ring page ``j % ring_blocks``, mapped into ``win_tables``
while the slot holds a reservation and zeroed (the group's own trash
block 0) while it does not.  A chunk of up to ``chunk`` rows is written
and then attended: the oldest position any of its rows can see and the
newest it writes are less than ``ring_blocks`` pages apart, so a write
never lands on a page still visible.  The ring cannot run out, so
admission is decided by the full group alone; however long a request,
a window layer holds ``ring_blocks`` pages of it.  Prefix sharing is
declined for such a model (both groups): a prefix re-attached in the
full group would leave the window layers without the keys their first
rows look back to.

**A third group, STATE (PR 34, docs/SERVING.md "State layers").**  A
state-space layer (``ops/ssm.py``) keeps no rows of a request: its
memory is a recurrent state of fixed size, read and written whole by
every step.  With ``state_layers`` > 0 the cache holds, a state layer,
the convolution's last inputs ``state_conv[i]`` ``(slots, taps - 1,
channels)`` in the compute dtype and the state ``state_ssm[i]`` float32
-- ``(slots, heads, head_dim, state)`` for Mamba-2, ``(slots, channels,
state)`` for Mamba-1; each layer's shapes its own (``state_shapes``) --
one array a layer, so that a program's update of a layer is in place
on a donated buffer --,
PROVISIONED A SLOT, not paged: a slot's state is there while the slot
is, whatever the request's length, so a free slot is all admission asks
of this group (the full group's blocks decide the rest).  Nothing is
zeroed on the device when a slot changes hands: the prefill program
reads the state of a lane whose chunk starts at position 0 as zero
(``programs.py``), which is what :meth:`reserve` relies on; a lane that
rides idle through a program keeps its state bit for bit.
:meth:`spill` carries a slot's two states with its K/V (a preempted
request resumes from both), :meth:`restore` writes them back through a
donated update (no copy of the pool).  Prefix sharing is declined for
such a model: a re-attached prefix has no state at its boundary.

A lightning-attention layer's state is a (heads, head_dim, head_dim)
float32 matrix a head and it has no convolution: its ``state_conv`` entry
is None (in the programs' arguments it takes no place).

**A fourth group, INDEX (docs/SERVING.md "Sparse layers").**  A sparse
layer (``ops/sparse_attention.py``) keeps, beside its K/V in the full
group, an index of compressed keys a slot: ``index_k[i]`` ``(slots,
entries, kv_heads, head_dim)`` in the compute dtype, entry ``j`` the mean
of the keys of positions ``stride j .. stride j + kernel - 1``, written by
the program that writes the last of them.  Provisioned a slot like a
state (a recycled slot's stale entries are never read: a query reads the
entries its own request has completed), spilled and restored with the
slot's K/V, counted by ``check_invariants`` / ``hbm_bytes`` /
``index_bytes()``; prefix sharing is declined (a re-attached prefix has
no index of its own).

**Quantized pools (PR 19, docs/SERVING.md "Quantized KV cache").**
``kv_dtype="int8" | "fp8"`` stores the pools in 1-byte elements with
per-block symmetric scale arrays ``scale_k``/``scale_v`` of shape
``(L, num_blocks, block_size)`` float32 living BESIDE the pools — one
scale per written position (shared across heads and head_dim), rows
addressed by the same physical block ids the tables hold.  The
allocator never looks at the scales: alloc/free/refcount/CoW/prefix
indexing are byte-for-byte the fp32 code paths (only
:meth:`ensure_private` additionally copies the scale row with the
block's device contents, and spill/restore carry the quantized ints +
scales so frames shrink by the element-size ratio).  The quantize rule
(:func:`quantize_kv`) and the dequant rule (``int.astype(f32) *
scale``) are module functions so the engine's scatter, the Pallas
kernel, and the gather fallback provably share ONE contract.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.obs import get_tracer

__all__ = [
    "PagedKVCache",
    "KVCacheOOM",
    "KV_DTYPES",
    "kv_pool_dtype",
    "kv_qmax",
    "quantize_kv",
    "dequantize_kv",
]

# the --serve-kv-dtype vocabulary; "fp32" means "full precision in the
# engine's compute dtype" (the legacy pool — possibly bf16 on a bf16
# model), so fp32 arms stay byte-identical to pre-r19 builds
KV_DTYPES = ("fp32", "bf16", "int8", "fp8")

# symmetric quantization range per storage format: int8 clips at +-127
# (the -128 code is unused so the grid is symmetric); fp8 e4m3fn's max
# finite value is 448
_QMAX = {"int8": 127.0, "fp8": 448.0}


def kv_qmax(kv_dtype: str) -> Optional[float]:
    """Symmetric quantization ceiling for ``kv_dtype`` (None when the
    format is full-precision and no scales exist)."""
    return _QMAX.get(kv_dtype)


def kv_pool_dtype(jnp, kv_dtype: str, fallback=None):
    """Resolve a ``kv_dtype`` name to the pool element dtype."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype {kv_dtype!r}: expected one of {KV_DTYPES}"
        )
    if kv_dtype == "fp32":
        return fallback if fallback is not None else jnp.float32
    if kv_dtype == "bf16":
        return jnp.bfloat16
    if kv_dtype == "int8":
        return jnp.int8
    return jnp.float8_e4m3fn


def quantize_kv(jnp, x, kv_dtype: str):
    """THE write-side quantization rule: symmetric per-position scales
    over the trailing ``(H, D)`` axes.  ``x`` is ``(..., H, D)`` float;
    returns ``(q, scale)`` with ``q`` in the pool dtype and ``scale``
    float32 of shape ``x.shape[:-2]``.  An all-zero position gets scale
    1.0 (its ints are zeros; dequant reproduces the zeros exactly) —
    never a divide-by-zero."""
    qmax = _QMAX[kv_dtype]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = xf / scale[..., None, None]
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(q), -qmax, qmax).astype(jnp.int8)
    else:
        q = q.astype(jnp.float8_e4m3fn)
    return q, scale


def dequantize_kv(jnp, q, scale):
    """THE read-side rule every consumer shares (engine gather fallback,
    Pallas in-register dequant, spill parity tests): cast the stored
    elements to f32 and multiply by the per-position scale.  ``q`` is
    ``(..., S, D)`` (positions on the second-to-last axis), ``scale``
    broadcasts over that axis: shape ``(..., S)``."""
    return q.astype(jnp.float32) * scale[..., None]


class KVCacheOOM(RuntimeError):
    """Raised when a reservation asks for more blocks than the free list
    (plus evictable cached blocks) holds.  The scheduler pre-checks
    :meth:`PagedKVCache.can_reserve`, so under the admission policy this
    surfaces only on misuse — it exists so exhaustion is an explicit,
    catchable condition, never a corrupted table."""


class PagedKVCache:
    """Free-list block allocator + the device-side paged K/V arrays.

    Host side: the free list, per-slot block tables, the prefix index
    with per-block refcounts, and the invariant checks (a block's
    refcount equals the number of tables mapping it, double-free
    rejected).  Device side: ``cache_k``/``cache_v`` of shape
    ``(L, num_blocks * block_size, H * D)``, written/read by the serving
    programs in :mod:`flexflow_tpu.serve.programs` through the Pallas
    kernels' block-table index_maps or gather/scatter indices derived
    from the block tables.
    """

    def __init__(
        self,
        num_layers: int,
        heads: int,
        head_dim: int,
        *,
        slots: int,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        max_blocks_per_seq: Optional[int] = None,
        max_seq_len: Optional[int] = None,
        dtype=None,
        kv_dtype: str = "fp32",
        prefix_sharing: bool = True,
        window_layers: int = 0,
        window: int = 0,
        chunk: int = 1,
        state_shapes: Sequence[Tuple[Optional[Tuple[int, ...]], Tuple[int, ...]]] = (),
        index_shapes: Sequence[Tuple[int, ...]] = (),
    ) -> None:
        """``num_layers`` full layers of ``heads`` K/V heads; with
        ``window_layers`` > 0 also that many layers that see ``window``
        positions back and are written at most ``chunk`` rows a call;
        a state layer for each ``(conv, ssm)`` pair of
        ``state_shapes``: a slot's ``conv`` = (taps - 1, channels) conv
        inputs (None: the layer has no conv) and its float32 ``ssm``
        state (``(heads, head_dim, state)`` for Mamba-2 and lightning
        attention, ``(channels, state)`` for Mamba-1); and a sparse
        layer's index of compressed keys for each shape of
        ``index_shapes`` (``(entries, kv_heads, head_dim)`` a slot)."""
        import jax.numpy as jnp

        assert block_size >= 1 and slots >= 1
        self.num_layers = num_layers
        self.heads = heads
        self.head_dim = head_dim
        self.slots = slots
        self.block_size = block_size
        self.window_layers = int(window_layers)
        self.window = int(window) if self.window_layers else 0
        assert not self.window_layers or self.window >= 1
        state_shapes = [(None if c is None else tuple(c), tuple(m)) for c, m in state_shapes]
        self.state_layers = len(state_shapes)
        self.index_layers = len(index_shapes)
        # declined for a model with window, state or index layers (module
        # docstring)
        self.prefix_sharing = (
            bool(prefix_sharing) and not self.window_layers and not self.state_layers
            and not self.index_layers
        )
        if max_blocks_per_seq is None:
            assert max_seq_len is not None, (
                "need max_blocks_per_seq or max_seq_len"
            )
            max_blocks_per_seq = -(-max_seq_len // block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        # positions a request may occupy: the table's reach, tightened
        # to the model's compiled position range when given (a block
        # boundary may overshoot it) — admission rejects past this
        self.position_limit = self.max_blocks_per_seq * block_size
        if max_seq_len is not None:
            self.position_limit = min(self.position_limit, int(max_seq_len))
        if num_blocks is None:
            # default: full provisioning (every slot can hold max length)
            # + the trash block; tests/benches pass a smaller pool to
            # exercise HBM sharing
            num_blocks = slots * self.max_blocks_per_seq + 1
        assert num_blocks >= 2, "need at least the trash block + one real"
        self.num_blocks = int(num_blocks)
        self.kv_dtype = str(kv_dtype)
        self.dtype = kv_pool_dtype(
            jnp, self.kv_dtype, fallback=dtype
        )
        self.quantized = self.kv_dtype in ("int8", "fp8")
        self.qmax = kv_qmax(self.kv_dtype)

        # block 0 is the trash block — never enters the free list
        self._free: deque = deque(range(1, self.num_blocks))
        self._owned: Dict[int, List[int]] = {}  # slot -> blocks, in order
        # sharing state: refcount per mapped block, cumulative-hash
        # index, retained (refcount-0 but still indexed) LRU, and the
        # per-slot count of leading READ-ONLY logical blocks (the CoW
        # write-isolation boundary shared_write_hazards audits)
        self._refcount: Dict[int, int] = {}
        self._index: Dict[bytes, int] = {}  # cum-hash -> physical block
        self._block_key: Dict[int, bytes] = {}  # reverse map
        self._cached: "OrderedDict[int, bytes]" = OrderedDict()  # LRU
        self._protected: Dict[int, int] = {}  # slot -> read-only blocks
        # observability counters (cumulative; engine snapshots them)
        self.prefix_hits = 0  # shareable block lookups that hit
        self.prefix_lookups = 0  # shareable block lookups attempted
        self.evictions = 0  # cached blocks recycled for fresh data
        self.cow_copies = 0  # ensure_private device copies performed
        # per-slot block tables; row = logical block idx -> physical id
        self.tables = np.zeros(
            (slots, self.max_blocks_per_seq), np.int32
        )
        # position-major: block n is rows n * block_size .., a row is all
        # heads of one position (the module docstring says why)
        shape = (
            num_layers, self.num_blocks * block_size, heads * head_dim,
        )
        self.cache_k = jnp.zeros(shape, self.dtype)
        self.cache_v = jnp.zeros(shape, self.dtype)
        # per-position symmetric scales, rows addressed by physical
        # block id exactly like the pools; zero scale dequantizes the
        # never-written trash/pad positions to exact zeros
        if self.quantized:
            sshape = (num_layers, self.num_blocks, block_size)
            self.scale_k = jnp.zeros(sshape, jnp.float32)
            self.scale_v = jnp.zeros(sshape, jnp.float32)
        else:
            self.scale_k = None
            self.scale_v = None
        # the window group: a ring of ``ring_blocks`` pages a slot, never
        # more than a table (then it never wraps)
        self.ring_blocks = 0
        self.win_tables = self.win_k = self.win_v = None
        if self.window_layers:
            if self.quantized:
                raise ValueError("a quantized pool is not built for window layers")
            self.ring_blocks = min(
                -(-(self.window + max(1, int(chunk))) // block_size) + 1,
                self.max_blocks_per_seq,
            )
            self.win_tables = np.zeros((slots, self.ring_blocks), np.int32)
            wshape = (
                self.window_layers,
                (slots * self.ring_blocks + 1) * block_size, heads * head_dim,
            )
            self.win_k = jnp.zeros(wshape, self.dtype)
            self.win_v = jnp.zeros(wshape, self.dtype)
        # the state group: one array a layer (module docstring)
        self.state_conv: List[Any] = []
        self.state_ssm: List[Any] = []
        self.state_spills = self.state_restores = 0
        if self.state_layers:
            if self.quantized:
                raise ValueError("a quantized pool is not built for state layers")
            cdt = dtype if dtype is not None else jnp.float32
            for conv, ssm in state_shapes:
                assert (conv is None or min(conv) >= 1) and min(ssm) >= 1, (conv, ssm)
                self.state_conv.append(None if conv is None else jnp.zeros((slots,) + conv, cdt))
                self.state_ssm.append(jnp.zeros((slots,) + ssm, jnp.float32))
        # the index group: a sparse layer's compressed keys, one array a
        # layer, provisioned a slot like a state
        self.index_k: List[Any] = [
            jnp.zeros((slots,) + tuple(shape), dtype if dtype is not None else jnp.float32)
            for shape in index_shapes
        ]
        self._set_row = None  # the jitted donated write of one slot's state

    # --- capacity queries --------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Retained prefix blocks: refcount 0, still indexed, evictable."""
        return len(self._cached)

    @property
    def allocatable_blocks(self) -> int:
        """Total blocks a single request could ever hold (pool minus
        trash) — the *permanent* rejection bound."""
        return self.num_blocks - 1

    @property
    def max_seq_len(self) -> int:
        return self.position_limit

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        """Fraction of shareable-block lookups served from the index
        (None until the first lookup)."""
        if not self.prefix_lookups:
            return None
        return self.prefix_hits / self.prefix_lookups

    def blocks_for(self, seq_len: int) -> int:
        return -(-int(seq_len) // self.block_size)

    def shareable_blocks(self, prompt) -> int:
        """How many leading FULL blocks of ``prompt`` are eligible for
        sharing.  The last prompt position is always kept private so the
        consumer's own prefill computes the first next-token
        distribution — hence blocks whose end reaches ``len(prompt)-1``
        are excluded: ``(len(prompt) - 1) // block_size``."""
        if prompt is None or not self.prefix_sharing:
            return 0
        return max(0, (int(len(prompt)) - 1) // self.block_size)

    def _prefix_key(self, prompt, nblocks: int) -> bytes:
        tok = np.asarray(prompt, np.int32)[: nblocks * self.block_size]
        return hashlib.sha1(tok.tobytes()).digest()

    def prefix_matches(self, prompt) -> List[int]:
        """Physical ids of the longest indexed run of leading full
        blocks of ``prompt`` (prefix property: stops at the first
        miss).  Pure lookup — no refcounts change."""
        out: List[int] = []
        for b in range(self.shareable_blocks(prompt)):
            blk = self._index.get(self._prefix_key(prompt, b + 1))
            if blk is None:
                break
            out.append(blk)
        return out

    def blocks_needed(self, seq_len: int, prompt=None) -> Tuple[int, int]:
        """``(total, shared)`` block counts for a reservation of
        ``seq_len`` with ``prompt`` — admission charges only
        ``total - shared`` (the budget arithmetic prefix sharing
        changes; the scheduler's rejection reasons cite both)."""
        total = self.blocks_for(seq_len)
        shared = min(len(self.prefix_matches(prompt)), total)
        return total, shared

    def can_reserve(self, seq_len: int, prompt=None) -> bool:
        total, shared = self.blocks_needed(seq_len, prompt)
        # cached blocks the reservation itself would re-attach are not
        # evictable for it, hence the subtraction is over the REST
        evictable = sum(
            1 for b in self._cached
            if b not in set(self.prefix_matches(prompt))
        )
        return total - shared <= len(self._free) + evictable

    def fits_ever(self, seq_len: int) -> bool:
        """Could this length be served by an EMPTY pool with no shared
        prefix?  False means the raw budget alone overflows the pool —
        see :meth:`fits_with_sharing` for the sharing-aware bound."""
        n = self.blocks_for(seq_len)
        return n <= self.allocatable_blocks and seq_len <= self.max_seq_len

    def fits_with_sharing(self, seq_len: int, prompt=None) -> bool:
        """Could this request EVER be admitted given the prefix blocks
        currently indexed?  (Its private blocks must fit the pool.)"""
        if seq_len > self.max_seq_len:
            return False
        total, shared = self.blocks_needed(seq_len, prompt)
        return total - shared <= self.allocatable_blocks

    # --- reserve / release -------------------------------------------------
    def _acquire(self, n: int, protect=()) -> List[int]:
        """Take ``n`` writable blocks: free list first, then evict LRU
        retained prefix blocks (never one in ``protect`` — the blocks
        this same reservation is re-attaching)."""
        protect = set(protect)
        out: List[int] = []
        while len(out) < n:
            if self._free:
                out.append(self._free.popleft())
                continue
            victim = None
            for b in self._cached:  # oldest first
                if b not in protect:
                    victim = b
                    break
            if victim is None:
                # roll back — a failed reserve must take nothing
                self._free.extendleft(reversed(out))
                raise KVCacheOOM(
                    f"need {n} KV blocks, {len(self._free)} free + "
                    f"{len(self._cached)} cached (pool "
                    f"{self.allocatable_blocks}, block {self.block_size})"
                )
            self._evict(victim)
            out.append(self._free.popleft())
        assert 0 not in out, "trash block leaked into the free list"
        return out

    def _evict(self, blk: int) -> None:
        self._cached.pop(blk)
        key = self._block_key.pop(blk)
        self._index.pop(key, None)
        self._free.append(blk)
        self.evictions += 1

    def reserve(self, slot: int, seq_len: int, prompt=None) -> List[int]:
        """Map ``blocks_for(seq_len)`` blocks into ``slot``'s table —
        prefix-index hits re-attached (refcount bump, zero allocation),
        the remainder taken off the free list (evicting retained blocks
        when it runs dry).  Raises :exc:`KVCacheOOM` when short (callers
        pre-check :meth:`can_reserve`)."""
        assert 0 <= slot < self.slots
        assert slot not in self._owned, f"slot {slot} already reserved"
        n = self.blocks_for(seq_len)
        assert n <= self.max_blocks_per_seq, (
            f"seq_len {seq_len} exceeds max_blocks_per_seq "
            f"{self.max_blocks_per_seq} x block_size {self.block_size}"
        )
        shared = self.prefix_matches(prompt)[:n]
        want = self.shareable_blocks(prompt)
        if want:
            self.prefix_lookups += min(want, n)
            self.prefix_hits += len(shared)
        fresh = self._acquire(n - len(shared), protect=shared)
        for b in shared:
            if b in self._cached:  # revive a retained block
                self._cached.pop(b)
            self._refcount[b] = self._refcount.get(b, 0) + 1
        for b in fresh:
            assert self._refcount.get(b, 0) == 0
            self._refcount[b] = 1
        blocks = shared + fresh
        self._owned[slot] = blocks
        self._protected[slot] = len(shared)
        self.tables[slot, :] = 0
        self.tables[slot, :n] = blocks
        if self.window_layers:
            self.win_tables[slot] = self._ring(slot)
        return blocks

    def _ring(self, slot: int) -> np.ndarray:
        """The window group's pages of ``slot``: its ring, after the
        group's trash block 0."""
        R = self.ring_blocks
        return 1 + slot * R + np.arange(R, dtype=np.int32)

    def shared_len(self, slot: int) -> int:
        """Positions of ``slot`` served by re-attached prefix blocks —
        the engine's prefill starts here."""
        return self._protected.get(slot, 0) * self.block_size

    def release(self, slot: int) -> None:
        """Drop ``slot``'s references (mid-flight slot recycling — the
        freed blocks are immediately reservable by a queued request, no
        recompile).  Registered blocks whose refcount reaches zero are
        RETAINED in the LRU (warm prefix cache); unregistered ones go
        straight back to the free list."""
        blocks = self._owned.pop(slot, None)
        assert blocks is not None, f"slot {slot} holds no reservation"
        self._protected.pop(slot, None)
        free_set = set(self._free)
        for b in blocks:
            rc = self._refcount.get(b, 0)
            assert rc >= 1 and b not in free_set, f"double-free of block {b}"
            rc -= 1
            self._refcount[b] = rc
            if rc == 0:
                del self._refcount[b]
                if b in self._block_key:
                    self._cached[b] = self._block_key[b]  # LRU tail
                else:
                    self._free.append(b)
        self.tables[slot, :] = 0
        if self.window_layers:
            self.win_tables[slot] = 0

    def refcount(self, blk: int) -> int:
        return self._refcount.get(blk, 0)

    def owned(self, slot: int) -> Tuple[int, ...]:
        return tuple(self._owned.get(slot, ()))

    # --- prefix registration / copy-on-write -------------------------------
    def commit_prefix(self, slot: int, prompt, upto: int) -> int:
        """Register ``slot``'s fully-written full-prompt blocks (all of
        whose positions are < ``upto`` AND prompt tokens) under their
        cumulative hashes, making them shareable by later reservations.
        Registered blocks become read-only for the producer too (the
        protected boundary advances).  Returns how many blocks are now
        registered for this slot."""
        if not self.prefix_sharing or slot not in self._owned:
            return 0
        plen = int(len(prompt))
        full = min(int(upto), plen) // self.block_size
        done = 0
        for b in range(min(full, len(self._owned[slot]))):
            blk = self._owned[slot][b]
            if blk in self._block_key:
                done += 1
                continue  # already registered (ours or re-attached)
            key = self._prefix_key(prompt, b + 1)
            if key in self._index:
                # another slot registered identical content first; keep
                # our private copy (merging would need a table rewrite)
                continue
            self._index[key] = blk
            self._block_key[blk] = key
            done += 1
        self._protected[slot] = max(self._protected.get(slot, 0), done)
        return done

    def ensure_private(self, slot: int, logical_idx: int) -> int:
        """Copy-on-write: make ``slot``'s ``logical_idx``-th block
        writable.  A block shared with other tables (refcount > 1) is
        replaced by a fresh copy of its device contents; a sole-owned
        but still-indexed block is simply de-registered.  Returns the
        (possibly new) physical id."""
        blocks = self._owned[slot]
        assert 0 <= logical_idx < len(blocks)
        blk = blocks[logical_idx]
        if self._refcount.get(blk, 0) <= 1:
            if blk in self._block_key:  # de-register: sole owner writes
                key = self._block_key.pop(blk)
                self._index.pop(key, None)
            self._protected[slot] = min(
                self._protected.get(slot, 0), logical_idx
            )
            return blk
        new = self._acquire(1, protect=blocks)[0]
        src, dst = self._rows([blk]), self._rows([new])
        self.cache_k = self.cache_k.at[:, dst].set(self.cache_k[:, src])
        self.cache_v = self.cache_v.at[:, dst].set(self.cache_v[:, src])
        if self.quantized:  # the scale row travels with its block
            self.scale_k = self.scale_k.at[:, new].set(self.scale_k[:, blk])
            self.scale_v = self.scale_v.at[:, new].set(self.scale_v[:, blk])
        self.cow_copies += 1
        self._refcount[blk] -= 1
        self._refcount[new] = 1
        blocks[logical_idx] = new
        self.tables[slot, logical_idx] = new
        self._protected[slot] = min(self._protected.get(slot, 0), logical_idx)
        return new

    def shared_write_hazards(self) -> List[Tuple[int, int, int]]:
        """The CoW-safety invariant ffcheck audits (docs/ANALYSIS.md):
        every block a slot may WRITE (logical index at or past its
        protected boundary) must be private and unindexed — the serving
        programs donate the whole pool, so a shared block in the write
        path would corrupt every other table mapping it.  Returns
        ``(slot, logical_idx, block)`` rows; empty == safe."""
        out: List[Tuple[int, int, int]] = []
        for slot, blocks in self._owned.items():
            for i in range(self._protected.get(slot, 0), len(blocks)):
                b = blocks[i]
                if self._refcount.get(b, 0) > 1 or b in self._block_key:
                    out.append((slot, i, b))
        return out

    # --- spill / restore (preemption) --------------------------------------
    def spill(self, slot: int, length: int) -> Dict[str, Any]:
        """Drain ``slot``'s first ``length`` positions to host as a
        per-layer payload (checkpoint convention: ``layer{i} -> {k, v}``
        arrays of shape ``(H, length, D)``, dtype preserved) and release
        the reservation.  The payload + :meth:`restore` round-trip is
        bit-exact, so a preempted request resumes its exact stream.

        The payload is DENSE — it carries no trace of this pool's
        ``block_size``/``num_blocks`` geometry, so it restores into a
        pool with a *different* geometry (the disagg prefill→decode
        handoff, serve/wire.py); only the model shape (layers, heads,
        head_dim) must match, which :meth:`restore` checks."""
        k, v = self.gather_dense(slot, length)
        payload = {
            "length": int(length),
            "layers": {
                f"layer{i}": {"k": np.asarray(k[i]), "v": np.asarray(v[i])}
                for i in range(self.num_layers)
            },
        }
        if self.quantized:
            # quantized frames carry the raw pool ints (above — dtype
            # preserved by gather_dense) plus their per-position scales;
            # fp32/bf16 payloads stay byte-identical to pre-r19 frames
            payload["kv_dtype"] = self.kv_dtype
            sk, sv = self.gather_scales(slot, length)
            for i in range(self.num_layers):
                payload["layers"][f"layer{i}"]["sk"] = np.asarray(sk[i])
                payload["layers"][f"layer{i}"]["sv"] = np.asarray(sv[i])
        if self.window_layers:
            # a window layer's payload starts at the page of the oldest
            # position the next row (at ``length``) can still see
            lo = self.window_first_held(length)
            wk, wv = self.gather_window(slot, lo, length)
            payload["window"] = {
                "lo": lo,
                "layers": {
                    f"layer{i}": {"k": wk[i], "v": wv[i]}
                    for i in range(self.window_layers)
                },
            }
        if self.state_layers:
            with get_tracer().span("state_spill", cat="serve"):
                payload["state"] = {
                    "layers": {
                        f"layer{i}": {"conv": None if c is None else np.asarray(c[slot]),
                                      "ssm": np.asarray(s[slot])}
                        for i, (c, s) in enumerate(zip(self.state_conv, self.state_ssm))
                    },
                }
                self.state_spills += 1
        if self.index_layers:
            payload["index"] = {
                f"layer{i}": np.asarray(a[slot]) for i, a in enumerate(self.index_k)
            }
        self.release(slot)
        return payload

    def window_first_held(self, length: int) -> int:
        """The first position, on a page boundary, that a window layer
        still has to hold for a request ``length`` positions in."""
        lo = max(0, int(length) - self.window + 1)
        return lo // self.block_size * self.block_size

    def _window_rows(self, slot: int, lo: int, hi: int) -> np.ndarray:
        """Rows of the window pool that hold ``slot``'s positions
        ``lo .. hi - 1`` (the ring's mapping, by the slot's table)."""
        pos = np.arange(int(lo), int(hi))
        page = self.win_tables[slot][(pos // self.block_size) % self.ring_blocks]
        return page * self.block_size + pos % self.block_size

    def gather_window(self, slot: int, lo: int, hi: int):
        """Host-side copy of ``slot``'s positions ``lo .. hi - 1`` in the
        window group: ``(window_layers, H, hi - lo, D)`` keys and values
        (at most a ring's worth are there to gather)."""
        assert hi - lo <= self.ring_blocks * self.block_size
        rows = self._window_rows(slot, lo, hi)
        L, H, D = self.window_layers, self.heads, self.head_dim
        k = np.asarray(self.win_k[:, rows]).reshape(L, -1, H, D).transpose(0, 2, 1, 3)
        v = np.asarray(self.win_v[:, rows]).reshape(L, -1, H, D).transpose(0, 2, 1, 3)
        return np.ascontiguousarray(k), np.ascontiguousarray(v)

    def restore(self, slot: int, payload: Dict[str, Any], seq_len: int,
                prompt=None) -> int:
        """Re-reserve ``seq_len`` for ``slot`` (prefix blocks still in
        the index re-attach — their contents are provably identical to
        the spilled data at those positions) and scatter the private
        remainder of the payload back into the fresh blocks.  Returns
        the re-attached shared length in positions.

        A QUANTIZED payload (``payload["kv_dtype"]`` in int8/fp8) may
        only restore into a pool of the SAME ``kv_dtype`` — and a
        full-precision payload may not restore into a quantized pool:
        re-quantizing someone else's ints would silently change the
        stream, so the mismatch is refused (reservation released first,
        like the model-shape refusal below).  Within a matching dtype
        the quantized ints and their scales scatter back verbatim — the
        spill→restore→spill round trip is bit-exact with no
        re-quantization step anywhere.

        The payload may come from a pool with a DIFFERENT
        ``block_size``/``num_blocks`` geometry (it is dense per layer —
        see :meth:`spill`): re-chunking happens here against THIS
        pool's block size, and the cross-geometry property test pins
        the round trip bit-exact.  Only the model shape must agree —
        a payload whose (layers, heads, head_dim) differ is refused
        before any block is written."""
        import jax.numpy as jnp

        self.reserve(slot, seq_len, prompt=prompt)
        payload_dtype = str(payload.get("kv_dtype", "fp32"))
        pool_q = self.quantized
        frame_q = payload_dtype in ("int8", "fp8")
        if (pool_q or frame_q) and payload_dtype != (
            self.kv_dtype if pool_q else "fp32"
        ):
            self.release(slot)
            raise ValueError(
                f"KV payload kv_dtype {payload_dtype!r} cannot restore "
                f"into a kv_dtype {self.kv_dtype!r} pool — re-quantizing "
                f"a handoff frame would silently change the stream; "
                f"spill and restore pools must agree on kv_dtype"
            )
        shared_pos = self.shared_len(slot)
        length = int(payload["length"])
        if ("window" in payload) != bool(self.window_layers):
            self.release(slot)
            raise ValueError(
                "KV payload and pool disagree on window layers: a payload "
                "restores into a pool of the same layer groups"
            )
        for group, held in (("state", self.state_layers), ("index", self.index_layers)):
            if (group in payload) != bool(held):
                self.release(slot)
                raise ValueError(
                    f"KV payload and pool disagree on {group} layers: a payload "
                    "restores into a pool of the same layer groups"
                )
        if self.window_layers:
            self._restore_window(slot, payload["window"], length)
        if self.state_layers:
            self._restore_state(slot, payload["state"])
        if self.index_layers:
            rows = [payload["index"][f"layer{i}"] for i in range(self.index_layers)]
            self._set_rows(slot, self.index_k, rows, "index payload")
        if length <= shared_pos:
            return shared_pos
        L, H, BS, D = (
            self.num_layers, self.heads, self.block_size, self.head_dim,
        )
        lo_blk = shared_pos // BS
        hi_blk = self.blocks_for(length)
        nb = hi_blk - lo_blk
        pad = hi_blk * BS - length
        k = np.stack([
            np.asarray(payload["layers"][f"layer{i}"]["k"]) for i in range(L)
        ])
        v = np.stack([
            np.asarray(payload["layers"][f"layer{i}"]["v"]) for i in range(L)
        ])
        if k.shape != (L, H, length, D) or v.shape != k.shape:
            self.release(slot)
            raise ValueError(
                f"KV payload shape {k.shape} does not match this pool's "
                f"model shape (layers={L}, heads={H}, length={length}, "
                f"head_dim={D}) — payloads are portable across block "
                f"geometries, not across model shapes"
            )
        if pad:
            zeros = np.zeros((L, H, pad, D), k.dtype)
            k = np.concatenate([k, zeros], axis=2)
            v = np.concatenate([v, zeros], axis=2)
        # (L, H, hi*BS, D) -> the private span's rows (L, nb*BS, H*D)
        k = k[:, :, lo_blk * BS:].transpose(0, 2, 1, 3).reshape(
            L, nb * BS, H * D
        )
        v = v[:, :, lo_blk * BS:].transpose(0, 2, 1, 3).reshape(
            L, nb * BS, H * D
        )
        ids = np.asarray(self._owned[slot][lo_blk:hi_blk], np.int32)
        assert not any(
            self._refcount.get(int(b), 0) > 1 or int(b) in self._block_key
            for b in ids
        ), "restore would write a shared block (CoW discipline breached)"
        rows = self._rows(ids)
        self.cache_k = self.cache_k.at[:, rows].set(jnp.asarray(k, self.dtype))
        self.cache_v = self.cache_v.at[:, rows].set(jnp.asarray(v, self.dtype))
        if self.quantized:
            sk = np.stack([
                np.asarray(payload["layers"][f"layer{i}"]["sk"],
                           np.float32)
                for i in range(L)
            ])
            sv = np.stack([
                np.asarray(payload["layers"][f"layer{i}"]["sv"],
                           np.float32)
                for i in range(L)
            ])
            if pad:
                zpad = np.zeros((L, pad), np.float32)
                sk = np.concatenate([sk, zpad], axis=1)
                sv = np.concatenate([sv, zpad], axis=1)
            sk = sk[:, lo_blk * BS:].reshape(L, nb, BS)
            sv = sv[:, lo_blk * BS:].reshape(L, nb, BS)
            self.scale_k = self.scale_k.at[:, ids].set(jnp.asarray(sk))
            self.scale_v = self.scale_v.at[:, ids].set(jnp.asarray(sv))
        return shared_pos

    def _restore_window(self, slot: int, win: Dict[str, Any], length: int) -> None:
        import jax.numpy as jnp

        L, H, D = self.window_layers, self.heads, self.head_dim
        lo = int(win["lo"])
        k = np.stack([np.asarray(win["layers"][f"layer{i}"]["k"]) for i in range(L)])
        v = np.stack([np.asarray(win["layers"][f"layer{i}"]["v"]) for i in range(L)])
        if k.shape != (L, H, length - lo, D) or v.shape != k.shape:
            self.release(slot)
            raise ValueError(
                f"window payload shape {k.shape} does not match this pool "
                f"(layers={L}, heads={H}, positions={length - lo}, head_dim={D})"
            )
        rows = self._window_rows(slot, lo, length)
        k = k.transpose(0, 2, 1, 3).reshape(L, -1, H * D)
        v = v.transpose(0, 2, 1, 3).reshape(L, -1, H * D)
        self.win_k = self.win_k.at[:, rows].set(jnp.asarray(k, self.dtype))
        self.win_v = self.win_v.at[:, rows].set(jnp.asarray(v, self.dtype))

    def _restore_state(self, slot: int, state: Dict[str, Any]) -> None:
        """Write a spilled slot's states back."""
        with get_tracer().span("state_restore", cat="serve"):
            layers = [state["layers"][f"layer{i}"] for i in range(self.state_layers)]
            self._set_rows(slot, self.state_conv, [g["conv"] for g in layers], "state payload conv")
            self._set_rows(slot, self.state_ssm, [g["ssm"] for g in layers], "state payload ssm")
            self.state_restores += 1

    def _set_rows(self, slot: int, pools: List[Any], rows, name: str) -> None:
        """``pools[i][slot] = rows[i]``, each through a donated update of
        its array (in place: no copy of a layer's pool); an absent array
        (a layer without a conv) takes nothing."""
        import jax

        if self._set_row is None:
            self._set_row = jax.jit(
                lambda pool, at, row: pool.at[at].set(row.astype(pool.dtype)),
                donate_argnums=0,
            )
        for i, row in enumerate(rows):
            if pools[i] is None and row is None:
                continue
            row = None if row is None else np.asarray(row)
            if pools[i] is None or row is None or row.shape != tuple(pools[i].shape[1:]):
                self.release(slot)
                want = None if pools[i] is None else tuple(pools[i].shape[1:])
                raise ValueError(
                    f"{name} {None if row is None else row.shape} does not "
                    f"match this pool's {want} a slot"
                )
            pools[i] = self._set_row(pools[i], np.int32(slot), row)

    @property
    def state_slots_held(self) -> int:
        """Slots whose state belongs to a request right now."""
        return len(self._owned) if self.state_layers else 0

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds over all state layers,
        whatever its request's length."""
        return sum(
            (a.size // self.slots) * a.dtype.itemsize
            for a in self.state_conv + self.state_ssm if a is not None
        )

    def state_bytes(self) -> int:
        """Physical footprint of the state group."""
        return self.state_bytes_per_slot * self.slots

    def index_bytes(self) -> int:
        """Physical footprint of the index group."""
        return sum(a.size * a.dtype.itemsize for a in self.index_k)

    def pages_held(self) -> Dict[str, int]:
        """Pages mapped into slots' tables right now, a layer group: the
        full group's follow the requests' budgets, the window group's are
        ``ring_blocks`` a slot that holds a reservation."""
        return {
            "full": sum(len(b) for b in self._owned.values()),
            "window": self.ring_blocks * len(self._owned) if self.window_layers else 0,
        }

    # --- invariants ---------------------------------------------------------
    def check_invariants(self) -> None:
        """Every block is free, retained (refcount 0 + indexed), or
        mapped by >= 1 table with a matching refcount; the trash block is
        none of these; the index and reverse map agree."""
        free = list(self._free)
        cached = list(self._cached)
        owned = [b for bs in self._owned.values() for b in bs]
        assert 0 not in free + cached + owned, "trash block allocated"
        counts: Dict[int, int] = {}
        for b in owned:
            counts[b] = counts.get(b, 0) + 1
        assert counts == self._refcount, (
            "refcounts disagree with table ownership",
            counts, self._refcount,
        )
        assert not (set(free) | set(cached)) & set(owned), (
            "block both free/cached and owned"
        )
        assert not set(free) & set(cached), "block both free and cached"
        all_ = free + cached + sorted(set(owned))
        assert sorted(all_) == list(range(1, self.num_blocks)), (
            "blocks leaked or invented"
        )
        for key, blk in self._index.items():
            assert self._block_key.get(blk) == key, "index/reverse mismatch"
        for blk in cached:
            assert blk in self._block_key, "retained block lost its key"
        if self.window_layers:
            for slot in range(self.slots):
                want = self._ring(slot) if slot in self._owned else 0
                assert (self.win_tables[slot] == want).all(), (
                    f"slot {slot}: the window group's table is not "
                    f"{'its ring' if slot in self._owned else 'the trash block'}"
                )
        assert len(self.state_conv) == len(self.state_ssm) == self.state_layers
        assert len(self.index_k) == self.index_layers
        for a in self.state_conv + self.state_ssm + self.index_k:
            assert a is None or (a.shape[0] == self.slots and not a.is_deleted()), (
                "a state layer's or an index layer's array is not one row a "
                "slot, or was donated and not stored back"
            )

    # --- device-side views -------------------------------------------------
    def _rows(self, blocks) -> np.ndarray:
        """The pool rows of physical ``blocks``, in order: block ``n`` is
        rows ``n * block_size .. (n + 1) * block_size - 1``."""
        ids = np.asarray(blocks, np.int32)[:, None] * self.block_size
        return (ids + np.arange(self.block_size, dtype=np.int32)).ravel()

    def table_row(self, slot: int):
        """One slot's (max_blocks_per_seq,) block table, for prefill."""
        return self.tables[slot].copy()

    def gather_dense(self, slot: int, seq_len: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side re-assembly of ``slot``'s first ``seq_len`` cached
        positions into dense ``(L, H, seq_len, D)`` arrays — the
        bit-parity bridge the tests use to compare paged contents
        against the dense session's cache (dtype preserved)."""
        ck = np.asarray(self.cache_k)
        cv = np.asarray(self.cache_v)
        L, H, D = self.num_layers, self.heads, self.head_dim
        n = self.blocks_for(seq_len)
        rows = self._rows(self.tables[slot][:n])[:seq_len]
        # (L, seq_len, H * D) rows -> heads in front of positions
        k = ck[:, rows].reshape(L, -1, H, D).transpose(0, 2, 1, 3)
        v = cv[:, rows].reshape(L, -1, H, D).transpose(0, 2, 1, 3)
        return np.ascontiguousarray(k), np.ascontiguousarray(v)

    def gather_scales(self, slot: int, seq_len: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side re-assembly of ``slot``'s per-position scales into
        dense ``(L, seq_len)`` float32 arrays (quantized pools only) —
        the companion of :meth:`gather_dense` for spill frames and
        parity tests."""
        assert self.quantized, "full-precision pools have no scales"
        sk = np.asarray(self.scale_k)
        sv = np.asarray(self.scale_v)
        row = self.tables[slot]
        L, BS = self.num_layers, self.block_size
        n = self.blocks_for(seq_len)
        sk = sk[:, row[:n]].reshape(L, n * BS)[:, :seq_len]
        sv = sv[:, row[:n]].reshape(L, n * BS)[:, :seq_len]
        return sk, sv

    def gather_dense_dequant(self, slot: int, seq_len: int) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`gather_dense`, dequantized to float32 via the shared
        :func:`dequantize_kv` rule when the pool is quantized (identity
        cast otherwise) — what parity tests compare against a
        full-precision session."""
        import jax.numpy as jnp

        k, v = self.gather_dense(slot, seq_len)
        if not self.quantized:
            return np.asarray(k, np.float32), np.asarray(v, np.float32)
        sk, sv = self.gather_scales(slot, seq_len)
        # k is (L, H, S, D); scales (L, S) broadcast over the S axis
        k = np.asarray(dequantize_kv(jnp, jnp.asarray(k),
                                     jnp.asarray(sk)[:, None, :]))
        v = np.asarray(dequantize_kv(jnp, jnp.asarray(v),
                                     jnp.asarray(sv)[:, None, :]))
        return k, v

    @property
    def bytes_per_token(self) -> int:
        """HBM bytes one cached position costs across all layers of both
        groups while every layer holds it (k+v elements over the K/V
        heads, plus the 2 float32 scales per layer when quantized) —
        the ffmetrics/1 ``kv_bytes_per_token`` field.  The state group
        costs nothing a position: :attr:`state_bytes_per_slot`."""
        layers = self.num_layers + self.window_layers
        elems = 2 * layers * self.heads * self.head_dim
        n = elems * self.cache_k.dtype.itemsize
        if self.quantized:
            n += 2 * self.num_layers * 4
        return n

    def hbm_bytes(self) -> int:
        """Physical pool footprint (both caches + scales, all four
        layer groups)."""
        n = 2 * self.cache_k.size * self.cache_k.dtype.itemsize
        if self.quantized:
            n += 2 * self.scale_k.size * 4
        if self.window_layers:
            n += 2 * self.win_k.size * self.win_k.dtype.itemsize
        return n + self.state_bytes() + self.index_bytes()
