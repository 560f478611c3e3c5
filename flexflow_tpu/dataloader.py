"""Data loading.

Reference: ``SingleDataLoader`` (``include/flexflow/dataloader.h:34-110``,
``src/dataloader/dataloader.cc``) — stages the full numpy array into
zero-copy memory once, then per-batch index tasks copy shards to each GPU
(``next_batch_xd_launcher``, ``dataloader.cc:232-300``), with float/int32/
int64 × dim variants as separate Legion tasks (``model.h:167-176``).

TPU-native: the full array stays in host RAM; each batch is device_put with
the batch's NamedSharding so every chip receives exactly its shard (the
"index task per point" becomes one sharded transfer).  An optional
double-buffer prefetches batch i+1 while step i runs — replacing the
overlap the reference gets from Legion's asynchronous task issue.
For multi-host runs, each process slices only its addressable portion
(``jax.make_array_from_process_local_data``).
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from flexflow_tpu.obs.trace import get_tracer
from flexflow_tpu.parallel.spec import TensorSharding


class SingleDataLoader:
    """One loader per model input tensor (mirrors reference 1:1 pairing of
    loader <-> ParallelTensor)."""

    def __init__(
        self,
        data: np.ndarray,
        batch_size: int,
        sharding: Optional[TensorSharding] = None,
        mesh: Optional[Mesh] = None,
        shuffle: bool = False,
        seed: int = 0,
    ) -> None:
        self.data = np.asarray(data)
        self.batch_size = batch_size
        self.num_samples = self.data.shape[0]
        self.sharding = sharding
        self.mesh = mesh
        self.shuffle = shuffle
        self._rng = np.random.default_rng(seed)
        self._order = np.arange(self.num_samples)

    @property
    def num_batches(self) -> int:
        return self.num_samples // self.batch_size

    def reset(self) -> None:
        """New epoch (reference ``reset()``); reshuffles if enabled."""
        if self.shuffle:
            self._rng.shuffle(self._order)

    def next_batch(self, idx: int):
        """Batch ``idx`` as a (possibly sharded) device array."""
        sel = self._order[idx * self.batch_size : (idx + 1) * self.batch_size]
        host = self.data[sel]
        if self.mesh is not None and self.sharding is not None and self.mesh.size > 1:
            ns = NamedSharding(self.mesh, self.sharding.partition_spec())
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(ns, host)
            return jax.device_put(host, ns)
        return host

    def __iter__(self) -> Iterator:
        for i in range(self.num_batches):
            yield self.next_batch(i)


class BatchIterator:
    """Zips several loaders (inputs + label) into per-step tuples.

    With ``prefetch_depth > 0`` a background producer thread assembles
    batches ahead of the step loop into a bounded queue — the pure-Python
    analog of the native ring-buffer loader (``native/ffdl.cc``): host
    row gather / fancy-indexing of batch i+1 overlaps device compute of
    batch i.  The producer draws batches in the SAME index order as the
    unprefetched path (``next_batch(0..n)`` against the epoch's fixed
    shuffle permutation), so prefetching never changes which rows a step
    sees.  Shutdown is clean: abandoning the iterator mid-epoch (break /
    GC) stops and joins the producer — it never blocks forever on a full
    queue (bounded timed puts against a stop event)."""

    def __init__(
        self,
        loaders: Sequence[SingleDataLoader],
        prefetch_depth: int = 0,
    ) -> None:
        assert loaders
        self.loaders = list(loaders)
        self.prefetch_depth = int(prefetch_depth)
        n = {l.num_batches for l in loaders}
        assert len(n) == 1, "loaders disagree on batch count"
        self.num_batches = n.pop()

    def reset(self) -> None:
        for l in self.loaders:
            l.reset()

    def __iter__(self):
        if self.prefetch_depth <= 0:
            for i in range(self.num_batches):
                yield tuple(l.next_batch(i) for l in self.loaders)
            return
        yield from self._iter_prefetched()

    def _iter_prefetched(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_depth)
        stop = threading.Event()
        done = object()  # end-of-epoch sentinel
        failed = []  # producer exception, re-raised in the consumer

        def _put(item) -> bool:
            """Bounded put that yields to the stop event instead of
            blocking forever when the consumer has gone away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce() -> None:
            try:
                for i in range(self.num_batches):
                    batch = tuple(l.next_batch(i) for l in self.loaders)
                    if not _put(batch):
                        return
            except BaseException as e:  # surface loader errors in the consumer
                failed.append(e)
            _put(done)

        t = threading.Thread(
            target=produce, daemon=True, name="ffdl-py-prefetch"
        )
        t.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    if failed:
                        raise failed[0]
                    break
                yield item
        finally:
            stop.set()
            try:  # drain so a producer blocked on a full queue exits now
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)


class ViewIterator:
    """The batches of an unshuffled epoch as views of the caller's arrays.

    Batch ``i`` of an epoch in row order is rows ``[i*b, (i+1)*b)`` of
    every array: one contiguous slice, so a numpy view of it is the whole
    batch, with no gather, no ring, no producer thread and no copy.  Same
    contract as :class:`BatchIterator` (``num_batches``, ``reset()``,
    iteration from batch 0).  A view keeps its array alive for as long as
    the placed batch holds it, so the step may still be queued when the
    next view is taken (the reason ``NativeBatchIterator`` must copy does
    not apply)."""

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int) -> None:
        self.arrays = [np.asarray(a) for a in arrays]
        n = {a.shape[0] for a in self.arrays}
        if len(n) != 1:
            raise ValueError(f"arrays disagree on sample count: {sorted(n)}")
        self.batch_size = batch_size
        self.num_batches = n.pop() // batch_size

    def reset(self) -> None:
        """Nothing to reshuffle: the order is the arrays' own."""

    def __iter__(self):
        b = self.batch_size
        for i in range(self.num_batches):
            yield tuple(a[i * b:(i + 1) * b] for a in self.arrays)


class DevicePrefetcher:
    """Look-ahead device placement: stage 2 of the 3-stage input pipeline
    (batch assembly -> H2D placement -> step).

    Wraps any batch iterable (:class:`BatchIterator`,
    :class:`ViewIterator`, ``NativeBatchIterator``, or a generator) and
    applies ``place_fn`` — typically ``Executor.place_batch`` — to batch
    i+1..i+depth-1 while the consumer still runs step i.  ``jax.device_put`` dispatches transfers
    asynchronously, so "placing ahead" just means issuing the H2D copy
    early enough that it overlaps device compute instead of sitting on the
    critical path (the role Legion's deferred index-task launches play in
    the reference's dataloader, ``dataloader.cc:232-300``)."""

    def __init__(
        self,
        it: Any,
        place_fn: Callable[[Any], Any],
        depth: int = 2,
    ) -> None:
        self.it = it
        self.place_fn = place_fn
        self.depth = max(1, int(depth))
        self.num_batches = getattr(it, "num_batches", None)

    def reset(self) -> None:
        reset = getattr(self.it, "reset", None)
        if reset is not None:
            reset()

    def __iter__(self):
        # ff.input.batch_wait is the time this stage waited for stage 1
        # (the loader), ff.input.h2d_place the time it spent issuing the
        # placement; both close before the yield
        tracer = get_tracer()
        staged: collections.deque = collections.deque()
        it, done = iter(self.it), object()
        while True:
            with tracer.span("batch_wait", cat="input"):
                batch = next(it, done)
            if batch is done:
                break
            with tracer.span("h2d_place", cat="input"):
                staged.append(self.place_fn(batch))
            if len(staged) >= self.depth:
                yield staged.popleft()
        while staged:
            yield staged.popleft()
