"""Where a batch of the native loader spends its host time.

Times, per batch and separately, the two host copies a batch of the
native loader (``runtime/native.py::NativeBatchIterator``) makes before
it is placed: the wait in ``ffdl_next`` for the producer thread's row
gather into a ring slot, and the consumer's owned copy of that slot.
Beside them: the same copy into a buffer that is reused (no fresh pages),
and the numpy view of the same rows, which is all an unshuffled epoch
needs.  No device is touched.

    python tools/time_loader_copies.py [--rows 16,64] [--batches 12]

Rows are 512 x 768 float32 (1.5 MiB, one BERT-Base sequence of float
embeddings), so 16 rows are a 25 MB batch and 64 rows a 100 MB batch.
Prints one JSON line of medians and 90th percentiles in ms; ``--out``
also writes it to a file.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu.runtime.native import NativeBatchIterator  # noqa: E402

ROW = (512, 768)


def _stats(ms):
    a = np.asarray(ms)
    return {"median": float(np.median(a)), "p90": float(np.percentile(a, 90)), "n": len(a)}


def _batches(it):
    """(pointer, wait ms) per batch of one epoch, straight from ffdl_next."""
    it.reset()
    while True:
        t0 = time.perf_counter()
        idx = it._lib.ffdl_next(it._h, it._out)
        t1 = time.perf_counter()
        if idx < 0:
            return
        yield it._out[0], (t1 - t0) * 1e3


def measure(rows: int, batches: int, epochs: int, depth: int) -> dict:
    x = np.ones((rows * batches,) + ROW, np.float32)  # faulted in once, here
    shape = (rows,) + ROW
    nbytes = int(np.prod(shape)) * 4

    def slot(ptr):
        buf = (ctypes.c_char * nbytes).from_address(ptr)
        return np.frombuffer(buf, np.float32).reshape(shape)

    # the producer alone: the consumer takes each slot and copies nothing
    gather = []
    it = NativeBatchIterator([x], rows, prefetch_depth=depth)
    for _ in range(epochs):
        gather += [w for _, w in _batches(it)]
    del it

    # the loader as fit drives it: wait, then an owned copy, the last
    # ``depth`` copies held as the device prefetcher holds placed batches
    wait, copy, held = [], [], []
    it = NativeBatchIterator([x], rows, prefetch_depth=depth)
    for _ in range(epochs):
        for ptr, w in _batches(it):
            t0 = time.perf_counter()
            held.append(slot(ptr).copy())
            copy.append((time.perf_counter() - t0) * 1e3)
            wait.append(w)
            held = held[-depth:]
    del it, held

    reused, view = [], []
    out = np.empty(shape, np.float32)
    for _ in range(epochs):
        for i in range(batches):
            t0 = time.perf_counter()
            np.copyto(out, x[i * rows:(i + 1) * rows])
            t1 = time.perf_counter()
            _ = x[i * rows:(i + 1) * rows]
            t2 = time.perf_counter()
            reused.append((t1 - t0) * 1e3)
            view.append((t2 - t1) * 1e3)
    return {
        "batch_mb": nbytes / 1e6,
        "gather_only_wait_ms": _stats(gather),
        "fit_like_wait_ms": _stats(wait),
        "fit_like_copy_ms": _stats(copy),
        "copy_into_reused_ms": _stats(reused),
        "view_ms": _stats(view),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", default="16,64")
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    res = {
        "cores": os.cpu_count(),
        "by_rows": {
            r: measure(int(r), a.batches, a.epochs, a.depth) for r in a.rows.split(",")
        },
    }
    line = json.dumps(res)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
