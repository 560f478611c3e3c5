"""Multi-host bootstrap — the TPU-native replacement for the reference's
multi-node stack (``MULTI-NODE.md``: GASNet-EX/UCX conduits for Legion data
movement + MPI as launcher + NCCL for gradient allreduce,
``CMakeLists.txt:47-52``, ``src/runtime/model.cc:3129-3167``).

On TPU one mechanism replaces all three: ``jax.distributed.initialize``
creates the multi-controller runtime (one process per host), the strategy's
mesh gains a host-spanning (DCN) outer axis via
``MachineMesh.build_hybrid``, and XLA routes collectives over ICI within a
slice and DCN across slices.  The launcher is anything that sets the
coordinator env vars (mpirun, SLURM, GKE — same role as the reference's
``mpi_wrapper1.sh``, ``tests/multinode_helpers/``).

Env/flag contract (either works; flags win):
  * ``--coordinator-address host:port`` / ``FF_COORDINATOR_ADDRESS``
  * ``--num-nodes N``                  / ``FF_NUM_NODES``
  * ``--node-id I``                    / ``FF_NODE_ID``
On a TPU pod (more than one entry in ``TPU_WORKER_HOSTNAMES``, or a
``MEGASCALE_COORDINATOR_ADDRESS``) all three are auto-detected by jax, so
``initialize_distributed()`` with no args is correct there; on one host it
starts nothing.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import jax

_initialized = False

# coordinator-connect failures worth retrying: the coordinator hasn't
# bound its port yet (rolling restart), or the connection raced a
# network blip.  Anything else (bad address, protocol mismatch) fails
# the same way on every attempt — retrying it only hides the error.
_TRANSIENT_CONNECT_MARKERS = (
    "deadline exceeded",
    "unavailable",
    "connection refused",
    "connection reset",
    "timed out",
    "failed to connect",
)


def _is_transient_connect_error(err: BaseException) -> bool:
    msg = str(err).lower()
    return any(mark in msg for mark in _TRANSIENT_CONNECT_MARKERS)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
    retries: int = 0,
    backoff_s: float = 1.0,
) -> None:
    """Start the multi-controller runtime.  Idempotent; a no-op for
    single-process runs (nothing configured and no env vars set).

    Mirrors the role of the reference's Legion ``Runtime::start`` +
    GASNet bootstrap (``src/runtime/cpp_driver.cc:26-46`` under mpirun);
    here every process runs the same program and jax stitches them into
    one logical device world.

    ``retries``/``backoff_s`` (``--coordinator-retries`` /
    ``--coordinator-backoff-s``): in a rolling restart the coordinator
    process routinely comes up AFTER its workers, so a transient
    connect failure gets up to ``retries`` more attempts with
    exponential backoff (``backoff_s * 2**attempt``).  Non-transient
    errors raise immediately; exhausting the budget raises one
    ``RuntimeError`` listing every attempt's failure.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("FF_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("FF_NUM_NODES"):
        num_processes = int(os.environ["FF_NUM_NODES"])
    if process_id is None and os.environ.get("FF_NODE_ID"):
        process_id = int(os.environ["FF_NODE_ID"])
    if coordinator_address is None and num_processes is None:
        # nothing configured.  A TPU pod announces itself through the
        # runtime's own variables — more than one worker hostname, or a
        # multi-slice coordinator — and jax.distributed autodetects the
        # rest; a failure there is a broken pod and raises.  One host
        # (or neither variable) is a single process: nothing to start.
        workers = [
            h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
            if h.strip()
        ]
        if len(workers) > 1 or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
            jax.distributed.initialize()
            _initialized = True
        return
    attempts = []
    for attempt in range(max(0, retries) + 1):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=local_device_ids,
            )
            _initialized = True
            return
        except RuntimeError as e:
            if not _is_transient_connect_error(e):
                raise
            attempts.append(f"attempt {attempt + 1}: {e}")
            if attempt >= retries:
                break
            time.sleep(backoff_s * (2 ** attempt))
    raise RuntimeError(
        f"could not connect to coordinator {coordinator_address!r} after "
        f"{len(attempts)} attempt(s) "
        f"(--coordinator-retries {retries}, base backoff {backoff_s}s):\n  "
        + "\n  ".join(attempts)
    )
