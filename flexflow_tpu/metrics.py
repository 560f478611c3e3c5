"""Training metrics.

Reference: ``src/metrics_functions/metrics_functions.cc`` (+ ``.cu``) —
``Metrics::compute`` launches a per-shard METRICS_COMP task producing
``PerfMetrics`` that are future-chain reduced (``FFModel::update_metrics_task``,
``src/runtime/model.cc:3388+``) and printed as throughput every 1000 steps
(``metrics_functions.cc:213-216``).

TPU-native: metrics are computed inside the jitted step (scalar outputs);
cross-device reduction is a ``jnp.sum`` the compiler turns into a psum.
``PerfMetrics`` accumulates on host across steps, mirroring the reference
struct (``include/flexflow/metrics_functions.h:19-42``).

Async accumulation: a ``float()`` on a per-step device scalar is a
blocking device round-trip — one forced pipeline flush per step.
:class:`DeviceMetricAccumulator` keeps the running ``sum += metric * rows``
ON DEVICE (a tiny jitted add per step, dispatched asynchronously like the
step itself) so the training loop fetches host values only at its K-step
flush boundaries; :meth:`PerfMetrics.merge_sums` folds a drained window
into the host accumulator.  This is the analog of the reference's
future-chained ``update_metrics_task`` reduction (``model.cc:3388+``) —
the host never waits on a metrics future it doesn't need yet.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.fftype import LossType, MetricsType


# What ops count inside the step program (``OpDef.step_counters`` /
# ``step_gauges``) rides the step's metrics under these prefixes: a
# counter adds up over steps, a gauge is averaged over rows like a metric.
COUNTER_PREFIX = "counter:"
GAUGE_PREFIX = "gauge:"


@dataclasses.dataclass
class PerfMetrics:
    """Host-side accumulator (reference ``metrics_functions.h:19-42``)."""

    train_all: int = 0
    train_correct: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0
    start_time: float = dataclasses.field(default_factory=time.time)
    # the ops' own counts (docs/OBSERVABILITY.md, "Step counters"):
    # counters summed over the epoch's steps, gauges as row-weighted sums
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    gauge_sums: Dict[str, float] = dataclasses.field(default_factory=dict)

    def gauge(self, name: str) -> Optional[float]:
        """Row-weighted mean of a step gauge over the epoch so far."""
        if name not in self.gauge_sums or not self.train_all:
            return None
        return self.gauge_sums[name] / self.train_all

    def _merge_counted(self, sums: Dict[str, float]) -> None:
        for k, v in sums.items():
            if k.startswith(COUNTER_PREFIX):
                n = k[len(COUNTER_PREFIX):]
                self.counters[n] = self.counters.get(n, 0.0) + v
            elif k.startswith(GAUGE_PREFIX):
                n = k[len(GAUGE_PREFIX):]
                self.gauge_sums[n] = self.gauge_sums.get(n, 0.0) + v

    def update(self, batch_metrics: Dict[str, float], batch_size: int) -> None:
        self.train_all += batch_size
        self._merge_counted({
            k: v * (batch_size if k.startswith(GAUGE_PREFIX) else 1)
            for k, v in batch_metrics.items()
        })
        if "accuracy" in batch_metrics:
            self.train_correct += int(batch_metrics["accuracy"] * batch_size + 0.5)
        self.cce_loss += batch_metrics.get("categorical_crossentropy", 0.0) * batch_size
        self.sparse_cce_loss += (
            batch_metrics.get("sparse_categorical_crossentropy", 0.0) * batch_size
        )
        self.mse_loss += batch_metrics.get("mean_squared_error", 0.0) * batch_size
        self.rmse_loss += batch_metrics.get("root_mean_squared_error", 0.0) * batch_size
        self.mae_loss += batch_metrics.get("mean_absolute_error", 0.0) * batch_size

    def merge_sums(self, sums: Dict[str, float], count: int) -> None:
        """Fold a drained :class:`DeviceMetricAccumulator` window — ``sums``
        is ``Σ metric_i * rows_i`` over the window's steps, ``count`` the
        total rows.  Same math as ``count`` calls to :meth:`update` with
        per-row means, minus the per-step host round-trips; per-metric
        sums are row-weighted on device so the two paths agree to float32
        tolerance (``accuracy * rows`` is an integer count up to fp error,
        so one rounding at the flush recovers the same correct-count as
        per-step rounding)."""
        self.train_all += count
        self._merge_counted(sums)
        if "accuracy" in sums:
            self.train_correct += int(sums["accuracy"] + 0.5)
        self.cce_loss += sums.get("categorical_crossentropy", 0.0)
        self.sparse_cce_loss += sums.get("sparse_categorical_crossentropy", 0.0)
        self.mse_loss += sums.get("mean_squared_error", 0.0)
        self.rmse_loss += sums.get("root_mean_squared_error", 0.0)
        self.mae_loss += sums.get("mean_absolute_error", 0.0)

    @property
    def accuracy(self) -> float:
        return self.train_correct / max(1, self.train_all)

    def throughput(self) -> float:
        """samples/s since construction (reference print at
        ``metrics_functions.cc:213-216``)."""
        dt = time.time() - self.start_time
        return self.train_all / dt if dt > 0 else 0.0


class DeviceMetricAccumulator:
    """On-device ``Σ metric * rows`` across a window of steps.

    ``add(metrics, rows)`` dispatches one tiny jitted tree-add (donated
    running sums, so no per-step garbage) and returns immediately — the
    device scalars are never fetched, so the step pipeline stays
    dispatch-ahead.  ``drain()`` is the ONE host synchronization point:
    it blocks on (and returns) the window's weighted sums plus the row
    count, then resets.  Weights may vary per call (``eval``'s tail batch
    passes its real row count)."""

    def __init__(self) -> None:
        self._sums: Optional[Dict[str, jax.Array]] = None
        self._count: int = 0
        self._acc = None  # jitted add, built lazily on the second step

    def add(self, metrics: Dict[str, jax.Array], rows: int) -> None:
        self._count += rows
        if not metrics:
            return
        w = float(rows)

        def weight(k, w):  # a counter adds up as it is
            return 1.0 if k.startswith(COUNTER_PREFIX) else w

        if self._sums is None:
            # first window step: weighted copy (eager async dispatch)
            self._sums = {
                k: jnp.asarray(v, jnp.float32) * weight(k, w)
                for k, v in metrics.items()
            }
            return
        if self._acc is None:
            self._acc = jax.jit(
                lambda s, m, w: {
                    k: s[k] + jnp.asarray(m[k], jnp.float32) * weight(k, w)
                    for k in s
                },
                donate_argnums=(0,),
            )
        self._sums = self._acc(self._sums, metrics, w)

    @property
    def count(self) -> int:
        """Rows accumulated since the last drain (no device access)."""
        return self._count

    def drain(self) -> Tuple[Dict[str, float], int]:
        """Fetch the window's ``(sums, rows)`` to host and reset.  This is
        the deliberate host sync — callers count it (see
        ``Executor.count_host_sync``)."""
        sums = {k: float(v) for k, v in (self._sums or {}).items()}
        count = self._count
        self._sums = None
        self._count = 0
        return sums, count


class Metrics:
    def __init__(self, loss_type: LossType, metrics: Sequence[MetricsType]) -> None:
        self.loss_type = loss_type
        self.metrics = list(metrics)

    def compute(self, logits: jax.Array, labels: jax.Array) -> Dict[str, jax.Array]:
        """Traced inside the step program. logits = final op output
        (post-softmax for CCE losses, matching the reference's contract)."""
        out: Dict[str, jax.Array] = {}
        sparse = self.loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY
        for m in self.metrics:
            if m is MetricsType.ACCURACY:
                if sparse:
                    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(-1)
                    lab = labels.reshape(pred.shape[0]).astype(jnp.int32)
                else:
                    lab = jnp.argmax(labels, axis=-1)
                    pred = jnp.argmax(logits, axis=-1)
                out["accuracy"] = jnp.mean((pred == lab).astype(jnp.float32))
            elif m is MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY:
                from flexflow_tpu.loss import sparse_categorical_crossentropy

                out["sparse_categorical_crossentropy"] = sparse_categorical_crossentropy(
                    logits, labels
                )
            elif m is MetricsType.CATEGORICAL_CROSSENTROPY:
                from flexflow_tpu.loss import categorical_crossentropy

                out["categorical_crossentropy"] = categorical_crossentropy(logits, labels)
            elif m is MetricsType.MEAN_SQUARED_ERROR:
                out["mean_squared_error"] = jnp.mean(
                    jnp.sum(jnp.square(logits - labels), axis=-1)
                )
            elif m is MetricsType.ROOT_MEAN_SQUARED_ERROR:
                out["root_mean_squared_error"] = jnp.sqrt(
                    jnp.mean(jnp.sum(jnp.square(logits - labels), axis=-1))
                )
            elif m is MetricsType.MEAN_ABSOLUTE_ERROR:
                out["mean_absolute_error"] = jnp.mean(
                    jnp.sum(jnp.abs(logits - labels), axis=-1)
                )
        return out
