"""FFModel — the model orchestrator.

TPU-native re-design of the reference god object ``FFModel``
(``include/flexflow/model.h:326-958``, ``src/runtime/model.cc`` 5,541 LoC):
the layer-builder API (``model.h:336-554``), ``compile()``
(``model.cc:2803-3169``), the training drivers, and the ``fit`` loop
(``python/flexflow/core/flexflow_cffi.py:2062-2104``).

What compile() does here vs the reference:
  reference                                   this build
  -----------------------------------------  -------------------------------
  create_operators_from_layers               layer list IS the PCG (1:1)
  GRAPH_OPTIMIZE task (Unity search)         flexflow_tpu.search (strategy)
  convert_graph_to_operators                 Strategy object
  map tensors / create partitions            NamedShardings on mesh
  apply_fusion                               XLA fusion (free)
  label tensor co-sharding (model.cc:3086)   Executor._label_pspec
  NCCL communicator setup (model.cc:3129)    none needed (GSPMD collectives)
  optimizer->init()                          Executor.init_params
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import queue
import threading
import time
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.dataloader import (
    BatchIterator,
    DevicePrefetcher,
    SingleDataLoader,
)
from flexflow_tpu.fftype import (
    ActiMode,
    AggrMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    PoolType,
)
from flexflow_tpu.initializer import Initializer
from flexflow_tpu.metrics import (
    COUNTER_PREFIX,
    GAUGE_PREFIX,
    DeviceMetricAccumulator,
    Metrics,
    PerfMetrics,
)
from flexflow_tpu.obs import (
    HealthError,
    configure_from_config,
    configure_monitor_from_config,
    get_monitor,
    get_tracer,
    setup_span,
)
from flexflow_tpu.ops.base import get_op_def
from flexflow_tpu.optimizer import Optimizer, SGDOptimizer
from flexflow_tpu.parallel.machine import MachineMesh, default_mesh
from flexflow_tpu.parallel.strategy import Strategy, data_parallel_strategy
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.tensor import Layer, Tensor

# auto metric-flush cadence for the async fit loop (K in
# --metrics-sync-every): large enough that the per-flush host round-trip
# amortizes to noise, small enough that the R17 recompile trigger and an
# epoch-end verbose print observe loss within a bounded, human-scale
# window (docs/OBSERVABILITY.md, "Sync points")
DEFAULT_METRICS_SYNC_EVERY = 32

# checkpoint schema id, recorded in the manifest.  ffckpt/1 is the
# PR-5 manifest-less format (still loadable, no digest check);
# ffckpt/2 adds the manifest: step, rng seed, dataloader cursor,
# strategy identity, and a content digest (docs/RESILIENCE.md)
CHECKPOINT_SCHEMA = "ffckpt/2"


class CheckpointError(RuntimeError):
    """A checkpoint file that must not be loaded: torn/truncated write,
    unreadable manifest, or content-digest mismatch.  The message names
    what failed — resume code catches this and falls back to the
    previous complete checkpoint."""


def _checkpoint_digest(flat: Dict[str, np.ndarray]) -> str:
    """Content digest over the payload arrays (key order normalized,
    dtype/shape included so a reinterpreted buffer also fails)."""
    h = hashlib.sha256()
    for key in sorted(flat):
        arr = np.ascontiguousarray(flat[key])
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return f"sha256:{h.hexdigest()}"


def _write_checkpoint_atomic(
    path: str, flat: Dict[str, np.ndarray], manifest: Dict[str, Any],
) -> str:
    """Atomic checkpoint write: temp file in the target directory +
    flush + fsync + ``os.replace``.  A reader (or a resumed run) either
    sees the previous complete checkpoint or the new complete one —
    never a torn file, no matter where a SIGKILL lands
    (``tests/test_resilience.py`` kill-torture pins this).

    The manifest (with the content digest over every payload array)
    rides inside the archive as ``meta/manifest`` so the file stays a
    single self-describing ``.npz``.  Returns the path written —
    ``.npz`` is appended when missing, matching what ``np.savez`` does
    with a str path (writing through a file object skips that, so we
    replicate it for back-compat with ffckpt/1 call sites)."""
    if not path.endswith(".npz"):
        path += ".npz"
    manifest = dict(manifest)
    manifest["digest"] = _checkpoint_digest(flat)
    payload = dict(flat)
    payload["meta/manifest"] = np.frombuffer(
        json.dumps(manifest).encode(), dtype=np.uint8
    )
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    # fsync the directory so the rename itself survives a power cut
    # (best-effort: not all filesystems allow O_RDONLY dir fds)
    try:
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass
    return path


class _CheckpointWriter:
    """One background thread writing checkpoints off the step path
    (``--checkpoint-every K``): fit hands over the host snapshot and
    keeps stepping while the npz serialize + fsync happen here.  Queue
    depth 1 — if the previous write is still in flight the handoff
    blocks, which is the honest backpressure (checkpointing faster than
    the disk can fsync would otherwise queue unbounded host copies)."""

    def __init__(self) -> None:
        self._q: "queue.Queue[Optional[Tuple[str, Dict[str, np.ndarray], Dict[str, Any]]]]" = (
            queue.Queue(maxsize=1)
        )
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, name="ffckpt-writer", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                path, flat, manifest = item
                _write_checkpoint_atomic(path, flat, manifest)
            except BaseException as e:  # surfaced at the next flush/put
                self._err = e
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError(
                f"background checkpoint write failed: {err}"
            ) from err

    def put(
        self, path: str, flat: Dict[str, np.ndarray],
        manifest: Dict[str, Any],
    ) -> None:
        self._raise_pending()
        self._q.put((path, flat, manifest))

    def flush(self) -> None:
        """Block until every queued write hit disk; re-raise a failure."""
        self._q.join()
        self._raise_pending()

    def close(self) -> None:
        """Happy-path close: drain, stop the thread, raise on failure."""
        self._q.join()
        self._q.put(None)
        self._thread.join()
        self._raise_pending()

    def shutdown(self) -> None:
        """No-raise close for ``finally`` blocks — a writer error must
        not mask the in-flight exception that got us here."""
        try:
            self._q.join()
            self._q.put(None)
            self._thread.join()
        except BaseException:
            pass


def _load_substitution_xfers(cfg: FFConfig):
    """Resolve --substitution-json ('default' = the bundled rule set) and
    load its mixed GraphXfer/StructXfer list; None when the flag is
    unset.  The ONE resolution used by both compile's search branch and
    its import-replay branch."""
    if not cfg.substitution_json_file:
        return None
    import os as _os

    from flexflow_tpu.search.substitution import load_xfers_from_json

    rules_path = cfg.substitution_json_file
    if rules_path == "default":
        rules_path = _os.path.join(
            _os.path.dirname(__file__), "search", "substitutions.json"
        )
    return load_xfers_from_json(rules_path)


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None) -> None:
        self.config = config or FFConfig()
        # wire the process tracer BEFORE compile so search/compile spans
        # land in the trace (no-op when --trace-out/--trace-level unset)
        configure_from_config(self.config)
        # ... and the run-health monitor (--metrics-out / --health);
        # same contract: an off config leaves the current monitor alone
        configure_monitor_from_config(self.config)
        # ... and the deterministic fault plan (--fault-plan,
        # docs/RESILIENCE.md); an unset flag leaves the current plan alone
        from flexflow_tpu.runtime.faults import configure_faults_from_config

        configure_faults_from_config(self.config)
        # persistent compilation cache, before this model's first compile
        from flexflow_tpu.config import apply_compile_cache

        apply_compile_cache()
        # multi-host bootstrap before any device query (the reference starts
        # the Legion/GASNet runtime in the FFModel ctor, model.cc:1160).
        # Unconditional: initialize_distributed is a no-op when neither
        # flags, FF_* env vars, nor TPU-pod metadata are present.
        from flexflow_tpu.runtime.distributed import initialize_distributed

        initialize_distributed(
            self.config.coordinator_address,
            self.config.num_nodes_cli,
            self.config.node_id,
            retries=self.config.coordinator_retries,
            backoff_s=self.config.coordinator_backoff_s,
        )
        self.layers: List[Layer] = []
        self.graph_inputs: List[Tensor] = []
        self._name_counts: Dict[str, int] = {}
        self.executor: Optional[Executor] = None
        self.strategy: Optional[Strategy] = None
        self.label_tensor: Optional[Tensor] = None
        self._optimizer: Optional[Optimizer] = None
        # dataloader position of the most recent fit() step — what the
        # checkpoint manifest records so resume replays the exact batch
        # stream (docs/RESILIENCE.md, "Exact resume")
        self._fit_cursor: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------ util
    def _name(self, base: str, name: Optional[str]) -> str:
        if name:
            return name
        n = self._name_counts.get(base, 0)
        self._name_counts[base] = n + 1
        return f"{base}_{n}"

    def _add_layer(
        self,
        op_type: OperatorType,
        name: str,
        inputs: List[Tensor],
        attrs: Dict[str, Any],
    ) -> List[Tensor]:
        layer = Layer(op_type, name, inputs, attrs)
        outs = get_op_def(op_type).infer(layer)
        for i, (shape, dtype) in enumerate(outs):
            layer.outputs.append(
                Tensor(shape, dtype, owner_layer=layer, owner_idx=i, name=f"{name}:{i}")
            )
        self.layers.append(layer)
        return layer.outputs

    # ---------------------------------------------------------- input tensors
    def create_tensor(
        self,
        shape: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        name: Optional[str] = None,
    ) -> Tensor:
        """Reference ``FFModel::create_tensor`` (``model.cc``); shape
        includes the batch dim (dim 0, row-major — the reference's Legion
        dims are reversed)."""
        t = Tensor(tuple(shape), dtype, name=name or f"input_{len(self.graph_inputs)}")
        self.graph_inputs.append(t)
        return t

    # ------------------------------------------------------------- layer API
    # signatures follow include/flexflow/model.h:336-554
    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.NONE,
        use_bias: bool = True,
        kernel_initializer: Optional[Initializer] = None,
        bias_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        return self._add_layer(
            OperatorType.LINEAR,
            self._name("dense", name),
            [input],
            dict(
                out_dim=out_dim,
                activation=activation,
                use_bias=use_bias,
                kernel_initializer=kernel_initializer,
                bias_initializer=bias_initializer,
            ),
        )[0]

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: ActiMode = ActiMode.NONE,
        groups: int = 1,
        use_bias: bool = True,
        kernel_initializer: Optional[Initializer] = None,
        bias_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        return self._add_layer(
            OperatorType.CONV2D,
            self._name("conv2d", name),
            [input],
            dict(
                out_channels=out_channels,
                kernel_h=kernel_h,
                kernel_w=kernel_w,
                stride_h=stride_h,
                stride_w=stride_w,
                padding_h=padding_h,
                padding_w=padding_w,
                activation=activation,
                groups=groups,
                use_bias=use_bias,
                kernel_initializer=kernel_initializer,
                bias_initializer=bias_initializer,
            ),
        )[0]

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: PoolType = PoolType.MAX,
        activation: ActiMode = ActiMode.NONE,
        name: Optional[str] = None,
    ) -> Tensor:
        return self._add_layer(
            OperatorType.POOL2D,
            self._name("pool2d", name),
            [input],
            dict(
                kernel_h=kernel_h,
                kernel_w=kernel_w,
                stride_h=stride_h,
                stride_w=stride_w,
                padding_h=padding_h,
                padding_w=padding_w,
                pool_type=pool_type,
                activation=activation,
            ),
        )[0]

    def batch_norm(self, input: Tensor, relu: bool = True, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.BATCHNORM, self._name("batch_norm", name), [input], dict(relu=relu)
        )[0]

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: Optional[str] = None,
    ) -> Tensor:
        return self._add_layer(
            OperatorType.LAYERNORM,
            self._name("layer_norm", name),
            [input],
            dict(axes=tuple(a % input.ndim for a in axes), elementwise_affine=elementwise_affine, eps=eps),
        )[0]

    def rms_norm(
        self, input: Tensor, eps: float = 1e-6, zero_centered: bool = False,
        name: Optional[str] = None,
    ) -> Tensor:
        """``zero_centered``: the weight starts at 0 and scales by
        ``1 + w`` (``x / rms(x) * (1 + w)``, in float32)."""
        attrs = dict(eps=eps, zero_centered=True) if zero_centered else dict(eps=eps)
        return self._add_layer(
            OperatorType.RMS_NORM, self._name("rms_norm", name), [input], attrs
        )[0]

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.NONE,
        dtype: DataType = DataType.FLOAT,
        kernel_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        return self._add_layer(
            OperatorType.EMBEDDING,
            self._name("embedding", name),
            [input],
            dict(
                num_entries=num_entries,
                out_dim=out_dim,
                aggr=aggr,
                dtype=dtype,
                kernel_initializer=kernel_initializer,
            ),
        )[0]

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        causal: bool = False,
        use_flash: bool = True,
        bias: bool = False,
        kernel_initializer: Optional[Initializer] = None,
        name: Optional[str] = None,
        num_kv_heads: int = 0,
    ) -> Tensor:
        """Reference ``FFModel::multihead_attention``
        (``include/flexflow/model.h:336-554``): ``bias`` adds projection
        biases (bq/bk/bv/bo) like the reference's bias flag.
        ``num_kv_heads`` (0: ``num_heads``): grouped K/V heads."""
        # the default stays out of the attrs: a layer built before it
        # existed keeps its params_key
        grouped = {"num_kv_heads": int(num_kv_heads)} if num_kv_heads else {}
        assert not num_kv_heads or num_heads % num_kv_heads == 0
        return self._add_layer(
            OperatorType.MULTIHEAD_ATTENTION,
            self._name("attention", name),
            [query, key, value],
            dict(
                **grouped,
                embed_dim=embed_dim,
                num_heads=num_heads,
                kdim=kdim or None,
                vdim=vdim or None,
                dropout=dropout,
                causal=causal,
                use_flash=use_flash,
                bias=bias,
                kernel_initializer=kernel_initializer,
            ),
        )[0]

    def gated_attention(
        self,
        input: Tensor,
        num_heads: int,
        num_kv_heads: int,
        head_dim: int,
        rotary_dim: int,
        rope_theta: float = 10000.0,
        eps: float = 1e-6,
        use_flash: bool = True,
        window: int = 0,
        zero_centered: bool = True,
        name: Optional[str] = None,
    ) -> Tensor:
        """Causal grouped-query self-attention with per-head q/k
        RMS-norm, rotary positions on the first ``rotary_dim`` dims (0:
        none), a sigmoid output gate and, with ``window``, sight of the
        last ``window`` keys only
        (:class:`flexflow_tpu.ops.attention.GatedAttention`)."""
        attrs = dict(
            num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
            rotary_dim=rotary_dim, rope_theta=rope_theta, eps=eps,
            use_flash=use_flash,
        )
        if window:
            attrs["window"] = int(window)
        if not zero_centered:
            attrs["zero_centered"] = False
        return self._add_layer(
            OperatorType.GATED_ATTENTION,
            self._name("gated_attention", name),
            [input],
            attrs,
        )[0]

    def gated_ffn(self, input: Tensor, hidden: int, name: Optional[str] = None) -> Tensor:
        """Dense gated FFN ``W_d (silu(W_g x) * W_u x)``
        (:class:`flexflow_tpu.ops.moe.GatedFFN`)."""
        return self._add_layer(
            OperatorType.GATED_FFN, self._name("gated_ffn", name), [input],
            dict(hidden=hidden),
        )[0]

    def gated_delta_net(
        self,
        input: Tensor,
        num_k_heads: int,
        num_v_heads: int,
        head_k_dim: int,
        head_v_dim: int,
        conv_kernel: int = 4,
        eps: float = 1e-6,
        name: Optional[str] = None,
    ) -> Tensor:
        """Linear attention with a gated delta-rule state
        (:class:`flexflow_tpu.ops.linear_attention.GatedDeltaNet`)."""
        return self._add_layer(
            OperatorType.GATED_DELTA_NET,
            self._name("gated_delta_net", name),
            [input],
            dict(
                num_k_heads=num_k_heads, num_v_heads=num_v_heads,
                head_k_dim=head_k_dim, head_v_dim=head_v_dim,
                conv_kernel=conv_kernel, eps=eps,
            ),
        )[0]

    def mamba2_mixer(
        self,
        input: Tensor,
        num_heads: int,
        head_dim: int,
        n_groups: int,
        state_size: int,
        conv_kernel: int = 4,
        chunk: int = 128,
        eps: float = 1e-5,
        time_step_limit: Optional[Tuple[float, float]] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        """State-space mixer with a scalar decay a head
        (:class:`flexflow_tpu.ops.ssm.Mamba2Mixer`)."""
        attrs = dict(
            num_heads=num_heads, head_dim=head_dim, n_groups=n_groups,
            state_size=state_size, conv_kernel=conv_kernel, chunk=chunk, eps=eps,
        )
        if time_step_limit is not None:
            attrs["time_step_limit"] = tuple(float(v) for v in time_step_limit)
        return self._add_layer(
            OperatorType.MAMBA2_MIXER, self._name("mamba2_mixer", name), [input], attrs,
        )[0]

    def softmax(self, input: Tensor, dim: int = -1, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.SOFTMAX, self._name("softmax", name), [input], dict(dim=dim)
        )[0]

    def dropout(self, input: Tensor, rate: float, seed: int = 0, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.DROPOUT, self._name("dropout", name), [input], dict(rate=rate, seed=seed)
        )[0]

    def flat(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.FLAT, self._name("flat", name), [input], {})[0]

    def concat(self, tensors: Sequence[Tensor], axis: int, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.CONCAT, self._name("concat", name), list(tensors), dict(axis=axis)
        )[0]

    def split(
        self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int, name: Optional[str] = None
    ) -> List[Tensor]:
        if isinstance(sizes, int):
            assert input.shape[axis] % sizes == 0
            sizes = [input.shape[axis] // sizes] * sizes
        return self._add_layer(
            OperatorType.SPLIT,
            self._name("split", name),
            [input],
            dict(sizes=tuple(sizes), axis=axis),
        )

    def reshape(self, input: Tensor, shape: Sequence[int], name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.RESHAPE, self._name("reshape", name), [input], dict(shape=tuple(shape))
        )[0]

    def transpose(self, input: Tensor, perm: Sequence[int], name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.TRANSPOSE, self._name("transpose", name), [input], dict(perm=tuple(perm))
        )[0]

    def reverse(self, input: Tensor, axis: int, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.REVERSE, self._name("reverse", name), [input], dict(axis=axis)
        )[0]

    def reduce_sum(
        self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: Optional[str] = None
    ) -> Tensor:
        return self._add_layer(
            OperatorType.REDUCE_SUM,
            self._name("reduce_sum", name),
            [input],
            dict(axes=tuple(axes), keepdims=keepdims),
        )[0]

    def reduce_mean(
        self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: Optional[str] = None
    ) -> Tensor:
        return self._add_layer(
            OperatorType.REDUCE_MEAN,
            self._name("reduce_mean", name),
            [input],
            dict(axes=tuple(axes), keepdims=keepdims),
        )[0]

    def batch_matmul(
        self,
        a: Tensor,
        b: Tensor,
        a_seq_length_dim: Optional[int] = None,
        b_seq_length_dim: Optional[int] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        """``FFModel::batch_matmul`` (``model.h:481-485``): the seq-length
        dims enable iteration masking for incremental decoding — positions
        >= the ``seq_length`` passed to :meth:`eval_batch` are zeroed."""
        return self._add_layer(
            OperatorType.BATCHMATMUL,
            self._name("batch_matmul", name),
            [a, b],
            dict(a_seq_length_dim=a_seq_length_dim, b_seq_length_dim=b_seq_length_dim),
        )[0]

    def gather(self, data: Tensor, index: Tensor, dim: int = 0, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.GATHER, self._name("gather", name), [data, index], dict(dim=dim)
        )[0]

    def cast(self, input: Tensor, dtype: DataType, name: Optional[str] = None) -> Tensor:
        return self._add_layer(
            OperatorType.CAST, self._name("cast", name), [input], dict(dtype=dtype)
        )[0]

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name: Optional[str] = None) -> List[Tensor]:
        return self._add_layer(
            OperatorType.TOPK, self._name("topk", name), [input], dict(k=k, sorted=sorted)
        )

    def group_by(
        self, data: Tensor, assign: Tensor, n_experts: int, alpha: float = 1.0, name: Optional[str] = None
    ) -> List[Tensor]:
        return self._add_layer(
            OperatorType.GROUP_BY,
            self._name("group_by", name),
            [data, assign],
            dict(n_experts=n_experts, alpha=alpha),
        )

    def aggregate(
        self, inputs: Sequence[Tensor], n: int, lambda_bal: float = 0.0, name: Optional[str] = None
    ) -> Tensor:
        return self._add_layer(
            OperatorType.AGGREGATE,
            self._name("aggregate", name),
            list(inputs),
            dict(n=n, lambda_bal=lambda_bal),
        )[0]

    def aggregate_spec(
        self, inputs: Sequence[Tensor], n: int, lambda_bal: float = 0.0, name: Optional[str] = None
    ) -> Tensor:
        return self._add_layer(
            OperatorType.AGGREGATE_SPEC,
            self._name("aggregate_spec", name),
            list(inputs),
            dict(n=n, lambda_bal=lambda_bal),
        )[0]

    def experts(
        self,
        input: Tensor,
        assign: Tensor,
        gate_preds: Tensor,
        gate_full: Tensor,
        num_experts: int,
        hidden: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        name: Optional[str] = None,
    ) -> Tensor:
        """Fused expert block (dispatch + batched expert FFN + combine) with
        batched ``(n, ...)`` expert weights — the expert-parallel-ready form
        of the reference's group_by -> dense experts -> aggregate pipeline
        (``src/ops/moe.cc:20-44``).  See :class:`flexflow_tpu.ops.moe.Experts`."""
        return self._add_layer(
            OperatorType.EXPERTS,
            self._name("experts", name),
            [input, assign, gate_preds, gate_full],
            dict(n_experts=num_experts, hidden=hidden, alpha=alpha, lambda_bal=lambda_bal),
        )[0]

    def routed_experts(
        self,
        input: Tensor,
        n_experts: int,
        top_k: int,
        hidden: int,
        first_expert: int = 0,
        held: Optional[int] = None,
        shared_hidden: int = 0,
        score: str = "softmax",
        route_norm: bool = True,
        route_scale: float = 1.0,
        router_bias: bool = False,
        shared_gated: bool = True,
        expert_form: str = "gated",
        name: Optional[str] = None,
    ) -> Tensor:
        """One share of a dropless sparse-MoE block with gated (SiLU)
        experts -- or, ``expert_form`` ``relu2`` | ``relu``, ungated ones
        of two matrices: the router covers all ``n_experts``, this share holds
        ``held`` of them from ``first_expert`` on and returns their part
        (plus the shared expert's, when ``shared_hidden`` > 0).  The
        router's rule (``score``, ``route_norm``, ``route_scale``,
        ``router_bias``) and ``shared_gated`` are
        :class:`flexflow_tpu.ops.moe.RoutedExperts`'s."""
        held = n_experts if held is None else held
        assert 0 <= first_expert and first_expert + held <= n_experts
        attrs = dict(
            n_experts=n_experts, first_expert=first_expert, held=held,
            top_k=top_k, hidden=hidden, shared_hidden=shared_hidden,
        )
        # the defaults stay out of the attrs: a layer built before they
        # existed keeps its params_key
        if score != "softmax":
            attrs["score"] = score
        if not route_norm:
            attrs["route_norm"] = False
        if route_scale != 1.0:
            attrs["route_scale"] = float(route_scale)
        if router_bias:
            attrs["router_bias"] = True
        if not shared_gated:
            attrs["shared_gated"] = False
        if expert_form != "gated":
            assert expert_form in ("relu2", "relu"), expert_form
            attrs["expert_form"] = expert_form
        return self._add_layer(
            OperatorType.ROUTED_EXPERTS,
            self._name("routed_experts", name),
            [input],
            attrs,
        )[0]

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.04,
        fused: bool = False,
        name: Optional[str] = None,
    ) -> Tensor:
        """Composite MoE — mirrors ``FFModel::moe`` (``src/ops/moe.cc:20-44``):
        gate -> topk -> group_by -> experts -> aggregate.

        ``fused=True`` lowers the group_by/experts/aggregate tail to the
        single batched :meth:`experts` op — same math, expert-parallel
        capable (weights shard over the ``expert`` mesh axis)."""
        gate = self.dense(input, num_exp, ActiMode.NONE, name=self._name("moe_gate", name))
        gate = self.softmax(gate)
        topk_out, topk_idx = self.top_k(gate, num_select)
        if fused:
            return self.experts(
                input, topk_idx, topk_out, gate, num_exp, expert_hidden_size,
                alpha, lambda_bal, name=self._name("moe_experts", name),
            )
        grouped = self.group_by(input, topk_idx, num_exp, alpha)
        experts = [
            self.dense(
                self.dense(g, expert_hidden_size, ActiMode.RELU),
                input.shape[-1],
            )
            for g in grouped
        ]
        return self.aggregate(
            [topk_out, topk_idx, topk_idx, gate] + experts, num_exp, lambda_bal
        )

    # ------------------------------------------- parallel ops (SURVEY §2.4)
    # reference: src/parallel_ops/{partition,combine,replicate,reduction}.cc
    # exposed on FFModel like the C API's flexflow_model_add_* wrappers.
    def repartition(
        self, input: Tensor, dim: int, degree: int, axis: Optional[str] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        """Shard ``dim`` ``degree``-ways (``src/parallel_ops/partition.cc``)."""
        return self._add_layer(
            OperatorType.REPARTITION,
            self._name("repartition", name),
            [input],
            dict(dim=dim % input.ndim, degree=degree, axis=axis),
        )[0]

    def combine(self, input: Tensor, dim: int, degree: int, name: Optional[str] = None) -> Tensor:
        """Unshard ``dim`` (``src/parallel_ops/combine.cc``) — all-gather."""
        return self._add_layer(
            OperatorType.COMBINE,
            self._name("combine", name),
            [input],
            dict(dim=dim % input.ndim, degree=degree),
        )[0]

    def replicate(self, input: Tensor, degree: int = 1, name: Optional[str] = None) -> Tensor:
        """Replicate (``src/parallel_ops/replicate.cc``); grad sums replicas."""
        return self._add_layer(
            OperatorType.REPLICATE, self._name("replicate", name), [input], dict(degree=degree)
        )[0]

    def reduction(self, input: Tensor, degree: int = 1, name: Optional[str] = None) -> Tensor:
        """Sum partial replicas (``src/parallel_ops/reduction.cc``)."""
        return self._add_layer(
            OperatorType.REDUCTION, self._name("reduction", name), [input], dict(degree=degree)
        )[0]

    def fused_parallel_op(
        self, input: Tensor, ops: Sequence[Tuple[str, Dict[str, Any]]], name: Optional[str] = None
    ) -> Tensor:
        """Chained resharding (``src/parallel_ops/fused_parallel_op.cc``);
        ``ops`` is a list of ``(op_type_value, attrs)`` pairs."""
        return self._add_layer(
            OperatorType.FUSED_PARALLEL,
            self._name("fused_parallel", name),
            [input],
            dict(ops=tuple((OperatorType(o).value, dict(a)) for o, a in ops)),
        )[0]

    def cache(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        """Cached activations op (``src/ops/cache.cc``); see ops.tensor_ops.Cache."""
        return self._add_layer(OperatorType.CACHE, self._name("cache", name), [input], {})[0]

    def parameter(
        self,
        shape: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        initializer=None,
        trainable: bool = True,
        name: Optional[str] = None,
    ) -> Tensor:
        """Free trainable tensor with no producing layer — the graph form of
        the reference's Weight NoOp source (``src/ops/noop.cc``) and the
        target of torch.fx ``get_attr`` imports (``model.py:1628``)."""
        return self._add_layer(
            OperatorType.WEIGHT,
            self._name("parameter", name),
            [],
            dict(shape=tuple(shape), dtype=dtype, initializer=initializer,
                 trainable=trainable),
        )[0]

    # elementwise builders (model.h unary/binary API)
    def add(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_ADD, self._name("add", name), [x, y], {})[0]

    def subtract(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_SUB, self._name("sub", name), [x, y], {})[0]

    def multiply(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_MUL, self._name("mul", name), [x, y], {})[0]

    def divide(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_DIV, self._name("div", name), [x, y], {})[0]

    def max(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_MAX, self._name("max", name), [x, y], {})[0]

    def min(self, x: Tensor, y: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.EW_MIN, self._name("min", name), [x, y], {})[0]

    def _unary(self, op: OperatorType, x: Tensor, name: Optional[str], **attrs) -> Tensor:
        return self._add_layer(op, self._name(op.value, name), [x], attrs)[0]

    def relu(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.RELU, x, name)

    def sigmoid(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SIGMOID, x, name)

    def tanh(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.TANH, x, name)

    def elu(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.ELU, x, name)

    def gelu(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.GELU, x, name)

    def exp(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.EXP, x, name)

    def sin(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SIN, x, name)

    def cos(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.COS, x, name)

    def rsqrt(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.RSQRT, x, name)

    def pow(self, x: Tensor, exponent: float, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.POW, x, name, exponent=exponent)

    def identity(self, x: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.IDENTITY, x, name)

    def scalar_multiply(self, x: Tensor, scalar: float, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SCALAR_MULTIPLY, x, name, scalar=scalar)

    def scalar_add(self, x: Tensor, scalar: float, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x: Tensor, scalar: float, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SCALAR_SUB, x, name, scalar=scalar)

    def scalar_true_divide(self, x: Tensor, scalar: float, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.SCALAR_TRUE_DIV, x, name, scalar=scalar)

    # --------------------------------------------------------------- compile
    @setup_span("model")
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: LossType = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[MetricsType] = (),
        mesh: Optional[MachineMesh] = None,
        strategy: Optional[Strategy] = None,
        seed: Optional[int] = None,
    ) -> None:
        """Pick/search a strategy, build the jitted step, init params.

        Reference: ``FFModel::compile`` (``src/runtime/model.cc:2803-3169``).
        Strategy resolution order: explicit arg > --import-strategy file >
        Unity search (if --search-budget set) > default data-parallel.
        """
        assert self.layers, "empty model"
        cfg = self.config
        # pre-resolution args retained for recompile() (R17): a None mesh/
        # strategy re-resolves against the altered graph
        self._compile_call = dict(
            optimizer=optimizer, loss_type=loss_type, metrics=list(metrics),
            mesh=mesh, strategy=strategy, seed=seed,
        )
        self._optimizer = optimizer or SGDOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay
        )
        logits = self.layers[-1].outputs[0]

        if mesh is None:
            mesh = cfg.build_mesh() or default_mesh()
        # machine model + profiler, shared by the search AND the
        # observability exports below so --taskgraph/--profiling report the
        # same costs the search optimized
        from flexflow_tpu.search.cost import TPUMachineModel

        if cfg.machine_model_file:
            machine = TPUMachineModel.from_file(cfg.machine_model_file)
        else:
            # price for the chip actually present (detect() falls back to
            # v5p-class defaults off-TPU)
            machine = TPUMachineModel.detect()
        # multi-host: the dcn axis spans processes — price its collectives
        # at DCN bandwidth
        if jax.process_count() > 1 and not machine.dcn_axes:
            machine.dcn_axes = (cfg.dcn_axis,)
        # cost-model tier (--cost-model analytic|measured|calibrated,
        # docs/OBSERVABILITY.md "Calibration loop").  "calibrated"
        # composes with the measured tier: corrections apply on top of
        # whichever base is active.
        assert cfg.cost_model in ("analytic", "measured", "calibrated"), (
            f"unknown --cost-model {cfg.cost_model!r}"
        )
        profiler = None
        if cfg.use_measured_cost or cfg.cost_model == "measured":
            from flexflow_tpu.search.simulator import OpProfiler

            profiler = OpProfiler(cfg.cost_cache_file)
        calibration = None
        if cfg.cost_model == "calibrated":
            from flexflow_tpu.search.calibration import CalibrationStore

            if cfg.calibration_store_file:
                # load REFUSES a store fit for a different machine-model
                # identity / backend / dtype (CalibrationMismatch) — a
                # wrong store must fail loudly, not silently mis-price
                calibration = CalibrationStore.load(
                    cfg.calibration_store_file,
                    expect_identity=machine.source,
                    expect_backend=jax.default_backend(),
                    expect_dtype=cfg.compute_dtype,
                )
            else:
                # empty store: the calibrated tier with identity
                # corrections — prices byte-identically to the base tier
                calibration = CalibrationStore(
                    machine.source, jax.default_backend(), cfg.compute_dtype
                )

        searched = False  # did unity_search pick (and price) this strategy?
        if strategy is None:
            if cfg.import_strategy_file:
                with open(cfg.import_strategy_file) as f:
                    strategy = Strategy.from_json(f.read())
                # replay any recorded structural rewrites and re-key the
                # assignments by layer NAME (guids are process-local) —
                # sets rewritten_layers/output_remap so the adoption
                # branch below applies them like a fresh search would
                from flexflow_tpu.search.algebraic import (
                    StructXfer,
                    default_struct_xfers,
                )

                rules = list(default_struct_xfers(inference=True)) + [
                    x
                    for x in (_load_substitution_xfers(cfg) or ())
                    if isinstance(x, StructXfer)
                ]
                strategy.rebind(self.layers, rules)
            elif cfg.search_budget > 0 and not cfg.only_data_parallel:
                from flexflow_tpu.search import unity_search
                from flexflow_tpu.search.candidates import SearchOptions

                extra_xfers = _load_substitution_xfers(cfg)

                serve_spec = None
                if cfg.search_objective == "serve":
                    # --objective serve: search placements for the
                    # decode loop (docs/SERVING.md) — slots/SLO/flush
                    # cadence from the serving flags, steady-state
                    # prefix depth = the compiled position range
                    from flexflow_tpu.serve.objective import ServeSpec

                    serve_spec = ServeSpec(
                        slots=cfg.serve_slots or cfg.batch_size,
                        kv_len=(
                            self.graph_inputs[0].shape[1]
                            if self.graph_inputs
                            and self.graph_inputs[0].ndim >= 2
                            else 512
                        ),
                        slo_p99_ms=cfg.serve_slo_ms,
                        sync_every=cfg.serve_sync_every,
                        # price the arm the engine will run: auto
                        # resolves to paged on the TPU deployments the
                        # search targets, so only an explicit gather
                        # prices the dense materialization
                        attn=(
                            "gather" if cfg.serve_attn == "gather"
                            else "paged"
                        ),
                        # the chunked-prefill arm (r20) prices the
                        # same chunk shape the engine will run
                        prefill_chunk=cfg.serve_prefill_chunk,
                        spec_k=cfg.serve_spec_k,
                        spec_accept=cfg.serve_spec_accept,
                        spec_draft_frac=(
                            cfg.serve_spec_draft_layers
                            / max(1, sum(
                                1 for ly in self.layers
                                if ly.op_type.name
                                == "MULTIHEAD_ATTENTION"
                            ))
                            if cfg.serve_spec_draft_layers > 0
                            else 0.5
                        ),
                        # fleet axes (serve/fleet.py): priced only when
                        # --serve-replicas > 1
                        replicas=cfg.serve_replicas,
                        routing=cfg.serve_routing,
                        # quantized arms (r19): priced only when the
                        # flags move off fp32
                        kv_dtype=cfg.serve_kv_dtype,
                        weight_dtype=cfg.serve_weight_dtype,
                    )
                strategy = unity_search(
                    self.layers,
                    mesh,
                    graph_inputs=self.graph_inputs,
                    budget=cfg.search_budget,
                    alpha=cfg.search_alpha,
                    machine=machine,
                    profiler=profiler,
                    struct_xfers=(
                        "default" if cfg.enable_graph_rewrites else None
                    ),
                    mem_budget_bytes=(
                        cfg.device_memory_gb * (1 << 30)
                        if cfg.device_memory_gb > 0
                        else None
                    ),
                    options=SearchOptions(
                        param_parallel=cfg.enable_parameter_parallel,
                        attribute_parallel=cfg.enable_attribute_parallel,
                    ),
                    mem_search_iters=(
                        cfg.memory_search_budget
                        if cfg.memory_search_budget > 0
                        else 8
                    ),
                    extra_xfers=extra_xfers,
                    objective=cfg.search_objective,
                    serve=serve_spec,
                    calibration=calibration,
                    # pipeline axis of the search (docs/PIPELINE.md):
                    # off|auto|S, with M pinned by --microbatches
                    pipeline=cfg.pipeline,
                    microbatches=cfg.microbatches or None,
                    # overlapped-gradient-sync axis (docs/PERF.md): the
                    # search prices every mesh candidate with the ring
                    # adjustment, so an overlappable placement can win
                    grad_overlap=cfg.grad_overlap,
                )
                searched = True
            else:
                strategy = data_parallel_strategy(self.layers, mesh)
        # --pipeline without a search (imported / hand-built / default
        # data-parallel strategies): attach the spec directly when a
        # repeated-block chain supports it; declined legality prints the
        # reason and falls back to the non-pipelined step.  A searched
        # strategy is left alone — when the priced pipeline variant LOST
        # the search, forcing one on anyway would override the search's
        # answer with an unpriced guess.
        if (
            cfg.pipeline != "off"
            and strategy.pipeline is None
            and not searched
        ):
            from flexflow_tpu.parallel.pipeline import (
                attach_pipeline_from_config,
            )

            reason = attach_pipeline_from_config(
                strategy, strategy.rewritten_layers or self.layers, cfg,
                self.graph_inputs,
            )
            if reason is not None and jax.process_index() == 0:
                print(f"[pipeline] declined: {reason}")
        # --grad-overlap resolution (docs/PERF.md "Overlapped gradient
        # sync"): a searched winner already carries the decision
        # (strategy.grad_overlap, priced by the search's overlap
        # adjustment); imported / hand-built / data-parallel strategies
        # resolve here — "auto" rings only when the overlap pricing
        # beats the fused tail sync, "ring" forces the decomposition.
        # Either way the aggregated pricing is attached so
        # exposed_comm_s lands in last_step_stats / ffmetrics.
        assert cfg.grad_overlap in ("off", "auto", "ring"), (
            f"unknown --grad-overlap value {cfg.grad_overlap!r}"
        )
        grad_overlap_resolved = "off"
        if cfg.grad_overlap != "off":
            if strategy.grad_overlap != "ring":
                try:
                    from flexflow_tpu.search.cost import (
                        grad_overlap_adjustment,
                    )

                    lyrs = strategy.rewritten_layers or self.layers
                    delta, price = grad_overlap_adjustment(
                        lyrs, strategy, machine, mode=cfg.grad_overlap
                    )
                    if price is not None and (
                        cfg.grad_overlap == "ring" or delta > 0.0
                    ):
                        strategy.grad_overlap = "ring"
                        strategy.grad_overlap_price = price
                        if strategy.predicted_step_s is not None and delta:
                            strategy.predicted_step_s = max(
                                0.0, strategy.predicted_step_s - delta
                            )
                except Exception:  # noqa: BLE001 — pricing must never block a run
                    pass
            grad_overlap_resolved = (
                "ring"
                if (strategy.grad_overlap == "ring"
                    or cfg.grad_overlap == "ring")
                else "off"
            )
        self.strategy = strategy
        # calibration loop: an instrumented run (--metrics-out / --health
        # / --drift) pairs every step record with the strategy's priced
        # cost.  Strategies the search priced already carry it; imported
        # / data-parallel / hand-built ones are estimated here (pure host
        # math) so the prediction corpus grows on EVERY observed run.
        # The disabled path skips this entirely — zero-overhead guards
        # stay byte-identical.
        if (
            getattr(strategy, "predicted_step_s", None) is None
            and get_monitor().enabled
        ):
            try:
                from flexflow_tpu.search.cost import (
                    estimate_pipeline_step_time,
                    estimate_strategy_cost,
                )

                lyrs = strategy.rewritten_layers or self.layers
                pred = None
                if strategy.pipeline is not None:
                    # imported / hand-attached pipelined strategy: price
                    # the 1F1B schedule, not the non-pipelined walk —
                    # the drift watchdog compares against THIS number
                    from flexflow_tpu.parallel.pipeline import (
                        select_pipeline_chain,
                    )

                    chain = select_pipeline_chain(
                        lyrs, strategy.pipeline.stages
                    )
                    if chain is not None:
                        price = estimate_pipeline_step_time(
                            lyrs, strategy, machine,
                            chain=chain,
                            stages=strategy.pipeline.stages,
                            microbatches=strategy.pipeline.microbatches,
                            stage_axis=strategy.pipeline.stage_axis,
                        )
                        if price is not None:
                            pred = price["step_s"]
                            strategy.pipeline_price = price
                if pred is None:
                    pred = estimate_strategy_cost(
                        lyrs, strategy, machine,
                        grad_overlap=(
                            "ring" if strategy.grad_overlap == "ring"
                            else "off"
                        ),
                    )
                if calibration is not None:
                    pred = calibration.correct_step("fit", pred)
                strategy.predicted_step_s = pred
            except Exception:  # noqa: BLE001 — pricing must never block a run
                pass
        if strategy.rewritten_layers is not None:
            # the search's joint (rewrite x placement) winner changed the
            # graph structure (reference Graph::graph_optimize returning
            # best_graph, graph.cc:2046-2161) — adopt it: the rewritten
            # list is what executes, and user-held output handles resolve
            # through the remap
            self.layers = strategy.rewritten_layers
            logits = strategy.resolve_tensor(logits)
        # exports + profiling print only on process 0 (multi-host runs share
        # the filesystem/stdout; the reference's exports run in the
        # singleton GRAPH_OPTIMIZE task, mapper.cc:274)
        if jax.process_index() == 0:
            if cfg.profiling and getattr(machine, "decision_stats", None):
                ds = machine.decision_stats
                print(
                    f"[machine-model] {machine.source}: collective routing "
                    f"decisions ring={ds['ring']} "
                    f"hierarchical={ds['hierarchical']} "
                    f"(min(ring, hierarchical) per slice-crossing "
                    f"collective, docs/MACHINE_MODEL.md)"
                )
            self._write_exports(cfg, strategy, machine, profiler)

        self.executor = Executor(
            layers=self.layers,
            graph_inputs=self.graph_inputs,
            logits=logits,
            strategy=strategy,
            optimizer=self._optimizer,
            loss_type=loss_type,
            metrics=Metrics(loss_type, metrics),
            seed=seed if seed is not None else cfg.rng_seed,
            compute_dtype=cfg.compute_dtype,
            param_dtype=cfg.param_dtype,
            remat_policy=cfg.remat_policy,
            dcn_axis=cfg.dcn_axis,
            zero1=cfg.enable_zero1,
            profiling=cfg.profiling,
            stack_blocks=cfg.stack_blocks,
            verify_compiled=cfg.verify_compiled,
            grad_overlap=grad_overlap_resolved,
        )
        with get_tracer().span("init_params", cat="compile"):
            self.executor.init_params()
        # run-health monitor context: what a debug bundle snapshots
        # beyond the step stream.  Providers are evaluated at dump time,
        # so a post-compile recompile()/optimize_for_inference() bundle
        # reflects the strategy the run actually died under.
        monitor = get_monitor()
        if monitor.enabled:
            cfg_doc = dataclasses.asdict(cfg)
            cfg_doc["mesh"] = {
                "shape": list(strategy.mesh.shape),
                "axis_names": list(strategy.mesh.axis_names),
            }
            monitor.set_context(
                config=cfg_doc,
                strategy_provider=lambda: self.strategy.to_json(
                    layers=self.layers
                ),
                memory_provider=lambda: (
                    self.executor.memory_snapshot()
                    if self.executor is not None
                    else None
                ),
            )

    def _write_exports(self, cfg, strategy, machine, profiler) -> None:
        """Strategy/observability outputs (reference --export-strategy /
        --compgraph / --taskgraph / --profiling, model.cc:3609-3670).
        Called on process 0 only."""
        if cfg.export_strategy_file:
            with open(cfg.export_strategy_file, "w") as f:
                # self.layers is the (possibly rewritten) list the
                # assignments refer to; per-op names make the export
                # importable across processes (Strategy.rebind)
                f.write(strategy.to_json(layers=self.layers))
        if cfg.export_strategy_computation_graph_file:
            from flexflow_tpu.utils import export_dot

            export_dot(
                self.layers,
                cfg.export_strategy_computation_graph_file,
                strategy=strategy,
                graph_inputs=self.graph_inputs,
            )
        if cfg.taskgraph_file:
            from flexflow_tpu.utils import export_taskgraph

            cost_model = None
            if profiler is not None:
                from flexflow_tpu.search.simulator import MeasuredCostModel

                cost_model = MeasuredCostModel(
                    profiler, strategy.mesh, machine, layers=self.layers
                )
            export_taskgraph(
                self.layers, strategy, cfg.taskgraph_file,
                machine=machine, cost_model=cost_model,
            )
        if cfg.profiling:
            from flexflow_tpu.utils import format_profiling_table, profiling_rows

            print(format_profiling_table(
                profiling_rows(
                    self.layers, strategy, machine=machine, profiler=profiler
                )
            ))

    def recompile(self, preserve_weights: bool = True) -> None:
        """Rebuild the step program after a model alteration (R17:
        reference ``RecompileState`` recompilation path,
        ``recompile.h:26-41``).  Re-runs :meth:`compile` with the original
        arguments (auto-derived mesh/strategy re-resolve against the
        altered graph) and restores every weight whose (layer, name,
        shape) survived."""
        assert self.executor is not None, "call compile() first"
        # alter functions mutate layer attrs IN PLACE (guids unchanged),
        # which the block-structure memos key past — drop them so chain
        # detection sees the altered graph (flexflow_tpu.blocks)
        from flexflow_tpu.blocks import invalidate_signatures

        invalidate_signatures(self.layers)
        snapshot = self.get_weights() if preserve_weights else None
        old_opt = None
        if preserve_weights:
            # per-layer layout (stacked buckets unstacked) so optimizer
            # moments survive a recompile that changes --stack-blocks or
            # the chain structure itself
            old_ex = self.executor
            old_opt = {
                key: (
                    old_ex.unstack_tree(jax.tree.map(self._to_numpy, val))
                    if isinstance(val, dict)
                    else self._to_numpy(val)
                )
                for key, val in old_ex.opt_state.items()
            }
        # the host-side step counter seeds the per-step dropout rng stream;
        # custom optimizers may lack a 'step' entry in opt_state, so carry
        # it explicitly or the stream replays already-used keys
        old_step = self.executor._step_count
        # the host-sync ledger is per-RUN accounting (bench A/B and the
        # async-fit tests read deltas across a whole fit), so it survives
        # the executor swap
        old_syncs = self.executor.host_syncs
        old_stall = self.executor.host_stall_s
        self.compile(**self._compile_call)
        self.executor.host_syncs = old_syncs
        self.executor.host_stall_s = old_stall
        if preserve_weights:
            self.executor._step_count = old_step
        if snapshot is None:
            return
        self._restore_matching_weights(snapshot)
        ex = self.executor
        # carry optimizer state (Adam moments / SGD momentum / step count)
        # for surviving weights — a mid-training recompile must not reset
        # the trajectory of unaltered layers
        if old_opt is not None:
            new_opt = ex.opt_state
            for key, old_val in old_opt.items():
                if key not in new_opt:
                    continue
                if not isinstance(old_val, dict):  # e.g. the step counter
                    new_opt[key] = jax.device_put(old_val)
                    continue
                # per-layer entries route into the new executor's layout;
                # shape mismatches (altered layers) silently reset
                ex.assign_opt_entries(key, old_val, shape_skip=True)

    def optimize_for_inference(
        self, budget: int = 32, alpha: float = 1.05
    ) -> Tuple[str, ...]:
        """Re-search the compiled model's graph with the full algebraic
        rewrite set INCLUDING training-illegal rules (BatchNorm folding,
        ``search.algebraic.FoldBNConv``), transporting the trained weights
        across every applied rewrite, then rebuild the step program.

        Reference: the TASO-heritage inference substitution classes in
        ``substitutions/graph_subst_3_v2.json`` (conv+bn folding etc.),
        applied by ``GraphXfer::create_new_graph``
        (``src/runtime/substitution.cc:1726-1868``).

        Returns the applied rule names (empty if nothing won on cost).
        Training after this call is NOT meaningful when BN folding was
        applied — the folded conv has no batch-statistics semantics.
        """
        assert self.executor is not None, "call compile() first"
        from flexflow_tpu.search.algebraic import default_struct_xfers
        from flexflow_tpu.search.substitution import base_optimize

        st = self.strategy
        res = base_optimize(
            self.layers, st.mesh, dict(st.ops), budget=budget, alpha=alpha,
            struct_xfers=default_struct_xfers(inference=True),
            inference=True, return_joint=True,
        )
        if not res.applied:
            return ()
        # transport trained weights through the applied rewrite sequence
        # (each weight_map reads the evolving {layer: {w: array}} dict)
        weights = self.get_weights()
        for wm in res.wmaps:
            if wm is not None:
                weights.update(wm(weights))
        new_st = Strategy(st.mesh)
        new_st.ops = res.assign
        new_st.rewritten_layers = res.layers
        new_st.output_remap = res.remap
        new_st.applied_rewrites = st.applied_rewrites + res.applied
        # keep the replay detail: an export after optimize_for_inference
        # must stay importable (Strategy.rebind)
        new_st.applied_detail = st.applied_detail + res.applied_detail
        self._compile_call["strategy"] = new_st
        self._compile_call["mesh"] = st.mesh
        self.compile(**self._compile_call)
        self._restore_matching_weights(weights)
        return res.applied

    def _restore_matching_weights(
        self, weights: Dict[str, Dict[str, np.ndarray]]
    ) -> None:
        """set_weights restricted to entries whose (layer, name, shape)
        exists in the freshly compiled executor — shared by recompile()
        and optimize_for_inference().  Per-layer in, so weights survive a
        recompile that flips ``--stack-blocks`` (the executor routes them
        into whatever layout it now uses)."""
        self.executor.assign_weight_entries(
            weights, strict=False, shape_skip=True
        )

    # ------------------------------------------------------------------- fit
    def _resolve_metrics_sync_every(
        self, override: Optional[int] = None
    ) -> int:
        """Effective K for the K-step metric flush (``--metrics-sync-every``,
        docs/OBSERVABILITY.md "Sync points").  An enabled health monitor
        or ``--profiling`` forces K=1 — both exist to observe every step,
        and the executor's instrumented path syncs per step anyway.
        Otherwise: the explicit value, or ``DEFAULT_METRICS_SYNC_EVERY``
        when unset/auto (0)."""
        if get_monitor().enabled or self.config.profiling:
            return 1
        k = override if override is not None else self.config.metrics_sync_every
        return int(k) if k and k > 0 else DEFAULT_METRICS_SYNC_EVERY

    def _flush_metrics(
        self, acc: DeviceMetricAccumulator, pm: PerfMetrics, tracer
    ) -> None:
        """Drain the device-side metric window into ``pm`` — the async
        loop's ONE deliberate host sync per K steps, counted and timed."""
        if acc.count == 0:
            return
        with tracer.span("metric_flush", cat="fit"):
            t0 = time.perf_counter()
            sums, count = acc.drain()
            self.executor.count_host_sync(1, stall_s=time.perf_counter() - t0)
            pm.merge_sums(sums, count)
        if tracer.enabled:
            # the ops' own counts of this window, under their names
            for k, v in sums.items():
                if k.startswith(COUNTER_PREFIX):
                    tracer.counter(k[len(COUNTER_PREFIX):], v)
                elif k.startswith(GAUGE_PREFIX):
                    tracer.sample(k[len(GAUGE_PREFIX):], v / count, level="step")
        tracer.counter("fit.metric_flushes")

    def fit(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray]],
        y: np.ndarray,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        verbose: bool = True,
        shuffle: bool = False,
        seed: int = 0,
        recompile_state: Optional["RecompileState"] = None,
        metrics_sync_every: Optional[int] = None,
        resume: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        checkpoint_path: Optional[str] = None,
        recovery: Optional["RecoveryPolicy"] = None,
    ) -> PerfMetrics:
        """Canonical training loop (reference ``FFModel.fit``,
        ``flexflow_cffi.py:2062-2104``).  Each iteration is one cached jit
        call — the analog of replaying a Legion trace — and the loop is
        END-TO-END asynchronous, the analog of Legion deferred execution:
        the host runs ahead of the devices and never blocks on a result
        it doesn't need yet.

        Three-stage input pipeline: batch assembly (the native C++
        prefetching loader ``native/ffdl.cc`` when its build is
        available, else the pure-Python loaders with a background
        producer thread) -> device placement (:class:`DevicePrefetcher`
        dispatches the H2D transfer of batch i+1 while step i runs) ->
        the jitted step.

        Metrics accumulate ON DEVICE (``DeviceMetricAccumulator``) and are
        fetched to host only every ``metrics_sync_every`` steps and at
        epoch end (K resolution: :meth:`_resolve_metrics_sync_every`;
        K=1 restores the fully synchronous per-step ``float()`` path).
        The R17 recompile trigger is evaluated under the same window —
        it fires within K steps of its condition becoming true
        (``RecompileState.observe_window``).

        Resilience (docs/RESILIENCE.md): ``resume=path`` restores a
        :meth:`save_checkpoint` file INCLUDING its manifest cursor —
        step count, per-step rng stream, and dataloader epoch/batch
        position — so a killed-and-resumed run is bit-identical to the
        uninterrupted one.  ``checkpoint_every=K`` snapshots every K
        optimizer steps to ``checkpoint_path`` on a background writer
        thread (the snapshot itself is the one counted host sync; the
        npz serialize + fsync happen off the step path).  ``recovery``
        (a :class:`~flexflow_tpu.runtime.recompile.RecoveryPolicy`)
        catches device-loss ``RuntimeError``s, shrinks the machine
        model, re-searches, restores, and continues; ``--health
        restore`` rewinds anomalies to the last good checkpoint and
        skips the poison batch (capped by ``--max-restores``)."""
        assert self.executor is not None, "call compile() first"
        cfg = self.config
        if resume is None:
            resume = cfg.resume_from or None
        ckpt_every = (
            checkpoint_every if checkpoint_every is not None
            else cfg.checkpoint_every
        )
        ckpt_path = (
            checkpoint_path if checkpoint_path is not None
            else cfg.checkpoint_path
        )
        if ckpt_path and not ckpt_path.endswith(".npz"):
            ckpt_path = ckpt_path + ".npz"  # match save_checkpoint/np.savez
        bs = batch_size or cfg.batch_size
        epochs = epochs or cfg.epochs
        xs = list(x) if isinstance(x, (list, tuple)) else [x]

        from flexflow_tpu.runtime.native import (
            NativeBatchIterator,
            native_available,
        )

        depth = max(1, cfg.prefetch_depth)
        if native_available():
            it = NativeBatchIterator(
                [np.asarray(a) for a in xs] + [np.asarray(y)], bs,
                shuffle=shuffle, seed=seed, prefetch_depth=depth,
            )
        else:
            loaders = [
                SingleDataLoader(a, bs, None, None, shuffle=shuffle, seed=seed)
                for a in xs
            ] + [SingleDataLoader(y, bs, None, None, shuffle=shuffle, seed=seed)]
            # identical seed => identical permutation => rows stay aligned
            it = BatchIterator(loaders, prefetch_depth=depth)
        if it.num_batches == 0:
            raise ValueError(
                f"dataset has {len(xs[0])} samples < batch_size {bs}: zero batches"
            )

        tracer = get_tracer()
        profiling = cfg.profiling and jax.process_index() == 0
        K = self._resolve_metrics_sync_every(metrics_sync_every)
        nb = it.num_batches

        # --- resume: restore weights/opt/step AND position -------------
        start_epoch, skip_batches = 0, 0
        if resume:
            manifest = self.load_checkpoint(resume)
            cursor = (manifest or {}).get("loader")
            if cursor:
                if (bool(cursor.get("shuffle", False)) != bool(shuffle)
                        or int(cursor.get("seed", 0)) != int(seed)):
                    raise CheckpointError(
                        f"resume {resume!r}: checkpoint was written with "
                        f"shuffle={cursor.get('shuffle')}/"
                        f"seed={cursor.get('seed')} but fit was called "
                        f"with shuffle={shuffle}/seed={seed} — the data "
                        "order would diverge; pass the original values"
                    )
                if int(cursor.get("batches", nb)) != nb:
                    raise CheckpointError(
                        f"resume {resume!r}: checkpoint saw "
                        f"{cursor.get('batches')} batches/epoch, this "
                        f"fit has {nb} — dataset or batch size changed; "
                        "the saved cursor does not map onto this run"
                    )
                start_epoch = int(cursor.get("epoch", 0))
                skip_batches = int(cursor.get("batch", 0))
                if skip_batches >= nb:  # killed exactly at an epoch edge
                    start_epoch, skip_batches = start_epoch + 1, 0
            # replay the loader's epoch permutations: each reset()
            # advances the SAME persistent rng the original run used,
            # so epoch start_epoch shuffles identically (the loop below
            # contributes the one remaining reset)
            for _ in range(start_epoch):
                it.reset()

        last_ckpt: Optional[str] = resume or None
        writer = (
            _CheckpointWriter()
            if (ckpt_every and ckpt_every > 0 and ckpt_path) else None
        )
        # place_fn resolves self.executor LATE so a mid-epoch recompile
        # (R17) or an elastic recovery swaps the placement target along
        # with the step program
        prefetch = DevicePrefetcher(
            it, lambda b: self.executor.place_batch(b), depth=depth
        )
        ok = False
        try:
            pm = self._fit_loop(
                prefetch=prefetch, it=it, epochs=epochs, nb=nb, bs=bs,
                K=K, tracer=tracer, profiling=profiling, verbose=verbose,
                shuffle=shuffle, seed=seed, recompile_state=recompile_state,
                start_epoch=start_epoch, skip_batches=skip_batches,
                writer=writer, ckpt_every=ckpt_every, ckpt_path=ckpt_path,
                last_ckpt=last_ckpt, recovery=recovery, depth=depth,
            )
            ok = True
        finally:
            if writer is not None:
                if ok:
                    writer.close()  # drain + surface a failed write
                else:
                    writer.shutdown()  # never mask the in-flight error
        if jax.process_index() == 0:
            tracer.save()  # no-op without --trace-out
        get_monitor().flush()  # fsync the metrics stream (no-op when off)
        return pm  # the FINAL epoch's metrics (reference parity)

    def _fit_loop(
        self, *, prefetch, it, epochs, nb, bs, K, tracer, profiling,
        verbose, shuffle, seed, recompile_state, start_epoch,
        skip_batches, writer, ckpt_every, ckpt_path, last_ckpt,
        recovery, depth,
    ) -> PerfMetrics:
        """The epoch/batch loop body of :meth:`fit`, factored out so the
        checkpoint-writer lifecycle wraps it cleanly."""
        cfg = self.config
        pm = PerfMetrics()
        loss = None
        restores = 0
        with tracer.span(
            "fit", cat="fit", epochs=epochs, batches=nb, metrics_sync_every=K
        ):
            if tracer.enabled:
                tracer.sample("fit.prefetch_depth", float(depth), level="step")
            for epoch in range(start_epoch, epochs):
                it.reset()
                # per-EPOCH accumulation, like the reference's reset_metrics()
                # at each epoch start (flexflow_cffi.py fit / base_model._train)
                pm = PerfMetrics()
                acc = DeviceMetricAccumulator()
                window: List[Any] = []  # raw device (loss, metrics) for R17
                with tracer.span("epoch", cat="fit", epoch=epoch):
                    for bi, (inputs, labels) in enumerate(prefetch):
                        if epoch == start_epoch and bi < skip_batches:
                            # resume replay: the original run consumed
                            # this batch before the kill — advance the
                            # loader past it without training
                            continue
                        try:
                            # step_dispatch: the fast path's place +
                            # enqueue (with the tracer on, the whole
                            # blocking instrumented step)
                            with tracer.span(
                                "batch", cat="fit", level="op", batch=bi
                            ), tracer.span("step_dispatch", cat="fit"):
                                loss, m = self.executor.train_step(
                                    inputs, labels
                                )
                        except HealthError:
                            # --health restore: rewind to the last good
                            # checkpoint and SKIP the poison batch
                            # (docs/RESILIENCE.md, "Restore policy")
                            if (cfg.health == "restore"
                                    and last_ckpt is not None
                                    and os.path.exists(last_ckpt)
                                    and restores < cfg.max_restores):
                                if writer is not None:
                                    writer.flush()
                                self.load_checkpoint(last_ckpt)
                                restores += 1
                                tracer.counter("health.restores")
                                if tracer.enabled:
                                    tracer.instant(
                                        "health_restore", cat="health",
                                        checkpoint=last_ckpt, batch=bi,
                                        restores=restores,
                                    )
                                continue
                            raise
                        except RuntimeError as e:
                            # elastic recovery: a matching device-loss
                            # error shrinks the machine model,
                            # re-searches, restores, and continues
                            if recovery is not None and recovery.matches(e):
                                if writer is not None:
                                    writer.flush()
                                recovery.recover(
                                    self, e, checkpoint=last_ckpt
                                )
                                continue
                            raise
                        # position AFTER this step: the manifest cursor a
                        # checkpoint written now embeds, so resume knows
                        # exactly which batch comes next
                        self._fit_cursor = {
                            "epoch": epoch, "batch": bi + 1,
                            "shuffle": bool(shuffle), "seed": int(seed),
                            "batches": nb,
                        }
                        if (writer is not None
                                and self.executor._step_count % ckpt_every
                                == 0):
                            # the host snapshot is the checkpoint's ONE
                            # device sync — counted truthfully; the npz
                            # serialize + fsync run on the writer thread
                            t0 = time.perf_counter()
                            with tracer.span("checkpoint_snapshot", cat="fit"):
                                flat, manifest = self._snapshot_checkpoint()
                            self.executor.count_host_sync(
                                1, stall_s=time.perf_counter() - t0
                            )
                            writer.put(ckpt_path, flat, manifest)
                            last_ckpt = ckpt_path
                            tracer.counter("fit.checkpoints")
                        # reference --profiling per-iteration ELAPSED prints
                        # (model.cc:3650-3653): per-step wall split
                        if profiling and self.executor.last_step_stats:
                            s = self.executor.last_step_stats
                            print(
                                f"[profiling] step {s['step']}: "
                                f"{s['total_s'] * 1e3:.2f} ms "
                                f"(dispatch {s['dispatch_s'] * 1e3:.2f} ms, "
                                f"device {s['device_s'] * 1e3:.2f} ms, "
                                f"stall {s['host_stall_s'] * 1e3:.2f} ms, "
                                f"jit {s['jit_cache']})"
                            )
                        if K <= 1:
                            # synchronous reference path: one forced device
                            # round-trip per step (pipeline flush), counted
                            t0 = time.perf_counter()
                            fl = float(loss)
                            fm = {k: float(v) for k, v in m.items()}
                            self.executor.count_host_sync(
                                1, stall_s=time.perf_counter() - t0
                            )
                            pm.update(fm, bs)
                            # R17 recompile hook: per-iteration trigger/alter,
                            # like the reference's recompile_on_condition in
                            # the train loop (moe.cc:180)
                            if recompile_state is not None:
                                recompile_state.observe(fl, fm)
                                recompile_state.maybe_recompile(self)
                            continue
                        acc.add(m, bs)
                        if recompile_state is not None:
                            window.append((loss, m))
                        if (bi + 1) % K == 0 or bi + 1 == nb:
                            self._flush_metrics(acc, pm, tracer)
                            if recompile_state is not None and window:
                                recompile_state.observe_window(window, self)
                                window = []
                if verbose and loss is not None:
                    # the flush already forced the epoch's last step to
                    # completion, so this float() reads a ready scalar
                    print(
                        f"epoch {epoch}: loss={float(loss):.4f} "
                        f"accuracy={pm.accuracy:.4f} "
                        f"throughput={pm.throughput():.2f} samples/s"
                    )
        return pm  # the FINAL epoch's metrics

    def eval(
        self,
        x: Union[np.ndarray, Sequence[np.ndarray]],
        y: np.ndarray,
        batch_size: Optional[int] = None,
        verbose: bool = False,
    ) -> PerfMetrics:
        """Loss & metrics in test mode over the full dataset, batch by
        batch (reference ``FFModel.eval``, ``flexflow_cffi.py:2106``:
        reset metrics, iterate batches, accumulate PerfMetrics).  A tail
        batch shorter than ``batch_size`` is padded to the compiled batch
        shape (one jit trace) but only its real rows enter the metrics,
        each batch weighted by its actual row count.  Reuses fit's async
        input pipeline (placement look-ahead) and device-side metric
        accumulation — ONE host sync for the whole pass instead of one
        per batch."""
        assert self.executor is not None, "call compile() first"
        bs = batch_size or self.config.batch_size
        xs = [
            np.asarray(a)
            for a in (x if isinstance(x, (list, tuple)) else [x])
        ]
        ya = np.asarray(y)
        ex = self.executor
        pm = PerfMetrics()
        import jax.numpy as _jnp

        n = xs[0].shape[0]
        assert all(a.shape[0] == n for a in xs) and ya.shape[0] == n, (
            f"inputs/labels disagree on sample count: "
            f"{[a.shape[0] for a in xs]} vs labels {ya.shape[0]}"
        )

        # same 3-stage pipeline as fit: batch slicing/padding -> device
        # placement look-ahead -> forward; metrics accumulate on device and
        # are fetched ONCE at the end (no per-batch float() round-trips)
        def batches():
            for start in range(0, n, bs):
                rows = min(bs, n - start)
                bx = [a[start:start + rows] for a in xs]
                if rows < bs:
                    bx = [
                        np.concatenate([b, np.repeat(b[-1:], bs - rows, axis=0)])
                        for b in bx
                    ]
                yield bx, ya[start:start + rows], rows

        def place(item):
            bx, yb, rows = item
            placed = [
                ex._place(b, ex._input_pspec(t), t.shape[0])
                for b, t in zip(bx, ex.graph_inputs)
            ]
            return placed, _jnp.asarray(yb), rows

        prefetch = DevicePrefetcher(
            batches(), place, depth=max(1, self.config.prefetch_depth)
        )
        acc = DeviceMetricAccumulator()
        with get_tracer().span("eval", cat="fit", samples=n):
            for placed, yb, rows in prefetch:
                logits = ex.forward(placed)
                # only the real rows enter the metrics: a padded tail
                # batch is sliced back to its actual row count, and each
                # batch is weighted by that count in the accumulator
                m = ex.metrics.compute(logits[:rows], yb)
                acc.add(m, rows)
            t0 = time.perf_counter()
            sums, count = acc.drain()
            ex.count_host_sync(1, stall_s=time.perf_counter() - t0)
            pm.merge_sums(sums, count)
        if verbose:
            print("eval: " + " ".join(
                f"{k}={v:.4f}" for k, v in (("accuracy", pm.accuracy),)
            ))
        return pm

    def last_step_stats(self) -> Optional[Dict[str, Any]]:
        """Timing of the most recent training step (see
        docs/OBSERVABILITY.md for the field glossary): ``step``,
        ``total_s``, ``host_s``, ``dispatch_s``, ``device_s``,
        ``compile_s``, ``jit_cache``.  None until a step has run with
        tracing or ``--profiling`` enabled — the untraced fast path
        records nothing (it would have to force a device sync)."""
        assert self.executor is not None, "call compile() first"
        return self.executor.last_step_stats

    def trace_summary(self) -> Dict[str, Any]:
        """The process tracer's machine-readable rollup (phases, spans,
        counters) — the summary dict ``bench.py`` consumers read."""
        return get_tracer().summary()

    def eval_batch(
        self, x: Sequence[np.ndarray], seq_length: Optional[int] = None
    ) -> jax.Array:
        """Inference forward.  ``seq_length`` is the per-call iteration
        config (reference ``forward(seq_length)``, ``model.cc:2415-2420``):
        ops that declared seq-length dims mask positions beyond it."""
        assert self.executor is not None
        xs = list(x) if isinstance(x, (list, tuple)) else [x]
        return self.executor.forward(xs, seq_length=seq_length)

    # ------------------------------------------------- weight access (R3 API)
    def get_weights(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Host copy of all weights, trainable AND stateful (BN running
        stats) — reference ``ParallelTensorBase::get_tensor``
        (``parallel_tensor.h:168``).  Always the PER-LAYER layout: a
        stacked executor (``--stack-blocks``) expands its depth-stacked
        chain buckets, so callers never see the storage layout."""
        assert self.executor is not None
        ex = self.executor
        out: Dict[str, Dict[str, np.ndarray]] = ex.unstack_tree(
            jax.tree.map(np.asarray, ex.params)
        )
        for lname, ws in ex.unstack_tree(
            jax.tree.map(np.asarray, ex.state)
        ).items():
            out.setdefault(lname, {}).update(ws)
        return out

    def weight_shape(self, layer_name: str, weight_name: str) -> Tuple[int, ...]:
        """Global shape of one weight from executor/layer METADATA — no
        device-to-host transfer (the C API's parameter handles size
        buffers with this; ``get_weights`` would materialize every
        table)."""
        if self.executor is not None:
            shp = self.executor.weight_global_shape(layer_name, weight_name)
            if shp is not None:
                return shp
        for l in self.layers:
            if l.name == layer_name:
                from flexflow_tpu.ops.base import get_op_def

                for w in get_op_def(l.op_type).weights(l):
                    if w.name == weight_name:
                        return tuple(int(s) for s in w.shape)
        raise KeyError(f"no weight {layer_name}/{weight_name}")

    def set_weights(self, weights: Dict[str, Dict[str, np.ndarray]]) -> None:
        """Reference ``set_tensor``/numpy attach
        (``examples/python/native/mnist_mlp_attach.py`` pattern).  Takes
        the PER-LAYER layout; members of scan-stacked chains are routed
        into their depth slice of the stacked bucket
        (``Executor.assign_weight_entries``)."""
        assert self.executor is not None
        self.executor.assign_weight_entries(weights, strict=True)

    @staticmethod
    def _to_numpy(x) -> np.ndarray:
        """Host copy that also works for process-sharded arrays (ZeRO-1
        moments on a multi-host mesh are not fully addressable; gather
        before converting)."""
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(x)

    # ----------------------------------------------- checkpoint / resume
    def _snapshot_checkpoint(
        self,
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
        """Host snapshot of the full training state (the ONE device
        sync of a checkpoint — callers count it) plus the ffckpt/2
        manifest: schema id, step, rng seed, dataloader cursor, and the
        strategy identity, so resume can restore *position*, not just
        weights (docs/RESILIENCE.md, "Manifest schema")."""
        assert self.executor is not None, "call compile() first"
        ex = self.executor
        flat: Dict[str, np.ndarray] = {}

        def put(prefix, tree):
            # ALWAYS the per-layer layout: a stacked executor
            # (--stack-blocks) unstacks its chain buckets here, so a
            # checkpoint written by either layout loads into the other
            # (and into any strategy — arrays re-place on load)
            for lname, ws in ex.unstack_tree(
                {k: {w: self._to_numpy(a) for w, a in v.items()}
                 for k, v in tree.items()}
            ).items():
                for wname, arr in ws.items():
                    flat[f"{prefix}/{lname}/{wname}"] = arr

        put("params", ex.params)
        put("state", ex.state)
        for key, val in ex.opt_state.items():
            if isinstance(val, dict):
                put(f"opt/{key}", val)
            else:
                flat[f"opt_scalar/{key}"] = np.asarray(val)
        flat["meta/step_count"] = np.asarray(ex._step_count)
        strat = self.strategy
        manifest: Dict[str, Any] = {
            "schema": CHECKPOINT_SCHEMA,
            "step": int(ex._step_count),
            "rng_seed": int(ex.seed),
            "strategy": {
                "mesh_shape": list(strat.mesh.shape),
                "axis_names": list(strat.mesh.axis_names),
                "pipeline": (
                    strat.pipeline.stages
                    if getattr(strat, "pipeline", None) is not None
                    else None
                ),
            } if strat is not None else None,
            "loader": (
                dict(self._fit_cursor) if self._fit_cursor else None
            ),
        }
        return flat, manifest

    def save_checkpoint(self, path: str) -> str:
        """Full training checkpoint: params + stateful weights (BN stats)
        + optimizer state + step count + the ffckpt/2 manifest, one
        ``.npz`` written ATOMICALLY (temp + fsync + ``os.replace``) with
        an embedded content digest — a reader never observes a torn
        file, and :meth:`load_checkpoint` refuses a corrupt one.

        Exceeds the reference, which checkpoints weights only via tensor
        attach (``parallel_tensor.h:164-169``; SURVEY §5: "No
        optimizer-state checkpointing") — resuming there silently resets
        Adam moments.  Multi-host callers should write from process 0.
        Returns the path actually written (``.npz`` appended when
        missing, matching ``np.savez``).
        """
        tracer = get_tracer()
        with tracer.span("checkpoint_save", cat="io", path=path):
            flat, manifest = self._snapshot_checkpoint()
            out = _write_checkpoint_atomic(path, flat, manifest)
        tracer.counter(
            "checkpoint.bytes_written",
            float(sum(a.nbytes for a in flat.values())),
        )
        return out

    def load_checkpoint(self, path: str) -> Optional[Dict[str, Any]]:
        """Restore a :meth:`save_checkpoint` file into the compiled model
        (weights re-placed with their current sharding — a checkpoint
        written under one strategy loads under any other).  Returns the
        embedded manifest (None for legacy ffckpt/1 files, which carry
        neither manifest nor digest).

        REFUSES bad files with :class:`CheckpointError` naming what
        failed: a torn/truncated archive (unreadable zip), an unreadable
        manifest, or a content-digest mismatch.  Nothing is written into
        the executor until the whole file has been read and verified."""
        assert self.executor is not None, "call compile() first"
        ex = self.executor
        with get_tracer().span("checkpoint_load", cat="io", path=path):
            try:
                with np.load(path) as z:
                    flat = {key: np.asarray(z[key]) for key in z.files}
            except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
                raise CheckpointError(
                    f"checkpoint {path!r} is torn or truncated — the "
                    f"archive is unreadable ({type(e).__name__}: {e}); "
                    "refusing to load. Recover from the previous "
                    "complete checkpoint."
                ) from e
            manifest: Optional[Dict[str, Any]] = None
            raw = flat.pop("meta/manifest", None)
            if raw is not None:
                try:
                    manifest = json.loads(raw.tobytes().decode())
                except (UnicodeDecodeError, ValueError) as e:
                    raise CheckpointError(
                        f"checkpoint {path!r} has an unreadable manifest "
                        f"({e}); refusing to load"
                    ) from e
                want = manifest.get("digest")
                got = _checkpoint_digest(flat)
                if want != got:
                    raise CheckpointError(
                        f"checkpoint {path!r} failed its content-digest "
                        f"check: manifest records {want}, the file hashes "
                        f"to {got} — the payload was corrupted after "
                        "writing; refusing to load"
                    )
            weights: Dict[str, Dict[str, np.ndarray]] = {}
            opt: Dict[str, Dict[str, Dict[str, np.ndarray]]] = {}
            step_count = None
            for key, arr in flat.items():
                # layer names may themselves contain '/', so parse as
                # prefix[/okey]/<lname...>/wname with wname = last segment
                # (weight names are framework-defined, never contain '/')
                prefix, rest = key.split("/", 1)
                if prefix == "meta":
                    if rest == "step_count":
                        step_count = int(arr)
                elif prefix == "opt_scalar":
                    ex.opt_state[rest] = jax.device_put(arr)
                elif prefix == "opt":
                    okey, rest = rest.split("/", 1)
                    lname, wname = rest.rsplit("/", 1)
                    opt.setdefault(okey, {}).setdefault(lname, {})[wname] = arr
                else:  # params / state
                    lname, wname = rest.rsplit("/", 1)
                    weights.setdefault(lname, {})[wname] = arr
            if step_count is not None:
                ex._step_count = step_count
            # batch the writes: the per-layer entries route into whatever
            # layout the live executor uses (members of scan-stacked
            # chains land in their depth slice, each full bucket written
            # with ONE device_put)
            self.set_weights(weights)
            for okey, entries in opt.items():
                ex.assign_opt_entries(okey, entries)
        return manifest

    @property
    def num_parameters(self) -> int:
        assert self.executor is not None
        return sum(
            int(np.prod(w.shape)) for lw in self.executor.params.values() for w in lw.values()
        )
