"""``ffkv/1`` — the versioned, digest-checked KV handoff codec
(docs/SERVING.md, "Disaggregated prefill/decode").

``ffdrain/1`` (engine.py) and ``ffkv/1`` carry the same thing — request
state with a per-layer KV spill payload — so they share ONE flattening:
each request becomes named numpy arrays (``r{i}/prompt``,
``r{i}/tokens``, ``r{i}/kv/layer{j}/{k,v}``) plus a JSON-able meta dict,
and the whole frame rides with a content digest over the arrays
(the checkpoint writer's discipline, :mod:`flexflow_tpu.model`).
The drain path writes that flattening atomically to disk; this module
additionally frames ONE request into in-memory ``.npz`` bytes — the
exact wire format a DCN transport between a prefill pool and a decode
pool carries (transport.py), digest-verified on receive before any
block is restored.

The KV payload itself (``{"length", "layers": {layer{i}: {k, v}}}``,
dense ``(H, length, D)`` per layer) is deliberately geometry-free:
``PagedKVCache.restore`` re-chunks it into the DESTINATION pool's
``block_size``/``num_blocks`` geometry, so a spill from a prefill pool
with 8-position blocks restores bit-exactly into a decode pool with
16-position blocks (the cross-geometry property test pins this).
"""

from __future__ import annotations

import io
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "KV_SCHEMA",
    "HandoffError",
    "flatten_requests",
    "unflatten_requests",
    "verify_flat",
    "encode_handoff",
    "decode_handoff",
    "kv_payload_nbytes",
]

# wire schema id: bump ONLY on incompatible layout changes (adding meta
# keys is compatible — readers use .get)
KV_SCHEMA = "ffkv/1"

# meta keys every frame carries (the pre-disagg ffdrain/1 vocabulary —
# kept exact so old drain files and new ones stay interchangeable)
_META_KEYS = (
    "id", "max_new_tokens", "eos_id", "tenant", "tier", "deadline_ms",
    "preemptions", "session",
)
# latency bookkeeping that crosses the pool boundary with the request
# (floats in the manifest; absent on drain payloads, which resume on
# the same engine clock anyway)
_TIMING_KEYS = (
    "arrival_s", "arrival_abs_s", "t_submit", "t_admitted",
    "t_first_token",
)


def _defaulted(meta: Dict[str, Any]) -> Dict[str, Any]:
    meta.setdefault("tenant", "default")
    meta.setdefault("tier", "batch")
    return meta


class HandoffError(RuntimeError):
    """A handoff frame that must not be restored: torn bytes, missing
    manifest, wrong schema, or content-digest mismatch.  The message
    names what failed — the router drops the frame truthfully instead
    of scattering corrupt K/V into the decode pool."""


def flatten_requests(
    requests: List[Dict[str, Any]],
) -> Tuple[Dict[str, np.ndarray], List[Dict[str, Any]]]:
    """Flatten request dicts (the :meth:`ServeEngine.drain` /
    handoff shape) into named arrays + JSON-able metas.  The inverse is
    :func:`unflatten_requests`; ``ffdrain/1`` files and ``ffkv/1``
    frames both wrap this."""
    flat: Dict[str, np.ndarray] = {}
    metas: List[Dict[str, Any]] = []
    for i, r in enumerate(requests):
        flat[f"r{i}/prompt"] = np.asarray(r["prompt"], np.int32)
        flat[f"r{i}/tokens"] = np.asarray(r.get("tokens", ()), np.int64)
        kv = r.get("kv_spill")
        kv_dtype = None
        if kv is not None:
            # quantized spills (r19): a kv_dtype tag plus per-layer
            # per-position scale arrays ride as EXTRA named arrays, so
            # the frame digest covers them (the PR-16 trace pattern) —
            # a tampered scale fails verify exactly like tampered KV.
            # fp32/bf16 spills carry neither, keeping those frames
            # byte-identical to pre-r19 builds.  fp8 element arrays are
            # stored as uint8 VIEWS: np.savez round-trips ml_dtypes
            # float8 as raw void bytes, losing the dtype — the
            # kv_dtype meta key is what views them back on decode.
            kv_dtype = kv.get("kv_dtype")
            if "window" in kv:
                # refused rather than dropped: a frame without it would
                # restore a window layer with no keys to look back to
                raise HandoffError(
                    "ffdrain/1 and ffkv/1 do not carry the window group's "
                    "payload of a model with window layers (docs/SERVING.md): "
                    "its spills restore in memory only"
                )
            for lname, d in kv["layers"].items():
                k, v = np.asarray(d["k"]), np.asarray(d["v"])
                if kv_dtype == "fp8":
                    k, v = k.view(np.uint8), v.view(np.uint8)
                flat[f"r{i}/kv/{lname}/k"] = k
                flat[f"r{i}/kv/{lname}/v"] = v
                if "sk" in d:
                    flat[f"r{i}/kv/{lname}/sk"] = np.asarray(
                        d["sk"], np.float32
                    )
                    flat[f"r{i}/kv/{lname}/sv"] = np.asarray(
                        d["sv"], np.float32
                    )
        meta: Dict[str, Any] = {
            "id": int(r["id"]),
            "max_new_tokens": int(r["max_new_tokens"]),
            "eos_id": r.get("eos_id"),
            "tenant": r.get("tenant", "default"),
            "tier": r.get("tier", "batch"),
            "deadline_ms": r.get("deadline_ms"),
            "preemptions": int(r.get("preemptions", 0)),
            # session id crosses replicas with the KV (fleet migration);
            # additive — old frames read it back as None via .get
            "session": r.get("session"),
            "kv_length": int(kv["length"]) if kv is not None else None,
        }
        if kv_dtype is not None:
            meta["kv_dtype"] = str(kv_dtype)
        for key in _TIMING_KEYS:
            if r.get(key) is not None:
                meta[key] = float(r[key])
        # optional trace context (PR 16, ffspan/1): an extra named array
        # — JSON bytes — so the digest COVERS it (a tampered trace fails
        # verify like tampered KV).  Absent when tracing is off, which
        # keeps untraced frames byte-identical to pre-trace builds; old
        # readers ignore the unknown array, old frames simply lack it.
        tr = r.get("trace")
        if tr is not None:
            flat[f"r{i}/trace"] = np.frombuffer(
                json.dumps(tr).encode(), dtype=np.uint8
            )
        metas.append(meta)
    return flat, metas


def unflatten_requests(
    flat: Dict[str, np.ndarray], metas: List[Dict[str, Any]],
) -> List[Dict[str, Any]]:
    """Rebuild the request-dict list :func:`flatten_requests` consumed
    (the shape :meth:`ServeEngine.resume_from_drain` and the disagg
    router both take)."""
    requests: List[Dict[str, Any]] = []
    for i, meta in enumerate(metas):
        kv = None
        if meta.get("kv_length") is not None:
            kv_dtype = meta.get("kv_dtype")
            layers: Dict[str, Any] = {}
            j = 0
            while f"r{i}/kv/layer{j}/k" in flat:
                k = flat[f"r{i}/kv/layer{j}/k"]
                v = flat[f"r{i}/kv/layer{j}/v"]
                if kv_dtype == "fp8":
                    # undo the uint8 storage view (see flatten)
                    import ml_dtypes

                    k = k.view(ml_dtypes.float8_e4m3fn)
                    v = v.view(ml_dtypes.float8_e4m3fn)
                layers[f"layer{j}"] = {"k": k, "v": v}
                sk = flat.get(f"r{i}/kv/layer{j}/sk")
                if sk is not None:
                    layers[f"layer{j}"]["sk"] = sk
                    layers[f"layer{j}"]["sv"] = flat[
                        f"r{i}/kv/layer{j}/sv"
                    ]
                j += 1
            kv = {"length": int(meta["kv_length"]), "layers": layers}
            if kv_dtype is not None:
                kv["kv_dtype"] = str(kv_dtype)
        d: Dict[str, Any] = {
            key: meta.get(key) for key in _META_KEYS + _TIMING_KEYS
            if key in meta or key in _META_KEYS
        }
        _defaulted(d)
        d["preemptions"] = int(meta.get("preemptions", 0))
        d["prompt"] = flat[f"r{i}/prompt"]
        d["tokens"] = [int(t) for t in flat[f"r{i}/tokens"]]
        d["kv_spill"] = kv
        raw_tr = flat.get(f"r{i}/trace")
        if raw_tr is not None:
            d["trace"] = json.loads(np.asarray(raw_tr).tobytes().decode())
        requests.append(d)
    return requests


def verify_flat(
    flat: Dict[str, np.ndarray], what: str,
    want_schema: Optional[str] = None,
) -> Dict[str, Any]:
    """Pop ``meta/manifest`` from ``flat`` (in place), parse it, and
    digest-check the remaining arrays.  Returns the manifest.  Raises
    :class:`HandoffError` when the frame lies about its contents."""
    from flexflow_tpu.model import _checkpoint_digest

    raw = flat.pop("meta/manifest", None)
    if raw is None:
        raise HandoffError(
            f"{what} has no manifest — not a "
            f"{want_schema or 'ffkv/ffdrain'} payload"
        )
    manifest = json.loads(np.asarray(raw).tobytes().decode())
    if want_schema is not None and manifest.get("schema") != want_schema:
        raise HandoffError(
            f"{what} carries schema {manifest.get('schema')!r}, "
            f"expected {want_schema!r}"
        )
    want, got = manifest.get("digest"), _checkpoint_digest(flat)
    if want != got:
        raise HandoffError(
            f"{what} failed its content-digest check: manifest records "
            f"{want}, payload hashes to {got}; refusing to restore"
        )
    return manifest


def encode_handoff(request: Dict[str, Any]) -> bytes:
    """Frame ONE request (dict with ``prompt``/``tokens``/``kv_spill``
    + meta) as self-describing, digest-stamped ``ffkv/1`` bytes — what
    :class:`~flexflow_tpu.serve.transport.Transport` carries between
    pools.  The spill arrays are host numpy already (spill materializes
    them), so encoding never touches the device."""
    from flexflow_tpu.model import _checkpoint_digest

    flat, metas = flatten_requests([request])
    manifest = {
        "schema": KV_SCHEMA,
        "requests": metas,
        "digest": _checkpoint_digest(flat),
    }
    payload = dict(flat)
    payload["meta/manifest"] = np.frombuffer(
        json.dumps(manifest).encode(), dtype=np.uint8
    )
    buf = io.BytesIO()
    np.savez(buf, **payload)
    return buf.getvalue()


def decode_handoff(data: bytes) -> Dict[str, Any]:
    """Digest-verify and unpack one :func:`encode_handoff` frame back
    into the request dict.  Refuses torn or tampered frames with a
    truthful :class:`HandoffError`."""
    import zipfile

    try:
        with np.load(io.BytesIO(data)) as z:
            flat = {k: np.asarray(z[k]) for k in z.files}
    except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
        raise HandoffError(
            f"handoff frame is torn or truncated "
            f"({type(e).__name__}: {e}); refusing to restore"
        ) from e
    manifest = verify_flat(flat, "handoff frame", want_schema=KV_SCHEMA)
    reqs = unflatten_requests(flat, manifest["requests"])
    if len(reqs) != 1:
        raise HandoffError(
            f"handoff frame holds {len(reqs)} requests, expected 1"
        )
    return reqs[0]


def kv_payload_nbytes(kv: Optional[Dict[str, Any]]) -> int:
    """Dense bytes of one spill payload (the quantity the DCN pricing
    charges — block padding is a pool-local artifact and does not cross
    the wire)."""
    if kv is None:
        return 0
    return int(sum(
        d["k"].nbytes + d["v"].nbytes
        + (d["sk"].nbytes + d["sv"].nbytes if "sk" in d else 0)
        for d in kv["layers"].values()
    ))
