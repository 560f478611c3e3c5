"""Shared by the serve tests: which layer functions the decode and the
prefill program call, traced over an engine's geometry on the paged arm
-- the attention kernels and the page write, the two state-space mixers,
the differential combine and the gated memory unit -- counted by name.

A model without a cross-decoder pins what its programs were before the
cross-decoder existed: every layer's attention writes its pages and
reads them through the prefill kernel in a prefill, no layer runs at
decode width there, and none of the cross-decoder's functions is
traced."""

from __future__ import annotations

import collections

import jax

from flexflow_tpu.ops import attention, ssm
from flexflow_tpu.ops.pallas import paged_attention as pa
from flexflow_tpu.serve.programs import build_serve_programs

COUNTED = (
    (pa, "paged_decode_attention"), (pa, "paged_prefill_attention"), (pa, "paged_kv_write"),
    (ssm, "mamba1_mixer"), (ssm, "mamba2_mixer"), (ssm, "gated_memory"),
    (attention, "diff_combine"),
)


def traced_calls(monkeypatch, eng):
    """``{"decode": Counter, "prefill": Counter}``: the calls each
    program makes while it is traced (``jax.eval_shape``, nothing runs)."""
    calls = collections.Counter()
    for mod, name in COUNTED:
        def counted(*a, _fn=getattr(mod, name), _name=name, **k):
            calls[_name] += 1
            return _fn(*a, **k)

        monkeypatch.setattr(mod, name, counted)
    progs = build_serve_programs(eng.model, eng.kv, attn_kernel="paged", return_probs=False)
    idle_decode, idle_prefill = eng._idle_args()
    out = {}
    for what, prog, args in (("decode", progs.decode, idle_decode),
                             ("prefill", progs.prefill, idle_prefill)):
        calls.clear()
        jax.eval_shape(prog, progs.params_arg, *eng._kvs(), *args)
        out[what] = collections.Counter(calls)
    return out


def assert_no_cross_decoder(monkeypatch, eng):
    """``eng``'s spec has no layer after its last pool-writing one, and
    its programs trace as they did before there was one: one kernel read
    and one page write an attention layer in either program (the decode
    kernel in a decode, the prefill kernel in a prefill), one mixer call
    a state-space layer, and nothing of the cross-decoder."""
    spec = eng.spec
    assert spec.self_layers == spec.num_layers
    assert not spec.head_tied
    branches = [br for ls in spec.layers for br in ls.branches]
    assert all(br.kv_source < 0 and not br.differential for br in branches if br.is_attention)
    assert not any(br.kind in ("mamba1", "gmu") for br in branches)
    n_attn = sum(br.is_attention for br in branches)
    n_state = sum(br.kind == "mamba2" for br in branches)
    calls = traced_calls(monkeypatch, eng)
    for what, kernel in (("decode", "paged_decode_attention"),
                         ("prefill", "paged_prefill_attention")):
        want = {kernel: n_attn, "paged_kv_write": n_attn, "mamba2_mixer": n_state}
        assert calls[what] == collections.Counter({k: v for k, v in want.items() if v}), what
