"""``PagedKVCache`` with a state group (ISSUE 34): a recurrent state a
slot a state layer beside the paged K/V pool -- reserve / release /
spill / restore / ``check_invariants`` / ``hbm_bytes``; caches of one and
of two groups behave as before."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.serve.kvcache import PagedKVCache


def cache(**kw):
    return PagedKVCache(1, 2, 16, slots=3, block_size=4, max_seq_len=64,
                        state_shapes=[((3, 12), (4, 8, 16))] * 2, **kw)


def fill(kv, seed=0):
    rng = np.random.default_rng(seed)
    kv.cache_k = jnp.asarray(rng.standard_normal(kv.cache_k.shape), jnp.float32)
    kv.cache_v = jnp.asarray(rng.standard_normal(kv.cache_v.shape), jnp.float32)
    kv.state_conv = [jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in kv.state_conv]
    kv.state_ssm = [jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in kv.state_ssm]


def test_the_state_group_is_provisioned_a_slot_and_counted():
    kv = cache(dtype=jnp.bfloat16)
    assert [a.shape for a in kv.state_conv] == [(3, 3, 12)] * 2
    assert [a.shape for a in kv.state_ssm] == [(3, 4, 8, 16)] * 2
    assert {a.dtype for a in kv.state_conv} == {jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in kv.state_ssm} == {jnp.dtype(jnp.float32)}
    assert not kv.prefix_sharing  # a re-attached prefix has no state at its boundary
    per_slot = 2 * (3 * 12 * 2 + 4 * 8 * 16 * 4)
    assert kv.state_bytes_per_slot == per_slot and kv.state_bytes() == 3 * per_slot
    assert kv.hbm_bytes() == 2 * kv.cache_k.size * 2 + 3 * per_slot
    assert kv.bytes_per_token == 2 * 1 * 2 * 16 * 2  # K/V only: state costs nothing a position
    assert kv.state_slots_held == 0
    kv.reserve(0, 40)
    kv.reserve(2, 9)
    kv.check_invariants()
    assert kv.state_slots_held == 2 and kv.pages_held() == {"full": 10 + 3, "window": 0}
    kv.release(0)
    kv.check_invariants()
    assert kv.state_slots_held == 1
    # admission is the full group's: a slot's state is there while the slot is
    assert kv.can_reserve(64) and not kv.can_reserve(64 * 4)
    with pytest.raises(ValueError, match="quantized pool"):
        cache(kv_dtype="int8")


def test_spill_and_restore_carry_the_state_with_the_keys():
    kv = cache()
    kv.reserve(1, 40)
    fill(kv)
    length = 30
    kv_before = kv.gather_dense(1, length)
    conv_before = [np.asarray(a[1]) for a in kv.state_conv]
    ssm_before = [np.asarray(a[1]) for a in kv.state_ssm]
    others = [np.asarray(a[jnp.asarray([0, 1])]) for a in kv.state_ssm]
    payload = kv.spill(1, length)
    kv.check_invariants()
    assert not kv._owned and kv.state_spills == 1 and kv.state_restores == 0
    assert set(payload["state"]["layers"]) == {"layer0", "layer1"}
    assert payload["state"]["layers"]["layer1"]["ssm"].dtype == np.float32
    kv.restore(2, payload, 40)  # into another slot
    kv.check_invariants()
    assert kv.state_restores == 1 and kv.state_slots_held == 1
    for a, b in zip(kv_before, kv.gather_dense(2, length)):
        np.testing.assert_array_equal(a, b)
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(kv.state_conv[i][2]), conv_before[i])
        np.testing.assert_array_equal(np.asarray(kv.state_ssm[i][2]), ssm_before[i])
        # the other slots' rows are what they were
        np.testing.assert_array_equal(np.asarray(kv.state_ssm[i][jnp.asarray([0, 1])]), others[i])
    # a payload restores into a pool of the same groups only
    plain = PagedKVCache(1, 2, 16, slots=1, block_size=4, max_seq_len=64)
    with pytest.raises(ValueError, match="state layers"):
        plain.restore(0, payload, 40)
    plain.check_invariants()
    plain.reserve(0, 8)
    with pytest.raises(ValueError, match="state layers"):
        cache().restore(0, plain.spill(0, 6), 40)
    other = PagedKVCache(1, 2, 16, slots=1, block_size=4, max_seq_len=64,
                         state_shapes=[((3, 12), (4, 8, 8))] * 2)
    with pytest.raises(ValueError, match="state payload ssm"):
        other.restore(0, payload, 40)
    other.check_invariants()


def test_a_donated_state_array_not_stored_back_is_caught():
    kv = cache()
    kv.state_ssm[0].delete()
    with pytest.raises(AssertionError, match="state layer"):
        kv.check_invariants()


def test_one_and_two_group_caches_are_what_they_were():
    kv = PagedKVCache(2, 4, 8, slots=2, block_size=4, max_seq_len=32)
    assert kv.state_layers == 0 and kv.state_conv == [] and kv.prefix_sharing
    assert kv.state_bytes() == 0 and kv.state_slots_held == 0
    kv.reserve(0, 10)
    assert kv.hbm_bytes() == 2 * kv.cache_k.size * 4
    assert "state" not in kv.spill(0, 6)
    kv.check_invariants()
    two = PagedKVCache(1, 2, 16, slots=3, block_size=4, max_seq_len=4096,
                       window_layers=4, window=8, chunk=8)
    two.reserve(0, 100)
    assert two.hbm_bytes() == 2 * 4 * (two.cache_k.size + two.win_k.size)
    payload = two.spill(0, 50)
    assert "window" in payload and "state" not in payload
    two.check_invariants()
