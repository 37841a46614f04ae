"""DPF correctness: unit + hypothesis property tests.

Invariants under test (the cryptographic contract of core/dpf.py):
  P1  Eval(k0, j) XOR Eval(k1, j) == 1{j == alpha}      (point function)
  P2  eval_range tiles eval_all exactly (shard-parallel form)
  P3  additive word shares sum to beta * 1{j == alpha} mod 2^32
  P4  byte shares sum to 1{j == alpha} mod 256 (MXU matmul form)
  P5  each key alone is (statistically) uninformative: leaf bits of a
      single party are ~balanced — a smoke-level distinguisher check
"""
import hashlib

import numpy as np
import pytest
from _prop import given, settings, st

import jax.numpy as jnp

from repro.config import PIRConfig
from repro.core import dpf, protocol

RNG = np.random.default_rng(7)


def _keys(alpha, log_n, **kw):
    return dpf.gen_keys(np.random.default_rng(42), alpha, log_n, **kw)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=9), st.data())
def test_onehot_property(log_n, data):
    alpha = data.draw(st.integers(0, (1 << log_n) - 1))
    k0, k1 = _keys(alpha, log_n)
    _, t0 = dpf.eval_all(k0)
    _, t1 = dpf.eval_all(k1)
    onehot = np.asarray(t0 ^ t1)
    assert onehot.sum() == 1
    assert onehot[alpha] == 1


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=2, max_value=8), st.data())
def test_eval_range_tiles_eval_all(log_n, data):
    alpha = data.draw(st.integers(0, (1 << log_n) - 1))
    log_range = data.draw(st.integers(0, log_n))
    k0, _ = _keys(alpha, log_n)
    seeds_all, t_all = dpf.eval_all(k0)
    n_blocks = 1 << (log_n - log_range)
    width = 1 << log_range
    for blk in range(n_blocks):
        seeds, t = dpf.eval_range(k0, blk, log_range)
        np.testing.assert_array_equal(
            np.asarray(t), np.asarray(t_all[blk * width:(blk + 1) * width]))
        np.testing.assert_array_equal(
            np.asarray(seeds),
            np.asarray(seeds_all[blk * width:(blk + 1) * width]))


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=4), st.data())
def test_additive_word_shares(log_n, n_words, data):
    alpha = data.draw(st.integers(0, (1 << log_n) - 1))
    beta = data.draw(st.lists(st.integers(0, (1 << 32) - 1),
                              min_size=n_words, max_size=n_words))
    payload = np.asarray(beta, np.uint32)
    k0, k1 = _keys(alpha, log_n, payload=payload)
    out = []
    for k in (k0, k1):
        seeds, t = dpf.eval_all(k)
        out.append(np.asarray(dpf.leaf_words(k, seeds, t, n_words),
                              np.uint32))
    total = (out[0].astype(np.uint64) + out[1].astype(np.uint64)) \
        % (1 << 32)
    expect = np.zeros(((1 << log_n), n_words), np.uint64)
    expect[alpha] = payload
    np.testing.assert_array_equal(total, expect)


@pytest.mark.slow   # ~1-2 min on the 1-core container
def test_byte_shares_sum_mod_256():
    log_n = 7
    alpha = 93
    k0, k1 = _keys(alpha, log_n, payload=np.array([1], np.uint32))
    shares = []
    for k in (k0, k1):
        s = dpf.eval_bytes_batch(dpf.stack_keys([k]), 0, log_n)
        shares.append(np.asarray(s, np.int64)[0])
    total = (shares[0] + shares[1]) % 256
    expect = np.zeros(1 << log_n, np.int64)
    expect[alpha] = 1
    np.testing.assert_array_equal(total, expect)


@pytest.mark.slow   # ~1-2 min on the 1-core container
def test_single_key_leaf_bits_balanced():
    """One party's selection bits look ~uniform (no trivial leakage)."""
    log_n = 12
    k0, _ = _keys(1234, log_n)
    _, t = dpf.eval_all(k0)
    frac = float(np.asarray(t).mean())
    assert 0.40 < frac < 0.60, frac


def test_keys_differ_per_alpha():
    k_a, _ = _keys(3, 6)
    k_b, _ = _keys(4, 6)
    assert not np.array_equal(np.asarray(k_a.cw_seed),
                              np.asarray(k_b.cw_seed))


def test_batched_eval_matches_single():
    log_n = 6
    alphas = [0, 5, 63]
    keys = [dpf.gen_keys(np.random.default_rng(i), a, log_n)[0]
            for i, a in enumerate(alphas)]
    batch = dpf.stack_keys(keys)
    bits = np.asarray(dpf.eval_bits_batch(batch, 0, log_n))
    for i, k in enumerate(keys):
        _, t = dpf.eval_all(k)
        np.testing.assert_array_equal(bits[i], np.asarray(t))


def test_invalid_alpha_raises():
    with pytest.raises(ValueError):
        _keys(1 << 5, 5)


def _key_digest(keys) -> str:
    """SHA-256 over every key's leaves: dtype, shape and bytes."""
    h = hashlib.sha256()
    for key in keys:
        for leaf in (key.root_seed, key.cw_seed, key.cw_t, key.cw_final):
            if leaf is None:
                h.update(b"none")
                continue
            a = np.asarray(leaf)
            h.update(str((a.dtype.str, a.shape)).encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _rng(seed):
    return np.random.default_rng(seed)


#: digests of the keys that the device Gen (a per-level loop of jnp ops
#: over ``chacha_block``) made for these rng seeds; the host Gen must make
#: the same bytes, so a key from either is served alike
GOLDEN_KEYS = {
    "xor-6": (
        lambda: dpf.gen_keys(_rng(101), 37, 6),
        "cac957d80a4ee9aa092b44437c491da4a1d7d48bebdda2edd044e9aad58b5079"),
    "xor-25": (
        lambda: dpf.gen_keys(_rng(202), 31_415_926, 25),
        "e443b2e3101b6d46c1d4482fb70be9edca2e931da9a4684cd4b6154b244ccc01"),
    "xor-6-chacha8": (
        lambda: dpf.gen_keys(_rng(404), 63, 6, rounds=8),
        "61608011f9480fb0c318103936462bc2fcd753dd12451a4fc57ba984b59392f7"),
    "additive-20-words": (
        lambda: dpf.gen_keys(_rng(303), 5, 10,
                             payload=np.arange(1, 21, dtype=np.uint32)),
        "19b1588edd1e10daede47efff3d4ba519f0b52dc789025e758914d8d723fb0df"),
    "additive-dpf-2": (
        lambda: protocol.get("additive-dpf-2").query_gen(
            _rng(505), 4000, PIRConfig(n_items=1 << 12, item_bytes=32,
                                       protocol="additive-dpf-2")),
        "e8e2db34e7d28857156afca590b562ffbb099093ad05ff167b875cd8c6c4a8fd"),
    "xor-dpf-k3": (
        lambda: protocol.get("xor-dpf-k").query_gen(
            _rng(606), 200, PIRConfig(n_items=1 << 8, item_bytes=32,
                                      protocol="xor-dpf-k", n_servers=3)),
        "11b8ebb08a876164598116fc2a6a5ff772f0c969e9fb70838760fdc1517d138c"),
}


@pytest.mark.parametrize("case", sorted(GOLDEN_KEYS))
def test_gen_keys_match_golden_bytes(case):
    make, digest = GOLDEN_KEYS[case]
    assert _key_digest(make()) == digest
