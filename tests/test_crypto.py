"""Crypto layer: AES-128 reference vectors + ChaCha PRG properties.

The AES reference documents parity with the paper's PRF choice (IM-PIR
uses AES-128 via AES-NI); the DPF construction is PRF-agnostic and the
repo's production PRG is the ChaCha ARX permutation (DESIGN.md §2).
"""
import numpy as np
import pytest
from _prop import given, settings, st

import jax.numpy as jnp

from repro.crypto.aes_ref import aes_ggm_double, encrypt_block
from repro.crypto.chacha import (LANE_BITS, chacha_block, chacha_block_packed,
                                 ggm_double, prg_bits)


def test_aes128_fips197_vector():
    """FIPS-197 Appendix C.1."""
    key = np.frombuffer(bytes.fromhex("000102030405060708090a0b0c0d0e0f"),
                        np.uint8)
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)
    ct = encrypt_block(pt, key)
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"


def test_aes_ggm_double_deterministic_and_split():
    seed = np.arange(16, dtype=np.uint8)
    s_l, t_l, s_r, t_r = aes_ggm_double(seed)
    s_l2, t_l2, _, _ = aes_ggm_double(seed)
    np.testing.assert_array_equal(s_l, s_l2)
    assert t_l == t_l2
    assert not np.array_equal(s_l, s_r)     # children differ
    assert t_l in (0, 1) and t_r in (0, 1)


@pytest.mark.parametrize("counter", [0, 1, 2])
@pytest.mark.parametrize("rounds", [8, 12, 20])
def test_chacha_block_packed_matches_device_stream(rounds, counter):
    """The client's host ChaCha is the servers' stream, bit for bit, for
    one key alone and for several packed side by side into one pass."""
    keys = np.random.default_rng(rounds * 10 + counter).integers(
        0, 1 << 32, size=(5, 4), dtype=np.uint32)
    want = np.asarray(chacha_block(jnp.asarray(keys), counter=counter,
                                   rounds=rounds))
    shifts = [LANE_BITS * i for i in range(len(keys))]
    packed = [sum(int(k[w]) << s for s, k in zip(shifts, keys))
              for w in range(4)]
    out = chacha_block_packed(packed, len(keys), counter=counter,
                              rounds=rounds)
    got = np.array([[(x >> s) & 0xFFFFFFFF for x in out] for s in shifts],
                   np.uint32)
    np.testing.assert_array_equal(got, want)
    one = chacha_block_packed([int(w) for w in keys[0]], 1,
                              counter=counter, rounds=rounds)
    np.testing.assert_array_equal(np.array(one, np.uint32), want[0])


def test_chacha_block_shape_and_determinism():
    key = jnp.arange(4, dtype=jnp.uint32)
    out1 = np.asarray(chacha_block(key))
    out2 = np.asarray(chacha_block(key))
    np.testing.assert_array_equal(out1, out2)
    assert out1.shape == (16,)
    # different counter -> different stream (domain separation)
    out3 = np.asarray(chacha_block(key, counter=1))
    assert not np.array_equal(out1, out3)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
def test_ggm_double_children_distinct(a, b):
    seeds = jnp.asarray([[a, b, a ^ b, (a + b) & 0xFFFFFFFF]], jnp.uint32)
    s_l, t_l, s_r, t_r = ggm_double(seeds)
    assert not np.array_equal(np.asarray(s_l), np.asarray(s_r))
    assert set(np.asarray([t_l, t_r]).ravel()) <= {0, 1}


def test_prg_bits_lengths_and_domain_separation():
    seeds = jnp.asarray([[1, 2, 3, 4]], jnp.uint32)
    w20 = np.asarray(prg_bits(seeds, 20))
    w4 = np.asarray(prg_bits(seeds, 4))
    assert w20.shape == (1, 20)
    np.testing.assert_array_equal(w20[:, :4], w4)       # prefix-consistent
    blk0 = np.asarray(chacha_block(seeds, counter=0))
    assert not np.array_equal(w20[0, :16], blk0[0])      # ctr-separated


def test_chacha_bit_balance():
    """Output bits of the PRG are ~balanced (smoke-level PRF sanity)."""
    seeds = jnp.asarray(
        np.random.default_rng(0).integers(0, 1 << 32, size=(256, 4),
                                          dtype=np.uint32))
    blk = np.asarray(chacha_block(seeds))
    bits = np.unpackbits(blk.view(np.uint8))
    frac = bits.mean()
    assert 0.49 < frac < 0.51, frac
