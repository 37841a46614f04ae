"""GGM level-expansion Pallas kernel — DPF evaluation's inner loop on TPU.

Paper analogue
--------------
IM-PIR keeps DPF evaluation (the GGM tree, AES-128 via AES-NI) on the *host*
CPU because UPMEM DPUs have no crypto units (paper §3.2); after the PIM
offload this becomes the dominant cost (76.45% of query latency, Table 1).
The TPU adaptation replaces AES with a ChaCha-style ARX permutation whose
add/rotate/xor structure is exactly the VPU's 32-bit SIMD shape, so one
breadth-first tree level — ``[n,4]u32 seeds -> [2n,4]u32 + control bits`` —
is a single lane-parallel kernel invocation.

Layout
------
Seeds enter *word-transposed*: ``seeds_t[4, n]`` — the 4 seed words are
sublanes, the n tree nodes are lanes (n is the long axis). The ChaCha state
is then 16 row vectors of length TILE; every quarter-round op is a full-width
VPU op. Outputs: ``children_t[8, n]`` (rows 0:4 left child seed, 4:8 right)
and ``tbits[2, n]`` (left/right control bits), with the BGI correction words
already applied (masked by the parent t-bit).

Bit-exactness: this kernel must produce the same stream as
``repro.crypto.chacha.ggm_double`` (the jnp reference used by key
generation); tests/test_kernels.py asserts exact equality over shape sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.crypto.chacha import chacha_rows
from repro.engine.backend import resolve_interpret

U32 = jnp.uint32


def _ggm_expand_kernel(seeds_ref, t_ref, cw_seed_ref, cw_t_ref,
                       child_ref, tout_ref, *, rounds: int):
    """Expand one tile of GGM nodes: seeds [4,T] -> children [8,T], t [2,T]."""
    seed_rows = [seeds_ref[i, :] for i in range(4)]
    out = chacha_rows(seed_rows, counter=0, rounds=rounds)
    t = t_ref[0, :]
    mask = jnp.uint32(0) - t                       # 0x0 / 0xFFFFFFFF
    t_l = (out[8] & U32(1)) ^ (t & cw_t_ref[0, 0])
    t_r = (out[9] & U32(1)) ^ (t & cw_t_ref[1, 0])
    for i in range(4):
        cw = cw_seed_ref[i, 0]
        child_ref[i, :] = out[i] ^ (mask & cw)          # left child word i
        child_ref[4 + i, :] = out[4 + i] ^ (mask & cw)  # right child word i
    tout_ref[0, :] = t_l
    tout_ref[1, :] = t_r


def ggm_expand_level(
    seeds_t: jax.Array,
    t_bits: jax.Array,
    cw_seed: jax.Array,
    cw_t: jax.Array,
    *,
    rounds: int = 12,
    tile: int = 1024,
    interpret: bool | None = None,
):
    """One corrected GGM level for ``n`` nodes (lane-parallel).

    Args:
      seeds_t: ``[4, n] uint32`` word-transposed node seeds.
      t_bits:  ``[n] uint32`` node control bits.
      cw_seed: ``[4] uint32`` level seed correction word.
      cw_t:    ``[2] uint32`` level (tL, tR) control corrections.
      interpret: ``None`` resolves against the engine backend probe
        (``REPRO_FORCE_BACKEND``), outside the jit boundary.

    Returns ``(children_t [8, n], t_children [2, n])`` — lane j's children
    are column j of each half; the caller interleaves to leaf order.
    """
    return _ggm_expand_level_jit(seeds_t, t_bits, cw_seed, cw_t,
                                 rounds=rounds, tile=tile,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rounds", "tile", "interpret"))
def _ggm_expand_level_jit(
    seeds_t: jax.Array,
    t_bits: jax.Array,
    cw_seed: jax.Array,
    cw_t: jax.Array,
    *,
    rounds: int,
    tile: int,
    interpret: bool,
):
    n = seeds_t.shape[1]
    tile = min(tile, n)
    if n % tile:
        raise ValueError(f"n={n} not divisible by tile={tile}")
    grid = (n // tile,)
    kernel = functools.partial(_ggm_expand_kernel, rounds=rounds)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((4, 1), lambda i: (0, 0)),
            pl.BlockSpec((2, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, tile), lambda i: (0, i)),
            pl.BlockSpec((2, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, n), U32),
            jax.ShapeDtypeStruct((2, n), U32),
        ],
        interpret=interpret,
    )(
        seeds_t.astype(U32),
        t_bits.astype(U32)[None, :],
        cw_seed.astype(U32)[:, None],
        cw_t.astype(U32)[:, None],
    )
