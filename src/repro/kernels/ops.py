"""Public jit'd entry points for the Pallas kernels.

These wrappers own layout conversion (row-major DB <-> the kernels' word-
transposed form), tile selection, and the interpret-mode switch: on the CPU
container every kernel body executes in Pallas interpret mode (bit-exact
Python evaluation); on a real TPU backend ``interpret=False`` compiles the
same BlockSpec program to Mosaic.

The PIR server (core/server.py) calls these when ``use_kernels=True``; the
pure-jnp forms in kernels/ref.py remain the oracles and the GSPMD dry-run
path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.engine.backend import (default_interpret, legal_tile, on_tpu,
                                  resolve_interpret)
from repro.kernels.dpxor import dpxor_t
from repro.kernels.fused_scan import fused_scan_add, fused_scan_xor_t
from repro.kernels.ggm_expand import ggm_expand_level
from repro.kernels.pir_matmul import lwe_matmul, pir_matmul

U32 = jnp.uint32


def _on_tpu() -> bool:
    """Compat alias — backend probing now lives in ``engine/backend.py``
    (one probe for plan selection AND interpret defaults, overridable via
    ``REPRO_FORCE_BACKEND``)."""
    return on_tpu()


# ``default_interpret``/``resolve_interpret`` are re-exported from
# engine.backend unchanged: real Mosaic only on an (effective) TPU backend.
# Since the fused-scan PR every kernel module's own entry point resolves
# ``interpret=None`` through the same probe (outside its jit boundary), so
# these wrappers just pass the request through.
__all__ = ["default_interpret", "resolve_interpret", "dpxor",
           "dpxor_transposed", "fused_scan_xor", "fused_scan_bytes", "fused_tile",
           "ggm_expand", "ggm_eval_leaves", "lwe_gemm", "pir_gemm"]


# ---------------------------------------------------------------------------
# dpXOR
# ---------------------------------------------------------------------------

def dpxor(db_words: jax.Array, bits: jax.Array, *, tile_r: int = 2048,
          interpret: bool | None = None) -> jax.Array:
    """Select-XOR scan, row-major DB: [R, W] u32 × [Q, R] bits -> [Q, W].

    Transposes to the kernel's word-major layout; production servers keep
    the DB pre-transposed and call :func:`dpxor_transposed` to avoid paying
    the transpose per query batch.

    ``tile_r`` is a *request*: the engine legalizes it to the largest
    power-of-two divisor of the row count (``engine.legal_tile``) — the
    old ``min(tile_r, R)`` clamp produced illegal tiles on
    non-power-of-two row counts.
    """
    return dpxor_t(db_words.T, bits,
                   tile_r=legal_tile(db_words.shape[0], tile_r, pow2=True),
                   interpret=interpret)


def dpxor_transposed(db_t: jax.Array, bits: jax.Array, *, tile_r: int = 2048,
                     interpret: bool | None = None) -> jax.Array:
    """Select-XOR scan on a pre-transposed [W, R] DB shard."""
    return dpxor_t(db_t, bits,
                   tile_r=legal_tile(db_t.shape[1], tile_r, pow2=True),
                   interpret=interpret)


# ---------------------------------------------------------------------------
# Fused GGM-expand + scan megakernel (kernels/fused_scan.py)
# ---------------------------------------------------------------------------

def fused_tile(rows: int, tile_r: int, clog: int) -> tuple[int, int]:
    """Legalize the megakernel's (tile_r, chunk_log) request for a shard.

    tile_r legalizes to the largest power-of-two divisor of the row count;
    chunk_log clamps so one DB tile always holds whole chunks (the kernel
    expands each tile's leaves from its own chunk roots — a chunk spanning
    tiles would need cross-tile expansion state).
    """
    tile = legal_tile(rows, tile_r, pow2=True)
    return tile, min(clog, tile.bit_length() - 1)


def fused_scan_xor(db_words: jax.Array, roots: jax.Array,
                   cw_seed_lv: jax.Array, cw_t_lv: jax.Array, *,
                   tile_r: int = 2048, depth: int = 2, rounds: int = 12,
                   interpret: bool | None = None) -> jax.Array:
    """Fused expand+XOR megakernel, row-major DB entry point.

    Args:
      db_words:   ``[R, W] uint32`` row-major DB shard.
      roots:      ``[Q, 5, C] uint32`` chunk roots, chunks on lanes: 4
                  seed-word rows, then the control bits
                  (``dpf.eval_roots_batch`` with ``stop_log = log2(R/C)``).
      cw_seed_lv: ``[Q, clog, 4] uint32`` — the *last* clog levels of each
                  key's ``cw_seed`` (``key.cw_seed[:, log_n-clog:, :]``).
      cw_t_lv:    ``[Q, clog, 2] uint32`` — same slice of ``cw_t``.
      tile_r:     requested DMA tile (legalized; must hold whole chunks —
                  callers legalize chunk_log via the same rule, see
                  ``core/protocol.py _fused_pallas_inputs``).
      depth:      rotating DMA buffer count.
    """
    tile, _ = fused_tile(db_words.shape[0], tile_r, cw_seed_lv.shape[1])
    return fused_scan_xor_t(db_words.T, roots, cw_seed_lv, cw_t_lv,
                            tile_r=tile, depth=depth, rounds=rounds,
                            interpret=interpret)


def fused_scan_bytes(db_bytes: jax.Array, roots: jax.Array,
                     cw_seed_lv: jax.Array, cw_t_lv: jax.Array,
                     cw_final: jax.Array, *, party: int,
                     tile_r: int = 2048, depth: int = 2, rounds: int = 12,
                     interpret: bool | None = None) -> jax.Array:
    """Fused expand+select-add megakernel over the int8 byte view.

    Same chunk-root inputs as :func:`fused_scan_xor` plus ``cw_final [Q]``
    (payload correction word) and the static ``party``; returns
    ``[Q, L] int32`` bit-identical to the materialized int8 GEMM. The
    kernel streams the view transposed (``[L, R]``, a free bitcast of its
    resident layout, see ``kernels/pir_matmul.py``).
    """
    tile, _ = fused_tile(db_bytes.shape[0], tile_r, cw_seed_lv.shape[1])
    return fused_scan_add(db_bytes.T, roots, cw_seed_lv, cw_t_lv, cw_final,
                          party=party, tile_r=tile, depth=depth,
                          rounds=rounds, interpret=interpret)


# ---------------------------------------------------------------------------
# GGM expansion
# ---------------------------------------------------------------------------

def ggm_expand(seeds: jax.Array, t_bits: jax.Array, cw_seed: jax.Array,
               cw_t: jax.Array, *, rounds: int = 12, tile: int = 65536,
               interpret: bool | None = None):
    """One corrected GGM level, leaf-major: [n,4] -> ([2n,4], [2n]).

    Wraps the lane-parallel kernel with the transpose + child interleave so
    callers see the same contract as ``core.dpf._expand_level``.

    Note on ``tile``: on the CPU container, XLA compile time of the
    interpret-mode emulation grows superlinearly in (chacha rounds × grid
    steps), so the default tile keeps grid=1 for any realistic test size.
    On TPU (interpret=False) the intended production tile is 512–2048 lanes
    (VMEM: 16 state rows × tile × 4 B ≲ 128 KB per step).
    """
    n = seeds.shape[0]
    children_t, t2 = ggm_expand_level(
        seeds.T, t_bits, cw_seed, cw_t,
        rounds=rounds, tile=legal_tile(n, tile), interpret=interpret,
    )
    # children_t: [8, n] (rows 0:4 = left seed words, 4:8 = right).
    left = children_t[0:4, :].T                   # [n, 4]
    right = children_t[4:8, :].T                  # [n, 4]
    children = jnp.stack([left, right], axis=1).reshape(2 * n, 4)
    t_out = jnp.stack([t2[0, :], t2[1, :]], axis=1).reshape(2 * n)
    return children, t_out


def ggm_eval_leaves(key_root: jax.Array, key_t0: jax.Array,
                    cw_seed: jax.Array, cw_t: jax.Array, log_n: int,
                    *, rounds: int = 12, interpret: bool | None = None):
    """Full-domain GGM leaf expansion driven by the Pallas level kernel.

    key_root [4], key_t0 scalar, cw_seed [log_n, 4], cw_t [log_n, 2]
    -> (seeds [2^log_n, 4], t_bits [2^log_n]).
    """
    seeds = key_root[None, :]
    t = jnp.asarray(key_t0, U32)[None]
    for level in range(log_n):
        seeds, t = ggm_expand(seeds, t, cw_seed[level], cw_t[level],
                              rounds=rounds, interpret=interpret)
    return seeds, t


# ---------------------------------------------------------------------------
# PIR matmul
# ---------------------------------------------------------------------------

def pir_gemm(shares: jax.Array, db_bytes: jax.Array, *, tile_q: int = 8,
             tile_r: int = 1024, tile_l: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """Batched additive-PIR contraction: [Q, R] i8 × [R, L] i8 -> [Q, L] i32.

    Requested tiles legalize to the largest divisor of their dimension
    (``engine.legal_tile``), so non-power-of-two shapes pick a working
    tiling instead of tripping ``pir_matmul``'s divisibility check.
    """
    q, r = shares.shape
    l = db_bytes.shape[1]
    return pir_matmul(
        shares, db_bytes,
        tile_q=legal_tile(q, tile_q), tile_r=legal_tile(r, tile_r),
        tile_l=legal_tile(l, tile_l),
        interpret=interpret,
    )


def lwe_gemm(ct: jax.Array, db_bytes32: jax.Array, *, tile_q: int = 8,
             tile_r: int = 1024, tile_l: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """Single-server LWE contraction: [Q, R] i32 × [R, L] i32 -> [Q, L] i32.

    int32 twin of :func:`pir_gemm` (same blocked program, 4-byte streams);
    the accumulate wraps mod 2^32 = mod q, so this is the exact Z_q GEMM
    of the lwe-simple-1 answer step.
    """
    q, r = ct.shape
    l = db_bytes32.shape[1]
    return lwe_matmul(
        ct, db_bytes32,
        tile_q=legal_tile(q, tile_q), tile_r=legal_tile(r, tile_r),
        tile_l=legal_tile(l, tile_l),
        interpret=interpret,
    )
