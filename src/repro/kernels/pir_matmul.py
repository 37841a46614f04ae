"""Batched-query PIR as an int8 GEMM — the MXU operational-intensity lever.

Beyond-paper rationale (DESIGN.md §2)
-------------------------------------
The paper's dpXOR reads the whole DB *per query*: operational intensity is a
fixed ~1 op/byte, pinned to the memory roofline (its Fig. 3b). With additive
Z_256 shares, a batch of Q queries against the same DB shard is one matrix
product ``shares[Q, R] × db[R, L]`` — the DB is read once per *batch*,
multiplying intensity by Q and moving the scan toward the compute roofline.
UPMEM DPUs have no matrix unit, so the paper cannot make this move; the TPU's
MXU executes int8×int8→int32 natively.

Correctness over Z_256: answers only matter mod 256 and 2^8 | 2^32, so int32
accumulation (and any wraparound) preserves the residue; the client reduces
mod 256 at reconstruction.

Kernel: classic three-loop blocked matmul. Grid = (Q tiles, L tiles, R
tiles); R is the innermost (sequential) accumulation dimension so each
``[TQ, TL]`` output block stays resident in VMEM while ``[TQ, TR]`` share
and ``[TL, TR]`` DB tiles stream through.

Layout: the kernel reads the DB *transposed*, ``[L, R]``, and contracts
the last dims of both operands. A byte view ``[R, L]`` with L < 128 is
resident on a TPU in the transposed physical layout (XLA makes R the
minor dim), so ``db.T`` is a free bitcast; a row-major ``[R, L]`` operand
would instead cost a lane-padded relayout copy of the whole DB per call
(4 GiB of temp for a 1 GiB, 32-byte-record DB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine.backend import resolve_interpret

I32 = jnp.int32


def _matmul_kernel(s_ref, d_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        s_ref[...],
        d_ref[...],
        (((1,), (1,)), ((), ())),          # [TQ, TR] x [TL, TR] -> [TQ, TL]
        preferred_element_type=I32,
    )


def pir_matmul(
    shares: jax.Array,
    db_bytes: jax.Array,
    *,
    tile_q: int = 8,
    tile_r: int = 1024,
    tile_l: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """``shares[Q, R] i8 × db[R, L] i8 -> [Q, L] i32`` partial PIR answers.

    Tile defaults target the MXU's 128-multiple alignment on the reduction
    and lane dims; Q (query batch) may be small, so it rides the sublane
    dim. ``interpret=None`` resolves against the engine backend probe
    (``REPRO_FORCE_BACKEND``), outside the jit boundary.
    """
    return _pir_matmul_jit(shares, db_bytes, tile_q=tile_q, tile_r=tile_r,
                           tile_l=tile_l,
                           interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_r", "tile_l", "interpret")
)
def _pir_matmul_jit(
    shares: jax.Array,
    db_bytes: jax.Array,
    *,
    tile_q: int,
    tile_r: int,
    tile_l: int,
    interpret: bool,
) -> jax.Array:
    q, r = shares.shape
    r2, l = db_bytes.shape
    if r != r2:
        raise ValueError(f"reduction mismatch {shares.shape} x {db_bytes.shape}")
    tile_q, tile_r, tile_l = min(tile_q, q), min(tile_r, r), min(tile_l, l)
    for name, dim, t in (("Q", q, tile_q), ("R", r, tile_r), ("L", l, tile_l)):
        if dim % t:
            raise ValueError(f"{name}={dim} not divisible by tile {t}")
    grid = (q // tile_q, l // tile_l, r // tile_r)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, tile_r), lambda i, j, k: (i, k)),
            pl.BlockSpec((tile_l, tile_r), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_l), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, l), I32),
        interpret=interpret,
    )(shares.astype(jnp.int8), db_bytes.astype(jnp.int8).T)


def lwe_matmul(
    ct: jax.Array,
    db_bytes32: jax.Array,
    *,
    tile_q: int = 8,
    tile_r: int = 1024,
    tile_l: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """``ct[Q, R] i32 × db[R, L] i32 -> [Q, L] i32`` LWE PIR answers.

    Same blocked three-loop program as :func:`pir_matmul` — identical grid
    and BlockSpecs, int32 operands instead of int8. Correctness over Z_q
    with q = 2^32: int32 accumulation wraps mod 2^32, so the GEMM computes
    the Z_q contraction exactly (DESIGN.md §10). Streams are 4× wider than
    the int8 path, which is why the engine registers a separate descriptor
    with its own VMEM footprint model. The TPU compiler refuses this body
    (the v5e MXU has no int32 matmul), so the engine never offers it on a
    TPU backend; there the LWE step contracts with XLA's int32 dot.
    """
    return _lwe_matmul_jit(ct, db_bytes32, tile_q=tile_q, tile_r=tile_r,
                           tile_l=tile_l,
                           interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_r", "tile_l", "interpret")
)
def _lwe_matmul_jit(
    ct: jax.Array,
    db_bytes32: jax.Array,
    *,
    tile_q: int,
    tile_r: int,
    tile_l: int,
    interpret: bool,
) -> jax.Array:
    q, r = ct.shape
    r2, l = db_bytes32.shape
    if r != r2:
        raise ValueError(f"reduction mismatch {ct.shape} x {db_bytes32.shape}")
    tile_q, tile_r, tile_l = min(tile_q, q), min(tile_r, r), min(tile_l, l)
    for name, dim, t in (("Q", q, tile_q), ("R", r, tile_r), ("L", l, tile_l)):
        if dim % t:
            raise ValueError(f"{name}={dim} not divisible by tile {t}")
    grid = (q // tile_q, l // tile_l, r // tile_r)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, tile_r), lambda i, j, k: (i, k)),
            pl.BlockSpec((tile_l, tile_r), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_l), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, l), I32),
        interpret=interpret,
    )(ct.astype(I32), db_bytes32.astype(I32).T)
