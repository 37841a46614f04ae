"""Fused GGM-expand + DB-scan Pallas megakernel with double-buffered DMA.

Paper analogue
--------------
IM-PIR's core win is doing the oblivious scan where the bytes live: each
UPMEM bank scans its MRAM-resident chunk in place instead of hauling the
database across the memory bus (paper §3.3). The TPU analogue is this
kernel: the DB shard stays in HBM and streams through VMEM tiles exactly
once per *batch*, while the DPF selection vector for that tile is expanded
on the fly from per-chunk GGM subtree roots — so the one-hot expansion
never exists in HBM at all (the earlier "fused" path kept bits out of HBM
but still round-tripped each chunk's fold through separate XLA ops).

Structure (DESIGN.md §13)
-------------------------
One ``pallas_call`` with no grid. The DB and the chunk-root key groups
live in ``pl.ANY`` memory space (HBM on TPU); ``[depth, ...]`` VMEM
scratch buffers hold the rotating DB tiles and two more the key groups,
each with its own DMA semaphores:

  prologue:  start the copies of DB tiles 0..depth-1 and key group 0
  tile i:    (first tile of a group: wait for its keys, start the next
             group's copy)  ->  wait DB slot (i % depth)  ->  expand the
             tile's GGM leaves from its chunk roots  ->  accumulate the
             select-reduction  ->  start the copy of DB tile i+depth
             into the freed slot

Only VMEM refs are ever loaded: the correction words of the last ``clog``
levels (a few hundred bytes) are VMEM-resident whole, and the answer is
written through a VMEM output block. The same ``fori_loop`` program runs
under interpret mode (bit-exact CPU validation — ``pltpu.emit_pipeline``
cannot, which is why the rotation is manual) and compiles to genuinely
overlapped DMA on real TPUs.

Inputs are *chunk roots*: the host precomputes each query's GGM descent
down to depth ``log_n - chunk_log`` (``dpf.eval_roots_batch`` — shared
across all chunks, unlike the chunked-jnp path which re-descends per
chunk) and ships ``[Q, 5, C]`` chunk roots (4 seed-word rows and the
control-bit row, chunks on lanes) plus the last ``chunk_log`` levels of
correction words. The roots stay lane-dense all the way: the jitted entry
points lay them out as ``[5, Q, C]`` (lanes padded to whole key groups),
and the kernel fetches one *key group* — the roots of ``128 / cpt``
consecutive tiles, 128 lanes, or one tile's ``cpt`` lanes when a tile holds
more chunks than that — per DMA, double-buffered beside the DB stream.
Tile i's ``cpt`` roots are rotated to lanes ``[0, cpt)`` of its group with
one dynamic lane roll. (A per-tile block ``[Q, 5 * cpt]`` would pad each
tile's few words to a 128-lane vreg in HBM: 25 MB per query at
2^25 rows, against 320 KiB dense.) The kernel breadth-expands those
``chunk_log`` levels in VMEM with the same ChaCha rounds as
``kernels/ggm_expand.py`` (bit-exactness with ``crypto.chacha`` is what
makes the byte-parity suite possible), interleaving children so leaf j of
the tile lands in lane j.

Two accumulation bodies share the expansion:

  xor       bits -> full-word masks -> AND with the [W, tile_r] DB tile
            -> lane-halving XOR fold (exactly ``dpxor``'s reduction), so
            the answer is bit-identical to the materialized path.
  additive  leaf seeds -> payload-conversion PRG (counter=1) -> Z_256
            shares as int8 (two's complement of the byte, the sign
            semantics of the materialized int8 GEMM) -> int8 x int8 ->
            int32 dot against the [L, tile_r] int8 DB tile (the byte view
            transposed, which is its resident HBM layout): bit-identical
            int32 to the materialized GEMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.crypto.chacha import chacha_rows
from repro.engine.backend import resolve_interpret
from repro.kernels.dpxor import _fold_xor_lanes

U32 = jnp.uint32

#: rows per chunk root in the key array: 4 seed words + control bit
KEY_WORDS = 5
#: lanes of one key group: DMA windows stay whole 128-lane vregs
KEY_LANES = 128
#: correction-word columns per level: 4 seed words + (tL, tR)
CW_WORDS = 6


def _interleave(left: jax.Array, right: jax.Array) -> jax.Array:
    """[Q, m] x2 -> [Q, 2m] with children interleaved to leaf order."""
    q, m = left.shape
    return jnp.stack([left, right], axis=-1).reshape(q, 2 * m)


def _expand_tile(seed_rows, t, cw_ref, *, clog: int, rounds: int):
    """Breadth-expand ``clog`` corrected GGM levels for one DB tile.

    seed_rows: list of 4 ``[Q, m]`` u32 chunk-root seed words; t: ``[Q, m]``
    control bits. cw_ref ``[clog, Q, 6]`` carries the per-query correction
    words (4 seed words, then tL, tR) for the *last* clog tree levels.
    Returns (leaf seed_rows [Q, m << clog] x4, leaf t [Q, m << clog]).
    """
    for lvl in range(clog):
        out = chacha_rows(seed_rows, counter=0, rounds=rounds)
        cw = cw_ref[lvl]                                     # [Q, 6]
        mask = U32(0) - t                                    # [Q, m]
        new_rows = []
        for w in range(4):
            cw_w = mask & cw[:, w:w + 1]
            new_rows.append(_interleave(out[w] ^ cw_w, out[4 + w] ^ cw_w))
        t_l = (out[8] & U32(1)) ^ (t & cw[:, 4:5])
        t_r = (out[9] & U32(1)) ^ (t & cw[:, 5:6])
        seed_rows = new_rows
        t = _interleave(t_l, t_r)
    return seed_rows, t


def _scan_tiles(db_hbm, keys_hbm, db_buf, key_buf, db_sem, key_sem,
                cw_ref, acc0, accumulate, *, tile_r: int, clog: int,
                depth: int, rounds: int, n_tiles: int):
    """The rotating-DMA tile loop both bodies share.

    ``db_hbm`` is the DB shard with rows on its last axis, ``keys_hbm``
    the ``[5, Q, C']`` chunk roots (:func:`_pack_keys`); ``accumulate(acc,
    seed_rows, t, db_tile_ref)`` folds one tile's expanded leaves into the
    accumulator.
    """
    cpt = tile_r >> clog
    lanes = _key_lanes(cpt)
    per_group = lanes // cpt                  # tiles per key group
    n_groups = -(-n_tiles // per_group)

    def db_copy(i, slot):
        return pltpu.make_async_copy(db_hbm.at[:, pl.ds(i * tile_r, tile_r)],
                                     db_buf.at[slot], db_sem.at[slot])

    def key_copy(g, slot):
        return pltpu.make_async_copy(
            keys_hbm.at[:, :, pl.ds(g * lanes, lanes)], key_buf.at[slot],
            key_sem.at[slot])

    key_copy(0, 0).start()
    for s in range(min(depth, n_tiles)):   # prologue: fill the pipeline
        db_copy(s, s).start()

    def body(i, acc):
        slot = jax.lax.rem(i, depth)
        g = i // per_group
        kslot = jax.lax.rem(g, 2)

        @pl.when(jax.lax.rem(i, per_group) == 0)
        def _():                           # a new key group: its roots
            key_copy(g, kslot).wait()      # in, the next group's copy out
            if n_groups > 1:               # (one group: a one-slot buffer)
                @pl.when(g + 1 < n_groups)
                def _():
                    key_copy(g + 1, 1 - kslot).start()

        db_copy(i, slot).wait()
        blk = key_buf[kslot]                               # [5, Q, lanes]
        if per_group > 1:                  # tile i's roots to lanes [0, cpt)
            off = jax.lax.rem(i, per_group) * cpt
            blk = pltpu.roll(blk, jax.lax.rem(lanes - off, lanes), 2)
            blk = blk[:, :, :cpt]
        seed_rows, t = _expand_tile([blk[w] for w in range(4)], blk[4],
                                    cw_ref, clog=clog, rounds=rounds)
        acc = accumulate(acc, seed_rows, t, db_buf.at[slot])

        @pl.when(i + depth < n_tiles)
        def _():                           # refill the slot just freed
            db_copy(i + depth, slot).start()
        return acc

    return jax.lax.fori_loop(0, n_tiles, body, acc0)


def _fused_xor_kernel(cw_ref, keys_hbm, db_hbm, out_ref, db_buf, key_buf,
                      db_sem, key_sem, *, tile_r: int, clog: int, depth: int,
                      rounds: int, n_tiles: int):
    """XOR body: db_t [W, R] (ANY) -> out [Q, W] (VMEM)."""
    q, w_words = out_ref.shape

    def accumulate(acc, seed_rows, t, db_tile):
        mask = U32(0) - t                                  # [Q, tile_r]
        masked = mask[:, None, :] & db_tile[...][None, :, :]  # [Q, W, TR]
        return acc ^ _fold_xor_lanes(masked)[..., 0]

    out_ref[...] = _scan_tiles(
        db_hbm, keys_hbm, db_buf, key_buf, db_sem, key_sem, cw_ref,
        jnp.zeros((q, w_words), U32), accumulate, tile_r=tile_r, clog=clog,
        depth=depth, rounds=rounds, n_tiles=n_tiles)


def _fused_add_kernel(cw_ref, cwf_ref, keys_hbm, db_hbm, out_ref, db_buf,
                      key_buf, db_sem, key_sem, *, tile_r: int, clog: int,
                      depth: int, rounds: int, n_tiles: int, party: int):
    """Additive body: db_t [L, R] i8 (ANY) -> out [Q, L] i32 (VMEM)."""
    q, n_bytes = out_ref.shape
    cwf = cwf_ref[...] & U32(0xFF)                         # [Q, 1]

    def accumulate(acc, seed_rows, t, db_tile):
        # payload conversion: word 0 of the counter=1 block (prg_bits)
        conv = chacha_rows(seed_rows, counter=1, rounds=rounds)[0]
        share = ((conv & U32(0xFF)) + t * cwf) & U32(0xFF)
        if party == 1:
            share = (U32(256) - share) & U32(0xFF)
        # the byte as int8 (share - 256 where share >= 128): the same
        # operand the materialized int8 GEMM contracts
        s32 = share.astype(jnp.int32)
        s8 = jnp.where(share >= U32(128), s32 - 256, s32).astype(jnp.int8)
        return acc + jax.lax.dot_general(
            s8, db_tile[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)              # [Q, L]

    out_ref[...] = _scan_tiles(
        db_hbm, keys_hbm, db_buf, key_buf, db_sem, key_sem, cw_ref,
        jnp.zeros((q, n_bytes), jnp.int32), accumulate, tile_r=tile_r,
        clog=clog, depth=depth, rounds=rounds, n_tiles=n_tiles)


def _check_args(r, c, clog, tile_r, depth):
    if tile_r <= 0 or tile_r & (tile_r - 1):
        raise ValueError(f"tile_r must be a power of two, got {tile_r}")
    if r % tile_r:
        raise ValueError(f"rows {r} not divisible by tile_r {tile_r}")
    if (1 << clog) > tile_r:
        raise ValueError(f"chunk 2^{clog} exceeds tile_r {tile_r}: "
                         "legalize chunk_log <= log2(tile_r) first")
    if c << clog != r:
        raise ValueError(f"{c} chunk roots x 2^{clog} leaves != rows {r}")
    if depth < 1:
        raise ValueError(f"buffer depth must be >= 1, got {depth}")


def _pack_keys(roots, cw_seed_lv, cw_t_lv, *, tile_r: int):
    """Lay the chunk-root inputs out for the kernel.

    roots ``[Q, 5, C]`` -> keys ``[5, Q, C']``: chunks stay on lanes,
    zero-padded to whole key groups (never read); the CW levels
    ``[Q, clog, 4]`` + ``[Q, clog, 2]`` -> ``[max(clog, 1), Q, 6]``. A
    zero-level expansion (roots already are the leaves) ships one
    never-read CW level, since zero-sized operands break interpret-mode
    block padding.
    """
    q, _, c = roots.shape
    clog = cw_seed_lv.shape[1]
    lanes = _key_lanes(tile_r >> clog)
    keys = jnp.pad(roots.astype(U32).transpose(1, 0, 2),
                   ((0, 0), (0, 0), (0, -c % lanes)))
    cws = jnp.concatenate([cw_seed_lv.astype(U32), cw_t_lv.astype(U32)],
                          axis=-1).transpose(1, 0, 2)      # [clog, Q, 6]
    if clog == 0:
        cws = jnp.zeros((1, q, CW_WORDS), U32)
    return keys, cws


def _key_lanes(cpt: int) -> int:
    """Lanes of one key group: 128 (the roots of 128 / cpt tiles), or one
    tile's cpt roots where that is more."""
    return max(KEY_LANES, cpt)


def _scratch(depth, n_tiles, db_tile, db_dtype, q, cpt):
    d = min(depth, n_tiles)
    lanes = _key_lanes(cpt)
    k = min(2, -(-n_tiles * cpt // lanes))    # key groups double-buffered
    return [pltpu.VMEM((d,) + db_tile, db_dtype),
            pltpu.VMEM((k, KEY_WORDS, q, lanes), U32),
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.DMA((k,))]


def fused_scan_xor_t(db_t: jax.Array, roots: jax.Array,
                     cw_seed_lv: jax.Array, cw_t_lv: jax.Array, *,
                     tile_r: int, depth: int, rounds: int = 12,
                     interpret: bool | None = None) -> jax.Array:
    """Fused expand+XOR-scan over a word-transposed DB shard.

    Args:
      db_t:       ``[W, R] uint32`` word-transposed DB shard.
      roots:      ``[Q, 5, C] uint32`` chunk roots: 4 seed-word rows, then
                  the control bits (``dpf.eval_roots_batch``).
      cw_seed_lv: ``[Q, clog, 4] uint32`` seed CWs for the last clog levels.
      cw_t_lv:    ``[Q, clog, 2] uint32`` (tL, tR) CWs for the same levels.
      tile_r:     DB rows per DMA tile (power of two dividing R).
      depth:      rotating DMA buffer count (2 = classic double buffer).
      interpret: ``None`` resolves against the engine backend probe
        (``REPRO_FORCE_BACKEND``), outside the jit boundary.

    Returns ``[Q, W] uint32`` per-query XOR answers, bit-identical to the
    materialized ``eval_bits`` + ``dpxor`` path.
    """
    return _fused_scan_xor_jit(db_t, roots, cw_seed_lv, cw_t_lv,
                               tile_r=tile_r, depth=depth, rounds=rounds,
                               interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile_r", "depth", "rounds",
                                             "interpret"))
def _fused_scan_xor_jit(db_t: jax.Array, roots: jax.Array,
                        cw_seed_lv: jax.Array, cw_t_lv: jax.Array, *,
                        tile_r: int, depth: int, rounds: int,
                        interpret: bool) -> jax.Array:
    w, r = db_t.shape
    q, _, c = roots.shape
    clog = cw_seed_lv.shape[1]
    _check_args(r, c, clog, tile_r, depth)
    n_tiles = r // tile_r
    keys, cws = _pack_keys(roots, cw_seed_lv, cw_t_lv, tile_r=tile_r)
    kernel = functools.partial(
        _fused_xor_kernel, tile_r=tile_r, clog=clog,
        depth=min(depth, n_tiles), rounds=rounds, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),   # cws (whole)
            pl.BlockSpec(memory_space=pl.ANY),    # keys (per key group)
            pl.BlockSpec(memory_space=pl.ANY),    # db_t (streamed)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((q, w), U32),
        scratch_shapes=_scratch(depth, n_tiles, (w, tile_r), U32, q,
                                tile_r >> clog),
        interpret=interpret,
    )(cws, keys, db_t.astype(U32))


def fused_scan_add(db_bytes_t: jax.Array, roots: jax.Array,
                   cw_seed_lv: jax.Array, cw_t_lv: jax.Array,
                   cw_final: jax.Array, *, party: int,
                   tile_r: int, depth: int, rounds: int = 12,
                   interpret: bool | None = None) -> jax.Array:
    """Fused expand+select-add over a byte-transposed int8 DB shard.

    ``db_bytes_t [L, R] int8``; ``cw_final [Q] uint32`` is the payload
    correction word; other args as :func:`fused_scan_xor_t`. Returns
    ``[Q, L] int32`` — bit-identical to ``eval_bytes_batch`` + the int8
    GEMM (``answer_additive_matmul``).
    """
    return _fused_scan_add_jit(db_bytes_t, roots, cw_seed_lv, cw_t_lv,
                               cw_final, party=party,
                               tile_r=tile_r, depth=depth, rounds=rounds,
                               interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile_r", "depth", "rounds",
                                             "party", "interpret"))
def _fused_scan_add_jit(db_bytes_t: jax.Array, roots: jax.Array,
                        cw_seed_lv: jax.Array, cw_t_lv: jax.Array,
                        cw_final: jax.Array, *, party: int, tile_r: int,
                        depth: int, rounds: int,
                        interpret: bool) -> jax.Array:
    l, r = db_bytes_t.shape
    q, _, c = roots.shape
    clog = cw_seed_lv.shape[1]
    _check_args(r, c, clog, tile_r, depth)
    n_tiles = r // tile_r
    keys, cws = _pack_keys(roots, cw_seed_lv, cw_t_lv, tile_r=tile_r)
    kernel = functools.partial(
        _fused_add_kernel, tile_r=tile_r, clog=clog,
        depth=min(depth, n_tiles), rounds=rounds, n_tiles=n_tiles,
        party=party)
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),   # cws (whole)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # cw_final [Q, 1]
            pl.BlockSpec(memory_space=pl.ANY),    # keys (per key group)
            pl.BlockSpec(memory_space=pl.ANY),    # db_bytes_t (streamed)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((q, l), jnp.int32),
        scratch_shapes=_scratch(depth, n_tiles, (l, tile_r), jnp.int8, q,
                                tile_r >> clog),
        interpret=interpret,
    )(cws, cw_final.astype(U32)[:, None], keys, db_bytes_t.astype(jnp.int8))
