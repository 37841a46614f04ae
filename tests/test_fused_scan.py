"""Fused GGM-expand + DB-scan megakernel (kernels/fused_scan.py).

Three concerns, in cost order:

* **Byte parity** against the materialized oracle (host GGM expansion +
  reference scan) — integer-exact, so every comparison is array_equal.
  The fast tier keeps the compile count minimal (each distinct static
  (tile_r, clog, depth) config is a fresh interpret-mode compile on this
  container); the full legalized grid, party-1 additive, and sharded
  start_block cases run in the slow tier.
* **VMEM footprint model** at the 16 MiB boundary — pure arithmetic on
  the engine descriptors, no compiles. The double-buffer factor must be
  the term that flips feasibility. (tests/test_tpu_compile.py checks the
  model against what the v5e compiler accepts.)
* **Backend resolution** (REPRO_FORCE_BACKEND) — the one probe governs
  interpret mode for every Pallas entry point, enforced both
  functionally and as a source convention.
"""
import os
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dpf, pir
import importlib

backend_mod = importlib.import_module("repro.engine.backend")
from repro.engine.kernels import ProblemShape, get_kernel
from repro.kernels import ops

RNG = np.random.default_rng(23)

LOG_N = 5
N = 1 << LOG_N
W = 2                    # item_bytes 8
L = 8

DB_WORDS = jnp.asarray(RNG.integers(0, 1 << 32, size=(N, W),
                                    dtype=np.uint32))
DB_BYTES = jnp.asarray(RNG.integers(-128, 128, size=(N, L)).astype(np.int8))
IDXS = [0, 13, 31]


def _xor_keys(party=0):
    return dpf.stack_keys([dpf.gen_keys(RNG, i, LOG_N)[party]
                           for i in IDXS])


def _add_key_pairs():
    """Both parties' batched additive keys, from the same key pairs."""
    pairs = [dpf.gen_keys(RNG, i, LOG_N, payload=np.array([1], np.uint32),
                          payload_mod=256) for i in IDXS]
    return tuple(dpf.stack_keys([p[b] for p in pairs]) for b in (0, 1))


def _add_keys(party=0):
    return _add_key_pairs()[party]


def _fused_xor(keys, db, tile_r, clog, depth, start_block=0,
               log_local=LOG_N):
    roots = dpf.eval_roots_batch(keys, start_block, log_local, clog)
    lvl0 = keys.log_n - clog
    return ops.fused_scan_xor(db, roots, keys.cw_seed[:, lvl0:, :],
                              keys.cw_t[:, lvl0:, :],
                              tile_r=tile_r, depth=depth)


def _fused_add(keys, db, tile_r, clog, depth):
    roots = dpf.eval_roots_batch(keys, 0, LOG_N, clog)
    lvl0 = keys.log_n - clog
    return ops.fused_scan_bytes(db, roots, keys.cw_seed[:, lvl0:, :],
                                keys.cw_t[:, lvl0:, :],
                                keys.cw_final[:, 0], party=int(keys.party),
                                tile_r=tile_r, depth=depth)


# ---------------------------------------------------------------------------
# Byte parity — fast tier (two xor compiles, one additive)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_r,clog,depth", [
    (8, 3, 2),       # multi-tile, double-buffered, mid-depth expand
    (32, 0, 1),      # degenerate: roots ARE the leaves (zero CW levels)
])
def test_fused_xor_parity(tile_r, clog, depth):
    keys = _xor_keys()
    bits = dpf.eval_bits_batch(keys, 0, LOG_N)
    want = jax.vmap(lambda b: pir.dpxor(DB_WORDS, b))(bits)
    got = _fused_xor(keys, DB_WORDS, tile_r, clog, depth)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_add_parity():
    keys = _add_keys()
    shares = dpf.eval_bytes_batch(keys, 0, LOG_N)
    want = pir.answer_additive_matmul(DB_BYTES, shares)
    got = _fused_add(keys, DB_BYTES, tile_r=8, clog=2, depth=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


#: 2^9 rows: enough chunk roots for several 128-lane key groups, and for
#: the chunk-root expansion's lane levels (C > 2^7)
LOG_BIG = 9
DB_WORDS_BIG = jnp.asarray(RNG.integers(0, 1 << 32, size=(1 << LOG_BIG, W),
                                        dtype=np.uint32))
DB_BYTES_BIG = jnp.asarray(RNG.integers(-128, 128, size=(1 << LOG_BIG, L))
                           .astype(np.int8))


@pytest.mark.parametrize("body,q,tile_r,clog,start_block", [
    ("xor", 1, 128, 0, 0),   # 128 roots per tile: one tile per key group
    ("xor", 3, 8, 3, 0),     # 1 root per tile, 64 tiles: a partial group
    ("xor", 3, 8, 3, 1),     # the same kernel on the upper half of 2^10
    ("add", 1, 2, 1, 0),     # 1 root per tile, 256 tiles: two full groups
    ("add", 3, 8, 1, 0),     # 4 roots per tile, 32 tiles per group
])
def test_fused_lane_dense_parity(body, q, tile_r, clog, start_block):
    """Chunk roots ``[Q, 5, C]`` in key groups of 128 lanes, each tile's
    roots rolled out of its group: answers equal the materialized oracle's
    across group boundaries, partial groups and a shard's start_block."""
    log_n = LOG_BIG + (start_block > 0)
    idxs = [int(i) for i in RNG.integers(0, 1 << log_n, size=q)]
    payload = dict(payload=np.array([1], np.uint32), payload_mod=256) \
        if body == "add" else {}
    party = q % 2                        # party 1 at Q = 1, party 0 at 3
    keys = dpf.stack_keys([dpf.gen_keys(RNG, i, log_n, **payload)[party]
                           for i in idxs])
    roots = dpf.eval_roots_batch(keys, start_block, LOG_BIG, clog)
    assert roots.shape == (q, 5, 1 << (LOG_BIG - clog))
    lv = slice(log_n - clog, None)
    if body == "xor":
        bits = dpf.eval_bits_batch(keys, start_block, LOG_BIG)
        want = jax.vmap(lambda b: pir.dpxor(DB_WORDS_BIG, b))(bits)
        got = ops.fused_scan_xor(DB_WORDS_BIG, roots, keys.cw_seed[:, lv],
                                 keys.cw_t[:, lv], tile_r=tile_r, depth=2)
    else:
        shares = dpf.eval_bytes_batch(keys, start_block, LOG_BIG)
        want = pir.answer_additive_matmul(DB_BYTES_BIG, shares)
        got = ops.fused_scan_bytes(DB_BYTES_BIG, roots, keys.cw_seed[:, lv],
                                   keys.cw_t[:, lv], keys.cw_final[:, 0],
                                   party=party, tile_r=tile_r, depth=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Byte parity — slow tier: full legalized grid, party 1, sharding
# ---------------------------------------------------------------------------

@pytest.mark.slow   # one interpret-mode compile per distinct config
def test_fused_xor_parity_full_grid():
    keys = _xor_keys(party=1)
    bits = dpf.eval_bits_batch(keys, 0, LOG_N)
    want = jax.vmap(lambda b: pir.dpxor(DB_WORDS, b))(bits)
    for tile_r in (8, 16, 32):
        for clog in range(tile_r.bit_length()):
            for depth in (1, 2, 4):
                d = max(1, min(depth, N // tile_r))
                got = _fused_xor(keys, DB_WORDS, tile_r, clog, d)
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want),
                    err_msg=f"tile={tile_r} clog={clog} depth={d}")


@pytest.mark.slow
def test_fused_add_party1_and_reconstruction():
    k0, k1 = _add_key_pairs()
    got0 = _fused_add(k0, DB_BYTES, tile_r=16, clog=3, depth=2)
    got1 = _fused_add(k1, DB_BYTES, tile_r=16, clog=3, depth=2)
    sh0 = dpf.eval_bytes_batch(k0, 0, LOG_N)
    sh1 = dpf.eval_bytes_batch(k1, 0, LOG_N)
    np.testing.assert_array_equal(
        np.asarray(got0), np.asarray(pir.answer_additive_matmul(DB_BYTES,
                                                                sh0)))
    np.testing.assert_array_equal(
        np.asarray(got1), np.asarray(pir.answer_additive_matmul(DB_BYTES,
                                                                sh1)))
    # the shares reconstruct the selected rows mod 256
    rec = (np.asarray(got0) + np.asarray(got1)) % 256
    rows = np.asarray(DB_BYTES).astype(np.uint8)[IDXS]
    np.testing.assert_array_equal(rec.astype(np.uint8), rows)


@pytest.mark.slow
def test_fused_xor_sharded_start_block():
    """Shard-local evaluation: start_block offsets the GGM descent."""
    keys = _xor_keys()
    log_local = LOG_N - 2
    rows_local = 1 << log_local
    for blk in range(4):
        shard = DB_WORDS[blk * rows_local:(blk + 1) * rows_local]
        bits = dpf.eval_bits_batch(keys, blk, log_local)
        want = jax.vmap(lambda b: pir.dpxor(shard, b))(bits)
        got = _fused_xor(keys, shard, tile_r=4, clog=2, depth=2,
                         start_block=blk, log_local=log_local)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"shard {blk}")


# ---------------------------------------------------------------------------
# VMEM footprint model at the 16 MiB edge (pure arithmetic, no compiles)
# ---------------------------------------------------------------------------

def test_xor_footprint_formula():
    desc = get_kernel("xor-fused-pallas")
    shape = ProblemShape(bucket=4, rows=1 << 20, item_bytes=32)
    p = {"tile_r": 1024, "chunk_log": 8, "depth": 2}
    want = 4 * (2 * 8 * 1024          # two u32 DB buffers [W, TR]
                + 4 * 1024 * 128      # expansion: 128 words/row/query
                + 4 * 8 * 256         # lane fold: 256 words/query/word
                + 2 * 5 * 8 * 128     # two key-group slots [5, Q->8, 128]
                + 8 * 8 * 128         # 8 CW levels [Q->8, 6->128]
                + 8 * 128)            # out [Q->8, W->128]
    assert desc.footprint_fn(shape, p) == want


def test_vmem_boundary_double_buffer_factor():
    """At the 16 MiB edge the rotating-buffer term must be what flips
    feasibility: same tile, deeper buffering -> infeasible."""
    from repro.analysis.roofline import VMEM_BYTES
    desc = get_kernel("xor-fused-pallas")
    shape = ProblemShape(bucket=1, rows=1 << 20, item_bytes=1536)
    shallow = {"tile_r": 2048, "chunk_log": 8, "depth": 2}
    deep = dict(shallow, depth=4)
    assert desc.footprint_fn(shape, shallow) <= VMEM_BYTES
    assert desc.footprint_fn(shape, deep) > VMEM_BYTES
    assert desc.feasible(shape, shallow)
    assert not desc.feasible(shape, deep)
    # the delta between the two is exactly the extra slots: a u32 DB
    # buffer [W, TR] each (the key groups keep two slots at any depth)
    extra = desc.footprint_fn(shape, deep) - desc.footprint_fn(shape,
                                                               shallow)
    assert extra == 4 * 2 * 384 * 2048


def test_add_footprint_counts_buffers():
    desc = get_kernel("gemm-fused-pallas")
    shape = ProblemShape(bucket=2, rows=1 << 16, item_bytes=64)
    f1 = desc.footprint_fn(shape, {"tile_r": 2048, "chunk_log": 8,
                                   "depth": 1})
    f3 = desc.footprint_fn(shape, {"tile_r": 2048, "chunk_log": 8,
                                   "depth": 3})
    # two extra int8 tiles [L, TR]; the key groups keep two slots
    assert f3 - f1 == 2 * 2048 * 64


def test_legalize_couples_chunk_to_tile():
    """chunk_log can never exceed log2(tile_r): a DMA tile holds whole
    chunks; depth never exceeds the tile count."""
    desc = get_kernel("xor-fused-pallas")
    shape = ProblemShape(bucket=2, rows=256, item_bytes=16)
    p = desc.legalize_fn(shape, {"tile_r": 64, "chunk_log": 12,
                                 "depth": 8})
    assert p["tile_r"] == 64
    assert p["chunk_log"] == 6
    assert p["depth"] == 4               # 256/64 tiles
    for params in desc.candidates(shape):
        assert (1 << params["chunk_log"]) <= params["tile_r"]
        assert 1 <= params["depth"] <= max(1, shape.rows
                                           // params["tile_r"])


# ---------------------------------------------------------------------------
# REPRO_FORCE_BACKEND governs interpret mode for every Pallas entry point
# ---------------------------------------------------------------------------

def test_force_backend_resolves_interpret(monkeypatch):
    monkeypatch.setenv(backend_mod.FORCE_BACKEND_ENV, "tpu")
    assert backend_mod.resolve_interpret(None) is False
    monkeypatch.setenv(backend_mod.FORCE_BACKEND_ENV, "cpu")
    assert backend_mod.resolve_interpret(None) is True
    # explicit requests always win over the probe
    assert backend_mod.resolve_interpret(False) is False
    monkeypatch.setenv(backend_mod.FORCE_BACKEND_ENV, "tpu")
    assert backend_mod.resolve_interpret(True) is True


def test_all_pallas_wrappers_resolve_interpret():
    """Source convention: every pallas_call site in kernels/ either
    resolves via resolve_interpret at the wrapper seam or receives the
    already-resolved static bool inside a jitted body. A raw
    ``interpret=None``/hardcoded flag reaching pallas_call would silently
    decouple that kernel from REPRO_FORCE_BACKEND."""
    kdir = pathlib.Path(ops.__file__).parent
    modules = ["dpxor.py", "ggm_expand.py", "pir_matmul.py",
               "fused_scan.py"]
    for name in modules:
        src = (kdir / name).read_text()
        assert "pl.pallas_call" in src, name
        assert "resolve_interpret(interpret)" in src, (
            f"{name}: wrapper must resolve interpret through the one "
            f"backend probe (engine/backend.py)")
