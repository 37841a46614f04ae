"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the TPU compiler refuses here what interpret mode accepts —
loads from HBM refs, unaligned slices, more VMEM than a kernel may use,
matmul types the MXU lacks.

Shapes are one chip's share of the paper's 1 GB point (2^25 rows x 32 B,
bucket 4). Each test compiles with ``interpret=False`` and checks that the
Pallas kernel is in the program (``tpu_custom_call``). The engine's
megakernel answer steps, at buckets 1, 2 and 4, also check the step's HBM
beside its arguments against 1 % of the DB view. Two megakernel
cases sit on either side of the engine's VMEM footprint model's 16 MiB
bound and check the model against what the compiler accepts. One step
is compiled sharded over a described 1 x 4 mesh at 2^30 rows
(``pir-32g-4chip``). Nothing is executed, so nothing here says anything
about results or times.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from repro.config import PIRConfig
from repro.configs.pir import PIR_1G_LWE, PIR_32G_4CHIP
from repro.core import protocol as protocol_mod
from repro.core.server import BucketedServeFns
from repro.engine.backend import FORCE_BACKEND_ENV
from repro.engine.kernels import ProblemShape, get_kernel
from repro.engine.tuner import heuristic_plan
from repro.kernels import dpxor, fused_scan, ggm_expand, pir_matmul

ROWS = 1 << 25
WORDS = 8                      # 32-byte records
Q = 4
U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back without the chip:
    # keep it out of any persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _fused_xor(tile_r, clog, depth=2):
    return lambda db, r, cs, ct: fused_scan.fused_scan_xor_t(
        db, r, cs, ct, tile_r=tile_r, depth=depth, interpret=False)


def _fused_xor_shapes(rows, words, clog):
    c = rows >> clog
    return [((words, rows), U32), ((Q, 5, c), U32), ((Q, clog, 4), U32),
            ((Q, clog, 2), U32)]


def test_dpxor_compiles(one_chip):
    c = _compile(lambda db, b: dpxor.dpxor_t(db, b, tile_r=2048,
                                             interpret=False),
                 one_chip, ((WORDS, ROWS), U32), ((Q, ROWS), U32))
    assert _has_kernel(c)


def test_ggm_expand_level_compiles(one_chip):
    n = ROWS // 2                     # the widest level's parents
    c = _compile(lambda s, t, cs, ct: ggm_expand.ggm_expand_level(
        s, t, cs, ct, tile=2048, interpret=False),
        one_chip, ((4, n), U32), ((n,), U32), ((4,), U32), ((2,), U32))
    assert _has_kernel(c)


def test_pir_matmul_compiles_without_db_relayout(one_chip):
    c = _compile(lambda s, d: pir_matmul.pir_matmul(
        s, d, tile_q=Q, tile_r=1024, tile_l=32, interpret=False),
        one_chip, ((Q, ROWS), jnp.int8), ((ROWS, 32), jnp.int8))
    assert _has_kernel(c)
    # the [R, 32] byte view streams as its transposed resident layout:
    # no lane-padded copy of the 1 GiB DB
    assert c.memory_analysis().temp_size_in_bytes < ROWS


def test_fused_scan_xor_compiles(one_chip):
    clog = 11                         # the engine's plan: tile 2048
    c = _compile(_fused_xor(2048, clog), one_chip,
                 *_fused_xor_shapes(ROWS, WORDS, clog))
    assert _has_kernel(c)


def test_fused_scan_add_compiles(one_chip):
    clog, c_roots = 11, ROWS >> 11
    c = _compile(lambda db, r, cs, ct, cf: fused_scan.fused_scan_add(
        db, r, cs, ct, cf, party=1, tile_r=2048, depth=2,
        interpret=False),
        one_chip, ((32, ROWS), jnp.int8), ((Q, 5, c_roots), U32),
        ((Q, clog, 4), U32), ((Q, clog, 2), U32), ((Q,), U32))
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < ROWS


def test_engine_lwe_step_compiles(one_chip):
    """The LWE answer step the engine picks on a TPU: XLA's int32 dot
    (Mosaic has no int32 matmul on v5e, so no Pallas body is offered)."""
    cfg = PIR_1G_LWE
    plan = heuristic_plan(cfg, Q, backend="tpu")
    assert (plan.expand, plan.scan) == ("materialize", "jnp")
    proto = protocol_mod.get(cfg.protocol)
    keys = proto.key_specs(cfg, Q)
    keys = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        keys)
    db = jax.ShapeDtypeStruct((cfg.n_items, cfg.item_bytes), jnp.int32,
                              sharding=one_chip)
    c = jax.jit(lambda d, k: proto.answer_local(d, k, 0, cfg.log_n, plan)
                ).lower(db, keys).compile()
    assert not _has_kernel(c)


@pytest.mark.parametrize("bucket", [1, 2, 4])
@pytest.mark.parametrize("protocol", ["xor-dpf-2", "additive-dpf-2"])
def test_engine_megakernel_step_fits_hbm(one_chip, monkeypatch, protocol,
                                         bucket):
    """The answer step the engine picks on a TPU above one chunk, for both
    share algebras: the megakernel, whose lane-dense chunk roots keep the
    step's HBM beside its arguments under 1 % of the DB view."""
    monkeypatch.setenv(FORCE_BACKEND_ENV, "tpu")   # Mosaic, not interpret
    cfg = PIRConfig(n_items=ROWS, item_bytes=32, protocol=protocol)
    plan = heuristic_plan(cfg, bucket, backend="tpu")
    assert (plan.expand, plan.tile_r) == ("fused-pallas", 2048)
    proto = protocol_mod.get(cfg.protocol)
    keys = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        proto.key_specs(cfg, bucket))
    view = ((ROWS, WORDS), U32) if proto.db_view == "words" \
        else ((ROWS, 32), jnp.int8)
    db = jax.ShapeDtypeStruct(*view, sharding=one_chip)
    c = jax.jit(lambda d, k: proto.answer_local(d, k, 0, cfg.log_n, plan)
                ).lower(db, keys).compile()
    assert _has_kernel(c)
    m = c.memory_analysis()
    assert m.peak_memory_in_bytes - m.argument_size_in_bytes < ROWS * 32 // 100


@pytest.mark.parametrize("item_bytes,fits", [(2048, True), (2560, False)])
def test_fused_xor_vmem_model_matches_compiler(one_chip, item_bytes, fits):
    """One case on each side of the footprint model's VMEM bound: the
    model's verdict is the compiler's."""
    from repro.analysis.roofline import VMEM_BYTES
    rows, clog, tile = 1 << 16, 11, 2048
    params = {"tile_r": tile, "chunk_log": clog, "depth": 2}
    shape = ProblemShape(bucket=Q, rows=rows, item_bytes=item_bytes)
    desc = get_kernel("xor-fused-pallas")
    assert desc.feasible(shape, params) is fits
    # near the bound: within 2.5 MiB of it on either side
    assert abs(desc.footprint_fn(shape, params) - VMEM_BYTES) < (5 << 19)
    shapes = _fused_xor_shapes(rows, item_bytes // 4, clog)
    if fits:
        assert _has_kernel(_compile(_fused_xor(tile, clog), one_chip,
                                    *shapes))
    else:
        with pytest.raises(Exception, match="vmem"):
            _compile(_fused_xor(tile, clog), one_chip, *shapes)


def test_sharded_step_over_four_chips_keeps_one_copy_of_its_shard(
        topo, monkeypatch):
    """``pir-32g-4chip``'s bucket-1 serve step, the database row-sharded
    over a described 1 x 4 v5e mesh (2^28 rows, 8 GiB per chip): the
    heuristic megakernel at a shard offset and the XOR reduce across the
    chips. The kernel reads the shard's resident layout transposed as a
    bitcast, so the step holds no second copy of the shard: its HBM
    beside its arguments stays under 1 % of the shard."""
    cfg = PIR_32G_4CHIP
    monkeypatch.setenv(FORCE_BACKEND_ENV, "tpu")   # Mosaic, not interpret
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"))
    serve = BucketedServeFns(cfg, mesh, buckets=(1,))
    fns, jitted = serve.fns_for(1)
    assert (fns.plan.expand, fns.plan.collective) == ("fused-pallas",
                                                      "gather")
    keys = serve.protocol.key_specs(cfg, 1)
    keys = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        keys, fns.key_shardings(keys))
    db = jax.ShapeDtypeStruct((cfg.n_items, WORDS), U32,
                              sharding=fns.db_sharding)
    c = jitted.lower(db, keys).compile()
    text = c.as_text()
    assert _has_kernel(c) and "all-gather" in text
    shard = cfg.n_items // 4 * cfg.item_bytes
    m = c.memory_analysis()
    assert m.argument_size_in_bytes >= shard
    assert m.peak_memory_in_bytes - m.argument_size_in_bytes < shard // 100
