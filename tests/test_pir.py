"""End-to-end PIR protocol tests: client + two servers, all server paths.

Covers the paper's Algorithm 1 on the reference (single-shard) forms and
the sharded server (shard_map over a local mesh) under the materialized
and fused plans and the additive GEMM, plus the cluster topology and the
aggregation collectives.
"""
import numpy as np
import pytest
from _prop import given, settings, st

import jax
import jax.numpy as jnp

from repro.config import PIRConfig
from repro.core import dpf, pir
from repro.core.protocol import ExecutionPlan
from repro.core.server import PIRServer, build_serve_fn
from repro.launch.mesh import make_local_mesh

RNG = np.random.default_rng(3)
LOG_N = 10
N = 1 << LOG_N
DB = pir.make_database(np.random.default_rng(0), N, 32)


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, N - 1))
def test_xor_roundtrip_reference(idx):
    cfg = PIRConfig(n_items=N)
    q = pir.query_gen(RNG, idx, cfg)
    r0 = pir.answer_xor(jnp.asarray(DB), q.keys[0])
    r1 = pir.answer_xor(jnp.asarray(DB), q.keys[1])
    rec = np.asarray(pir.reconstruct_xor(r0, r1))
    np.testing.assert_array_equal(rec, DB[idx])


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
def test_additive_roundtrip_reference():
    cfg = PIRConfig(n_items=N, mode="additive")
    dbb = pir.db_as_bytes(DB).astype(np.int8)
    for idx in (0, 17, N - 1):
        q = pir.query_gen(RNG, idx, cfg)
        rs = []
        for k in q.keys:
            shares = dpf.eval_bytes_batch(dpf.stack_keys([k]), 0, LOG_N)
            rs.append(pir.answer_additive_matmul(jnp.asarray(dbb), shares))
        rec = np.asarray(pir.reconstruct_additive(rs[0], rs[1]))[0]
        np.testing.assert_array_equal(rec, pir.db_as_bytes(DB)[idx])


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
@pytest.mark.parametrize("path", ["baseline", "fused", "matmul"])
def test_sharded_server_paths(mesh, path, plan_cache_off):
    from repro import engine
    mode = "additive" if path == "matmul" else "xor"
    cfg = PIRConfig(n_items=N, mode=mode, batch_queries=4)
    expand = "fused" if path == "fused" else "materialize"
    engine.record_plans(cfg, {4: ExecutionPlan(expand=expand)})
    servers = [PIRServer(party=b, db_words=DB, cfg=cfg, mesh=mesh,
                         n_queries=4) for b in (0, 1)]
    assert servers[0].bucketed.plan_for_bucket(4).expand == expand
    indices = [3, 99, 512, N - 1]
    k0, k1 = pir.batch_queries(RNG, indices, cfg)
    r0 = servers[0].answer(k0)
    r1 = servers[1].answer(k1)
    if path == "matmul":
        rec = np.asarray(pir.reconstruct_additive(r0, r1))
        expect = pir.db_as_bytes(DB)[indices]
    else:
        rec = np.asarray(pir.reconstruct_xor(r0, r1))
        expect = DB[indices]
    np.testing.assert_array_equal(rec, expect)


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
def test_collective_variants_agree(mesh):
    cfg = PIRConfig(n_items=N, batch_queries=2)
    idxs = [7, 700]
    k0, _ = pir.batch_queries(RNG, idxs, cfg)
    outs = []
    for coll in ("gather", "butterfly"):
        fns = build_serve_fn(cfg, mesh, n_queries=2,
                             plan=ExecutionPlan(collective=coll))
        db = jax.device_put(jnp.asarray(DB), fns.db_sharding)
        outs.append(np.asarray(fns.serve(db, k0)))
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
def test_fused_equals_baseline(mesh):
    cfg = PIRConfig(n_items=N, batch_queries=2)
    k0, _ = pir.batch_queries(RNG, [11, 222], cfg)
    res = {}
    for expand in ("materialize", "fused"):
        fns = build_serve_fn(cfg, mesh, n_queries=2,
                             plan=ExecutionPlan(expand=expand))
        db = jax.device_put(jnp.asarray(DB), fns.db_sharding)
        res[expand] = np.asarray(fns.serve(db, k0))
    np.testing.assert_array_equal(res["materialize"], res["fused"])


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
def test_two_server_deployment(mesh):
    from repro.runtime.serve_loop import TwoServerPIR
    cfg = PIRConfig(n_items=N, batch_queries=4)
    sys2 = TwoServerPIR(DB, cfg, mesh, n_queries=4)
    idx = [1, 2, 3, 1000]
    out = sys2.query(idx)
    np.testing.assert_array_equal(out, DB[idx])


@pytest.mark.slow   # jit-compiles serve/eval steps (~1 min each here)
def test_phase_split_matches_paper_structure():
    """Table 1 instrumentation path: eval-then-scan == fused answers."""
    cfg = PIRConfig(n_items=N, batch_queries=2)
    k0, k1 = pir.batch_queries(RNG, [5, 50], cfg)
    bits0 = pir.phase_eval_bits(k0, LOG_N)
    r0 = pir.phase_dpxor(jnp.asarray(DB), bits0)
    bits1 = pir.phase_eval_bits(k1, LOG_N)
    r1 = pir.phase_dpxor(jnp.asarray(DB), bits1)
    rec = np.asarray(pir.reconstruct_xor(r0, r1))
    np.testing.assert_array_equal(rec, DB[[5, 50]])
