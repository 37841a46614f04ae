"""Engine-plane tests: backend probe, legal tiling, search-space parity,
plan cache robustness, and the heuristic-fallback equivalence gate.

Fast tier: everything here runs eager or through small interpret-mode
kernel jits (log N <= 6 DBs, tiny tune budgets) — no serve-step compiles.

The two load-bearing guarantees (ISSUE 5 acceptance):
  * every candidate plan in the search space produces byte-identical
    answers (the tuner can never trade correctness for speed);
  * an empty/corrupted/stale plan cache resolves to exactly the
    heuristic's choices (asserted against an inline replica of its
    rules), so default behavior never depends on a cache file.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import engine
from repro.config import PIRConfig
from repro.core import pir
from repro.core import protocol as protocol_mod
from repro.core.protocol import ExecutionPlan
from repro.engine.backend import FORCE_BACKEND_ENV, legal_tile
from repro.engine.cache import PlanCache, spec_signature
from repro.engine.kernels import ProblemShape
from repro.engine.tuner import TuneBudget, heuristic_plan, plan_label
from repro.kernels import ops, ref

RNG = np.random.default_rng(23)
LOG_N = 6
N = 1 << LOG_N


# ---------------------------------------------------------------------------
# backend probe + legal tiles
# ---------------------------------------------------------------------------

def test_backend_probe_and_force_override(monkeypatch):
    monkeypatch.delenv(FORCE_BACKEND_ENV, raising=False)
    assert engine.probe_backend() == jax.default_backend()
    # kernels/ops.py interpret default and plan selection read ONE probe
    assert ops.default_interpret() == (engine.probe_backend() != "tpu")
    monkeypatch.setenv(FORCE_BACKEND_ENV, "tpu")
    assert engine.probe_backend() == "tpu"
    assert ops.default_interpret() is False
    # plan selection is pinned too: CI can force the TPU plan rules on CPU
    plan = heuristic_plan(PIRConfig(n_items=N), 4)
    assert plan.scan == "pallas"
    monkeypatch.setenv(FORCE_BACKEND_ENV, "cpu")
    assert heuristic_plan(PIRConfig(n_items=N), 4).scan == "jnp"


def test_backend_submodule_not_shadowed_by_reexport():
    # regression (PR 9 note): a package global named ``backend`` used to
    # shadow the submodule attribute on ``repro.engine`` (module globals
    # ARE package attrs), so ``import repro.engine.backend as m`` bound
    # the re-exported *function* instead of the module. The probe is now
    # re-exported as ``probe_backend`` and the submodule must win.
    import importlib
    import types

    import repro.engine.backend as m
    assert isinstance(m, types.ModuleType)
    assert m is importlib.import_module("repro.engine.backend")
    assert getattr(engine, "backend") is m
    # the renamed re-export is the same callable as the module's probe
    assert engine.probe_backend is m.backend
    assert engine.probe_backend() == m.backend()
    assert "backend" not in engine.__all__
    assert "probe_backend" in engine.__all__


def test_legal_tile_rules():
    # divides evenly: the request is kept
    assert legal_tile(4096, 2048, pow2=True) == 2048
    assert legal_tile(64, 2048, pow2=True) == 64
    # non-power-of-two dims: largest pow2 divisor <= request
    assert legal_tile(96, 2048, pow2=True) == 32
    assert legal_tile(96, 16, pow2=True) == 16
    # non-pow2 mode: largest divisor <= request
    assert legal_tile(1536, 1024) == 768
    assert legal_tile(192, 128) == 96
    assert legal_tile(7, 4) == 1          # prime rows: only 1 divides
    with pytest.raises(ValueError):
        legal_tile(0, 8)
    with pytest.raises(ValueError):
        legal_tile(8, 0)


def test_ops_non_pow2_shard_shapes_regression():
    """min(tile, R) used to emit illegal tiles on non-pow2 row counts —
    the engine's legal-tile computation must pick a working tiling."""
    db = jnp.asarray(RNG.integers(0, 1 << 32, size=(96, 8),
                                  dtype=np.uint32))
    bits = jnp.asarray(RNG.integers(0, 2, size=(2, 96), dtype=np.uint32))
    got = ops.dpxor(db, bits)             # default request 2048 -> tile 32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.dpxor_ref(db, bits)))

    s = jnp.asarray(RNG.integers(-128, 128, size=(2, 192), dtype=np.int8))
    d = jnp.asarray(RNG.integers(-128, 128, size=(192, 32), dtype=np.int8))
    got = ops.pir_gemm(s, d, tile_r=128)  # 128 does not divide 192 -> 96
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.pir_matmul_ref(s, d)))


# ---------------------------------------------------------------------------
# heuristic fallback == an inline replica of its rules
# ---------------------------------------------------------------------------

#: the megakernel's tile per count of queries it scans, at 32 B records:
#: the largest tile <= 2048 whose modeled VMEM fits 16 MiB
#: (engine/kernels.py); the XOR and additive footprints agree at 32 B
_FUSED_TILE = {1: 2048, 3: 2048, 4: 2048, 12: 2048, 32: 512, 96: 256}


def _pre_engine_plan_for(cfg, n_queries, backend, chunk_log=12):
    """Inline replica of the heuristic's rules (independent of the engine
    code): materialize only while the DB fits one chunk; past that, on a
    TPU, XOR and additive take the megakernel (xor-dpf-k scans one
    pseudo-query per component of party 0's keys, 3), elsewhere XOR takes
    fused and additive the GEMM; LWE always the XLA int32 dot."""
    scan = "pallas" if backend == "tpu" else "jnp"
    proto = protocol_mod.get(cfg.protocol)
    small_db = cfg.n_items <= (1 << chunk_log)
    if proto.share_kind == "lwe":
        return ExecutionPlan(expand="materialize", scan="jnp",
                             chunk_log=chunk_log, tile_r=1024)
    if backend == "tpu" and not small_db:
        components = 3 if cfg.protocol == "xor-dpf-k" else 1
        tile = _FUSED_TILE[n_queries * components]
        return ExecutionPlan(expand="fused-pallas", scan="pallas",
                             chunk_log=min(chunk_log, tile.bit_length() - 1),
                             tile_r=tile, depth=2)
    if proto.share_kind == "additive":
        # tiles were then hardcoded in kernels/ops.py: gemm tile_r=1024
        return ExecutionPlan(expand="materialize", scan=scan,
                             chunk_log=chunk_log, tile_r=1024)
    if small_db:
        return ExecutionPlan(expand="materialize", scan=scan,
                             chunk_log=chunk_log)
    # the fused XOR body's fold is always the jnp dpxor
    return ExecutionPlan(expand="fused", scan="jnp", chunk_log=chunk_log)


@pytest.mark.parametrize("protocol", ["xor-dpf-2", "additive-dpf-2",
                                      "xor-dpf-k", "lwe-simple-1"])
def test_heuristic_reproduces_pre_engine_plan_for(protocol):
    n_servers = {"xor-dpf-k": 3, "lwe-simple-1": 1}.get(protocol, 2)
    for n_items in (1 << 10, 1 << 14, 1 << 20):
        cfg = PIRConfig(n_items=n_items, protocol=protocol,
                        n_servers=n_servers)
        for n_q in (1, 4, 32):
            for be in ("cpu", "tpu"):
                want = _pre_engine_plan_for(cfg, n_q, be)
                assert heuristic_plan(cfg, n_q, backend=be) == want
                # a cache miss must resolve identically (the fallback)
                got = engine.resolve(cfg, n_q, backend_name=be)
                if engine.plan_cache().get(be, cfg.protocol,
                                           spec_signature(cfg), n_q) is None:
                    assert got == want
                    assert got.provenance == "heuristic"


# ---------------------------------------------------------------------------
# search space: feasibility pruning + answer parity across ALL candidates
# ---------------------------------------------------------------------------

def test_candidate_space_prunes_infeasible_tiles():
    shape_ok = ProblemShape(bucket=32, rows=1 << 20, item_bytes=32)
    desc = engine.get_kernel("xor-materialize-pallas")
    tiles_ok = {p["tile_r"] for p in desc.candidates(shape_ok)}
    assert 4096 in tiles_ok               # 32q x 8w x 4096 x 4B = 4 MB: fits
    shape_big = ProblemShape(bucket=256, rows=1 << 20, item_bytes=32)
    tiles_big = {p["tile_r"] for p in desc.candidates(shape_big)}
    assert 4096 not in tiles_big          # 256q: 32 MB intermediate: pruned
    assert 512 in tiles_big               # but the space never goes empty
    # pruning happens before measurement: candidates() is pure arithmetic
    assert all(desc.feasible(shape_big, {"tile_r": t}) for t in tiles_big)


def test_fused_chunk_space_clips_to_shard():
    cands = engine.get_kernel("xor-fused").candidates(
        ProblemShape(bucket=4, rows=N, item_bytes=32))
    logs = {p["chunk_log"] for p in cands}
    assert logs == {LOG_N}                # chunks > shard are degenerate


def test_candidate_plans_cover_registered_kernels():
    """Every registered serve kernel of a share algebra contributes at
    least one candidate, and tile fields arrive legalized (fast-tier
    structural complement of the slow parity sweep below)."""
    cfg = PIRConfig(n_items=N)
    names = {(p.expand, p.scan) for p in engine.candidate_plans(cfg, 2)}
    assert names == {("materialize", "jnp"), ("materialize", "pallas"),
                     ("fused", "jnp"), ("fused-pallas", "pallas")}
    for p in engine.candidate_plans(cfg, 2):
        if p.scan == "pallas":
            assert N % p.tile_r == 0 and p.tile_r & (p.tile_r - 1) == 0
        if p.expand == "fused-pallas":
            # megakernel coupling: one DMA tile holds whole chunks, and
            # the rotation never exceeds the tile count
            assert (1 << p.chunk_log) <= p.tile_r
            assert 1 <= p.depth <= max(1, N // p.tile_r)
    cfga = PIRConfig(n_items=N, protocol="additive-dpf-2")
    names_a = {(p.expand, p.scan) for p in engine.candidate_plans(cfga, 2)}
    assert names_a == {("materialize", "jnp"), ("materialize", "pallas"),
                       ("fused-pallas", "pallas")}
    for p in engine.candidate_plans(cfga, 2):
        if p.scan == "pallas" and p.expand == "materialize":
            assert N % p.tile_r == 0 and 2 % p.tile_q == 0 \
                and 32 % p.tile_l == 0


def test_lwe_gemm_candidates_cover_and_legalize():
    """The LWE GEMM rides the engine like the additive GEMM: jnp + pallas
    descriptors contribute candidates with legalized tiles."""
    cfg = PIRConfig(n_items=N, protocol="lwe-simple-1", n_servers=1)
    plans = engine.candidate_plans(cfg, 2)
    names = {(p.expand, p.scan) for p in plans}
    assert names == {("materialize", "jnp"), ("materialize", "pallas")}
    for p in plans:
        if p.scan == "pallas":
            assert N % p.tile_r == 0 and 2 % p.tile_q == 0 \
                and 32 % p.tile_l == 0


def test_lwe_pallas_gemm_not_offered_on_tpu():
    """The v5e MXU has no int32 matmul, so Mosaic refuses the Pallas LWE
    body: on a TPU backend neither the heuristic nor the tuner's search
    space may offer it (the XLA int32 dot compiles)."""
    cfg = PIRConfig(n_items=N, protocol="lwe-simple-1", n_servers=1)
    assert not engine.get_kernel("lwe-gemm-pallas").mosaic
    plans = engine.candidate_plans(cfg, 2, backend="tpu")
    assert {(p.expand, p.scan) for p in plans} == {("materialize", "jnp")}
    assert heuristic_plan(cfg, 4, backend="tpu").scan == "jnp"
    # every other share algebra keeps its Pallas candidates on a TPU
    for proto in ("xor-dpf-2", "additive-dpf-2"):
        got = engine.candidate_plans(PIRConfig(n_items=N, protocol=proto),
                                     2, backend="tpu")
        assert any(p.scan == "pallas" for p in got)


def test_additive_megakernel_tile_fits_vmem_per_bucket():
    """The heuristic's megakernel plan shrinks its tile until the VMEM
    model fits, so big buckets never resolve to a plan Mosaic refuses."""
    desc = engine.get_kernel("gemm-fused-pallas")
    cfg = PIRConfig(n_items=1 << 25, protocol="additive-dpf-2")
    for bucket, tile in ((1, 2048), (8, 2048), (16, 1024), (32, 512)):
        plan = heuristic_plan(cfg, bucket, backend="tpu")
        assert (plan.expand, plan.tile_r) == ("fused-pallas", tile)
        shape = engine.problem_shape(cfg, bucket)
        assert desc.feasible(shape, {"tile_r": plan.tile_r,
                                     "chunk_log": plan.chunk_log,
                                     "depth": plan.depth})


def test_xor_megakernel_tile_fits_vmem_per_bucket():
    """XOR twin: on a TPU the XOR heuristic takes the megakernel too, and
    shrinks its tile by the XOR body's VMEM model as buckets grow."""
    desc = engine.get_kernel("xor-fused-pallas")
    cfg = PIRConfig(n_items=1 << 25)
    for bucket, tile in ((1, 2048), (8, 2048), (16, 1024), (32, 512)):
        plan = heuristic_plan(cfg, bucket, backend="tpu")
        assert (plan.expand, plan.tile_r) == ("fused-pallas", tile)
        shape = engine.problem_shape(cfg, bucket)
        assert desc.feasible(shape, {"tile_r": plan.tile_r,
                                     "chunk_log": plan.chunk_log,
                                     "depth": plan.depth})


def test_lwe_gemm_feasibility_prunes_before_int8_gemm():
    """int32 operands: the LWE GEMM's VMEM footprint is 4x the int8
    streams, so the same tile crosses the budget earlier. At the boundary
    the int8 descriptor accepts a tile the LWE descriptor prunes."""
    lwe_desc = engine.get_kernel("lwe-gemm-pallas")
    int8_desc = engine.get_kernel("gemm-pallas")
    shape = ProblemShape(bucket=16, rows=1 << 20, item_bytes=256)
    # boundary tile: A = tr*(tq+tl) = 4.46 MB of streamed blocks ->
    # int8 ~2A = 8.9 MB fits the 16 MiB budget, int32 ~8A = 35.7 MB not
    tile = {"tile_q": 16, "tile_r": 16384, "tile_l": 256}
    assert int8_desc.feasible(shape, tile)
    assert not lwe_desc.feasible(shape, tile)
    # the shipped ladder itself never goes empty for either kernel
    assert lwe_desc.candidates(shape)
    assert {tuple(sorted(c.items())) for c in lwe_desc.candidates(shape)} \
        <= {tuple(sorted(c.items())) for c in int8_desc.candidates(shape)}


def test_lwe_plan_resolution_through_engine(tmp_path, monkeypatch):
    """ISSUE 6 acceptance: the LWE GEMM plan resolves through the engine —
    heuristic on a cache miss, tuned provenance in plan_report on a hit."""
    from repro.core.server import BucketedServeFns
    from repro.engine.kernels import descriptor_for_plan
    from repro.launch.mesh import make_local_mesh
    cfg = PIRConfig(n_items=N, protocol="lwe-simple-1", n_servers=1)
    path = str(tmp_path / "plans.json")
    tuned = ExecutionPlan(expand="materialize", scan="jnp", tile_r=512,
                          tile_q=8, tile_l=128, provenance="tuned")
    c = PlanCache(path)
    c.put(engine.probe_backend(), cfg.protocol, spec_signature(cfg), 2, tuned)
    c.save()
    monkeypatch.setenv("REPRO_PLAN_CACHE", path)
    engine.plan_cache(reload=True)
    try:
        # cache miss (bucket 4): lwe shares the additive GEMM heuristic
        # (materialize + GEMM reduction tile) and maps onto the lwe kernels
        miss = engine.resolve(cfg, 4, backend_name="cpu")
        assert miss.provenance == "heuristic"
        assert (miss.expand, miss.scan) == ("materialize", "jnp")
        assert descriptor_for_plan(miss, "lwe").name == "lwe-gemm-jnp"
        assert descriptor_for_plan(
            ExecutionPlan(scan="pallas"), "lwe").name == "lwe-gemm-pallas"
        # cache hit (bucket 2) -> tuned provenance through plan_report
        b = BucketedServeFns(cfg, make_local_mesh(), buckets=(2,))
        rep = b.plan_report()[2]
        assert rep["provenance"] == "tuned"
        assert b.plan_for_bucket(2).tile_r == 512
        assert rep["predicted_step_bytes"] > 0
        assert b.n_compiles == 0           # resolution never lowers
    finally:
        monkeypatch.delenv("REPRO_PLAN_CACHE")
        engine.plan_cache(reload=True)


def test_ggm_descriptor_registered_with_space():
    desc = engine.get_kernel("ggm-expand")
    assert not desc.serve                 # tuned standalone, not in plans
    cands = desc.candidates(ProblemShape(bucket=1, rows=1 << 16,
                                         item_bytes=4))
    assert {p["tile"] for p in cands} <= {512, 2048, 8192, 65536}
    assert cands                          # something survives pruning


@pytest.mark.slow          # ~30 s of XLA compile per candidate plan here
@pytest.mark.parametrize("protocol,n_servers", [
    ("xor-dpf-2", 2), ("additive-dpf-2", 2), ("xor-dpf-k", 3),
    ("lwe-simple-1", 1),
])
def test_all_candidate_plans_answer_identically(protocol, n_servers):
    """Byte parity across the whole search space, per registered protocol:
    whatever the tuner picks, the answer shares cannot change.

    Slow tier: each candidate plan is a fresh jit of ``answer_local``
    (~30 s compile on this container). The fast tier keeps per-kernel
    oracle parity (tests/test_kernels.py, tests/test_protocols.py) and
    ``test_candidate_plans_cover_registered_kernels`` below; the CI gate
    additionally measures two tunes end-to-end
    (``python -m repro.engine --smoke``)."""
    cfg = PIRConfig(n_items=N, protocol=protocol, n_servers=n_servers)
    proto = protocol_mod.get(cfg.protocol)
    db_words = pir.make_database(np.random.default_rng(5), N, 32)
    from repro.db import DatabaseSpec
    db = jnp.asarray(DatabaseSpec.from_config(cfg)
                     .pack_host(db_words, proto.db_view))
    keys = pir.batch_queries(np.random.default_rng(6), [3, N - 2], cfg)[0]

    plans = engine.candidate_plans(cfg, 2)
    assert len(plans) >= 2                # always >1 way to run a step
    ref_ans = None
    for plan in plans:
        fn = jax.jit(lambda d, k, p=plan: proto.answer_local(d, k, 0,
                                                             LOG_N, p))
        ans = np.asarray(jax.block_until_ready(fn(db, keys)))
        if ref_ans is None:
            ref_ans = ans
        else:
            np.testing.assert_array_equal(
                ans, ref_ans, err_msg=f"plan {plan_label(plan)} diverged")


# ---------------------------------------------------------------------------
# plan cache: round-trip, corruption, stale schema
# ---------------------------------------------------------------------------

def test_plan_cache_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path)
    plan = ExecutionPlan(expand="fused", scan="jnp", chunk_log=10,
                         tile_r=512, provenance="tuned")
    cfg = PIRConfig(n_items=N)
    cache.put("cpu", cfg.protocol, spec_signature(cfg), 4, plan,
              meta={"tuned_s": 0.001})
    assert cache.save() is not None
    re = PlanCache(path)
    hit = re.get("cpu", cfg.protocol, spec_signature(cfg), 4)
    assert hit == plan and hit.provenance == "tuned"
    assert re.get("cpu", cfg.protocol, spec_signature(cfg), 8) is None
    assert re.get("tpu", cfg.protocol, spec_signature(cfg), 4) is None


def test_engine_resolve_uses_cache_hit(tmp_path, monkeypatch):
    from repro.core.server import BucketedServeFns
    from repro.launch.mesh import make_local_mesh
    path = str(tmp_path / "plans.json")
    cfg = PIRConfig(n_items=N)
    tuned = ExecutionPlan(expand="fused", scan="jnp", chunk_log=5,
                          collective="butterfly", provenance="tuned")
    c = PlanCache(path)
    c.put("cpu", cfg.protocol, spec_signature(cfg), 4, tuned)
    c.save()
    monkeypatch.setenv("REPRO_PLAN_CACHE", path)
    monkeypatch.setenv(FORCE_BACKEND_ENV, "cpu")
    engine.plan_cache(reload=True)
    try:
        got = engine.resolve(cfg, 4)
        assert got.provenance == "tuned"
        # the cached plan comes back as recorded: tiling and collective
        assert got == tuned and got.collective == "butterfly"
        # other buckets still miss -> heuristic, with the gather collective
        miss = engine.resolve(cfg, 8)
        assert miss.provenance == "heuristic" and miss.collective == "gather"
        # the serving stack resolves through the same seam
        b = BucketedServeFns(cfg, make_local_mesh(), buckets=(4, 8))
        assert b.plan_for_bucket(4).provenance == "tuned"
        assert b.plan_for_bucket(8).provenance == "heuristic"
    finally:
        monkeypatch.delenv("REPRO_PLAN_CACHE")
        monkeypatch.delenv(FORCE_BACKEND_ENV)
        engine.plan_cache(reload=True)


@pytest.mark.parametrize("payload", [
    "{not json at all",                                        # corrupted
    json.dumps({"schema": 999, "plans": {}}),                  # stale schema
    json.dumps({"schema": 1, "plans": {"k": {"plan": {
        "expand": "materialize", "scan": "jnp", "warp": 9}}}}),  # bad field
    json.dumps({"schema": 1, "plans": []}),                    # malformed
])
def test_plan_cache_degrades_to_heuristic(tmp_path, monkeypatch, payload):
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        f.write(payload)
    cache = PlanCache(path)                # must not raise
    assert len(cache) == 0
    assert cache.load_error is not None
    monkeypatch.setenv("REPRO_PLAN_CACHE", path)
    engine.plan_cache(reload=True)
    try:
        cfg = PIRConfig(n_items=N)
        got = engine.resolve(cfg, 4, backend_name="cpu")
        assert got == heuristic_plan(cfg, 4, backend="cpu")
        assert got.provenance == "heuristic"
    finally:
        monkeypatch.delenv("REPRO_PLAN_CACHE")
        engine.plan_cache(reload=True)


def test_plan_cache_disabled_via_env(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", "off")
    assert engine.cache_path() is None
    cache = engine.plan_cache(reload=True)
    assert cache.path is None and cache.save() is None
    monkeypatch.delenv("REPRO_PLAN_CACHE")
    engine.plan_cache(reload=True)


# ---------------------------------------------------------------------------
# measured tuner (tiny budget) + build-time plan resolution
# ---------------------------------------------------------------------------

@pytest.mark.slow          # two answer_local compiles (~30 s each here)
def test_tuner_tiny_budget_picks_no_worse_than_heuristic(tmp_path):
    cfg = PIRConfig(n_items=1 << 8, item_bytes=32)
    cache = PlanCache(str(tmp_path / "plans.json"))
    budget = TuneBudget(max_candidates=1, warmup=1, iters=1,
                        max_seconds=60.0)
    res = engine.tune(cfg, 2, budget=budget, cache=cache)
    assert res.plan.provenance == "tuned"
    assert res.tuned_s <= res.heuristic_s + 1e-9
    assert plan_label(res.heuristic) in res.timings
    # the winner was persisted under the engine's cache key
    cache.save()
    hit = PlanCache(cache.path).get(engine.probe_backend(), cfg.protocol,
                                    spec_signature(cfg), 2)
    assert hit == res.plan


def test_bucketed_serve_fns_resolve_plans_at_build_time():
    """Plan resolution is per bucket and needs no compile: plan_for_bucket
    and plan_report work before any serve step is built."""
    from repro.core.server import BucketedServeFns
    from repro.launch.mesh import make_local_mesh
    cfg = PIRConfig(n_items=N)
    b = BucketedServeFns(cfg, make_local_mesh(), buckets=(2, 4))
    assert b.n_compiles == 0
    p2, p4 = b.plan_for_bucket(2), b.plan_for_bucket(4)
    assert p2 == engine.resolve(cfg, 2)
    assert p4 == engine.resolve(cfg, 4)
    assert b.plan_for_bucket(2) is p2      # cached: one resolution/bucket
    rep = b.plan_report()
    assert set(rep) == {2, 4}
    for row in rep.values():
        assert row["provenance"] in ("heuristic", "tuned")
        assert row["predicted_step_bytes"] > 0
    assert b.n_compiles == 0               # nothing was lowered for this


def test_predicted_bytes_models_are_sane():
    cfg = PIRConfig(n_items=1 << 14)
    fused = ExecutionPlan(expand="fused", scan="jnp")
    mat_pl = ExecutionPlan(expand="materialize", scan="pallas")
    rep_f = engine.plan_report(cfg, fused, 8)
    rep_m = engine.plan_report(cfg, mat_pl, 8)
    # the Pallas scan reads the DB once per batch; the fused path streams
    # it once per query -> strictly more modeled traffic at Q=8
    assert rep_f["predicted_step_bytes"] > rep_m["predicted_step_bytes"]
    assert rep_m["provenance"] == "heuristic"
