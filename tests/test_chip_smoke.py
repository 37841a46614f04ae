"""chip_smoke.py's phases at smoke scale on the CPU, plus the pieces it
leans on: the compile-cache location and the strict local mesh.

The phases run here in interpret mode at ``PIR_SMOKE`` scale (2^14 rows)
with the same byte-for-byte oracle the chip run uses, so the script
cannot rot between chip runs. Only ``main()`` demands a TPU.
"""
import importlib.util
import pathlib

import numpy as np
import pytest

import jax

from repro.configs.pir import PIR_SMOKE, PIR_SMOKE_ADD
from repro.core import pir
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def db():
    return pir.make_database(np.random.default_rng(0), PIR_SMOKE.n_items,
                             PIR_SMOKE.item_bytes)


@pytest.fixture
def no_plan_cache(monkeypatch):
    from repro import engine
    monkeypatch.setenv("REPRO_PLAN_CACHE", "off")
    engine.plan_cache(reload=True)
    yield
    monkeypatch.delenv("REPRO_PLAN_CACHE")
    engine.plan_cache(reload=True)


def test_indices_cover_both_ends(smoke):
    idx = smoke.pick_indices(0, PIR_SMOKE.n_items)
    assert idx[:2] == [0, PIR_SMOKE.n_items - 1]
    assert len(idx) == smoke.N_INDICES
    assert all(0 <= i < PIR_SMOKE.n_items for i in idx)
    assert idx == smoke.pick_indices(0, PIR_SMOKE.n_items)


def test_phases_one_chip_layout(smoke, db, no_plan_cache):
    mesh = make_local_mesh()
    idx = smoke.pick_indices(0, PIR_SMOKE.n_items)
    rep_a, system = smoke.phase_engine_plans(PIR_SMOKE, mesh, db, idx)
    assert set(rep_a["plans"]) == {1, 4}
    assert rep_a["session_records"] == len(idx)
    assert rep_a["n_compiles"] == 4            # 2 parties x 2 buckets
    rep_b = smoke.phase_megakernel_xor(PIR_SMOKE, mesh, system.db, db, idx)
    assert rep_b["plans"][4].startswith("fused-pallas/")
    rep_c = smoke.phase_additive(PIR_SMOKE_ADD, mesh, db, idx)
    assert rep_c["plans"][4] == "materialize/jnp"   # CPU: no megakernel
    for rep in (rep_a, rep_b, rep_c):
        assert rep["compile_s"] > 0 and rep["backend_compiles"] > 0
        assert rep["warm_query_s_b4"] > 0


def test_phase_sharded_checks_every_device(smoke, db, no_plan_cache):
    mesh = make_local_mesh(data=1, model=len(jax.devices()))
    idx = smoke.pick_indices(3, PIR_SMOKE.n_items)
    rep = smoke.phase_sharded(PIR_SMOKE, mesh, db, idx)
    assert rep["rows_per_device"] == PIR_SMOKE.n_items // mesh.devices.size


def test_check_names_the_differing_records(smoke):
    want = np.arange(12, dtype=np.uint32).reshape(3, 4)
    got = want.copy()
    got[1, 2] ^= 1
    with pytest.raises(AssertionError, match=r"records \[1\]"):
        smoke._check("x", got, want)


@pytest.mark.parametrize("forced", ["", "tpu"])
def test_main_refuses_without_a_tpu(smoke, monkeypatch, forced):
    monkeypatch.setenv("REPRO_PLAN_CACHE", "off")
    if forced:
        monkeypatch.setenv("REPRO_FORCE_BACKEND", forced)
    else:
        monkeypatch.delenv("REPRO_FORCE_BACKEND", raising=False)
    with pytest.raises(SystemExit, match="REPRO_FORCE_BACKEND|no TPU"):
        smoke.main([])


def test_compile_cache_dir_from_env_or_fixed_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # JAX reads it
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_local_mesh_never_clamps():
    n = len(jax.devices())
    assert make_local_mesh(data=1, model=n).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_local_mesh(data=1, model=n + 1)
