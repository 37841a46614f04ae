"""Runtime: fault policies, straggler logic, elastic planning, the local
mesh and the compile-cache location, train loop."""
import pathlib

import numpy as np
import pytest

import jax

from repro.config import MeshConfig, OptimizerConfig, RunConfig
from repro.configs import SMOKES
from repro.configs.shapes import SMOKE_TRAIN
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh, split_devices
from repro.runtime.elastic import carve_submeshes, plan_mesh, rebuild_mesh
from repro.runtime.fault import (HeartbeatRegistry, PoisonPolicy,
                                 RetryStats, StragglerMonitor, retry_step)

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# fault policies (injectable clocks — deterministic)
# ---------------------------------------------------------------------------

def test_heartbeat_suspects():
    t = [0.0]
    reg = HeartbeatRegistry(timeout=10.0, clock=lambda: t[0])
    reg.beat("a")
    reg.beat("b")
    t[0] = 5.0
    reg.beat("b")
    t[0] = 12.0
    assert reg.suspects() == ["a"]
    assert reg.healthy() == ["b"]


def test_retry_step_backoff():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_step(flaky, retries=3, sleep=sleeps.append) == "ok"
    assert sleeps == [0.5, 1.0]


def test_retry_step_exhausts():
    def always():
        raise RuntimeError("down")
    with pytest.raises(RuntimeError):
        retry_step(always, retries=2, sleep=lambda s: None)


def test_heartbeat_remove_retires_departed_participant():
    """Departure is not failure: a removed participant must stop showing
    up as a suspect forever (the replica registry's leave path)."""
    t = [0.0]
    reg = HeartbeatRegistry(timeout=10.0, clock=lambda: t[0])
    reg.beat("a")
    reg.beat("b")
    assert reg.remove("a") is True
    assert reg.remove("a") is False              # already gone
    assert reg.forget("nope") is False           # alias, unknown id
    t[0] = 100.0                                 # way past timeout
    assert reg.suspects() == ["b"]               # "a" never resurfaces
    assert reg.healthy() == []


def test_retry_step_backoff_is_capped():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 7:
            raise RuntimeError("transient")
        return "ok"

    assert retry_step(flaky, retries=6, base_delay=0.5, max_delay=2.0,
                      sleep=sleeps.append) == "ok"
    assert sleeps == [0.5, 1.0, 2.0, 2.0, 2.0, 2.0]   # capped, not 16.0


def test_retry_step_surfaces_attempt_stats():
    stats = RetryStats()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_step(flaky, retries=3, sleep=lambda s: None,
                      stats=stats) == "ok"
    assert stats.attempts == 3
    assert stats.retried == 2
    assert stats.slept_s == pytest.approx(0.5 + 1.0)
    # stats accumulate across calls (the router reuses one instance)
    retry_step(lambda: "ok", stats=stats, sleep=lambda s: None)
    assert stats.attempts == 4 and stats.retried == 2


def test_poison_policy_transitions():
    p = PoisonPolicy(max_consecutive=3)
    assert p.observe(1.0) == "ok"
    assert p.observe(float("nan")) == "skip"
    assert p.observe(float("inf")) == "skip"
    assert p.observe(float("nan")) == "rewind"
    assert p.consecutive == 0
    assert p.total_skipped == 3


def test_straggler_detection_and_reassign():
    mon = StragglerMonitor(factor=2.0, alpha=1.0)
    for c, lat in (("c0", 1.0), ("c1", 1.1), ("c2", 5.0)):
        mon.record(c, lat)
    assert mon.stragglers() == ["c2"]
    queues = {"c0": [1], "c1": [2], "c2": [3, 4]}
    out = mon.reassign(queues)
    assert out["c2"] == []
    assert sorted(sum(out.values(), [])) == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# elastic planning
# ---------------------------------------------------------------------------

def test_plan_mesh_shrinks_data_axis():
    cfg = plan_mesh(256, model_axis=16)
    assert cfg.shape == (16, 16)
    cfg = plan_mesh(192, model_axis=16)   # lost 4 nodes of 16 devices
    assert cfg.shape == (8, 16)           # data halves, model pinned
    cfg = plan_mesh(512, model_axis=16, prefer_pods=2)
    assert cfg.shape == (2, 16, 16)


def test_rebuild_mesh_local():
    mesh = rebuild_mesh(model_axis=1)
    assert "model" in mesh.axis_names


def test_plan_mesh_non_pow2_device_counts():
    """Stragglers rarely leave neat shapes: data rounds DOWN to the
    largest power of two that fits; leftovers idle until the next
    resize."""
    assert plan_mesh(96, model_axis=16).shape == (4, 16)     # 96//16=6 -> 4
    assert plan_mesh(17, model_axis=16).shape == (1, 16)
    assert plan_mesh(3, model_axis=1).shape == (2, 1)
    assert plan_mesh(1, model_axis=1).shape == (1, 1)


def test_plan_mesh_prefer_pods_divides_before_rounding():
    cfg = plan_mesh(96, model_axis=16, prefer_pods=2)        # 48 per pod
    assert cfg.shape == (2, 2, 16) and cfg.axes == ("pod", "data", "model")
    cfg = plan_mesh(64, model_axis=16, prefer_pods=4)        # 16 per pod
    assert cfg.shape == (4, 1, 16)


def test_plan_mesh_rejects_too_few_devices():
    with pytest.raises(ValueError, match="< model axis"):
        plan_mesh(8, model_axis=16)
    with pytest.raises(ValueError, match="< model axis"):
        rebuild_mesh([object()] * 2, model_axis=4)


def test_split_devices_partitions_or_shares():
    devs = [f"d{i}" for i in range(8)]
    groups = split_devices(2, devs)
    assert groups == [devs[:4], devs[4:]]                    # disjoint halves
    groups = split_devices(3, devs)                          # 8//3=2 each
    assert [len(g) for g in groups] == [2, 2, 2]             # 2 idle
    assert len({d for g in groups for d in g}) == 6
    # degenerate single-host case: too few devices -> every group gets
    # the FULL list (replicas share silicon, keep separate schedulers)
    groups = split_devices(4, devs[:2], min_per_group=1)
    assert groups == [devs[:2]] * 4
    groups = split_devices(2, devs, min_per_group=8)
    assert groups == [devs] * 2
    with pytest.raises(ValueError, match=">= 1"):
        split_devices(0, devs)


def test_carve_submeshes_one_mesh_per_replica():
    meshes = carve_submeshes(2, model_axis=1)
    assert len(meshes) == 2
    for m in meshes:
        assert "model" in m.axis_names and "data" in m.axis_names


# ---------------------------------------------------------------------------
# train loop end-to-end (smoke scale): ckpt + resume + rewind path
# ---------------------------------------------------------------------------

def _loop(tmp_path, steps=6):
    from repro.runtime.train_loop import TrainLoop, TrainLoopConfig
    run = RunConfig(
        model=SMOKES["granite-3-2b"], shape=SMOKE_TRAIN,
        mesh=MeshConfig(shape=(1, 1), axes=("data", "model")),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                  total_steps=steps))
    return TrainLoop(run, make_local_mesh(),
                     TrainLoopConfig(total_steps=steps, ckpt_every=2,
                                     ckpt_dir=str(tmp_path), log_every=0),
                     log=lambda s: None)


def test_train_loop_with_checkpointing(tmp_path):
    loop = _loop(tmp_path)
    with loop.mesh:
        res = loop.run_loop()
    assert res.final_step == 6
    assert len(res.losses) == 6
    assert loop.ckpt.latest_step() == 6


def test_train_loop_resume(tmp_path):
    loop = _loop(tmp_path, steps=4)
    with loop.mesh:
        loop.run_loop()
    loop2 = _loop(tmp_path, steps=4)
    with loop2.mesh:
        res = loop2.run_loop(resume=True)
    assert res.final_step == 4       # resumed at 4, nothing left to do
    assert res.losses == []


# ---------------------------------------------------------------------------
# launch: the local mesh and the compile-cache location
# ---------------------------------------------------------------------------

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
