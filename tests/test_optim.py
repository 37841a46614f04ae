"""Optimizer + compression tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig
from repro.optim import compression
from repro.optim.optimizer import (adafactor_init, adafactor_update,
                                   adamw_init, adamw_update,
                                   clip_by_global_norm, lr_schedule,
                                   opt_init, opt_update, spec_for_state)


def _quadratic_params():
    return {"w": jnp.asarray([3.0, -2.0, 1.5]),
            "b": jnp.asarray([[1.0, -1.0], [0.5, 2.0]])}


def _grad(params):
    # grad of 0.5*||p||^2 is p: minimizing drives params to 0
    return params


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizers_minimize_quadratic(name):
    cfg = OptimizerConfig(name=name, lr=0.1, warmup_steps=0,
                          total_steps=10000, weight_decay=0.0)
    params = _quadratic_params()
    state = opt_init(cfg, params)
    for _ in range(60):
        params, state, m = opt_update(cfg, _grad(params), state, params)
    norm = sum(float(jnp.sum(p * p)) for p in jax.tree_util.tree_leaves(params))
    assert norm < 0.5, (name, norm)
    assert np.isfinite(m["grad_norm"])


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 1e-3) < 1e-9          # end of warmup
    assert lrs[-1] < lrs[1]                    # decayed
    assert lrs[-1] >= 0.1 * 1e-3 - 1e-12       # floor at 10%


def test_global_norm_clip():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - np.sqrt(1000.0)) < 1e-3
    total = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert abs(total - 1.0) < 1e-5


def test_adafactor_state_is_factored():
    params = {"big": jnp.zeros((64, 32)), "vec": jnp.zeros((16,))}
    state = adafactor_init(params)
    assert state.vr["big"].shape == (64,)
    assert state.vc["big"].shape == (32,)
    assert state.v["big"] == ()
    assert state.v["vec"].shape == (16,)


def test_spec_for_state_shapes():
    from jax.sharding import PartitionSpec as P
    params = {"w": jnp.zeros((8, 4))}
    specs = {"w": P(None, "model")}
    shapes = jax.eval_shape(lambda: params)
    s = spec_for_state(OptimizerConfig(name="adafactor"), specs, shapes)
    assert s.vr["w"] == P(None)
    assert s.vc["w"] == P("model")
    s2 = spec_for_state(OptimizerConfig(name="adamw"), specs, shapes)
    assert s2.m["w"] == P(None, "model")


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bounded():
    g = jnp.asarray(np.random.default_rng(0).standard_normal(1000) * 5)
    q, s = compression.quantize(g)
    err = np.abs(np.asarray(compression.dequantize(q, s) - g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_sum():
    """EF carries the residual: quantized stream sums to the true sum."""
    rng = np.random.default_rng(1)
    grads_seq = [
        {"w": jnp.asarray(rng.standard_normal(64) * 0.01)}
        for _ in range(50)
    ]
    ef = compression.ef_init(grads_seq[0])
    total_sent = np.zeros(64)
    for g in grads_seq:
        q, s, ef = compression.compress_with_feedback(g, ef)
        total_sent += np.asarray(compression.dequantize(q["w"], s["w"]))
    true_total = sum(np.asarray(g["w"]) for g in grads_seq)
    residual = np.asarray(ef["w"])
    np.testing.assert_allclose(total_sent + residual, true_total,
                               rtol=1e-4, atol=1e-5)


def test_compressed_psum_single_axis():
    """shard_map form over a 1-device axis degenerates to identity mean."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("pod",))
    g = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    ef = compression.ef_init(g)

    def f(g, ef):
        return compression.compressed_psum(g, ef, "pod")

    out, _ = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(g, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=0.05)
