"""HLO cost analyzer + roofline model tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import hlo_cost, roofline


def _compile(f, *shapes):
    structs = [jax.ShapeDtypeStruct(s, np.float32) for s in shapes]
    return jax.jit(f).lower(*structs).compile()


def test_flops_single_matmul():
    c = _compile(lambda a, b: a @ b, (128, 64), (64, 32))
    cost = hlo_cost.analyze(c.as_text())
    assert abs(cost.flops - 2 * 128 * 64 * 32) / cost.flops < 0.05


def test_flops_scan_multiplied():
    def f(x, w):
        def body(c, _):
            return c @ w, ()
        c, _ = jax.lax.scan(body, x, jnp.arange(13))
        return c
    c = _compile(f, (64, 64), (64, 64))
    cost = hlo_cost.analyze(c.as_text())
    expect = 13 * 2 * 64 ** 3
    assert abs(cost.flops - expect) / expect < 0.05
    assert cost.unknown_loops == 0


def test_flops_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, ()
            c2, _ = jax.lax.scan(inner, c, jnp.arange(4))
            return c2, ()
        c, _ = jax.lax.scan(outer, x, jnp.arange(3))
        return c
    c = _compile(f, (32, 32), (32, 32))
    cost = hlo_cost.analyze(c.as_text())
    expect = 12 * 2 * 32 ** 3
    assert abs(cost.flops - expect) / expect < 0.1


def test_dynamic_slice_not_full_operand():
    """Slicing one row of a big table must not count the whole table."""
    def f(table, i):
        return jax.lax.dynamic_slice_in_dim(table, 0, 1, 0)
    big = jax.ShapeDtypeStruct((4096, 1024), np.float32)
    idx = jax.ShapeDtypeStruct((), np.int32)
    c = jax.jit(f).lower(big, idx).compile()
    cost = hlo_cost.analyze(c.as_text())
    assert cost.bytes < 4096 * 1024 * 4 * 0.5   # far below full-table read


def test_roofline_terms_and_bottleneck():
    r = roofline.Roofline(name="x", n_chips=256,
                          hlo_flops=256 * 197e12,       # 1 s compute
                          hlo_bytes=256 * 819e9 * 2,    # 2 s memory
                          collective_bytes=256 * 50e9 * 0.5,
                          model_flops=0.5 * 256 * 197e12)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 0.5) < 1e-9
    assert r.bottleneck == "memory"
    assert abs(r.step_time - 2.0) < 1e-9
    assert abs(r.mfu - 0.25) < 1e-9
    assert abs(r.useful_flop_ratio - 0.5) < 1e-9


def test_collective_parse_counts_psum():
    """An 8-way pmapped psum lowers to an all-reduce we can count."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()), ("d",))   # 1 device: still emits
    x = jax.ShapeDtypeStruct((8, 128), np.float32)

    def f(a):
        return jax.lax.psum(a, "d")

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d", None),
                                   out_specs=P(None, None),
                                   check_vma=False))
    c = fn.lower(x).compile()
    cost = hlo_cost.analyze(c.as_text())
    # single-device all-reduce may fold away; just assert the parse ran
    assert cost.bytes >= 0


def test_model_flops_for():
    assert roofline.model_flops_for(10, 5, training=True) == 300
    assert roofline.model_flops_for(10, 5, training=False) == 100


def test_format_table():
    r = roofline.Roofline(name="cell", n_chips=2, hlo_flops=1e12,
                          hlo_bytes=1e12, collective_bytes=1e9,
                          model_flops=5e11)
    txt = roofline.format_table([r.to_dict()])
    assert "cell" in txt and "|" in txt


def test_peak_table_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.hbm_bytes_per_s, v5e.bf16_flops, v5e.int8_ops) == \
        (819e9, 197e12, 393e12)
    assert roofline.HBM_BW == v5e.hbm_bytes_per_s
    # this process's device: the CPU host reads its one nominal row
    assert roofline.device_kind() == "cpu"
    assert roofline.peak_bytes_per_s() == roofline.PEAKS["cpu"].hbm_bytes_per_s
    assert roofline.achieved_fraction(819e9, 1.0, kind="TPU v5 lite") == 1.0


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "gpu"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        roofline.peak_bytes_per_s(kind)
