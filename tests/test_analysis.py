"""The scan-aware HLO cost model and roofline plumbing (deliverable g)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.analysis import Roofline, model_flops
from repro.launch.hlo_cost import (_shape_bytes, _wire_bytes,
                                   scan_scaled_costs)
from repro.models.config import INPUT_SHAPES
from repro.configs.registry import get_config


def test_scan_trip_scaling_exact():
    def f(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = scan_scaled_costs(c.as_text(), 1)
    assert r["flops"] == 8 * 2 * 128 ** 3


def test_nested_scan_trip_scaling_exact():
    def f(x, w):
        def outer(c, wl):
            def inner(c2, _):
                return jnp.tanh(c2 @ wl), None
            c2, _ = jax.lax.scan(inner, c, jnp.arange(3))
            return c2, None
        y, _ = jax.lax.scan(outer, x, w)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = scan_scaled_costs(c.as_text(), 1)
    assert r["flops"] == 5 * 3 * 2 * 64 ** 3


def test_shape_bytes_parsing():
    assert _shape_bytes("f32[16,4]{1,0}") == 256
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(s32[], f32[2,2]{1,0}, pred[3])") == 4 + 16 + 3
    assert _shape_bytes("s8[100]") == 100


def test_wire_model():
    # ring all-reduce moves ~2x payload across (g-1)/g links
    assert _wire_bytes("all-reduce", 1000, 2) == 1000.0
    assert _wire_bytes("all-gather", 1600, 16) == 1600 * 15 / 16
    assert _wire_bytes("reduce-scatter", 100, 4) == 300.0
    assert _wire_bytes("all-reduce", 1000, 1) == 0.0


def test_model_flops_formulas():
    cfg = get_config("mamba2-1.3b")
    n = cfg.active_param_count()
    tr = INPUT_SHAPES["train_4k"]
    assert model_flops(cfg, tr) == 6.0 * n * 256 * 4096
    de = INPUT_SHAPES["decode_32k"]
    assert model_flops(cfg, de) == 2.0 * n * 128


def test_moe_active_params_much_smaller():
    cfg = get_config("qwen3-moe-235b-a22b")
    assert cfg.param_count() > 2e11          # ~235B total
    assert cfg.active_param_count() < 0.3e11  # ~22B active
    l4 = get_config("llama4-maverick-400b-a17b")
    assert 3.5e11 < l4.param_count() < 4.5e11
    assert l4.active_param_count() < 0.25e11


def test_roofline_bottleneck_classification():
    r = Roofline(arch="a", shape="s", mesh="m", chips=256,
                 hlo_flops_per_device=197e12,      # 1 s compute
                 hlo_bytes_per_device=819e9 * 3,   # 3 s memory
                 collective_bytes_per_device=50e9 * 2,  # 2 s collective
                 collective_breakdown={}, model_flops_global=197e12 * 256,
                 memory_per_device={})
    assert r.bottleneck == "memory"
    assert r.t_compute == pytest.approx(1.0)
    assert r.useful_flops_ratio == pytest.approx(1.0)


def test_collectives_detected_in_shardmap_hlo():
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "data")

    c = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                              out_specs=P(None), check_vma=False)).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    r = scan_scaled_costs(c.as_text(), 1)
    # group size 1 -> zero wire cost, but parse must not crash
    assert isinstance(r["collectives"], dict)
