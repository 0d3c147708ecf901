"""Compile-only checks of the fused wire kernels and the SSD kernel pair
for a TPU v5e.

Mosaic compiles each kernel for a described (not attached) v5e chip at a
real mamba2-1.3b bucket: 48 units of 524288 gradients (the per-layer
w_bc leaves), i.e. 49152 tile rows of 512. Interpret mode cannot see
what these catch: layouts Mosaic refuses, tiles that overflow VMEM, and
lowerings with no TPU rule. The SSD kernels compile at mamba2-1.3b's
full shape: batch 8 x 2048 tokens, 64 heads of 64, state 128, chunk 64.
Nothing runs, so nothing here is a timing.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ssd
from repro.kernels.pack import fields_pack_pallas, fields_unpack_pallas
from repro.kernels.qsgd import qsgd_pack_pallas_rows, qsgd_unpack_pallas_rows
from repro.kernels.sign import (majority_pallas, sign_pack_pallas_rows,
                                sign_unpack_pallas_rows)
from repro.kernels.terngrad import (terngrad_pack_pallas_rows,
                                    terngrad_unpack_pallas_rows)

N_UNITS, D = 48, 524288               # mamba2-1.3b: 48 x (2048 x 256) w_bc
RPU = D // 512                        # tile rows per unit
R = N_UNITS * RPU                     # 49152 tile rows
QSGD_WIDTH = 6                        # qsgd(16): codes in [0, 32]
INDEX_WIDTH = 19                      # top-k indices into 524288


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(s):
    """name -> (kernel call, its argument shapes on the described chip)."""
    tile = _sds((R, 512), jnp.float32, s)
    col_u = _sds((R, 1), jnp.uint32, s)
    col_f = _sds((R, 1), jnp.float32, s)

    def words(width):
        return _sds((R // 8, 128 * width), jnp.uint32, s)
    return {
        "qsgd_pack": (lambda x, k0, k1, n: qsgd_pack_pallas_rows(
            x, k0, k1, n, 16, QSGD_WIDTH, d=D, rpu=RPU, interpret=False),
            (tile, col_u, col_u, col_f)),
        "qsgd_unpack": (lambda w, f: qsgd_unpack_pallas_rows(
            w, f, 16, QSGD_WIDTH, interpret=False),
            (words(QSGD_WIDTH), col_f)),
        "terngrad_pack": (lambda x, k0, k1, c: terngrad_pack_pallas_rows(
            x, k0, k1, c, d=D, rpu=RPU, interpret=False),
            (tile, col_u, col_u, col_f)),
        "terngrad_unpack": (lambda w, c: terngrad_unpack_pallas_rows(
            w, c, interpret=False), (words(2), col_f)),
        "sign_pack": (lambda x: sign_pack_pallas_rows(
            x, d=D, rpu=RPU, interpret=False), (tile,)),
        "sign_unpack": (lambda w: sign_unpack_pallas_rows(
            w, interpret=False), (words(1),)),
        "fields_pack": (lambda f: fields_pack_pallas(
            f, INDEX_WIDTH, interpret=False),
            (_sds((R, 512), jnp.int32, s),)),
        "fields_unpack": (lambda w: fields_unpack_pallas(
            w, INDEX_WIDTH, interpret=False), (words(INDEX_WIDTH),)),
        "majority": (lambda w: majority_pallas(w, interpret=False),
                     (_sds((4, N_UNITS * D // 32), jnp.uint32, s),)),
    }


@pytest.mark.parametrize("name", ["qsgd_pack", "qsgd_unpack",
                                  "terngrad_pack", "terngrad_unpack",
                                  "sign_pack", "sign_unpack", "fields_pack",
                                  "fields_unpack", "majority"])
def test_fused_wire_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernels(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


SSD_B, SSD_S, SSD_H, SSD_P, SSD_N, SSD_Q = 8, 2048, 64, 64, 128, 64


def _ssd_kernels(s):
    """name -> (SSD kernel call or its gradient, argument shapes)."""
    hb = ssd.head_block(SSD_H, SSD_P, SSD_N, SSD_Q)
    nc, nhb = SSD_S // SSD_Q, SSD_H // hb
    x = _sds((SSD_B, SSD_S, SSD_H * SSD_P), jnp.bfloat16, s)
    bc = _sds((SSD_B, SSD_S, SSD_N), jnp.bfloat16, s)
    dt_r = _sds((SSD_B, nc, SSD_H, SSD_Q), jnp.float32, s)
    a = _sds((nhb, hb, 1), jnp.float32, s)
    dl = _sds((1, SSD_H * SSD_P), jnp.float32, s)
    state = _sds((SSD_B, nhb, SSD_N, hb * SSD_P), jnp.float32, s)
    states = _sds((SSD_B, nc, nhb, SSD_N, hb * SSD_P), jnp.float32, s)
    dt = _sds((SSD_B, SSD_S, SSD_H), jnp.float32, s)
    heads = _sds((SSD_H,), jnp.float32, s)

    def loss(x, dt, A, Bm, Cm, D):
        y, fin = ssd.ssd(x, dt, A, Bm, Cm, D, SSD_Q, hb=hb, interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(fin)
    return {
        "ssd_fwd": (lambda *a_: ssd._fwd_call(
            *a_, hb=hb, with_states=False, interpret=False),
            (x, dt_r, a, bc, bc, dl, state)),
        "ssd_fwd_states": (lambda *a_: ssd._fwd_call(
            *a_, hb=hb, with_states=True, interpret=False),
            (x, dt_r, a, bc, bc, dl, state)),
        "ssd_bwd": (lambda *a_: ssd._bwd_call(
            *a_, hb=hb, interpret=False),
            (x, dt_r, a, bc, bc, dl, states, x, state)),
        "ssd_grad": (jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)),
                     (x, dt, heads, bc, bc, heads)),
    }


@pytest.mark.parametrize("name", ["ssd_fwd", "ssd_fwd_states", "ssd_bwd",
                                  "ssd_grad"])
def test_ssd_kernel_compiles_for_v5e(one_chip, name):
    """Each SSD kernel, and the gradient program that joins the forward
    with states to the backward, at full mamba2-1.3b shape."""
    fn, args = _ssd_kernels(one_chip)[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
    kernels = {"ssd_grad": ("%ssd_fwd_states", "%ssd_bwd")}.get(
        name, ("%" + name,))
    for k in kernels:
        assert k + "." in text, (name, k)
