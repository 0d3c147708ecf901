"""Pallas TPU kernel: fused RMSNorm (rowwise) — the per-token hot spot
shared by every assigned architecture."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 64


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * g).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, gamma: jax.Array, eps: float = 1e-5,
                   *, interpret: bool) -> jax.Array:
    """x (R, D) rows normalized over D (D multiple of 128)."""
    R, D = x.shape
    assert R % BLOCK_R == 0 and D % 128 == 0, (R, D)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // BLOCK_R,),
        in_specs=[pl.BlockSpec((BLOCK_R, D), lambda i: (i, 0)),
                  pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((BLOCK_R, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(x, gamma.reshape(1, D))
