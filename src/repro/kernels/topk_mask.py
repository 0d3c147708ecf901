"""Pallas TPU kernel: block-local Top-k masking via threshold bisection.

Exact global Top-k needs a global sort — a poor fit for the TPU memory
hierarchy. Instead each (row-block) keeps its own top-k by magnitude,
finding the k-th magnitude with a fixed 24-step bisection over
[0, rowmax] (pure VPU compare/reduce per step, no sort, no gather).

Block-local Top-k is a FINER partition than layer-wise — Lemma 1 of the
paper covers any partition, so the convergence theory transfers verbatim
(this is the 'block-wise' granularity in core.granularity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 8            # rows per grid step; each ROW is one top-k unit
BLOCK_C = 512
ITERS = 24


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...]
    mag = jnp.abs(x)
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(i, carry):
        lo, hi = carry
        thr = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= thr).astype(jnp.int32), axis=-1,
                      keepdims=True)
        pred = cnt > k
        return jnp.where(pred, thr, lo), jnp.where(pred, hi, thr)

    lo, hi = jax.lax.fori_loop(0, ITERS, body, (lo, hi))
    o_ref[...] = x * (mag >= lo).astype(x.dtype)


def topk_mask_pallas(x: jax.Array, k: int, *, interpret: bool
                     ) -> jax.Array:
    """x (R, C): per-row top-k mask. R % BLOCK_R == 0, C == BLOCK_C."""
    R, C = x.shape
    assert R % BLOCK_R == 0 and C == BLOCK_C, (R, C)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(R // BLOCK_R,),
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x)
