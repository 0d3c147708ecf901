"""Pallas TPU kernel: fused TernGrad quantize + dequantize, and the fused
single-launch ternarize+PACK wire kernels.

out = scale · sign(x) · 1[u < |x|/scale], with the per-unit scale
(max |x| over the compression unit) computed outside — same
granularity-polymorphic design as the QSGD kernel.

`terngrad_pack_pallas_rows` / `terngrad_unpack_pallas_rows` are the
wire hot path: ONE launch per bucket turning gradient tiles into 2-bit
codes packed as uint32 words (1 f32 read + 1/16 word write per element),
Bernoulli draws generated in-kernel from per-row threefry key columns
(kernels/prng.py — bit-exact to jax.random.bernoulli, so payloads stay
byte-identical to the legacy three-pass path). See kernels/qsgd.py for
the design notes; this module is its 2-bit mirror.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import prng, ref
from repro.kernels.pack import pack_grid, tile_spec, word_shape, word_spec

BLOCK_R = 256
BLOCK_C = 512
_EPS = 1e-12


def _terngrad_kernel(x_ref, u_ref, scale_ref, o_ref):
    x = x_ref[...]
    u = u_ref[...]
    s = jnp.maximum(scale_ref[0, 0], _EPS)
    b = (u < jnp.abs(x) / s).astype(x.dtype)
    o_ref[...] = jnp.sign(x) * b * s


def _terngrad_rows_kernel(x_ref, u_ref, scale_ref, o_ref):
    x = x_ref[...]
    u = u_ref[...]
    s = jnp.maximum(scale_ref[...], _EPS)      # (BLOCK_R, 1): per-row scale
    b = (u < jnp.abs(x) / s).astype(x.dtype)
    o_ref[...] = jnp.sign(x) * b * s


def terngrad_pallas_rows(x: jax.Array, noise: jax.Array, scales: jax.Array,
                         *, interpret: bool) -> jax.Array:
    """Per-ROW-scale TernGrad: one fused dispatch for a whole UnitPlan
    bucket. scales: (R, 1) — max|x| of the unit each tile row belongs to."""
    R, C = x.shape
    assert R % BLOCK_R == 0 and C == BLOCK_C, (R, C)
    assert scales.shape == (R, 1), scales.shape
    return pl.pallas_call(
        _terngrad_rows_kernel,
        grid=(R // BLOCK_R,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x, noise, scales)


# --------------------------------------------------------------------------
# fused single-launch ternarize + word-pack (the wire encode hot path)
# --------------------------------------------------------------------------

TERN_WIDTH = 2


def _tern_pack_kernel(x_ref, k0_ref, k1_ref, s_ref, o_ref, *,
                      d: int, rpu: int):
    from repro.kernels.qsgd import _row_positions
    x = x_ref[...]                                   # (R, 512) f32
    pos = _row_positions(x.shape, rpu)
    u = prng.uniform_at(k0_ref[...], k1_ref[...], pos)
    codes = ref.terngrad_codes_ref(x, u, s_ref[...])
    codes = jnp.where(pos < d, codes, 0)             # zero word padding
    o_ref[...] = ref.pack_fields_tile(codes, TERN_WIDTH)


def _tern_unpack_kernel(w_ref, s_ref, o_ref):
    codes = ref.unpack_fields_tile(w_ref[...], TERN_WIDTH)
    o_ref[...] = ref.terngrad_decode_ref(codes, s_ref[...])


def terngrad_pack_pallas_rows(x: jax.Array, k0: jax.Array, k1: jax.Array,
                              scales: jax.Array, *, d: int, rpu: int,
                              interpret: bool) -> jax.Array:
    """Fused ternarize+pack over a bucket tile: x (R, 512) f32 with
    R == pack_tile_rows(R), per-row threefry key columns k0/k1 (R, 1)
    uint32 and unit scales (max|x| + 1e-12 already added) scales (R, 1)
    f32 -> (R//8, 256) uint32 payload words. ONE launch."""
    R, C = x.shape
    assert C == BLOCK_C, (R, C)
    assert k0.shape == k1.shape == scales.shape == (R, 1)
    col = tile_spec(R, 1)
    return pl.pallas_call(
        functools.partial(_tern_pack_kernel, d=d, rpu=rpu),
        grid=pack_grid(R),
        in_specs=[tile_spec(R), col, col, col],
        out_specs=word_spec(R, TERN_WIDTH),
        out_shape=word_shape(R, TERN_WIDTH),
        interpret=interpret,
    )(x, k0, k1, scales)


def terngrad_unpack_pallas_rows(words: jax.Array, scales: jax.Array, *,
                                interpret: bool) -> jax.Array:
    """Fused unpack+dequantize: words (R//8, 256) uint32 + per-row payload
    scales (R, 1) -> (R, 512) f32."""
    R = scales.shape[0]
    assert words.shape == word_shape(R, TERN_WIDTH).shape, words.shape
    return pl.pallas_call(
        _tern_unpack_kernel,
        grid=pack_grid(R),
        in_specs=[word_spec(R, TERN_WIDTH), tile_spec(R, 1)],
        out_specs=tile_spec(R),
        out_shape=jax.ShapeDtypeStruct((R, BLOCK_C), jnp.float32),
        interpret=interpret,
    )(words, scales)


def terngrad_pallas(x: jax.Array, noise: jax.Array, scale: jax.Array,
                    *, interpret: bool) -> jax.Array:
    R, C = x.shape
    assert R % BLOCK_R == 0 and C == BLOCK_C, (R, C)
    return pl.pallas_call(
        _terngrad_kernel,
        grid=(R // BLOCK_R,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x, noise, scale.reshape(1, 1))
