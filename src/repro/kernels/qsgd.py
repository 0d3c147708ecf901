"""Pallas TPU kernels: fused QSGD quantize + dequantize, and the fused
single-launch quantize+PACK wire kernels.

Elementwise + per-element stochastic rounding — pure VPU work. The unit
norm (layer-wise or entire-model, per the paper's granularity) is computed
outside and broadcast in as a scalar, so the SAME kernel serves both
granularities: the statistics unit is the caller's choice, which is
exactly the paper's subject.

Tiling: the flat gradient is reshaped to (rows, 128·LANES) and the grid
walks row-blocks of 8·SUBLANES — (8,128)-aligned VMEM tiles.

The `qsgd_pack_pallas_rows` / `qsgd_unpack_pallas_rows` family is
the wire hot path: ONE launch turns a whole UnitPlan bucket's gradient
tile into packed uint32 payload words (and back). Per element the pack
kernel reads 1 f32 and writes width/32 of a uint32 word — nothing else
touches memory: the stochastic-rounding uniforms are generated
IN-KERNEL from per-row threefry key columns (kernels/prng.py, bit-exact
to the jax.random.uniform draw of Compressor._quantize, so payloads stay
byte-identical to the legacy three-pass path), and the {0,1} bit tensor
of the old quantize -> bit-expand -> word-pack pipeline never exists
(kernels/ref.pack_fields_tile packs 32-field chunks with compile-time
shifts).

The error-feedback residual m = e - decode(words) deliberately does NOT
live in the unpack kernel: on the CPU backend LLVM's fp-contraction
fuses an in-kernel multiply+subtract into an FMA through every JAX-level
barrier (lax.optimization_barrier, bitcast laundering, fast-math flags —
all verified ineffective), which changes the residual's low bits versus
the two-step rounding the wire EF discipline is pinned to. ops.py forms
the residual in the caller's regime instead (see qsgd_unpack_ef_units).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import prng, ref
from repro.kernels.pack import pack_grid, tile_spec, word_shape, word_spec

BLOCK_R = 256          # rows per grid step (multiple of 8)
BLOCK_C = 512          # lane columns (multiple of 128)
_EPS = 1e-12


def _qsgd_kernel(x_ref, u_ref, norm_ref, o_ref, *, levels: int):
    x = x_ref[...]
    u = u_ref[...]
    n = jnp.maximum(norm_ref[0, 0], _EPS)
    y = jnp.abs(x) / n * levels
    lev = jnp.floor(y + u)
    o_ref[...] = jnp.sign(x) * lev * (n / levels)


def _qsgd_rows_kernel(x_ref, u_ref, norm_ref, o_ref, *, levels: int):
    x = x_ref[...]
    u = u_ref[...]
    n = jnp.maximum(norm_ref[...], _EPS)       # (BLOCK_R, 1): per-row scale
    y = jnp.abs(x) / n * levels
    lev = jnp.floor(y + u)
    o_ref[...] = jnp.sign(x) * lev * (n / levels)


def qsgd_pallas_rows(x: jax.Array, noise: jax.Array, norms: jax.Array,
                     levels: int, *, interpret: bool) -> jax.Array:
    """Per-ROW-scale QSGD: one fused dispatch for a whole UnitPlan bucket.

    x, noise: (R, C) f32 with R % BLOCK_R == 0, C == BLOCK_C; norms:
    (R, 1) f32 — the l2 norm of the compression unit each tile row belongs
    to (a unit spanning k tile rows repeats its norm k times). This is the
    batched form of qsgd_pallas: same arithmetic, unit statistics resolved
    per row instead of one scalar per launch."""
    R, C = x.shape
    assert R % BLOCK_R == 0 and C == BLOCK_C, (R, C)
    assert norms.shape == (R, 1), norms.shape
    return pl.pallas_call(
        functools.partial(_qsgd_rows_kernel, levels=levels),
        grid=(R // BLOCK_R,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x, noise, norms)


# --------------------------------------------------------------------------
# fused single-launch quantize + word-pack (the wire encode hot path)
# --------------------------------------------------------------------------

def _row_positions(block_shape, rpu: int):
    """Flat position of every (row, lane) inside its compression unit: a
    unit spans `rpu` consecutive tile rows of BLOCK_C lanes."""
    R, C = block_shape
    row = pl.program_id(0) * R + jax.lax.broadcasted_iota(jnp.int32,
                                                          (R, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    return (row % rpu) * C + col


def _qsgd_pack_kernel(x_ref, k0_ref, k1_ref, nrm_ref, o_ref, *,
                      levels: int, width: int, d: int, rpu: int):
    x = x_ref[...]                                   # (R, 512) f32
    pos = _row_positions(x.shape, rpu)
    u = prng.uniform_at(k0_ref[...], k1_ref[...], pos)
    codes = ref.qsgd_codes_ref(x, u, nrm_ref[...], levels)
    codes = jnp.where(pos < d, codes, 0)             # zero word padding
    o_ref[...] = ref.pack_fields_tile(codes, width)


def _qsgd_unpack_kernel(w_ref, fac_ref, o_ref, *, levels: int, width: int):
    codes = ref.unpack_fields_tile(w_ref[...], width)
    o_ref[...] = ref.qsgd_decode_ref(codes, fac_ref[...], levels)


def qsgd_pack_pallas_rows(x: jax.Array, k0: jax.Array, k1: jax.Array,
                          nrms: jax.Array, levels: int, width: int, *,
                          d: int, rpu: int, interpret: bool) -> jax.Array:
    """Fused quantize+pack over a bucket tile: x (R, 512) f32 with
    R == pack_tile_rows(R) (units of dim `d` spanning `rpu` rows each),
    per-row threefry key columns k0/k1 (R, 1) uint32 and unit norms nrms
    (R, 1) f32 (+1e-12 already added) -> (R//8, 128*width) uint32 payload
    words. ONE launch, 1 f32 read + 1 packed-word write per element."""
    R, C = x.shape
    assert C == BLOCK_C, (R, C)
    assert k0.shape == k1.shape == nrms.shape == (R, 1)
    col = tile_spec(R, 1)
    return pl.pallas_call(
        functools.partial(_qsgd_pack_kernel, levels=levels, width=width,
                          d=d, rpu=rpu),
        grid=pack_grid(R),
        in_specs=[tile_spec(R), col, col, col],
        out_specs=word_spec(R, width),
        out_shape=word_shape(R, width),
        interpret=interpret,
    )(x, k0, k1, nrms)


def qsgd_unpack_pallas_rows(words: jax.Array, facs: jax.Array, levels: int,
                            width: int, *, interpret: bool) -> jax.Array:
    """Fused unpack+dequantize: words (R//8, 128*width) uint32 + per-row
    dequant factors facs = norm/levels (R, 1), division done by the
    CALLER (see ref.qsgd_decode_ref) -> (R, 512) f32."""
    R = facs.shape[0]
    assert words.shape == word_shape(R, width).shape, (words.shape, width)
    return pl.pallas_call(
        functools.partial(_qsgd_unpack_kernel, levels=levels, width=width),
        grid=pack_grid(R),
        in_specs=[word_spec(R, width), tile_spec(R, 1)],
        out_specs=tile_spec(R),
        out_shape=jax.ShapeDtypeStruct((R, BLOCK_C), jnp.float32),
        interpret=interpret,
    )(words, facs)


def qsgd_pallas(x: jax.Array, noise: jax.Array, norm: jax.Array,
                levels: int, *, interpret: bool) -> jax.Array:
    """x, noise: (R, C) f32 with R % BLOCK_R == 0, C == BLOCK_C.
    norm: () f32. interpret=True runs the kernel body on CPU (validation);
    on TPU pass interpret=False."""
    R, C = x.shape
    assert R % BLOCK_R == 0 and C == BLOCK_C, (R, C)
    grid = (R // BLOCK_R,)
    return pl.pallas_call(
        functools.partial(_qsgd_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x, noise, norm.reshape(1, 1))
