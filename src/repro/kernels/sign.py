"""Pallas TPU kernels: fused signSGD sign+PACK wire kernels and the
majority-vote kernel on packed words.

signSGD's wire format is the purest case: 1 bit per entry (x >= 0), no
statistic leg, no randomness. The pack kernel reads the (R, 512) f32
gradient tile and writes 16 uint32 words per row in ONE launch (1 f32
read + 1/32 word write per element); unpack mirrors it (the EF residual
rides outside the kernel — see kernels/qsgd.py on fp-contraction).

`majority_pallas` is the signSGD-with-majority-vote aggregation
(Bernstein et al.) operating DIRECTLY on the packed words: per-bit
worker counts are kept as word-wide bit planes via a ripple-carry adder
and compared against ceil(n/2) with a borrow chain
(kernels/ref.majority_words_ref) — the {0,1} bit tensor never exists on
either side of the vote, and ties resolve to +1 (the x >= 0 convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.pack import pack_grid, tile_spec, word_shape, word_spec

BLOCK_C = 512
MAJ_C = 512            # majority-vote word columns per grid step


def _sign_pack_kernel(x_ref, o_ref, *, d: int, rpu: int):
    from repro.kernels.qsgd import _row_positions
    x = x_ref[...]                                   # (R, 512) f32
    pos = _row_positions(x.shape, rpu)
    codes = jnp.where(pos < d, ref.sign_codes_ref(x), 0)
    o_ref[...] = ref.pack_fields_tile(codes, 1)


def _sign_unpack_kernel(w_ref, o_ref):
    codes = ref.unpack_fields_tile(w_ref[...], 1)
    o_ref[...] = ref.sign_decode_ref(codes)


def _majority_kernel(w_ref, o_ref):
    o_ref[...] = ref.majority_words_ref(w_ref[...])[None, :]


def sign_pack_pallas_rows(x: jax.Array, *, d: int, rpu: int,
                          interpret: bool) -> jax.Array:
    """Fused sign+pack over a bucket tile: x (R, 512) f32 with
    R == pack_tile_rows(R) (units of dim `d` spanning `rpu` rows each) ->
    (R//8, 128) uint32 sign words. ONE launch, no noise, no statistic."""
    R, C = x.shape
    assert C == BLOCK_C, (R, C)
    return pl.pallas_call(
        functools.partial(_sign_pack_kernel, d=d, rpu=rpu),
        grid=pack_grid(R),
        in_specs=[tile_spec(R)],
        out_specs=word_spec(R, 1),
        out_shape=word_shape(R, 1),
        interpret=interpret,
    )(x)


def sign_unpack_pallas_rows(words: jax.Array, *,
                            interpret: bool) -> jax.Array:
    """Fused unpack+decode: words (R//8, 128) uint32 -> (R, 512) f32
    signs."""
    R = words.shape[0] * 8
    assert words.shape == word_shape(R, 1).shape, words.shape
    return pl.pallas_call(
        _sign_unpack_kernel,
        grid=pack_grid(R),
        in_specs=[word_spec(R, 1)],
        out_specs=tile_spec(R),
        out_shape=jax.ShapeDtypeStruct((R, BLOCK_C), jnp.float32),
        interpret=interpret,
    )(words)


def majority_pallas(words: jax.Array, *,
                    interpret: bool) -> jax.Array:
    """(n_workers, W) uint32 packed sign words with W % MAJ_C == 0 ->
    (W,) majority words, never unpacking to bits. Zero-padded word
    columns vote 0 everywhere and are truncated by the caller."""
    n, W = words.shape
    assert W % MAJ_C == 0, (n, W)
    out = pl.pallas_call(
        _majority_kernel,
        grid=(W // MAJ_C,),
        in_specs=[pl.BlockSpec((n, MAJ_C), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, MAJ_C), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, W), jnp.uint32),
        interpret=interpret,
    )(words)
    return out[0]
