"""Pallas TPU kernels: width-bit field packing, and the tile geometry every
pack/unpack kernel shares.

The hot inner loop of every wire codec (core/wire.py) is turning integer
fields (b-bit quantization levels, 1-bit signs, index records) into dense
uint32 words and back. A tile row holds PACK_C = 512 fields of one
compression unit; eight tile rows pack into one row of 128*width words
(kernels/ref.pack_fields_tile), so the words read row-major are the
little-endian bit stream of the fields and both sides of the kernel keep
a lane-dense minor dimension.

Grid: a tile of R rows (R a multiple of PACK_MIN_R) is walked in blocks
of min(R, PACK_BLOCK_R) rows; `pack_tile_rows` rounds a live row count
up to such an R. qsgd/terngrad/sign fuse their quantizers in front of the
same tile packer (kernels/{qsgd,terngrad,sign}.py) with the same specs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

PACK_C = 512           # fields per tile row (four 128-lane groups)
PACK_MIN_R = 64        # smallest tile: 8 rows of packed words
PACK_BLOCK_R = 1024    # tile rows per grid step once a tile outgrows one
_WR = ref.ROWS_PER_WORD_ROW


def pack_tile_rows(rows: int) -> int:
    """Tile rows holding `rows` live rows: a multiple of PACK_MIN_R up to
    one block, a multiple of PACK_BLOCK_R beyond it."""
    mult = PACK_MIN_R if rows <= PACK_BLOCK_R else PACK_BLOCK_R
    return -(-max(rows, 1) // mult) * mult


def _block_rows(R: int) -> int:
    assert R == pack_tile_rows(R), R
    return min(R, PACK_BLOCK_R)


def pack_grid(R: int):
    return (R // _block_rows(R),)


def tile_spec(R: int, cols: int = PACK_C) -> pl.BlockSpec:
    """Block of tile rows: (block, 512) fields/values, or a (block, 1)
    per-row column (unit statistic, key word)."""
    return pl.BlockSpec((_block_rows(R), cols), lambda i: (i, 0))


def word_spec(R: int, width: int) -> pl.BlockSpec:
    """The packed words of one block of tile rows."""
    return pl.BlockSpec((_block_rows(R) // _WR, PACK_C // 4 * width),
                        lambda i: (i, 0))


def word_shape(R: int, width: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((R // _WR, PACK_C // 4 * width), jnp.uint32)


def _fields_pack_kernel(f_ref, o_ref, *, width: int):
    o_ref[...] = ref.pack_fields_tile(f_ref[...], width)


def _fields_unpack_kernel(w_ref, o_ref, *, width: int):
    o_ref[...] = ref.unpack_fields_tile(w_ref[...], width)


def fields_pack_pallas(fields: jax.Array, width: int, *,
                       interpret: bool) -> jax.Array:
    """(R, 512) int32 fields (values < 2**width; R == pack_tile_rows(R))
    -> (R//8, 128*width) uint32 words."""
    R, C = fields.shape
    assert C == PACK_C, (R, C)
    return pl.pallas_call(
        functools.partial(_fields_pack_kernel, width=width),
        grid=pack_grid(R),
        in_specs=[tile_spec(R)],
        out_specs=word_spec(R, width),
        out_shape=word_shape(R, width),
        interpret=interpret,
    )(fields)


def fields_unpack_pallas(words: jax.Array, width: int, *,
                         interpret: bool) -> jax.Array:
    """(R//8, 128*width) uint32 -> (R, 512) int32 fields. Inverse of
    fields_pack_pallas."""
    R = words.shape[0] * _WR
    assert words.shape == word_shape(R, width).shape, (words.shape, width)
    return pl.pallas_call(
        functools.partial(_fields_unpack_kernel, width=width),
        grid=pack_grid(R),
        in_specs=[word_spec(R, width)],
        out_specs=tile_spec(R),
        out_shape=jax.ShapeDtypeStruct((R, PACK_C), jnp.int32),
        interpret=interpret,
    )(words)
