"""Elementwise threefry2x32 — jax.random.uniform, reproduced in-kernel.

The fused compress+pack kernels must draw the SAME stochastic-rounding
uniforms as `Compressor._quantize` (which calls jax.random.uniform /
jax.random.bernoulli) or their payloads stop being byte-identical to the
legacy three-pass wire path. jax.random can't be called inside a Pallas
kernel body, but its threefry2x32 generator is 20 rounds of uint32
add/xor/rotate — pure VPU work — so we reproduce it here as elementwise
jnp ops usable both inside kernel bodies and as a jit-able oracle.

`uniform_at(k0, k1, pos)` returns `jax.random.uniform(key, (n,))[pos]`
BIT-exactly for any draw length n > pos (tests/test_fused_kernels.py pins
this against jax itself, so a jax upgrade that changes the generator
fails loudly instead of silently corrupting payload identity). The
positional form is what a tiled kernel needs: each (row, lane) knows its
flat position inside the compression unit and evaluates only its own
counter.

Counter layout (jax's partitionable threefry path, the default since jax
0.5): a draw evaluates threefry2x32(key, (hi, lo)) on the 64-bit flat
index of each element split into two uint32 words, and XORs the two
output words — so position p (< 2**32) is o1 ^ o2 of the pair (0, p).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)
_ONE_F32 = np.uint32(0x3F800000)


def _rotl(x: Array, r: int) -> Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0: Array, k1: Array, x0: Array, x1: Array):
    """20-round threefry2x32 on broadcastable uint32 arrays — the exact
    arithmetic of jax's threefry2x32 primitive."""
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def random_bits_at(k0: Array, k1: Array, pos: Array) -> Array:
    """Bits of jax.random.bits(key, (n,))[pos] for uint32 keys (k0, k1).

    `pos` int32/uint32, any shape (positions past a unit's end are
    computed but meaningless — mask them downstream)."""
    p = pos.astype(jnp.uint32)
    o1, o2 = threefry2x32(k0, k1, jnp.zeros_like(p), p)
    return o1 ^ o2


def bits_to_uniform(bits: Array) -> Array:
    """uint32 bits -> f32 uniforms in [0, 1), jax.random.uniform's exact
    mantissa construction: (bits >> 9 | 0x3F800000) as float, minus 1."""
    fb = (bits >> np.uint32(9)) | _ONE_F32
    u = jax.lax.bitcast_convert_type(fb, jnp.float32) - 1.0
    return jnp.maximum(jnp.float32(0.0), u)


def uniform_at(k0: Array, k1: Array, pos: Array) -> Array:
    """jax.random.uniform(key, (n,))[pos], bit for bit, elementwise."""
    return bits_to_uniform(random_bits_at(k0, k1, pos))
