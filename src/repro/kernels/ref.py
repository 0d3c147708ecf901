"""Pure-jnp oracles for the compression kernels.

Each oracle is bit-compatible with its Pallas kernel given the same uniform
noise: the kernels are deterministic functions of (x, noise, params).
Shapes here are the kernels' canonical 2-D tiled layout (rows, 128·m).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array
_EPS = 1e-12


def qsgd_ref(x: Array, noise: Array, norm: Array, levels: int) -> Array:
    """Fused QSGD quantize+dequantize against a unit-level l2 norm.

    x (R, C) f32; noise (R, C) uniforms in [0,1); norm scalar f32.
    q_i = norm * sign(x_i) * floor(|x_i|/norm * s + u_i) / s
    """
    n = jnp.maximum(norm, _EPS)
    y = jnp.abs(x) / n * levels
    lev = jnp.floor(y + noise)
    return jnp.sign(x) * lev * (n / levels)


def terngrad_ref(x: Array, noise: Array, scale: Array) -> Array:
    """TernGrad quantize+dequantize: b_i ~ Bernoulli(|x_i|/scale);
    out = scale * sign(x) * b."""
    s = jnp.maximum(scale, _EPS)
    b = (noise < jnp.abs(x) / s).astype(x.dtype)
    return jnp.sign(x) * b * s


def topk_mask_ref(x: Array, k: int, iters: int = 24) -> Array:
    """Block-local top-k by magnitude via threshold bisection (per ROW).

    Keeps the elements with |x| >= thr where thr is the bisection estimate
    of the k-th largest magnitude (count(|x| >= thr) >= k >= count(> thr)).
    Identical arithmetic to the Pallas kernel: 'iters' halvings of
    [0, rowmax]. Ties at the threshold may keep slightly more than k.
    """
    mag = jnp.abs(x)
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(i, carry):
        lo, hi = carry
        thr = 0.5 * (lo + hi)
        cnt = jnp.sum(mag >= thr, axis=-1, keepdims=True)
        new_lo = jnp.where(cnt > k, thr, lo)
        new_hi = jnp.where(cnt > k, hi, thr)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    keep = mag >= lo
    return x * keep.astype(x.dtype)


# --------------------------------------------------------------------------
# quantize-to-codes oracles (the integer field streams the wire packs).
# Byte-identity contract: given the same uniforms as Compressor._quantize
# (jax.random.uniform / bernoulli — see kernels/prng.py), these produce the
# exact offset-binary codes the legacy three-pass wire path packs.
# --------------------------------------------------------------------------

def qsgd_codes_ref(x: Array, u: Array, nrm: Array, levels: int) -> Array:
    """QSGD offset-binary codes in [0, 2*levels]: stochastic-round
    |x|/nrm*levels with uniform u, then sign*level + levels. `nrm` is the
    unit l2 norm WITH the compressor's +1e-12 already added (broadcasts:
    scalar or per-row column)."""
    y = jnp.abs(x) / nrm * levels
    lo = jnp.floor(y)
    lev = lo + (u < (y - lo)).astype(y.dtype)
    return (jnp.sign(x) * lev).astype(jnp.int32) + levels


def terngrad_codes_ref(x: Array, u: Array, scale: Array) -> Array:
    """TernGrad codes in {0, 1, 2}: sign(x)*Bernoulli(|x|/scale) + 1.
    `scale` is max|x| WITH the compressor's +1e-12 already added."""
    b = (u < jnp.abs(x) / scale).astype(jnp.int32)
    return jnp.sign(x).astype(jnp.int32) * b + 1


def sign_codes_ref(x: Array) -> Array:
    """signSGD 1-bit codes: x >= 0."""
    return (x >= 0).astype(jnp.int32)


def qsgd_decode_ref(codes: Array, fac: Array, levels: int) -> Array:
    """Inverse of qsgd_codes_ref: (codes - levels) * fac where
    fac = nrm / levels is precomputed in the CALLER's compilation regime.
    (XLA strength-reduces division by a compile-time constant, so a
    kernel-side nrm / levels would not be bit-identical to the codec's
    eager dequant; the in-kernel multiply is a single exact IEEE op.)"""
    return (codes - levels).astype(jnp.float32) * fac


def terngrad_decode_ref(codes: Array, scale: Array) -> Array:
    return (codes - 1).astype(jnp.float32) * scale


def sign_decode_ref(codes: Array) -> Array:
    return (2 * codes - 1).astype(jnp.float32)


# --------------------------------------------------------------------------
# word-wise field packing: chunks of 32 width-bit fields -> exactly `width`
# uint32 words, with compile-time shift constants. Every 32-field chunk
# spans 32*width bits == width whole words, so ANY width packs without
# cross-chunk straddle — the core trick that removes the {0,1} bit-tensor
# (a 32x memory inflation) from both the jnp twins and the kernels.
#
# Layout: eight 512-field tile rows pack into one row of 128*width words,
# so both sides of the pack keep a lane-dense minor dimension (a multiple
# of 128) and neither Mosaic nor XLA pads a narrow one. The 32 fields of a
# word are brought onto the SUBLANE axis by 128x128 transposes (each
# 128-lane slice of a tile row is 4 chunks of 32 fields); the shifts and
# ORs then combine whole rows, and one transpose puts the words back on
# lanes. Shared by the Pallas kernel bodies (compiled or interpreted) and
# the jnp twins: pure jnp => identical arithmetic and identical payloads.
# --------------------------------------------------------------------------

#: tile rows whose fields fill one row of packed words
ROWS_PER_WORD_ROW = 8


def _word_pieces(width: int):
    """Per word t of a 32-field chunk: the (field j, shift) pairs landing
    in it (shift < 0: the field's low bits spill from word t-1)."""
    spans = []
    for t in range(width):
        spans.append([(j, j * width - 32 * t) for j in range(32)
                      if (j + 1) * width > 32 * t
                      and j * width < 32 * (t + 1)])
    return spans


def pack_fields_tile(fields: Array, width: int) -> Array:
    """(R, 512) int32 fields with R % 8 == 0, values < 2**width ->
    (R//8, 128*width) uint32 words. Read row-major, the words are the
    little-endian bit stream of the fields read row-major: field i's low
    bit lands at bit-stream position i*width."""
    R, C = fields.shape
    a = R // ROWS_PER_WORD_ROW
    v = fields.astype(jnp.uint32).reshape(a, ROWS_PER_WORD_ROW, C)
    # (sub-tile, chunk) on axis 0, field-in-chunk j on axis 1, a on lanes
    subs = [v[:, k, m:m + 128].T
            for k in range(ROWS_PER_WORD_ROW) for m in range(0, C, 128)]
    t = jnp.stack(subs).reshape(-1, 32, a)
    words = []
    for pieces in _word_pieces(width):
        w = None
        for j, s in pieces:
            f = t[:, j, :]
            f = f << jnp.uint32(s) if s >= 0 else f >> jnp.uint32(-s)
            w = f if w is None else w | f
        words.append(w)
    # rows (chunk, word-in-chunk) are the stream order; lanes back to a
    return jnp.stack(words, axis=1).reshape(-1, a).T


def unpack_fields_tile(words: Array, width: int) -> Array:
    """(A, 128*width) uint32 words -> (8*A, 512) int32 fields. Inverse of
    pack_fields_tile."""
    a, W = words.shape
    n_chunks = W // width
    z = words.T.reshape(n_chunks, width, a)
    mask = jnp.uint32((1 << width) - 1)
    fields = []
    for j in range(32):
        lo = j * width
        t0, s = lo // 32, lo % 32
        f = z[:, t0, :] >> jnp.uint32(s)
        if lo + width > 32 * (t0 + 1):               # straddles into t0+1
            f = f | (z[:, t0 + 1, :] << jnp.uint32(32 - s))
        fields.append(f & mask)
    f = jnp.stack(fields, axis=1).reshape(n_chunks // 4, 128, a)
    per_k = n_chunks // 4 // ROWS_PER_WORD_ROW       # 128-lane groups per row
    subs = [f[i].T for i in range(n_chunks // 4)]    # (a, 128) each
    rows = [jnp.concatenate(subs[k * per_k:(k + 1) * per_k], axis=1)
            for k in range(ROWS_PER_WORD_ROW)]
    return jnp.stack(rows, axis=1).reshape(
        ROWS_PER_WORD_ROW * a, per_k * 128).astype(jnp.int32)


def pack_fields_bitexpand_ref(vals: Array, width: int) -> Array:
    """The PRE-FUSION packing path, kept verbatim as the byte-identity
    oracle: expand each field to `width` {0,1} int32 bits (the 32x
    intermediate the fused paths eliminate), then weighted-sum into
    words. (k,) int32 -> (ceil(k*width/32),) uint32."""
    k = vals.shape[0]
    bits = ((vals[:, None] >> jnp.arange(width, dtype=jnp.int32)) & 1)
    flat = bits.reshape(k * width)
    pad = (-flat.shape[0]) % 32
    b = jnp.pad(flat, (0, pad)).reshape(-1, 32)
    return pack_bits_ref(b).reshape(-1)


# --------------------------------------------------------------------------
# bit-sliced majority vote on packed sign words: per-bit-position counts
# kept as word-wide bit PLANES (a ripple-carry adder over words), compared
# against ceil(n/2) with a borrow chain — O(n log n) word ops, and no
# {0,1} bit tensor ever exists. Ties resolve to +1 (2*count >= n), the
# x >= 0 sign convention.
# --------------------------------------------------------------------------

def majority_words_ref(words: Array) -> Array:
    """(n_workers, W) uint32 packed sign words -> (W,) majority words."""
    n, _ = words.shape
    planes = [jnp.zeros_like(words[0]) for _ in range(max(1, n.bit_length()))]
    for i in range(n):
        c = words[i]
        for pi in range(len(planes)):                # ripple-carry add 1 bit
            planes[pi], c = planes[pi] ^ c, planes[pi] & c
    thr = (n + 1) // 2                               # 2*count >= n
    borrow = jnp.zeros_like(words[0])
    for pi, a in enumerate(planes):                  # borrow of count - thr
        if (thr >> pi) & 1:
            borrow = ~a | borrow
        else:
            borrow = ~a & borrow
    return ~borrow                                   # count >= thr


def pack_bits_ref(bits: Array) -> Array:
    """(R, C) {0,1} int32 with C % 32 == 0 -> (R, C//32) uint32 words.

    Bit i of a row lands in word i//32 at position i%32 (little-endian bit
    order) — the layout the pack Pallas kernel and every wire codec
    (core/wire.py) share bit for bit.
    """
    R, C = bits.shape
    w = bits.reshape(R, C // 32, 32).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (w * weights).sum(axis=-1).astype(jnp.uint32)


def unpack_bits_ref(words: Array) -> Array:
    """(R, W) uint32 -> (R, 32*W) {0,1} int32. Inverse of pack_bits_ref."""
    R, W = words.shape
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(R, W * 32).astype(jnp.int32)


def rmsnorm_ref(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    """Row-wise RMSNorm (every arch's hot spot)."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)
