"""jit'd public wrappers around the Pallas kernels.

Handle padding/reshaping from arbitrary flat gradients to the kernels'
(rows, 512) tiled layout, generate the stochastic-rounding uniforms, and
run the pure-jnp reference instead when a caller passes use_pallas=False
(the tests compare the two). How a kernel runs follows
`jax.default_backend()` when the op is traced (`interpret_mode`):
compiled by Mosaic on TPU, interpreted on CPU, refused anywhere else.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import prng, ref
from repro.kernels.pack import (fields_pack_pallas, fields_unpack_pallas,
                                pack_tile_rows)
from repro.kernels.qsgd import (BLOCK_C, BLOCK_R, qsgd_pack_pallas_rows,
                                qsgd_pallas, qsgd_pallas_rows,
                                qsgd_unpack_pallas_rows)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.sign import (MAJ_C, majority_pallas, sign_pack_pallas_rows,
                                sign_unpack_pallas_rows)
from repro.kernels.terngrad import (terngrad_pack_pallas_rows,
                                    terngrad_pallas, terngrad_pallas_rows,
                                    terngrad_unpack_pallas_rows)
from repro.kernels.topk_mask import topk_mask_pallas

Array = jax.Array


def interpret_mode() -> bool:
    """The Pallas `interpret` flag for the default backend: False (Mosaic
    compiles the kernel) on TPU, True (the body runs as jax ops) on CPU.
    Any other platform has no Pallas path here, so it is an error rather
    than a silent fallback."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"no Pallas mode for platform {platform!r}: the "
                       f"kernels compile for tpu and are interpreted on cpu")


def _tile(x: Array):
    """flat (d,) -> padded (R, 512) with R % BLOCK_R == 0."""
    d = x.size
    cols = BLOCK_C
    rows = -(-d // cols)
    rows = -(-rows // BLOCK_R) * BLOCK_R
    pad = rows * cols - d
    xt = jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, cols)
    return xt, d


def _untile(xt: Array, d: int, shape) -> Array:
    return xt.reshape(-1)[:d].reshape(shape)


@partial(jax.jit, static_argnames=("levels", "use_pallas"))
def qsgd_compress(x: Array, key: Array, levels: int = 16,
                  use_pallas: bool = True) -> Array:
    """Fused QSGD quantize+dequantize over the WHOLE input (the caller
    picks the granularity unit, per the paper)."""
    xf = x.astype(jnp.float32)
    norm = jnp.linalg.norm(xf.reshape(-1))
    xt, d = _tile(xf)
    noise = jax.random.uniform(key, xt.shape)
    if use_pallas:
        out = qsgd_pallas(xt, noise, norm, levels, interpret=interpret_mode())
    else:
        out = ref.qsgd_ref(xt, noise, norm, levels)
    return _untile(out, d, x.shape).astype(x.dtype)


@partial(jax.jit, static_argnames=("use_pallas",))
def terngrad_compress(x: Array, key: Array, use_pallas: bool = True) -> Array:
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf))
    xt, d = _tile(xf)
    noise = jax.random.uniform(key, xt.shape)
    if use_pallas:
        out = terngrad_pallas(xt, noise, scale, interpret=interpret_mode())
    else:
        out = ref.terngrad_ref(xt, noise, scale)
    return _untile(out, d, x.shape).astype(x.dtype)


@partial(jax.jit, static_argnames=("k_per_block", "use_pallas"))
def blockwise_topk(x: Array, k_per_block: int,
                   use_pallas: bool = True) -> Array:
    """Block-local top-k mask: each 512-element row keeps its k largest
    magnitudes (the 'blockwise' granularity of core.granularity, realized
    natively on TPU tiles)."""
    xf = x.astype(jnp.float32)
    xt, d = _tile(xf)
    if use_pallas:
        out = topk_mask_pallas(xt, k_per_block, interpret=interpret_mode())
    else:
        out = ref.topk_mask_ref(xt, k_per_block)
    return _untile(out, d, x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# UnitPlan bucket entry points: a bucket matrix (n_units, d) compresses in
# ONE kernel dispatch. Each unit's statistic (l2 norm / max) is computed per
# row, tiled into the kernels' (R, 512) layout alongside the data, and the
# per-row-scale kernel variants consume it — the batched counterpart of the
# scalar-statistic wrappers above.
# --------------------------------------------------------------------------

def _tile_units(x2d: Array):
    """(n, d) bucket -> ((R, 512) tiles, live_rows, tile_rows_per_unit)."""
    n, d = x2d.shape
    rpu = -(-d // BLOCK_C)
    xp = jnp.pad(x2d, ((0, 0), (0, rpu * BLOCK_C - d)))
    rows = n * rpu
    R = -(-rows // BLOCK_R) * BLOCK_R
    xt = jnp.pad(xp.reshape(rows, BLOCK_C), ((0, R - rows), (0, 0)))
    return xt, rows, rpu


def _unit_noise(keys: Array, n: int, rpu: int, R: int) -> Array:
    """Per-unit uniforms over the padded tile span, one key per unit."""
    noise = jax.vmap(
        lambda k: jax.random.uniform(k, (rpu * BLOCK_C,)))(keys)
    return jnp.pad(noise.reshape(n * rpu, BLOCK_C), ((0, R - n * rpu),
                                                     (0, 0)))


def _row_scales(stat: Array, rpu: int, R: int) -> Array:
    """(n,) per-unit statistic -> (R, 1) per-tile-row scale column."""
    rows = stat.shape[0] * rpu
    s = jnp.repeat(stat, rpu)
    return jnp.pad(s, (0, R - rows), constant_values=1.0)[:, None]


@partial(jax.jit, static_argnames=("levels", "use_pallas"))
def qsgd_compress_units(x2d: Array, keys: Array, levels: int = 16,
                        use_pallas: bool = True) -> Array:
    """Fused QSGD over a whole bucket: rows of `x2d` are compression units
    (each with its own l2 norm), `keys` one PRNG key per unit. One Pallas
    dispatch regardless of the number of units."""
    xf = x2d.astype(jnp.float32)
    n, d = xf.shape
    norms = jnp.linalg.norm(xf, axis=1)
    xt, rows, rpu = _tile_units(xf)
    R = xt.shape[0]
    noise = _unit_noise(keys, n, rpu, R)
    scales = _row_scales(norms, rpu, R)
    if use_pallas:
        out = qsgd_pallas_rows(xt, noise, scales, levels,
                               interpret=interpret_mode())
    else:
        out = ref.qsgd_ref(xt, noise, scales, levels)  # (R,1) broadcasts
    return out[:rows].reshape(n, rpu * BLOCK_C)[:, :d].astype(x2d.dtype)


@partial(jax.jit, static_argnames=("use_pallas",))
def terngrad_compress_units(x2d: Array, keys: Array,
                            use_pallas: bool = True) -> Array:
    """Fused TernGrad over a whole bucket (per-row max scale)."""
    xf = x2d.astype(jnp.float32)
    n, d = xf.shape
    scales_u = jnp.max(jnp.abs(xf), axis=1)
    xt, rows, rpu = _tile_units(xf)
    R = xt.shape[0]
    noise = _unit_noise(keys, n, rpu, R)
    scales = _row_scales(scales_u, rpu, R)
    if use_pallas:
        out = terngrad_pallas_rows(xt, noise, scales,
                                   interpret=interpret_mode())
    else:
        out = ref.terngrad_ref(xt, noise, scales)
    return out[:rows].reshape(n, rpu * BLOCK_C)[:, :d].astype(x2d.dtype)


_UNIT_KERNELS = {
    "qsgd": lambda x, k, kw: qsgd_compress_units(
        x, k, kw.get("levels", 16), kw.get("use_pallas", True)),
    "terngrad": lambda x, k, kw: terngrad_compress_units(
        x, k, kw.get("use_pallas", True)),
}


def plan_compress(plan, grads, key: Array, kind: str = "qsgd", **kw):
    """Compress a gradient pytree through the Pallas kernels, driven by a
    core.plan.UnitPlan: gather each bucket, ONE fused kernel dispatch per
    bucket, scatter back.

    The per-unit PRNG KEYS come from the plan's fold tables (same keys as
    the jnp execution path), but the uniform draws differ: the kernel
    wrappers draw noise over the padded (rows, 512) tile span, while
    Compressor.sim draws exactly d uniforms — so outputs are the same
    operator family with the same per-unit statistics, NOT bit-identical
    to plan.execute(comp.sim, ...)."""
    if kind not in _UNIT_KERNELS:
        raise ValueError(f"no bucket kernel for {kind!r}; "
                         f"have {sorted(_UNIT_KERNELS)}")
    run = _UNIT_KERNELS[kind]
    flat = plan.flatten(grads)
    keys = plan.unit_keys(key)
    out = jnp.zeros((plan.exec_total,), jnp.float32)
    for b in plan.buckets:
        x = plan.gather_bucket(flat, b)
        kb = keys[jnp.asarray(b.unit_ids, jnp.int32)]
        out = plan.scatter_bucket(out, b, run(x, kb, kw))
    return plan.unflatten(out)


# --------------------------------------------------------------------------
# fused single-launch compress+pack ops (the wire hot path).
#
# A bucket matrix (n, d) becomes packed uint32 payload words in ONE kernel
# launch per bucket: quantize + word-pack fused, stochastic-rounding
# uniforms generated in-kernel from per-row threefry key columns
# (kernels/prng.py, bit-exact to jax.random), the {0,1} bit tensor of the
# legacy quantize -> bit-expand -> word-pack pipeline never materialized.
# The use_pallas=False twins run the IDENTICAL arithmetic (same tile
# helpers from kernels/ref.py) outside pallas_call — the references the
# tests hold the kernels to byte for byte; the codecs always run the
# kernels. Decode mirrors encode; the *_unpack_ef variants chain the
# single decode launch with the error-feedback residual m = e - xhat
# formed in the CALLER's regime (they are deliberately not jitted) —
# compiled together, the CPU backend's LLVM fp-contraction turns the
# mul+sub into an FMA through every JAX-level barrier, silently changing
# the low bits the EF discipline is pinned to.
# --------------------------------------------------------------------------

def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def words_per_unit(d: int, width: int) -> int:
    """uint32 payload words of one unit's packed field leg."""
    return _cdiv(d * width, 32)


def _tile_rows(x2d: Array):
    """(n, d) bucket -> ((R, 512) tiles, live_rows, rows_per_unit), with
    R = pack_tile_rows(live_rows)."""
    n, d = x2d.shape
    rpu = _cdiv(d, BLOCK_C)
    xp = jnp.pad(x2d, ((0, 0), (0, rpu * BLOCK_C - d)))
    rows = n * rpu
    R = pack_tile_rows(rows)
    xt = jnp.pad(xp.reshape(rows, BLOCK_C), ((0, R - rows), (0, 0)))
    return xt, rows, rpu


def _tile_word_rows(w2d: Array, width: int, rpu: int):
    """(n, words_per_unit) payload words -> ((R//8, 128*width) word tiles,
    live_rows, R): each unit's words padded to its rpu tile rows."""
    n, wpu = w2d.shape
    wpr = (BLOCK_C // 32) * width                    # words per tile row
    rows = n * rpu
    R = pack_tile_rows(rows)
    wp = jnp.pad(w2d, ((0, 0), (0, rpu * wpr - wpu))).reshape(-1)
    wt = jnp.pad(wp, (0, (R - rows) * wpr)).reshape(R // 8, 8 * wpr)
    return wt, rows, R


def _untile_words(wt: Array, n: int, rows: int, wpu: int) -> Array:
    """Word tiles -> (n, words_per_unit), dropping the in-unit word
    padding and the row padding."""
    wpr = wt.shape[1] // 8
    return wt.reshape(-1)[:rows * wpr].reshape(n, -1)[:, :wpu]


def _untile_rows(xt: Array, n: int, rows: int, d: int) -> Array:
    return xt[:rows].reshape(n, -1)[:, :d]


def _unit_col(v: Array, rpu: int, R: int) -> Array:
    """(n,) per-unit value -> (R, 1) per-tile-row column (repeat rpu,
    zero-pad the dead rows)."""
    s = jnp.repeat(v, rpu)
    return jnp.pad(s, (0, R - s.shape[0]))[:, None]


def _key_cols(keys: Array, rpu: int, R: int):
    """Per-unit PRNG keys (typed or raw uint32 (n, 2)) -> two (R, 1)
    uint32 key-word columns for the in-kernel threefry."""
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        kd = jax.random.key_data(keys)
    else:
        kd = keys
    kd = kd.astype(jnp.uint32)
    return (_unit_col(kd[:, 0], rpu, R), _unit_col(kd[:, 1], rpu, R))


def _tile_positions(R: int, rpu: int) -> Array:
    """In-unit flat position of every (row, lane) of a full tile — the
    jnp twin of the kernels' _row_positions."""
    row = jnp.arange(R, dtype=jnp.int32)[:, None]
    col = jnp.arange(BLOCK_C, dtype=jnp.int32)[None, :]
    return (row % rpu) * BLOCK_C + col


@partial(jax.jit, static_argnames=("levels", "width", "use_pallas"))
def qsgd_pack_units(x2d: Array, keys: Array, levels: int, width: int,
                    use_pallas: bool = True):
    """Fused QSGD encode of a whole bucket: (n, d) f32 + per-unit keys ->
    ((n, words_per_unit(d, width)) uint32 payload words, (n,) f32 norms).

    The norms already include the compressor's +1e-12 and are EXACTLY the
    payload norm field; words are byte-identical to the legacy
    quantize -> offset-code -> bit-expand -> pack pipeline."""
    xf = x2d.astype(jnp.float32)
    n, d = xf.shape
    nrms = jnp.linalg.norm(xf, axis=1) + 1e-12
    xt, rows, rpu = _tile_rows(xf)
    R = xt.shape[0]
    k0, k1 = _key_cols(keys, rpu, R)
    nc = _unit_col(nrms, rpu, R)
    if use_pallas:
        wt = qsgd_pack_pallas_rows(xt, k0, k1, nc, levels, width,
                                   d=d, rpu=rpu, interpret=interpret_mode())
    else:
        pos = _tile_positions(R, rpu)
        u = prng.uniform_at(k0, k1, pos)
        codes = jnp.where(pos < d,
                          ref.qsgd_codes_ref(xt, u, nc, levels), 0)
        wt = ref.pack_fields_tile(codes, width)
    return _untile_words(wt, n, rows, words_per_unit(d, width)), nrms


@partial(jax.jit, static_argnames=("d", "levels", "width", "use_pallas"))
def qsgd_unpack_units(words: Array, nrms: Array, d: int, levels: int,
                      width: int, use_pallas: bool = True) -> Array:
    """Fused QSGD decode: (n, words_per_unit) uint32 + payload norms ->
    (n, d) f32 — unpack + dequantize in one launch."""
    n = words.shape[0]
    rpu = _cdiv(d, BLOCK_C)
    wt, rows, R = _tile_word_rows(words, width, rpu)
    # divide in the CALLER's regime (ref.qsgd_decode_ref explains why)
    fc = _unit_col(nrms.astype(jnp.float32) / levels, rpu, R)
    if use_pallas:
        xt = qsgd_unpack_pallas_rows(wt, fc, levels, width,
                                     interpret=interpret_mode())
    else:
        codes = ref.unpack_fields_tile(wt, width)
        xt = ref.qsgd_decode_ref(codes, fc, levels)
    return _untile_rows(xt, n, rows, d)


def qsgd_unpack_ef_units(words: Array, nrms: Array, e2d: Array, d: int,
                         levels: int, width: int, use_pallas: bool = True):
    """QSGD decode + error-feedback: ONE unpack+dequantize launch, then
    the residual m = e - xhat formed in the caller's regime -> (xhat, m).

    The subtract deliberately stays OUTSIDE the kernel: LLVM fp-contraction
    on the CPU backend fuses an in-kernel mul+sub into an FMA through
    every JAX-expressible barrier (optimization_barrier, bitcast
    laundering, fast-math flags — all verified ineffective), which flips
    the residual's low bits versus the two-step rounding the wire EF
    discipline (core/wire.py with-state path) is bitwise-pinned to."""
    xhat = qsgd_unpack_units(words, nrms, d, levels, width,
                             use_pallas=use_pallas)
    return xhat, e2d.astype(jnp.float32) - xhat


@partial(jax.jit, static_argnames=("use_pallas",))
def terngrad_pack_units(x2d: Array, keys: Array, use_pallas: bool = True):
    """Fused TernGrad encode: (n, d) f32 + per-unit keys -> ((n,
    words_per_unit(d, 2)) uint32 words, (n,) f32 scales incl. +1e-12)."""
    xf = x2d.astype(jnp.float32)
    n, d = xf.shape
    scales = jnp.max(jnp.abs(xf), axis=1) + 1e-12
    xt, rows, rpu = _tile_rows(xf)
    R = xt.shape[0]
    k0, k1 = _key_cols(keys, rpu, R)
    sc = _unit_col(scales, rpu, R)
    if use_pallas:
        wt = terngrad_pack_pallas_rows(xt, k0, k1, sc, d=d, rpu=rpu,
                                       interpret=interpret_mode())
    else:
        pos = _tile_positions(R, rpu)
        u = prng.uniform_at(k0, k1, pos)
        codes = jnp.where(pos < d, ref.terngrad_codes_ref(xt, u, sc), 0)
        wt = ref.pack_fields_tile(codes, 2)
    return _untile_words(wt, n, rows, words_per_unit(d, 2)), scales


@partial(jax.jit, static_argnames=("d", "use_pallas"))
def terngrad_unpack_units(words: Array, scales: Array, d: int,
                          use_pallas: bool = True) -> Array:
    """Fused TernGrad decode: words + payload scales -> (n, d) f32."""
    n = words.shape[0]
    rpu = _cdiv(d, BLOCK_C)
    wt, rows, R = _tile_word_rows(words, 2, rpu)
    sc = _unit_col(scales.astype(jnp.float32), rpu, R)
    if use_pallas:
        xt = terngrad_unpack_pallas_rows(wt, sc, interpret=interpret_mode())
    else:
        xt = ref.terngrad_decode_ref(ref.unpack_fields_tile(wt, 2), sc)
    return _untile_rows(xt, n, rows, d)


def terngrad_unpack_ef_units(words: Array, scales: Array, e2d: Array,
                             d: int, use_pallas: bool = True):
    """TernGrad decode + EF residual (caller-regime subtract, see
    qsgd_unpack_ef_units for the fp-contraction rationale) -> (xhat, m)."""
    xhat = terngrad_unpack_units(words, scales, d, use_pallas=use_pallas)
    return xhat, e2d.astype(jnp.float32) - xhat


@partial(jax.jit, static_argnames=("use_pallas",))
def sign_pack_units(x2d: Array, use_pallas: bool = True) -> Array:
    """Fused signSGD encode: (n, d) f32 -> (n, words_per_unit(d, 1))
    uint32 sign words (bit = x >= 0). No statistic, no randomness."""
    xf = x2d.astype(jnp.float32)
    n, d = xf.shape
    xt, rows, rpu = _tile_rows(xf)
    if use_pallas:
        wt = sign_pack_pallas_rows(xt, d=d, rpu=rpu,
                                   interpret=interpret_mode())
    else:
        pos = _tile_positions(xt.shape[0], rpu)
        codes = jnp.where(pos < d, ref.sign_codes_ref(xt), 0)
        wt = ref.pack_fields_tile(codes, 1)
    return _untile_words(wt, n, rows, words_per_unit(d, 1))


@partial(jax.jit, static_argnames=("d", "use_pallas"))
def sign_unpack_units(words: Array, d: int,
                      use_pallas: bool = True) -> Array:
    """Fused signSGD decode: sign words -> (n, d) f32 in {-1, +1}."""
    n = words.shape[0]
    rpu = _cdiv(d, BLOCK_C)
    wt, rows, _ = _tile_word_rows(words, 1, rpu)
    if use_pallas:
        xt = sign_unpack_pallas_rows(wt, interpret=interpret_mode())
    else:
        xt = ref.sign_decode_ref(ref.unpack_fields_tile(wt, 1))
    return _untile_rows(xt, n, rows, d)


def sign_unpack_ef_units(words: Array, e2d: Array, d: int,
                         use_pallas: bool = True):
    """signSGD decode + EF residual (caller-regime subtract, see
    qsgd_unpack_ef_units for the fp-contraction rationale) -> (xhat, m)."""
    xhat = sign_unpack_units(words, d, use_pallas=use_pallas)
    return xhat, e2d.astype(jnp.float32) - xhat


@partial(jax.jit, static_argnames=("width", "use_pallas"))
def fields_pack_units(f2d: Array, width: int,
                      use_pallas: bool = True) -> Array:
    """Generic word-wise field packing of a bucket: (n, k) int32 fields
    (values < 2**width) -> (n, words_per_unit(k, width)) uint32 words,
    each unit's leg separately word-padded (the wire padding rule). The
    single-launch pack leg of the natural and sparse-index codecs."""
    n, k = f2d.shape
    ft, rows, rpu = _tile_rows(f2d.astype(jnp.int32))
    pos = _tile_positions(ft.shape[0], rpu)
    ft = jnp.where(pos < k, ft, 0)                   # zero word padding
    if use_pallas:
        wt = fields_pack_pallas(ft, width, interpret=interpret_mode())
    else:
        wt = ref.pack_fields_tile(ft, width)
    return _untile_words(wt, n, rows, words_per_unit(k, width))


@partial(jax.jit, static_argnames=("k", "width", "use_pallas"))
def fields_unpack_units(words: Array, k: int, width: int,
                        use_pallas: bool = True) -> Array:
    """Inverse of fields_pack_units -> (n, k) int32."""
    n = words.shape[0]
    rpu = _cdiv(k, BLOCK_C)
    wt, rows, _ = _tile_word_rows(words, width, rpu)
    if use_pallas:
        ft = fields_unpack_pallas(wt, width, interpret=interpret_mode())
    else:
        ft = ref.unpack_fields_tile(wt, width)
    return _untile_rows(ft, n, rows, k)


def pack_fields(vals: Array, width: int,
                use_pallas: bool = True) -> Array:
    """(k,) int32 fields -> (ceil(k*width/32),) uint32 words via WORD-WISE
    shifts — the per-unit field legs of the wire codecs (width 1 packs a
    {0,1} bit vector: bit i in word i//32 at position i%32). Replaces the
    legacy bit-expansion path (ref.pack_fields_bitexpand_ref, kept as the
    byte-identity oracle), whose k*width int32 bit tensor was a 32x
    memory inflation."""
    return fields_pack_units(vals[None], width, use_pallas=use_pallas)[0]


def unpack_fields(words: Array, k: int, width: int,
                  use_pallas: bool = True) -> Array:
    """Inverse of pack_fields -> int32 (k,)."""
    return fields_unpack_units(words[None], k, width,
                               use_pallas=use_pallas)[0]


@partial(jax.jit, static_argnames=("use_pallas",))
def majority_words(words2d: Array, use_pallas: bool = True) -> Array:
    """(n_workers, W) uint32 packed sign words -> (W,) majority-vote
    words (ties -> +1), computed DIRECTLY on the packed words via
    bit-sliced ripple-carry counting — the {0,1} bit tensor never exists
    (kernels/ref.majority_words_ref is the arithmetic on both paths)."""
    n, W = words2d.shape
    if use_pallas:
        Wp = _cdiv(W, MAJ_C) * MAJ_C
        wp = jnp.pad(words2d, ((0, 0), (0, Wp - W)))
        return majority_pallas(wp, interpret=interpret_mode())[:W]
    return ref.majority_words_ref(words2d)


# --------------------------------------------------------------------------
# chunk-granular dispatch (the streaming collective's unit of wire motion)
# --------------------------------------------------------------------------

def chunk_runs(sizes, chunk_bytes):
    """Partition consecutive payload regions into dispatch chunks.

    `sizes` are per-region byte counts (one fused message's per-bucket
    payload regions, in buffer order); the return value is a tuple of
    runs — tuples of region indices — covering 0..len(sizes)-1 in order.
    A run accumulates consecutive regions until its bytes reach
    `chunk_bytes`, then closes (the same greedy rule build_schedule uses
    for message fusion, one level down). `chunk_bytes` None or inf means
    one chunk for the whole message; 0 means one chunk per region. A
    single region larger than the threshold still gets its own chunk —
    regions are never split, so every chunk decodes with whole-bucket
    pack/unpack dispatches (chunk boundaries align with bucket regions,
    which is what lets the streaming executor decode each chunk the hop
    it arrives).
    """
    sizes = [int(s) for s in sizes]
    if not sizes:
        return ()
    if chunk_bytes is None or chunk_bytes != chunk_bytes or \
            chunk_bytes == float("inf"):
        return (tuple(range(len(sizes))),)
    cb = float(chunk_bytes)
    if cb < 0:
        raise ValueError(f"chunk_bytes must be >= 0, got {chunk_bytes!r}")
    runs, cur, cur_bytes = [], [], 0
    for i, s in enumerate(sizes):
        cur.append(i)
        cur_bytes += s
        if cur_bytes >= cb:
            runs.append(tuple(cur))
            cur, cur_bytes = [], 0
    if cur:
        runs.append(tuple(cur))
    return tuple(runs)


# --------------------------------------------------------------------------
# bytes-moved accounting (from the kernel specs, NOT wall-clocks: on this
# interpret-mode container microseconds measure Python, so BENCH artifacts
# gate on deterministic traffic counts — the repo's standing convention).
# --------------------------------------------------------------------------

def pack_bytes_moved(width: int, fused: bool, stochastic: bool = True):
    """Per-ELEMENT memory traffic of one bucket encode, from the kernel
    specs. Fused: the single launch reads the f32 tile (+ the per-row
    key/statistic columns, 12 bytes per 512-lane row) and writes width/8
    payload bytes; nothing else exists. Legacy (three-pass): quantize
    writes + re-reads an int32 code vector, bit-expansion writes +
    re-reads a width*4-byte {0,1} int32 tensor per element — the 32x
    inflation the fused path deletes. The per-unit statistic reduction
    (norm / max|x|) reads the input once on BOTH paths and is reported
    separately as stat_read so the kernel-proper gate stays honest."""
    cols = 12 if stochastic else 0                   # k0,k1,stat per row
    if fused:
        return {
            "read_bytes_per_elt": 4.0 + cols / BLOCK_C,
            "write_bytes_per_elt": width / 8.0,
            "intermediate_bytes_per_elt": 0.0,
            "stat_read_bytes_per_elt": 4.0 if stochastic else 0.0,
            "passes_over_data": 1,
            "launches_per_bucket": 1,
        }
    # legacy compose-of-passes: quantize -> {0,1} bit-expand -> word-pack
    inter = 4.0 + width * 4.0                        # codes + bit tensor
    return {
        "read_bytes_per_elt": 4.0 + inter,
        "write_bytes_per_elt": width / 8.0 + inter,
        "intermediate_bytes_per_elt": inter,
        "stat_read_bytes_per_elt": 4.0 if stochastic else 0.0,
        "passes_over_data": 3,
        "launches_per_bucket": 3,
    }


def unpack_bytes_moved(width: int, fused: bool, ef: bool = False):
    """Per-element decode traffic: fused reads width/8 payload bytes and
    writes the 4-byte f32 in ONE launch; legacy re-materializes the {0,1}
    bit tensor then the code vector before dequantizing. EF adds the
    residual pass m = e - xhat, which on BOTH paths runs in the caller's
    regime (re-read xhat + read e, write m — fp-contraction forbids an
    in-kernel subtract, see the *_unpack_ef_units docstrings), so the
    fused EF decode is 1 kernel launch + 1 elementwise pass."""
    if fused:
        return {
            "read_bytes_per_elt": width / 8.0 + (8.0 if ef else 0.0),
            "write_bytes_per_elt": 4.0 + (4.0 if ef else 0.0),
            "intermediate_bytes_per_elt": 0.0,
            "passes_over_data": 1 + (1 if ef else 0),
            "launches_per_bucket": 1,
        }
    inter = 4.0 + width * 4.0
    return {
        "read_bytes_per_elt": width / 8.0 + inter + (8.0 if ef else 0.0),
        "write_bytes_per_elt": 4.0 + inter + (4.0 if ef else 0.0),
        "intermediate_bytes_per_elt": inter,
        "passes_over_data": 3 + (1 if ef else 0),
        "launches_per_bucket": 3,
    }


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of pallas_call equations in fn's jaxpr (recursively) — the
    dispatch count BENCH_kernels.json records per op."""
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)

    def walk(jx) -> int:
        total = 0
        for eqn in jx.eqns:
            if "pallas_call" in eqn.primitive.name:
                total += 1
            for v in eqn.params.values():
                objs = v if isinstance(v, (tuple, list)) else (v,)
                for o in objs:
                    inner = getattr(o, "jaxpr", None)
                    if inner is not None:
                        total += walk(inner)
        return total

    return walk(jaxpr.jaxpr)


@partial(jax.jit, static_argnames=("eps", "use_pallas"))
def rmsnorm(x: Array, gamma: Array, eps: float = 1e-5,
            use_pallas: bool = True) -> Array:
    """(..., D) rowwise RMSNorm with D % 128 == 0."""
    shape = x.shape
    D = shape[-1]
    xr = x.reshape(-1, D)
    R = xr.shape[0]
    from repro.kernels.rmsnorm import BLOCK_R as NR
    pad = (-R) % NR
    xp = jnp.pad(xr, ((0, pad), (0, 0)))
    if use_pallas:
        out = rmsnorm_pallas(xp, gamma, eps, interpret=interpret_mode())
    else:
        out = ref.rmsnorm_ref(xp, gamma, eps)
    return out[:R].reshape(shape)
