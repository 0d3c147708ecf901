"""Shared neural layers: norms, RoPE, MLPs, memory-bounded attention
(chunked flash-style reference), and split-KV decode attention.

All functions are TP-aware (axis=None degrades to local)."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.dist import (DistConfig, all_gather, axis_index, pmax, psum,
                               region_in, region_out, tp_region_in,
                               tp_region_out, tp_shared)

Array = jax.Array
NEG_INF = -1e30


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    """f32 accumulation via the reduce's accumulator — NOT via converting
    the whole tensor (a whole-tensor convert at block entry makes XLA save
    the scan residual stack in f32: +0.5 GB/layer/device at train_4k)."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
    return x * inv * gamma.astype(x.dtype)


def layernorm(x: Array, gamma: Array, beta: Array, eps: float = 1e-5) -> Array:
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32) - jnp.square(mu)
    inv = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    out = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
    return out * gamma.astype(x.dtype) + beta.astype(x.dtype)


def apply_norm(p: dict, name: str, x: Array, cfg, dist=None) -> Array:
    """dist (with sp=True) marks a norm inside the sequence-parallel
    region: each TP rank sees a different seq shard, so the (replicated)
    norm params need their grads psum'd over tp (tp_shared)."""
    g = p[f"{name}_g"]
    if dist is not None and dist.sp:
        g = tp_shared(g, dist.tp)
    if cfg.norm == "layernorm":
        b = p[f"{name}_b"]
        if dist is not None and dist.sp:
            b = tp_shared(b, dist.tp)
        return layernorm(x, g, b, cfg.norm_eps)
    return rmsnorm(x, g, cfg.norm_eps)


# --------------------------------------------------------------------------
# RoPE (split-half convention)
# --------------------------------------------------------------------------

def rope(x: Array, pos: Array, theta: float) -> Array:
    """x: (..., S, H, dh) or (..., H, dh) with pos broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                       # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# MLP (column->row parallel)
# --------------------------------------------------------------------------

def add_bias(p: dict, name: str, y: Array, tp: Optional[str] = None) -> Array:
    """y + p[name] where the configuration has that bias (whisper's), else
    y. `tp` marks a bias used whole on every tensor-parallel rank whose
    gradient each rank holds in part (v's, like wv's)."""
    if name not in p:
        return y
    b = p[name] if tp is None else tp_shared(p[name], tp)
    return y + b.astype(y.dtype)


def mlp(p: dict, x: Array, cfg, dist: DistConfig, fd=None) -> Array:
    """fd: per-leaf fsdp dims for 2D-TP decode (see dist.fdot); None on the
    train path where weights arrive FSDP-gathered."""
    from repro.models.dist import fdot  # local import (cycle-free)
    fd = fd or {}
    xi = region_in(x, dist)
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(fdot(xi, p["w_gate"], fd.get("w_gate"), dist)) * \
            fdot(xi, p["w_in"], fd.get("w_in"), dist)
    else:   # whisper: exact (erf) GELU
        h = jax.nn.gelu(add_bias(p, "b_in", fdot(xi, p["w_in"],
                                                 fd.get("w_in"), dist)),
                        approximate=False)
    return add_bias(p, "b_out", region_out(
        fdot(h, p["w_out"], fd.get("w_out"), dist), dist))


# --------------------------------------------------------------------------
# memory-bounded attention (flash-style two-level chunking, pure JAX)
# --------------------------------------------------------------------------

def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      window: int = 0, q_offset: int = 0,
                      q_chunk: int = 1024, kv_chunk: int = 1024) -> Array:
    """q (B,Sq,H,dh); k,v (B,Sk,H,dh) — H already matched (GQA groups
    expanded). Running-softmax over kv chunks keeps peak memory at
    O(q_chunk·kv_chunk) per (B,H). window>0 = sliding-window mask.
    q_offset: global position of q[0] (cross-chunk causality in prefill)."""
    B, Sq, H, dh = q.shape
    dv = v.shape[-1]  # may differ from dh (MLA: qk dims != v dim)
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    pad_q = (-Sq) % q_chunk
    pad_k = (-Sk) % kv_chunk
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_chunk, kp.shape[1] // kv_chunk
    qb = qp.reshape(B, nq, q_chunk, H, dh).transpose(1, 0, 3, 2, 4)  # (nq,B,H,qc,dh)
    kb = kp.reshape(B, nk, kv_chunk, H, dh).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, kv_chunk, H, dv).transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(nq * q_chunk).reshape(nq, q_chunk)
    k_pos = jnp.arange(nk * kv_chunk).reshape(nk, kv_chunk)
    k_valid = k_pos < Sk

    def q_block(args):
        qi, qpos = args  # (B,H,qc,dh), (qc,)

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, vi, kpos, kval = kv
            s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32),
                           ki.astype(jnp.float32)) * scale
            mask = kval[None, :]
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            # window may be a traced per-layer value; <=0 disables it
            w = jnp.asarray(window)
            mask = mask & ((qpos[:, None] - kpos[None, :] < w) | (w <= 0))
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kb, vb, k_pos, k_valid))
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(q_block, (qb, q_pos))          # (nq,B,H,qc,dv)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, nq * q_chunk, H, dv)
    return out[:, :Sq].astype(q.dtype)


def expand_kv(k: Array, n_q_heads_local: int, tp_rank: Array,
              n_heads: int, n_kv: int) -> Array:
    """Map full kv heads (B,S,Hkv,dh) to the local q heads' kv
    (B,S,Hl,dh) given GQA grouping. tp_rank is the device's TP index.
    Padded q heads (global id >= n_heads) clip to the last kv head; their
    outputs are masked by head_mask()."""
    group = max(1, n_heads // max(1, n_kv))
    q_global = tp_rank * n_q_heads_local + jnp.arange(n_q_heads_local)
    kv_idx = jnp.clip(q_global // group, 0, n_kv - 1)
    return jnp.take(k, kv_idx, axis=2)


def head_mask(o: Array, cfg, dist: DistConfig, axis: int) -> Array:
    """Zero the outputs of TP-padding heads (n_heads rounded up to a
    multiple of the TP size so heads divide the mesh axis)."""
    Hl = o.shape[axis]
    gid = axis_index(dist.tp) * Hl + jnp.arange(Hl)
    m = (gid < cfg.n_heads).astype(o.dtype)
    shape = [1] * o.ndim
    shape[axis] = Hl
    return o * m.reshape(shape)


def sinusoid_positions(pos: Array, d: int) -> Array:
    """Sinusoidal absolute position embeddings, (...,) -> (..., d)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(1, half - 1)))
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# split-KV decode attention: cache sequence-sharded over the TP axis
# --------------------------------------------------------------------------

def quantize_kv(x: Array):
    """Per-vector int8 quantization of one token's k or v (B,Hkv,dh):
    returns (q int8, scale f32 (B,Hkv))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def splitkv_decode(q_local: Array, k_cache: Array, v_cache: Array,
                   slot_pos: Array, pos: Array, *, dist: DistConfig,
                   n_heads: int, n_kv: int, window: int = 0,
                   k_scale: Array = None, v_scale: Array = None) -> Array:
    """One-token attention against a cache whose sequence dim is sharded
    over dist.tp.

    q_local  (B, Hl, dh)   — this rank's q heads
    k_cache  (B, Hkv, Ss, dh), v_cache same — this rank's seq slice, ALL kv heads
    slot_pos (Ss,) int32   — global position stored in each slot (-1 empty)
    pos      ()            — current decode position
    Returns the LOCAL q heads' attention output (B, Hl, dh).

    Combine: all-gather q heads (tiny), partial softmax per rank over its
    slice, pmax/psum merge, then slice back the local heads.
    """
    B, Hl, dh = q_local.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    # all q heads everywhere (one token: this is a few KB)
    q_all = all_gather(q_local, dist.tp, gather_axis=1, tiled=True)  # (B,H,dh)
    H = q_all.shape[1]
    group = max(1, n_heads // max(1, n_kv))
    kv_of_q = jnp.arange(H) // group

    k_q = jnp.take(k_cache, kv_of_q, axis=1).astype(jnp.float32)
    v_q = jnp.take(v_cache, kv_of_q, axis=1).astype(jnp.float32)
    if k_scale is not None:  # int8 cache: dequantize with per-vector scales
        k_q = k_q * jnp.take(k_scale, kv_of_q, axis=1)[..., None]
        v_q = v_q * jnp.take(v_scale, kv_of_q, axis=1)[..., None]
    s = jnp.einsum("bhd,bhsd->bhs", q_all.astype(jnp.float32),
                   k_q) * scale
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    w = jnp.asarray(window)
    valid = valid & ((slot_pos > pos - w) | (w <= 0))
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_l = jnp.maximum(jnp.max(s, axis=-1), 2 * NEG_INF)         # (B,H)
    p = jnp.exp(s - m_l[..., None])
    den_l = jnp.sum(p, axis=-1)
    num_l = jnp.einsum("bhs,bhsd->bhd", p, v_q)

    m = pmax(m_l, dist.tp)
    corr = jnp.exp(m_l - m)
    num = psum(num_l * corr[..., None], dist.tp)
    den = psum(den_l * corr, dist.tp)
    o_all = num / jnp.maximum(den[..., None], 1e-30)            # (B,H,dh)

    r = axis_index(dist.tp)
    start = r * Hl
    o_local = jax.lax.dynamic_slice_in_dim(o_all, start, Hl, axis=1)
    return o_local.astype(q_local.dtype)


def cache_write(cache: Array, slot_pos: Array, new: Array, pos: Array,
                dist: DistConfig, ring_size: int = 0) -> Tuple[Array, Array]:
    """Write one token's k or v (B, Hkv, dh) into the seq-sharded cache.

    ring_size=0: contiguous layout — rank r owns [r·Ss, (r+1)·Ss).
    ring_size>0 (pure sliding-window archs): ring layout over the global
    window — global slot g = pos % ring lives on rank g // Ss. ring_size
    is STATIC (it fixes the cache allocation); the attention mask window
    may still be traced."""
    B, Hkv, Ss, dh = cache.shape
    r = axis_index(dist.tp)
    if ring_size > 0:
        g = pos % ring_size
    else:
        g = pos
    owner = g // Ss
    local = g - owner * Ss
    mine = owner == r
    upd = jax.lax.dynamic_update_slice_in_dim(
        cache, new[:, :, None, :].astype(cache.dtype), local, axis=2)
    cache = jnp.where(mine, upd, cache)
    spos = jnp.where(mine, slot_pos.at[local].set(pos), slot_pos)
    return cache, spos
