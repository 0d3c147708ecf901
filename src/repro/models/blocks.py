"""Transformer block variants (train + one-token decode paths)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.dist import (DistConfig, all_gather, axis_index, fdot,
                               pmax, psum, region_in, region_out,
                               tp_region_in, tp_region_out, tp_shared)
from repro.models.layers import (add_bias, apply_norm, cache_write,
                                 chunked_attention, expand_kv, head_mask, mlp,
                                 quantize_kv, rmsnorm, rope, splitkv_decode)
from repro.models.flash import flash_attention
from repro.models.moe import moe_ffn

Array = jax.Array


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

def gqa_attention(p: Dict, x: Array, cfg, dist: DistConfig, *, causal=True,
                  window=0, pos_offset=0, use_rope=True, prefix="",
                  collect_cache: int = 0, tp_size: int = 1):
    """x (B,S,d) -> (B,S,d) attention residual branch (norm included).

    collect_cache>0: also return this rank's seq-sharded decode cache of
    total length collect_cache (prefill), else cache=None."""
    dh = cfg.d_head
    h = apply_norm(p, f"{prefix}attn_norm", x, cfg, dist)
    hq = region_in(h, dist)
    B, S, _ = hq.shape
    q = add_bias(p, f"{prefix}bq", hq @ p[f"{prefix}wq"])
    Hl = q.shape[-1] // dh
    q = q.reshape(B, S, Hl, dh)
    k = (hq @ tp_shared(p[f"{prefix}wk"], dist.tp)).reshape(B, S, -1, dh)
    v = add_bias(p, f"{prefix}bv", hq @ tp_shared(p[f"{prefix}wv"], dist.tp),
              dist.tp).reshape(B, S, -1, dh)
    pos = pos_offset + jnp.arange(S)
    if use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    r = axis_index(dist.tp)
    ke = expand_kv(k, Hl, r, cfg.n_heads, cfg.n_kv_heads)
    ve = expand_kv(v, Hl, r, cfg.n_heads, cfg.n_kv_heads)
    o = flash_attention(q, ke, ve, jnp.float32(window), causal, pos_offset)
    o = head_mask(o, cfg, dist, axis=2)
    out = add_bias(p, f"{prefix}bo",
                region_out(o.reshape(B, S, -1) @ p[f"{prefix}wo"], dist))
    cache = None
    if collect_cache:
        Ss = collect_cache // tp_size
        kp = jnp.pad(k, ((0, 0), (0, max(0, collect_cache - S)), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, max(0, collect_cache - S)), (0, 0), (0, 0)))
        start = r * Ss
        ks = jax.lax.dynamic_slice_in_dim(kp, start, Ss, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, start, Ss, axis=1)
        spos = start + jnp.arange(Ss)
        spos = jnp.where(spos < S, spos, -1)
        kt = ks.transpose(0, 2, 1, 3)
        vt = vs.transpose(0, 2, 1, 3)
        if cfg.kv_cache_dtype == "int8":
            ksc = jnp.max(jnp.abs(kt.astype(jnp.float32)), -1) / 127.0 + 1e-12
            vsc = jnp.max(jnp.abs(vt.astype(jnp.float32)), -1) / 127.0 + 1e-12
            kq = jnp.clip(jnp.round(kt.astype(jnp.float32) / ksc[..., None]),
                          -127, 127).astype(jnp.int8)
            vq = jnp.clip(jnp.round(vt.astype(jnp.float32) / vsc[..., None]),
                          -127, 127).astype(jnp.int8)
            cache = {"k": kq, "v": vq, "k_scale": ksc.astype(jnp.float32),
                     "v_scale": vsc.astype(jnp.float32),
                     "slot_pos": spos.astype(jnp.int32)}
        else:
            cache = {"k": kt, "v": vt, "slot_pos": spos.astype(jnp.int32)}
    return out, cache


def gqa_cross_attention(p: Dict, x: Array, memory: Array, cfg,
                        dist: DistConfig) -> Array:
    """Cross-attention (whisper decoder): q from x, kv from encoder memory."""
    dh = cfg.d_head
    h = apply_norm(p, "cross_norm", x, cfg, dist)
    hq = region_in(h, dist)
    B, S, _ = hq.shape
    mq = tp_region_in(memory, dist.tp)
    q = add_bias(p, "cbq", hq @ p["cwq"])
    Hl = q.shape[-1] // dh
    q = q.reshape(B, S, Hl, dh)
    k = (mq @ tp_shared(p["cwk"], dist.tp)).reshape(B, memory.shape[1], -1, dh)
    v = add_bias(p, "cbv", mq @ tp_shared(p["cwv"], dist.tp),
              dist.tp).reshape(B, memory.shape[1], -1, dh)
    r = axis_index(dist.tp)
    ke = expand_kv(k, Hl, r, cfg.n_heads, cfg.n_kv_heads)
    ve = expand_kv(v, Hl, r, cfg.n_heads, cfg.n_kv_heads)
    o = flash_attention(q, ke, ve, jnp.float32(0), False, 0)
    o = head_mask(o, cfg, dist, axis=2)
    return add_bias(p, "cbo", region_out(o.reshape(B, S, -1) @ p["cwo"], dist))


def gqa_attention_decode(p: Dict, x: Array, cache: Dict, pos: Array, cfg,
                         dist: DistConfig, *, window=0, use_rope=True,
                         prefix="", fd=None) -> Tuple[Array, Dict]:
    """One-token attention with a seq-sharded cache.

    x (B,1,d); cache {k (B,Hkv,Ss,dh), v, slot_pos (Ss,)}.
    fd: per-leaf fsdp dim (2D-TP decode for FSDP-sharded archs).
    """
    fd = fd or {}
    B = x.shape[0]
    dh = cfg.d_head
    h = apply_norm(p, f"{prefix}attn_norm", x, cfg)
    hq = tp_region_in(h, dist.tp)
    q = add_bias(p, f"{prefix}bq",
              fdot(hq, p[f"{prefix}wq"], fd.get(f"{prefix}wq"), dist))
    Hl = q.shape[-1] // dh
    q = q.reshape(B, 1, Hl, dh)
    k = fdot(hq, tp_shared(p[f"{prefix}wk"], dist.tp),
             fd.get(f"{prefix}wk"), dist).reshape(B, 1, -1, dh)
    v = add_bias(p, f"{prefix}bv",
              fdot(hq, tp_shared(p[f"{prefix}wv"], dist.tp),
                   fd.get(f"{prefix}wv"), dist),
              dist.tp).reshape(B, 1, -1, dh)
    if use_rope:
        pvec = pos[None] if pos.ndim == 0 else pos
        q = rope(q, pvec[None, :], cfg.rope_theta)
        k = rope(k, pvec[None, :], cfg.rope_theta)
    q1 = q[:, 0]                                       # (B,Hl,dh)
    k1, v1 = k[:, 0], v[:, 0]                          # (B,Hkv,dh)
    ring = (cfg.sliding_window if (cfg.sliding_window > 0
                                   and cfg.swa_pattern == 0) else 0)
    new_cache = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        k1q, k1s = quantize_kv(k1)
        v1q, v1s = quantize_kv(v1)
        ck, spos = cache_write(cache["k"], cache["slot_pos"], k1q, pos,
                               dist, ring_size=ring)
        cv, _ = cache_write(cache["v"], cache["slot_pos"], v1q, pos, dist,
                            ring_size=ring)
        cks, _ = cache_write(cache["k_scale"][..., None],
                             cache["slot_pos"], k1s[..., None], pos, dist,
                             ring_size=ring)
        cvs, _ = cache_write(cache["v_scale"][..., None],
                             cache["slot_pos"], v1s[..., None], pos, dist,
                             ring_size=ring)
        cks, cvs = cks[..., 0], cvs[..., 0]
        o = splitkv_decode(q1, ck, cv, spos, pos, dist=dist,
                           n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           window=window, k_scale=cks, v_scale=cvs)
        new_cache.update(k=ck, v=cv, k_scale=cks, v_scale=cvs,
                         slot_pos=spos)
    else:
        ck, spos = cache_write(cache["k"], cache["slot_pos"], k1, pos, dist,
                               ring_size=ring)
        cv, _ = cache_write(cache["v"], cache["slot_pos"], v1, pos, dist,
                            ring_size=ring)
        o = splitkv_decode(q1, ck, cv, spos, pos, dist=dist,
                           n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                           window=window)
        new_cache.update(k=ck, v=cv, slot_pos=spos)
    o = head_mask(o, cfg, dist, axis=1)
    out = add_bias(p, f"{prefix}bo", tp_region_out(
        fdot(o.reshape(B, 1, -1).astype(x.dtype), p[f"{prefix}wo"],
             fd.get(f"{prefix}wo"), dist), dist.tp))
    return out, new_cache


# --------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3/DeepSeek style)
# --------------------------------------------------------------------------

def _mla_qkv(p, hq, cfg, dist, pos, fd=None):
    fd = fd or {}
    B, S, _ = hq.shape
    nope, rdim, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    cq = rmsnorm(fdot(hq, tp_shared(p["wq_down"], dist.tp),
                      fd.get("wq_down"), dist),
                 tp_shared(p["q_norm_g"], dist.tp), cfg.norm_eps)
    qf = cq @ p["wq_up"]                               # (B,S,Hl*(nope+rdim))
    Hl = qf.shape[-1] // (nope + rdim)
    qf = qf.reshape(B, S, Hl, nope + rdim)
    q_nope, q_rope = qf[..., :nope], qf[..., nope:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)

    kvd = fdot(hq, tp_shared(p["wkv_down"], dist.tp), fd.get("wkv_down"),
               dist)                                   # (B,S,r+rdim)
    c_kv = rmsnorm(kvd[..., :cfg.kv_lora_rank],
                   tp_shared(p["kv_norm_g"], dist.tp), cfg.norm_eps)
    k_rope = rope(kvd[..., None, cfg.kv_lora_rank:], pos, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope, Hl


def mla_attention(p: Dict, x: Array, cfg, dist: DistConfig, *,
                  pos_offset=0, collect_cache: int = 0, tp_size: int = 1):
    nope, rdim, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = apply_norm(p, "attn_norm", x, cfg, dist)
    hq = region_in(h, dist)
    B, S, _ = hq.shape
    pos = pos_offset + jnp.arange(S)
    q_nope, q_rope, c_kv, k_rope, Hl = _mla_qkv(p, hq, cfg, dist, pos)

    k_nope = (c_kv @ p["wk_up"]).reshape(B, S, Hl, nope)
    vv = (c_kv @ p["wv_up"]).reshape(B, S, Hl, vdim)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (B, S, Hl, rdim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    o = flash_attention(q, k, vv, jnp.float32(0), True, pos_offset)
    o = head_mask(o, cfg, dist, axis=2)
    out = region_out(o.reshape(B, S, -1) @ p["wo"], dist)
    cache = None
    if collect_cache:
        r = axis_index(dist.tp)
        Ss = collect_cache // tp_size
        cp = jnp.pad(c_kv, ((0, 0), (0, max(0, collect_cache - S)), (0, 0)))
        rp = jnp.pad(k_rope[:, :, 0, :],
                     ((0, 0), (0, max(0, collect_cache - S)), (0, 0)))
        start = r * Ss
        cs = jax.lax.dynamic_slice_in_dim(cp, start, Ss, axis=1)
        rs = jax.lax.dynamic_slice_in_dim(rp, start, Ss, axis=1)
        spos = start + jnp.arange(Ss)
        spos = jnp.where(spos < S, spos, -1)
        cache = {"ckv": cs[:, None], "krope": rs[:, None],
                 "slot_pos": spos.astype(jnp.int32)}
    return out, cache


def mla_attention_decode(p: Dict, x: Array, cache: Dict, pos: Array, cfg,
                         dist: DistConfig, fd=None) -> Tuple[Array, Dict]:
    """Absorbed MLA decode against a seq-sharded LATENT cache:
    cache {ckv (B,1,Ss,r), krope (B,1,Ss,rdim), slot_pos (Ss,)}."""
    B = x.shape[0]
    nope, rdim, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r_lat = cfg.kv_lora_rank
    h = apply_norm(p, "attn_norm", x, cfg)
    hq = tp_region_in(h, dist.tp)
    pvec = pos[None]
    q_nope, q_rope, c_kv, k_rope, Hl = _mla_qkv(p, hq, cfg, dist,
                                                pvec[None, :], fd=fd)

    # absorb k_up into q:  q_eff_h = q_nope_h · W_kup_h^T  -> latent space
    wk = p["wk_up"].reshape(r_lat, Hl, nope)
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk.astype(jnp.float32))          # (B,Hl,r)
    qr = q_rope[:, 0].astype(jnp.float32)               # (B,Hl,rdim)

    ck, spos = cache_write(cache["ckv"], cache["slot_pos"],
                           c_kv[:, :, None, :][:, 0], pos, dist)
    kr, _ = cache_write(cache["krope"], cache["slot_pos"],
                        k_rope[:, 0].transpose(0, 1, 2), pos, dist)

    # gather all heads' latent queries (tiny), split-KV over the cache
    q_all = all_gather(jnp.concatenate([q_eff, qr], -1), dist.tp,
                       gather_axis=1, tiled=True)        # (B,H,r+rdim)
    lat = jnp.concatenate([ck[:, 0], kr[:, 0]], -1)      # (B,Ss,r+rdim)
    s = jnp.einsum("bhr,bsr->bhs", q_all,
                   lat.astype(jnp.float32)) / jnp.sqrt(float(nope + rdim))
    valid = (spos >= 0) & (spos <= pos)
    s = jnp.where(valid[None, None, :], s, -1e30)
    m_l = jnp.maximum(jnp.max(s, axis=-1), -2e30)
    pr = jnp.exp(s - m_l[..., None])
    den_l = pr.sum(-1)
    num_l = jnp.einsum("bhs,bsr->bhr", pr, ck[:, 0].astype(jnp.float32))
    m = pmax(m_l, dist.tp)
    corr = jnp.exp(m_l - m)
    num = psum(num_l * corr[..., None], dist.tp)
    den = psum(den_l * corr, dist.tp)
    ctx = num / jnp.maximum(den[..., None], 1e-30)       # (B,H,r) latent ctx
    rk = axis_index(dist.tp)
    ctx_l = jax.lax.dynamic_slice_in_dim(ctx, rk * Hl, Hl, axis=1)
    wv = p["wv_up"].reshape(r_lat, Hl, vdim)
    o = jnp.einsum("bhr,rhv->bhv", ctx_l, wv.astype(jnp.float32))
    o = head_mask(o, cfg, dist, axis=1)
    fd = fd or {}
    out = tp_region_out(
        fdot(o.reshape(B, 1, -1).astype(x.dtype), p["wo"], fd.get("wo"),
             dist), dist.tp)
    return out, {"ckv": ck, "krope": kr, "slot_pos": spos}


# --------------------------------------------------------------------------
# full blocks (attention/ssm + mlp/moe), train path
# --------------------------------------------------------------------------

def decoder_block(p: Dict, x: Array, cfg, dist: DistConfig, *, window=0,
                  pos_offset=0, causal=True, use_rope=True,
                  memory: Optional[Array] = None, collect_cache: int = 0,
                  tp_size: int = 1):
    """Generic transformer block. Returns (x, aux_loss, cache|None).
    The cross-attention's operations are named `repro/cross_attention`
    in XLA's profile."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if cfg.attention == "mla":
        a, cache = mla_attention(p, x, cfg, dist, pos_offset=pos_offset,
                                 collect_cache=collect_cache, tp_size=tp_size)
        x = x + a
    elif cfg.attention != "none":
        a, cache = gqa_attention(p, x, cfg, dist, causal=causal,
                                 window=window, pos_offset=pos_offset,
                                 use_rope=use_rope,
                                 collect_cache=collect_cache, tp_size=tp_size)
        x = x + a
    if memory is not None:
        with jax.named_scope("repro/cross_attention"):
            x = x + gqa_cross_attention(p, x, memory, cfg, dist)
    h = apply_norm(p, "mlp_norm", x, cfg, dist)
    if cfg.n_experts:
        B, S, d = h.shape
        out, aux = moe_ffn(p, h.reshape(B * S, d), cfg, dist)
        x = x + out.reshape(B, S, d)
    else:
        x = x + mlp(p, h, cfg, dist)
    return x, aux, cache


def decoder_block_decode(p: Dict, x: Array, cache: Dict, pos: Array, cfg,
                         dist: DistConfig, *, window=0,
                         memory: Optional[Array] = None, fd=None):
    """One-token version of decoder_block. Returns (x, new_cache)."""
    if cfg.attention == "mla":
        a, new_cache = mla_attention_decode(p, x, cache, pos, cfg, dist,
                                            fd=fd)
    elif cfg.attention != "none":
        a, new_cache = gqa_attention_decode(p, x, cache, pos, cfg, dist,
                                            window=window,
                                            use_rope=cfg.use_rope, fd=fd)
    else:
        a, new_cache = 0.0, cache
    x = x + a
    if memory is not None:
        x = x + gqa_cross_attention(p, x, memory, cfg, dist)
    h = apply_norm(p, "mlp_norm", x, cfg)
    if cfg.n_experts:
        B, S, d = h.shape
        out, _ = moe_ffn(p, h.reshape(B * S, d), cfg, dist, fd=fd)
        x = x + out.reshape(B, S, d)
    else:
        x = x + mlp(p, h, cfg, dist, fd=fd)
    return x, new_cache
