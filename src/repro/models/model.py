"""Model assembly: parameter declaration, train/prefill/decode forward
passes, and cache layouts for every assigned architecture family.

All forward code runs either plainly (single device, all axes None) or
inside shard_map on the production mesh — the DistConfig decides.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.aggregation import CompressionConfig
from repro.models import blocks as B
from repro.models.config import InputShape, ModelConfig
from repro.models.dist import (DistConfig, all_gather, axis_index,
                               fsdp_param, key_to_bits, psum, tp_region_in,
                               tp_shared, vp_embed, vp_xent)
from repro.models.layers import apply_norm, sinusoid_positions
from repro.models.mamba2 import mamba2_block, mamba2_decode
from repro.models.params import LeafMeta, ParamBuilder

Array = jax.Array


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ==========================================================================
# parameter declaration
# ==========================================================================

def _add_norm(pb: ParamBuilder, path: str, shape, cfg, stacked):
    pb.add(path + "_g", shape, (None,) * len(shape), stacked=stacked,
           init="ones")
    if cfg.norm == "layernorm":
        pb.add(path + "_b", shape, (None,) * len(shape), stacked=stacked,
               init="zeros")


def _add_attn(pb: ParamBuilder, base: str, cfg: ModelConfig, tp_size: int,
              L: Optional[int], F, prefix: str = ""):
    """GQA attention tensors. L=None -> non-stacked (shared block)."""
    d = cfg.d_model
    Hp = _ceil_to(cfg.n_heads, tp_size)
    dh = cfg.d_head
    stk = L is not None
    lead = (L,) if stk else ()
    la = (None,) if stk else ()
    _add_norm(pb, f"{base}/{prefix}attn_norm", lead + (d,), cfg, stk)
    pb.add(f"{base}/{prefix}wq", lead + (d, Hp * dh), la + (F, "tp"),
           stacked=stk, fan_in_dim=len(lead))
    pb.add(f"{base}/{prefix}wk", lead + (d, cfg.n_kv_heads * dh),
           la + (F, None), stacked=stk, tp_grad_sync=True,
           fan_in_dim=len(lead))
    pb.add(f"{base}/{prefix}wv", lead + (d, cfg.n_kv_heads * dh),
           la + (F, None), stacked=stk, tp_grad_sync=True,
           fan_in_dim=len(lead))
    pb.add(f"{base}/{prefix}wo", lead + (Hp * dh, d), la + ("tp", F),
           stacked=stk, fan_in_dim=len(lead))
    if cfg.bias:
        _add_attn_bias(pb, f"{base}/{prefix}", cfg, tp_size, L)


def _add_attn_bias(pb: ParamBuilder, base: str, cfg: ModelConfig,
                   tp_size: int, L: Optional[int], names=("bq", "bv", "bo")):
    """Whisper's attention biases: on q (sharded like wq's heads), on v
    (replicated like wv, whose grads sync over tp) and on the output;
    the key projection has none."""
    lead = (L,) if L is not None else ()
    la = (None,) if L is not None else ()
    stk = L is not None
    Hp = _ceil_to(cfg.n_heads, tp_size)
    pb.add(base + names[0], lead + (Hp * cfg.d_head,), la + ("tp",),
           stacked=stk, init="zeros")
    pb.add(base + names[1], lead + (cfg.d_kv,), la + (None,), stacked=stk,
           tp_grad_sync=True, init="zeros")
    pb.add(base + names[2], lead + (cfg.d_model,), la + (None,),
           stacked=stk, init="zeros")


def _add_mla(pb: ParamBuilder, base: str, cfg: ModelConfig, tp_size: int,
             L: int, F):
    d = cfg.d_model
    Hp = _ceil_to(cfg.n_heads, tp_size)
    qr, r = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    _add_norm(pb, f"{base}/attn_norm", (L, d), cfg, True)
    pb.add(f"{base}/wq_down", (L, d, qr), (None, F, None), stacked=True,
           tp_grad_sync=True, fan_in_dim=1)
    pb.add(f"{base}/q_norm_g", (L, qr), (None, None), stacked=True,
           init="ones")
    pb.add(f"{base}/wq_up", (L, qr, Hp * (nope + rd)), (None, None, "tp"),
           stacked=True, fan_in_dim=1)
    pb.add(f"{base}/wkv_down", (L, d, r + rd), (None, F, None), stacked=True,
           tp_grad_sync=True, fan_in_dim=1)
    pb.add(f"{base}/kv_norm_g", (L, r), (None, None), stacked=True,
           init="ones")
    pb.add(f"{base}/wk_up", (L, r, Hp * nope), (None, None, "tp"),
           stacked=True, fan_in_dim=1)
    pb.add(f"{base}/wv_up", (L, r, Hp * vd), (None, None, "tp"),
           stacked=True, fan_in_dim=1)
    pb.add(f"{base}/wo", (L, Hp * vd, d), (None, "tp", F), stacked=True,
           fan_in_dim=1)


def _add_mlp(pb: ParamBuilder, base: str, cfg: ModelConfig, L: Optional[int],
             F, names=("w_gate", "w_in", "w_out"), d_ff=None,
             prefix: str = ""):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    stk = L is not None
    lead = (L,) if stk else ()
    la = (None,) if stk else ()
    _add_norm(pb, f"{base}/{prefix}mlp_norm", lead + (d,), cfg, stk)
    if cfg.mlp == "swiglu":
        pb.add(f"{base}/{prefix}{names[0]}", lead + (d, ff), la + (F, "tp"),
               stacked=stk, fan_in_dim=len(lead))
    pb.add(f"{base}/{prefix}{names[1]}", lead + (d, ff), la + (F, "tp"),
           stacked=stk, fan_in_dim=len(lead))
    pb.add(f"{base}/{prefix}{names[2]}", lead + (ff, d), la + ("tp", F),
           stacked=stk, fan_in_dim=len(lead))
    if cfg.bias:
        pb.add(f"{base}/{prefix}b_in", lead + (ff,), la + ("tp",),
               stacked=stk, init="zeros")
        pb.add(f"{base}/{prefix}b_out", lead + (d,), la + (None,),
               stacked=stk, init="zeros")


def _add_moe(pb: ParamBuilder, base: str, cfg: ModelConfig, L: int, F,
             prefix: str = ""):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    _add_norm(pb, f"{base}/{prefix}mlp_norm", (L, d), cfg, True)
    pb.add(f"{base}/{prefix}router", (L, d, E), (None, None, None),
           stacked=True, tp_grad_sync=True, fan_in_dim=1)
    if cfg.mlp == "swiglu":
        pb.add(f"{base}/{prefix}w_gate", (L, E, d, ff), (None, "tp", F, None),
               stacked=True, fan_in_dim=2)
    pb.add(f"{base}/{prefix}w_in", (L, E, d, ff), (None, "tp", F, None),
           stacked=True, fan_in_dim=2)
    pb.add(f"{base}/{prefix}w_out", (L, E, ff, d), (None, "tp", None, F),
           stacked=True, fan_in_dim=2)
    if cfg.moe_shared_expert:
        pb.add(f"{base}/{prefix}shared_w_gate", (L, d, ff), (None, F, "tp"),
               stacked=True, fan_in_dim=1)
        pb.add(f"{base}/{prefix}shared_w_in", (L, d, ff), (None, F, "tp"),
               stacked=True, fan_in_dim=1)
        pb.add(f"{base}/{prefix}shared_w_out", (L, ff, d), (None, "tp", F),
               stacked=True, fan_in_dim=1)


def _add_ssm(pb: ParamBuilder, base: str, cfg: ModelConfig, L: int, F):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    nh = d_in // cfg.ssm_head_dim
    N, K, G = cfg.ssm_state, cfg.ssm_conv, cfg.ssm_groups
    _add_norm(pb, f"{base}/norm_in", (L, d), cfg, True)
    pb.add(f"{base}/w_z", (L, d, d_in), (None, F, "tp"), stacked=True,
           fan_in_dim=1)
    pb.add(f"{base}/w_x", (L, d, d_in), (None, F, "tp"), stacked=True,
           fan_in_dim=1)
    pb.add(f"{base}/w_bc", (L, d, 2 * G * N), (None, F, None), stacked=True,
           tp_grad_sync=True, fan_in_dim=1)
    pb.add(f"{base}/w_dt", (L, d, nh), (None, F, "tp"), stacked=True,
           fan_in_dim=1)
    pb.add(f"{base}/conv_x", (L, d_in, K), (None, "tp", None), stacked=True,
           scale=0.5, fan_in_dim=2)
    pb.add(f"{base}/conv_bc", (L, 2 * G * N, K), (None, None, None),
           stacked=True, tp_grad_sync=True, scale=0.5, fan_in_dim=2)
    pb.add(f"{base}/A_log", (L, nh), (None, "tp"), stacked=True, init="zeros")
    pb.add(f"{base}/D", (L, nh), (None, "tp"), stacked=True, init="ones")
    pb.add(f"{base}/dt_bias", (L, nh), (None, "tp"), stacked=True,
           init="zeros")
    pb.add(f"{base}/norm_g", (L, d_in), (None, "tp"), stacked=True,
           init="ones")
    pb.add(f"{base}/w_out", (L, d_in, d), (None, "tp", F), stacked=True,
           fan_in_dim=1)


def declare_params(cfg: ModelConfig, tp_size: int) -> ParamBuilder:
    pb = ParamBuilder(cfg.dtype)
    F = "fsdp" if cfg.use_fsdp else None
    d, L = cfg.d_model, cfg.n_layers
    Vp = _ceil_to(cfg.vocab, 128)

    pb.add("embed", (Vp, d), ("tp", F), fan_in_dim=1)
    if not cfg.tie_embeddings:
        pb.add("head", (d, Vp), (F, "tp"), fan_in_dim=0)
    _add_norm(pb, "final_norm", (d,), cfg, False)

    if cfg.arch_type in ("dense", "vlm", "moe"):
        if cfg.n_experts and cfg.moe_every > 1:
            # interleaved MoE (llama4): one scan unit = dense block + MoE
            # block; params carry a_/b_ prefixes within the unit.
            assert cfg.moe_every == 2 and L % 2 == 0
            Lu = L // 2
            _add_attn(pb, "blocks", cfg, tp_size, Lu, F, prefix="a_")
            _add_mlp(pb, "blocks", cfg, Lu, F, prefix="a_")
            _add_attn(pb, "blocks", cfg, tp_size, Lu, F, prefix="b_")
            _add_moe(pb, "blocks", cfg, Lu, F, prefix="b_")
        else:
            _add_attn(pb, "blocks", cfg, tp_size, L, F) \
                if cfg.attention == "gqa" else _add_mla(pb, "blocks", cfg,
                                                        tp_size, L, F)
            if cfg.n_experts:
                _add_moe(pb, "blocks", cfg, L, F)
            else:
                _add_mlp(pb, "blocks", cfg, L, F)
    elif cfg.arch_type == "ssm":
        _add_ssm(pb, "blocks", cfg, L, F)
    elif cfg.arch_type == "hybrid":
        G = L // cfg.attn_every
        tail = L - G * cfg.attn_every
        _add_ssm(pb, "blocks", cfg, G * cfg.attn_every, F)
        if tail:
            _add_ssm(pb, "tail_blocks", cfg, tail, F)
        _add_attn(pb, "shared", cfg, tp_size, None, F)
        _add_mlp(pb, "shared", cfg, None, F)
    elif cfg.arch_type == "audio":
        Le = cfg.encoder_layers
        pb.add("dec_pos", (cfg.max_target_positions, d), (None, None),
               scale=0.02, fan_in_dim=1)
        _add_attn(pb, "encoder_blocks", cfg, tp_size, Le, F)
        _add_mlp(pb, "encoder_blocks", cfg, Le, F)
        _add_norm(pb, "enc_final_norm", (d,), cfg, False)
        _add_attn(pb, "decoder_blocks", cfg, tp_size, L, F)
        _add_norm(pb, "decoder_blocks/cross_norm", (L, d), cfg, True)
        pb.add("decoder_blocks/cwq",
               (L, d, _ceil_to(cfg.n_heads, tp_size) * cfg.d_head),
               (None, F, "tp"), stacked=True, fan_in_dim=1)
        pb.add("decoder_blocks/cwk", (L, d, cfg.d_kv), (None, F, None),
               stacked=True, tp_grad_sync=True, fan_in_dim=1)
        pb.add("decoder_blocks/cwv", (L, d, cfg.d_kv), (None, F, None),
               stacked=True, tp_grad_sync=True, fan_in_dim=1)
        pb.add("decoder_blocks/cwo",
               (L, _ceil_to(cfg.n_heads, tp_size) * cfg.d_head, d),
               (None, "tp", F), stacked=True, fan_in_dim=1)
        if cfg.bias:
            _add_attn_bias(pb, "decoder_blocks/", cfg, tp_size, L,
                           names=("cbq", "cbv", "cbo"))
        _add_mlp(pb, "decoder_blocks", cfg, L, F)
    else:
        raise ValueError(cfg.arch_type)
    return pb


# ==========================================================================
# the Model
# ==========================================================================

class Model:
    def __init__(self, cfg: ModelConfig, dist: DistConfig,
                 mesh_axis_sizes: Optional[Dict[str, int]] = None):
        self.cfg = cfg
        self.dist = dist
        sizes = mesh_axis_sizes or {}
        self.tp_size = sizes.get(dist.tp, 1) if dist.tp else 1
        self.dp_size = 1
        for a in dist.dp:
            self.dp_size *= sizes.get(a, 1)
        self.pb = declare_params(cfg, self.tp_size)
        self.meta = self.pb.meta()
        self.vocab_padded = _ceil_to(cfg.vocab, 128)
        self.dist_nosp = dataclasses.replace(dist, sp=False)

    def _eff(self, seq_len: int) -> DistConfig:
        """Sequence parallelism applies when enabled, tp>1, the seq divides
        the TP axis, and the arch is not enc-dec (whisper frames=1500)."""
        if (not self.dist.sp or self.dist.tp is None or self.tp_size <= 1
                or seq_len % self.tp_size != 0
                or self.cfg.arch_type == "audio"):
            return self.dist_nosp
        return self.dist

    def _sp_slice(self, x, dist):
        if not dist.sp:
            return x
        from repro.models.dist import make_slice_replicated
        return make_slice_replicated(self.tp_size)(x, dist.tp, 1)

    def _sp_gather(self, x, dist):
        if not dist.sp:
            return x
        from repro.models.dist import gather_replicated
        return gather_replicated(x, dist.tp, 1)

    # ---- plumbing ------------------------------------------------------
    def init(self, key):
        return self.pb.init(key)

    def param_shapes(self):
        return self.pb.shapes()

    def param_pspecs(self):
        return self.pb.pspecs(self.dist)

    def stacked(self):
        return self.pb.stacked_mask()

    def fsdp_mask(self):
        """True for leaves whose grads are aggregated inside backward
        (fsdp hook); False for leaves needing post-grad compressed_allreduce."""
        return jax.tree_util.tree_map(
            lambda m: m.fsdp_dim() is not None and self.dist.fsdp is not None,
            self.meta, is_leaf=lambda x: isinstance(x, LeafMeta))

    def _gather_leaf(self, w, meta: LeafMeta, kb, comp, consumed_lead=1):
        fd = meta.fsdp_dim()
        if fd is not None and self.dist.fsdp is not None:
            return fsdp_param(w, kb, fd - consumed_lead, self.dist, comp)
        return w

    def _gather_layer(self, p_layer: Dict, meta_layer: Dict, kb, comp,
                      consumed_lead=1):
        return {k: self._gather_leaf(w, meta_layer[k], kb, comp,
                                     consumed_lead)
                for k, w in p_layer.items()}

    def _decode_fd(self, meta_layer: Dict, consumed_lead=1):
        """fsdp-dim map for 2D-TP decode (weights stay sharded)."""
        if self.dist.fsdp is None:
            return {}
        out = {}
        for k, m in meta_layer.items():
            f = m.fsdp_dim()
            out[k] = None if f is None else f - consumed_lead
        return out

    def _layer_window(self, idx):
        cfg = self.cfg
        if cfg.swa_pattern > 0:
            return jnp.where((idx + 1) % cfg.swa_pattern == 0,
                             0, cfg.sliding_window)
        return cfg.sliding_window

    def _layer_keys(self, key, L):
        ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(L))
        return key_to_bits(ks)

    # ---- embedding / head ----------------------------------------------
    def _embed(self, params, tokens, kb, comp, dist=None):
        w = self._gather_leaf(params["embed"], self.meta["embed"], kb, comp,
                              consumed_lead=0)
        # NB: under SP the seq slice after the embedding uses an
        # all-gather adjoint (make_slice_replicated), so the vocab-sharded
        # table receives full-sequence cotangents — no extra sync needed.
        return vp_embed(w, tokens, self.dist.tp, self.vocab_padded)

    def _head_weight(self, params, kb, comp):
        """(d, V_local) head matrix, FSDP-gathered / tied-transposed."""
        if self.cfg.tie_embeddings:
            w = self._gather_leaf(params["embed"], self.meta["embed"], kb,
                                  comp, consumed_lead=0)
            return jnp.swapaxes(w, 0, 1)
        return self._gather_leaf(params["head"], self.meta["head"], kb,
                                 comp, consumed_lead=0)

    def _lm_loss(self, params, x, targets, kb, comp, eff):
        """Chunked fused head+xent (full logits never materialized).

        Cross-entropy needs every vocab shard per token, so SP exits first:
        x arrives GATHERED (replicated over tp) — the Megatron layout."""
        from repro.models.dist import vp_xent_chunked
        cfg = self.cfg
        Bt, S_tot = targets.shape
        x = apply_norm(params, "final_norm", x, cfg)
        w = self._head_weight(params, kb, comp)
        xi = tp_region_in(x, eff.tp)
        s = vp_xent_chunked(xi.reshape(-1, cfg.d_model), w,
                            targets.reshape(-1), eff.tp, cfg.vocab)
        return s / (Bt * S_tot)

    def _logits(self, params, x, kb, comp):
        if self.cfg.tie_embeddings:
            w = self._gather_leaf(params["embed"], self.meta["embed"], kb,
                                  comp, consumed_lead=0)
            return tp_region_in(x, self.dist.tp) @ w.T
        w = self._gather_leaf(params["head"], self.meta["head"], kb, comp,
                              consumed_lead=0)
        return tp_region_in(x, self.dist.tp) @ w

    # ---- decoder stacks (train / prefill) -------------------------------
    def _run_stack(self, p_blocks, meta_blocks, x, comp, key, *, block_kind,
                   pos_offset=0, causal=True, memory=None, collect_cache=0,
                   remat=True, dist=None):
        cfg = self.cfg
        dist = dist if dist is not None else self.dist_nosp
        leaves = jax.tree_util.tree_leaves(p_blocks)
        L = leaves[0].shape[0]
        kbs = self._layer_keys(key, L)

        interleaved = (block_kind == "decoder" and cfg.n_experts
                       and cfg.moe_every > 1)

        def apply(p_layer, x, kb, idx):
            g = self._gather_layer(p_layer, meta_blocks, kb, comp)
            if interleaved:
                ga = {k[2:]: v for k, v in g.items() if k.startswith("a_")}
                gb = {k[2:]: v for k, v in g.items() if k.startswith("b_")}
                cfg_a = dataclasses.replace(cfg, n_experts=0)
                x, aux_a, ca = B.decoder_block(
                    ga, x, cfg_a, dist, window=self._layer_window(2 * idx),
                    pos_offset=pos_offset, causal=causal,
                    use_rope=cfg.use_rope,
                    collect_cache=collect_cache, tp_size=self.tp_size)
                x, aux_b, cb = B.decoder_block(
                    gb, x, cfg, dist, window=self._layer_window(2 * idx + 1),
                    pos_offset=pos_offset, causal=causal,
                    use_rope=cfg.use_rope,
                    collect_cache=collect_cache, tp_size=self.tp_size)
                cache = (ca, cb) if collect_cache else None
                return x, aux_a + aux_b, cache
            if block_kind == "decoder":
                return B.decoder_block(
                    g, x, cfg, dist, window=self._layer_window(idx),
                    pos_offset=pos_offset, causal=causal,
                    use_rope=cfg.use_rope, memory=memory,
                    collect_cache=collect_cache, tp_size=self.tp_size)
            elif block_kind == "ssm":
                h = apply_norm(g, "norm_in", x, cfg, dist)
                if collect_cache:
                    out, (cstate, sstate) = mamba2_block(
                        g, h, cfg, dist, return_state=True)
                    cache = {"conv_x": cstate[0], "conv_bc": cstate[1],
                             "ssm": sstate}
                    return x + out, jnp.zeros((), jnp.float32), cache
                return (x + mamba2_block(g, h, cfg, dist),
                        jnp.zeros((), jnp.float32), None)
            raise ValueError(block_kind)

        if remat:
            apply = jax.checkpoint(
                apply, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=())

        def body(carry, xs):
            x, aux = carry
            p_layer, kb, idx = xs
            # barrier: stops XLA from hoisting a convert of the whole saved
            # residual stack to f32 outside the backward loop (0.5 GB/layer)
            x = jax.lax.optimization_barrier(x)
            x, aux_l, cache = apply(p_layer, x, kb, idx)
            return (x, aux + aux_l), cache

        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (p_blocks, kbs, jnp.arange(L)))
        return x, aux, caches

    # ---- hybrid (zamba2) stack ------------------------------------------
    def _run_hybrid(self, params, x, comp, key, *, collect_cache=0,
                    remat=True, dist=None):
        cfg = self.cfg
        dist = dist if dist is not None else self.dist_nosp
        k_per = cfg.attn_every
        Gn = cfg.n_layers // k_per
        meta_b = self.meta["blocks"]
        # reshape (G*k, ...) -> (G, k, ...)
        pg = jax.tree_util.tree_map(
            lambda w: w.reshape((Gn, k_per) + w.shape[1:]), params["blocks"])
        kbs = self._layer_keys(key, Gn)
        shared_meta = self.meta["shared"]

        def group(carry, xs):
            x = carry
            p_group, kb, gidx = xs

            def apply(p_group, x):
                def inner(carry2, xs2):
                    x2 = jax.lax.optimization_barrier(carry2)
                    p_layer, j = xs2
                    g = self._gather_layer(p_layer, meta_b, kb, comp)
                    h = apply_norm(g, "norm_in", x2, cfg, dist)
                    if collect_cache:
                        out, (cs, ss) = mamba2_block(g, h, cfg, dist,
                                                     return_state=True)
                        return x2 + out, {"conv_x": cs[0], "conv_bc": cs[1],
                                          "ssm": ss}
                    return x2 + mamba2_block(g, h, cfg, dist), None

                x, mcaches = jax.lax.scan(inner, x,
                                          (p_group, jnp.arange(k_per)))
                gs = self._gather_layer(params["shared"], shared_meta, kb,
                                        comp, consumed_lead=0)
                x, aux, acache = B.decoder_block(
                    gs, x, dataclasses.replace(cfg, n_experts=0), dist,
                    window=cfg.sliding_window, causal=True,
                    use_rope=cfg.use_rope, collect_cache=collect_cache,
                    tp_size=self.tp_size)
                return x, (mcaches, acache)

            if remat:
                apply = jax.checkpoint(
                    apply, policy=jax.checkpoint_policies.nothing_saveable)
            x, caches = apply(p_group, x)
            return x, caches

        x, (mcaches, acaches) = jax.lax.scan(group, x,
                                             (pg, kbs, jnp.arange(Gn)))
        tail_caches = None
        if "tail_blocks" in params:
            x, _, tail_caches = self._run_stack(
                params["tail_blocks"], self.meta["tail_blocks"], x, comp,
                jax.random.fold_in(key, 7777), block_kind="ssm",
                collect_cache=collect_cache, remat=remat, dist=dist)
        if collect_cache:
            return x, {"mamba": mcaches, "attn": acaches,
                       "tail": tail_caches}
        return x, None

    # ---- top-level forward: train loss ----------------------------------
    def loss(self, params, batch, key, comp: Optional[CompressionConfig] = None,
             remat: bool = True):
        cfg = self.cfg
        kb = key_to_bits(key)
        if cfg.arch_type == "audio":
            return self._loss_audio(params, batch, key, comp, remat)
        eff = self._eff(batch["tokens"].shape[1])
        x = self._embed(params, batch["tokens"], kb, comp, dist=eff)
        if cfg.arch_type == "vlm":
            patches = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([patches, x[:, patches.shape[1]:]], axis=1)
        if not cfg.use_rope:
            x = x + sinusoid_positions(jnp.arange(x.shape[1]),
                                       cfg.d_model).astype(x.dtype)[None]
        x = self._sp_slice(x, eff)
        if cfg.arch_type in ("dense", "moe", "vlm"):
            x, aux, _ = self._run_stack(params["blocks"], self.meta["blocks"],
                                        x, comp, key, block_kind="decoder",
                                        remat=remat, dist=eff)
        elif cfg.arch_type == "ssm":
            x, aux, _ = self._run_stack(params["blocks"], self.meta["blocks"],
                                        x, comp, key, block_kind="ssm",
                                        remat=remat, dist=eff)
        elif cfg.arch_type == "hybrid":
            x, _ = self._run_hybrid(params, x, comp, key, remat=remat,
                                    dist=eff)
            aux = jnp.zeros((), jnp.float32)
        else:
            raise ValueError(cfg.arch_type)
        x = self._sp_gather(x, eff)
        l = self._lm_loss(params, x, batch["targets"], kb, comp, eff)
        return l + 0.01 * aux

    def _loss_audio(self, params, batch, key, comp, remat):
        kb = key_to_bits(key)
        mem = self._encode_audio(params, batch["frames"], comp, key, remat)
        x = self._embed(params, batch["tokens"], kb, comp)
        x = x + self._decoder_positions(params, x.shape[1], x.dtype)
        x, aux, _ = self._run_stack(params["decoder_blocks"],
                                    self.meta["decoder_blocks"], x, comp,
                                    key, block_kind="decoder", memory=mem,
                                    remat=remat)
        return self._lm_loss(params, x, batch["targets"], kb, comp,
                             self.dist_nosp)

    def _encode_audio(self, params, frames, comp, key, remat):
        """Whisper's encoder over the frame embeddings: fixed sinusoidal
        positions, the stack, the final LayerNorm. Its operations are
        named `repro/encoder` in XLA's profile."""
        cfg = self.cfg
        with jax.named_scope("repro/encoder"):
            x = frames.astype(jnp.dtype(cfg.dtype))
            x = x + sinusoid_positions(jnp.arange(x.shape[1]),
                                       cfg.d_model).astype(x.dtype)[None]
            x, _, _ = self._run_stack(params["encoder_blocks"],
                                      self.meta["encoder_blocks"], x, comp,
                                      jax.random.fold_in(key, 99),
                                      block_kind="decoder", causal=False,
                                      remat=remat)
            return apply_norm(params, "enc_final_norm", x, cfg)

    def _check_positions(self, n: int) -> None:
        """The audio decoder's learned position table holds
        `max_target_positions` positions and no more."""
        cap = self.cfg.max_target_positions
        if self.cfg.arch_type == "audio" and n > cap:
            raise ValueError(
                f"{self.cfg.name}: the decoder's learned position table "
                f"holds {cap} positions; {n} were asked for")

    def _decoder_positions(self, params, S: int, dtype):
        """(1, S, d) embeddings of the audio decoder's positions 0..S-1:
        the first S rows of the learned table."""
        self._check_positions(S)
        return params["dec_pos"][None, :S].astype(dtype)

    # ---- prefill ---------------------------------------------------------
    def prefill(self, params, batch, key, remat: bool = True,
                cache_len: int = None):
        """Forward over the prompt; returns (last_logits, cache).

        cache_len: total cache capacity (>= prompt length) so generated
        tokens have slots; defaults to the prompt length (the dry-run's
        decode shapes supply a full-size cache as input instead)."""
        cfg = self.cfg
        kb = key_to_bits(key)
        comp = None
        S = batch["tokens"].shape[1]
        clen = self.cache_len(cache_len or S)
        if cfg.arch_type == "audio":
            mem = self._encode_audio(params, batch["frames"], comp, key,
                                     remat)
            x = self._embed(params, batch["tokens"], kb, comp)
            x = x + self._decoder_positions(params, S, x.dtype)
            x, _, caches = self._run_stack(
                params["decoder_blocks"], self.meta["decoder_blocks"], x,
                comp, key, block_kind="decoder", memory=mem,
                collect_cache=clen, remat=remat)
            caches = {"self": caches, "memory": mem}
        else:
            eff = self._eff(S)
            x = self._embed(params, batch["tokens"], kb, comp, dist=eff)
            if cfg.arch_type == "vlm":
                patches = batch["patch_embeds"].astype(x.dtype)
                x = jnp.concatenate([patches, x[:, patches.shape[1]:]],
                                    axis=1)
            if not cfg.use_rope:
                x = x + sinusoid_positions(jnp.arange(S),
                                           cfg.d_model).astype(x.dtype)[None]
            x = self._sp_slice(x, eff)
            if cfg.arch_type in ("dense", "moe", "vlm"):
                x, _, caches = self._run_stack(
                    params["blocks"], self.meta["blocks"], x, comp, key,
                    block_kind="decoder", collect_cache=clen, remat=remat,
                    dist=eff)
            elif cfg.arch_type == "ssm":
                x, _, caches = self._run_stack(
                    params["blocks"], self.meta["blocks"], x, comp, key,
                    block_kind="ssm", collect_cache=clen, remat=remat,
                    dist=eff)
            elif cfg.arch_type == "hybrid":
                x, caches = self._run_hybrid(params, x, comp, key,
                                             collect_cache=clen, remat=remat,
                                             dist=eff)
            x = self._sp_gather(x, eff)
        x = apply_norm(params, "final_norm", x, cfg)
        logits = self._logits(params, x[:, -1:], kb, comp)[:, 0]
        return logits, caches

    # ---- decode ----------------------------------------------------------
    def decode_step(self, params, token: Array, pos: Array, cache,
                    memory: Optional[Array] = None):
        """token (B,) int32, pos () int32. Returns (logits (B,Vl), cache)."""
        cfg, dist = self.cfg, self.dist_nosp
        zkb = jnp.zeros((2,), jnp.float32)
        key = jax.random.key(0)
        comp = None
        x = self._embed_decode(params, token[:, None])
        if cfg.arch_type == "audio":
            self._check_positions(self._decode_capacity(cache, pos))
            x = x + jnp.take(params["dec_pos"], pos[None],
                             axis=0).astype(x.dtype)[None]
        elif not cfg.use_rope:
            x = x + sinusoid_positions(pos[None], cfg.d_model
                                       ).astype(x.dtype)[None]

        if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
            bname = "decoder_blocks" if cfg.arch_type == "audio" else "blocks"
            p_blocks = params[bname]
            meta_b = self.meta[bname]
            L = jax.tree_util.tree_leaves(p_blocks)[0].shape[0]
            kbs = self._layer_keys(key, L)
            mem = cache.get("memory") if isinstance(cache, dict) and \
                "memory" in cache else memory
            layer_caches = cache["self"] if cfg.arch_type == "audio" else cache
            fd = self._decode_fd(meta_b)
            interleaved = cfg.n_experts and cfg.moe_every > 1

            def body(x, xs):
                p_layer, c_layer, kb, idx = xs
                if interleaved:
                    ga = {k[2:]: v for k, v in p_layer.items()
                          if k.startswith("a_")}
                    gb = {k[2:]: v for k, v in p_layer.items()
                          if k.startswith("b_")}
                    fda = {k[2:]: v for k, v in fd.items()
                           if k.startswith("a_")}
                    fdb = {k[2:]: v for k, v in fd.items()
                           if k.startswith("b_")}
                    cfg_a = dataclasses.replace(cfg, n_experts=0)
                    ca, cb = c_layer
                    x, nca = B.decoder_block_decode(
                        ga, x, ca, pos, cfg_a, dist,
                        window=self._layer_window(2 * idx), fd=fda)
                    x, ncb = B.decoder_block_decode(
                        gb, x, cb, pos, cfg, dist,
                        window=self._layer_window(2 * idx + 1), fd=fdb)
                    return x, (nca, ncb)
                x, new_c = B.decoder_block_decode(
                    p_layer, x, c_layer, pos, cfg, dist,
                    window=self._layer_window(idx), memory=mem, fd=fd)
                return x, new_c

            x, new_caches = jax.lax.scan(body, x,
                                         (p_blocks, layer_caches, kbs,
                                          jnp.arange(L)))
            new_cache = ({"self": new_caches, "memory": mem}
                         if cfg.arch_type == "audio" else new_caches)
        elif cfg.arch_type == "ssm":
            p_blocks = params["blocks"]
            meta_b = self.meta["blocks"]
            L = jax.tree_util.tree_leaves(p_blocks)[0].shape[0]
            kbs = self._layer_keys(key, L)

            def body(x, xs):
                p_layer, c_layer, kb = xs
                g = self._gather_layer(p_layer, meta_b, kb, comp)
                h = apply_norm(g, "norm_in", x, cfg)
                out, ((cx, cbc), ss) = mamba2_decode(
                    g, h, (c_layer["conv_x"], c_layer["conv_bc"]),
                    c_layer["ssm"], cfg, dist)
                return x + out, {"conv_x": cx, "conv_bc": cbc, "ssm": ss}

            x, new_cache = jax.lax.scan(body, x, (p_blocks, cache, kbs))
        elif cfg.arch_type == "hybrid":
            x, new_cache = self._decode_hybrid(params, x, pos, cache, key)
        else:
            raise ValueError(cfg.arch_type)

        x = apply_norm(params, "final_norm", x, cfg)
        logits = self._logits_decode(params, x)[:, 0]
        return logits, new_cache

    def _embed_decode(self, params, tokens):
        """Vocab-parallel lookup with the d dim left fsdp-sharded, then a
        tiny all_gather of the embedding features (2D-TP decode)."""
        from repro.models.dist import all_gather
        w = params["embed"]
        x = vp_embed(w, tokens, self.dist.tp, self.vocab_padded)
        if self.dist.fsdp is not None and \
                self.meta["embed"].fsdp_dim() is not None:
            x = all_gather(x, self.dist.fsdp, gather_axis=x.ndim - 1,
                           tiled=True)
        return x

    def _logits_decode(self, params, x):
        from repro.models.dist import fdot
        xi = tp_region_in(x, self.dist.tp)
        if self.cfg.tie_embeddings:
            w = params["embed"]  # (V_tp, d[/fsdp])
            fdim = self.meta["embed"].fsdp_dim()
            return fdot(xi, jnp.swapaxes(w, 0, 1),
                        0 if (fdim is not None and self.dist.fsdp) else None,
                        self.dist)
        w = params["head"]       # (d[/fsdp], V_tp)
        fdim = self.meta["head"].fsdp_dim()
        return fdot(xi, w,
                    0 if (fdim is not None and self.dist.fsdp) else None,
                    self.dist)

    def _decode_hybrid(self, params, x, pos, cache, key):
        cfg, dist = self.cfg, self.dist_nosp
        k_per = cfg.attn_every
        Gn = cfg.n_layers // k_per
        meta_b = self.meta["blocks"]
        pg = jax.tree_util.tree_map(
            lambda w: w.reshape((Gn, k_per) + w.shape[1:]), params["blocks"])
        kbs = self._layer_keys(key, Gn)
        mcache, acache, tail_cache = cache["mamba"], cache["attn"], \
            cache.get("tail")

        def group(x, xs):
            p_group, mc_group, ac, kb = xs

            def inner(x2, xs2):
                p_layer, c_layer = xs2
                g = self._gather_layer(p_layer, meta_b, kb, None)
                h = apply_norm(g, "norm_in", x2, cfg)
                out, ((cx, cbc), ss) = mamba2_decode(
                    g, h, (c_layer["conv_x"], c_layer["conv_bc"]),
                    c_layer["ssm"], cfg, dist)
                return x2 + out, {"conv_x": cx, "conv_bc": cbc, "ssm": ss}

            x, new_mc = jax.lax.scan(inner, x, (p_group, mc_group))
            gs = self._gather_layer(params["shared"], self.meta["shared"],
                                    kb, None, consumed_lead=0)
            x, new_ac = B.decoder_block_decode(
                gs, x, ac, pos, dataclasses.replace(cfg, n_experts=0), dist,
                window=cfg.sliding_window)
            return x, (new_mc, new_ac)

        x, (new_mc, new_ac) = jax.lax.scan(group, x, (pg, mcache, acache, kbs))
        new_tail = None
        if tail_cache is not None:
            p_tail = params["tail_blocks"]
            meta_t = self.meta["tail_blocks"]
            Lt = jax.tree_util.tree_leaves(p_tail)[0].shape[0]
            kbt = self._layer_keys(jax.random.fold_in(key, 7777), Lt)

            def tbody(x2, xs2):
                p_layer, c_layer, kb = xs2
                g = self._gather_layer(p_layer, meta_t, kb, None)
                h = apply_norm(g, "norm_in", x2, cfg)
                out, ((cx, cbc), ss) = mamba2_decode(
                    g, h, (c_layer["conv_x"], c_layer["conv_bc"]),
                    c_layer["ssm"], cfg, dist)
                return x2 + out, {"conv_x": cx, "conv_bc": cbc, "ssm": ss}

            x, new_tail = jax.lax.scan(tbody, x, (p_tail, tail_cache, kbt))
        return x, {"mamba": new_mc, "attn": new_ac, "tail": new_tail}

    def _decode_capacity(self, cache, pos) -> int:
        """The positions a decode step may reach: the position itself
        where it is known while tracing, else the self-attention cache's
        length (slots over all tensor-parallel ranks)."""
        if not isinstance(pos, jax.core.Tracer):
            return int(pos) + 1
        slots = cache["self"]["slot_pos"].shape[-1]
        return slots * self.tp_size

    # ---- cache layouts ----------------------------------------------------
    def cache_len(self, seq_len: int) -> int:
        cfg = self.cfg
        self._check_positions(seq_len)
        if cfg.sliding_window > 0 and cfg.swa_pattern == 0:
            return min(seq_len, cfg.sliding_window)
        return seq_len

    def _attn_cache_sds(self, L, batch, clen, dtype):
        cfg = self.cfg
        if cfg.attention == "mla":
            return {
                "ckv": jax.ShapeDtypeStruct(
                    (L, batch, 1, clen, cfg.kv_lora_rank), dtype),
                "krope": jax.ShapeDtypeStruct(
                    (L, batch, 1, clen, cfg.qk_rope_dim), dtype),
                "slot_pos": jax.ShapeDtypeStruct((L, clen), jnp.int32),
            }
        kdt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dtype
        out = {
            "k": jax.ShapeDtypeStruct(
                (L, batch, cfg.n_kv_heads, clen, cfg.d_head), kdt),
            "v": jax.ShapeDtypeStruct(
                (L, batch, cfg.n_kv_heads, clen, cfg.d_head), kdt),
            "slot_pos": jax.ShapeDtypeStruct((L, clen), jnp.int32),
        }
        if cfg.kv_cache_dtype == "int8":
            out["k_scale"] = jax.ShapeDtypeStruct(
                (L, batch, cfg.n_kv_heads, clen), jnp.float32)
            out["v_scale"] = jax.ShapeDtypeStruct(
                (L, batch, cfg.n_kv_heads, clen), jnp.float32)
        return out

    def _attn_cache_pspec(self, shard_batch: bool = True):
        dp = (tuple(self.dist.dp) or None) if shard_batch else None
        tp = self.dist.tp
        base = {"slot_pos": P(None, tp)}
        if self.cfg.attention == "mla":
            base.update(ckv=P(None, dp, None, tp, None),
                        krope=P(None, dp, None, tp, None))
        else:
            base.update(k=P(None, dp, None, tp, None),
                        v=P(None, dp, None, tp, None))
            if self.cfg.kv_cache_dtype == "int8":
                base.update(k_scale=P(None, dp, None, tp),
                            v_scale=P(None, dp, None, tp))
        return base

    def _ssm_cache_sds(self, L, batch, dtype):
        cfg = self.cfg
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim
        N, K, G = cfg.ssm_state, cfg.ssm_conv, cfg.ssm_groups
        return {
            "conv_x": jax.ShapeDtypeStruct((L, batch, K - 1, d_in), dtype),
            "conv_bc": jax.ShapeDtypeStruct((L, batch, K - 1, 2 * G * N),
                                            dtype),
            "ssm": jax.ShapeDtypeStruct(
                (L, batch, nh, cfg.ssm_head_dim, N), jnp.float32),
        }

    def _ssm_cache_pspec(self, shard_batch: bool = True):
        dp = (tuple(self.dist.dp) or None) if shard_batch else None
        tp = self.dist.tp
        return {"conv_x": P(None, dp, None, tp),
                "conv_bc": P(None, dp, None, None),
                "ssm": P(None, dp, tp, None, None)}

    def cache_shapes(self, seq_len: int, batch: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        clen = self.cache_len(seq_len)
        L = cfg.n_layers
        if cfg.arch_type in ("dense", "moe", "vlm"):
            if cfg.n_experts and cfg.moe_every > 1:
                half = self._attn_cache_sds(L // 2, batch, clen, dtype)
                return (half, half)
            return self._attn_cache_sds(L, batch, clen, dtype)
        if cfg.arch_type == "ssm":
            return self._ssm_cache_sds(L, batch, dtype)
        if cfg.arch_type == "hybrid":
            Gn = L // cfg.attn_every
            out = {"mamba": jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (Gn, cfg.attn_every) + s.shape[1:], s.dtype),
                self._ssm_cache_sds(1, batch, dtype)),
                "attn": self._attn_cache_sds(Gn, batch, clen, dtype)}
            tail = L - Gn * cfg.attn_every
            out["tail"] = (self._ssm_cache_sds(tail, batch, dtype)
                           if tail else None)
            return out
        if cfg.arch_type == "audio":
            out = {"self": self._attn_cache_sds(L, batch, clen, dtype),
                   "memory": jax.ShapeDtypeStruct(
                       (batch, cfg.frontend_seq, cfg.d_model), dtype)}
            return out
        raise ValueError(cfg.arch_type)

    def cache_pspecs(self, shard_batch: bool = True):
        """shard_batch=False: global batch < dp size (long_500k) — the
        cache replicates over the dp axes instead."""
        cfg = self.cfg
        dp = (tuple(self.dist.dp) or None) if shard_batch else None
        sb = shard_batch
        if cfg.arch_type in ("dense", "moe", "vlm"):
            if cfg.n_experts and cfg.moe_every > 1:
                return (self._attn_cache_pspec(sb), self._attn_cache_pspec(sb))
            return self._attn_cache_pspec(sb)
        if cfg.arch_type == "ssm":
            return self._ssm_cache_pspec(sb)
        if cfg.arch_type == "hybrid":
            m = {k: P(*((None,) + tuple(v)))
                 for k, v in self._ssm_cache_pspec(sb).items()}
            tail = (self._ssm_cache_pspec(sb)
                    if cfg.n_layers % cfg.attn_every else None)
            return {"mamba": m, "attn": self._attn_cache_pspec(sb),
                    "tail": tail}
        if cfg.arch_type == "audio":
            return {"self": self._attn_cache_pspec(sb),
                    "memory": P(dp, None, None)}
        raise ValueError(cfg.arch_type)

    def init_cache(self, seq_len: int, batch: int):
        """Materialize an empty cache (slot_pos = -1). Single-host sizes."""
        def mk(s):
            if s is None:
                return None
            arr = jnp.zeros(s.shape, s.dtype)
            return arr
        shapes = self.cache_shapes(seq_len, batch)
        cache = jax.tree_util.tree_map(mk, shapes)

        def fix_slots(path, x):
            if x is not None and path and getattr(path[-1], "key", "") == \
                    "slot_pos":
                return jnp.full(x.shape, -1, jnp.int32)
            return x
        return jax.tree_util.tree_map_with_path(fix_slots, cache)
