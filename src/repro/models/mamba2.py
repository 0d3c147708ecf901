"""Mamba2 / SSD (state-space duality, arXiv:2405.21060) in pure JAX.

Chunked SSD algorithm: within a chunk the recurrence is computed as a
masked quadratic form (MXU-friendly); across chunks a small lax.scan carries
the (heads, head_dim, state) SSM state. On TPU, where the shapes tile, the
same algorithm runs as one Pallas kernel pair (`kernels/ssd.py`) that keeps
each chunk's scores and the carried state in VMEM. Heads and inner channels are
TP-sharded; B/C projections are group-shared (G=1 ⇒ MQA-like) and therefore
TP-replicated with tp_shared grad sync.

Decode is the O(1) recurrent step on the carried state (this is why the SSM
architectures run long_500k natively).

The gated output RMSNorm is PER-HEAD (group size = head_dim) so its
statistics are invariant to how heads are sharded over TP — the same
reason Mamba-2 uses GroupNorm with ngroups = tp_size in Megatron.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import ssd as ssd_kernel
from repro.models.dist import (DistConfig, region_in, region_out,
                               tp_region_in, tp_region_out, tp_shared)
from repro.models.layers import rmsnorm

Array = jax.Array


def segsum(x: Array) -> Array:
    """x (..., Q) -> (..., Q, Q) with out[i,j] = sum_{l=j+1..i} x_l (i>=j),
    -inf above the diagonal."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(Q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, d, -jnp.inf)


def _causal_conv(x: Array, w: Array, state: Array = None):
    """Depthwise causal conv along seq. x (B,S,C), w (C,K).
    If state (B,K-1,C) is given it is prepended (decode/prefill carry).
    Returns (y (B,S,C), new_state (B,K-1,C))."""
    K = w.shape[1]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, k:k + x.shape[1], :] * w[:, k][None, None, :]
            for k in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else state
    return y, new_state


def ssd_chunked(xh: Array, dt: Array, A: Array, Bm: Array, Cm: Array,
                D: Array, chunk: int, init_state: Array = None
                ) -> Tuple[Array, Array]:
    """Chunked SSD scan.

    xh (B,S,H,P) values; dt (B,S,H) softplus'd step; A (H,) negative;
    Bm/Cm (B,S,N) group-shared input/output projections; D (H,) skip.
    Returns (y (B,S,H,P), final_state (B,H,P,N)).

    Compiled for TPU with shapes the kernel pair tiles, this runs
    `kernels.ssd.ssd`; otherwise (and on CPU, where this jnp form is the
    kernels' oracle) the einsums below.
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    hb = None if ops.interpret_mode() else \
        ssd_kernel.head_block(H, P, N, chunk)
    if hb is not None:
        y, final = ssd_kernel.ssd(xh.reshape(Bsz, S, H * P), dt, A, Bm, Cm,
                                  D, chunk, init_state, hb=hb,
                                  interpret=False)
        return y.reshape(Bsz, S, H, P), final
    pad = (-S) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = xh.shape[1] // chunk
    xc = xh.reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)

    dA = dtc * A[None, None, None, :]                     # (B,nc,Q,H) ≤ 0
    dA_h = dA.transpose(0, 1, 3, 2)                       # (B,nc,H,Q)
    dA_cum = jnp.cumsum(dA_h, axis=-1)                    # (B,nc,H,Q)

    # 1) intra-chunk (quadratic, masked).  NB: keep every einsum a
    # 2-operand contraction — multi-operand forms materialize 6-D
    # outer-product temporaries (4+ GB/device at train_4k).
    L = jnp.exp(segsum(dA_h))                             # (B,nc,H,Q,Q)
    CB = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)            # (B,nc,Q,Q)
    M = CB[:, :, None, :, :] * L * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", M, xc)

    # 2) per-chunk input states
    decay_to_end = jnp.exp(dA_cum[..., -1:] - dA_cum)     # (B,nc,H,Q)
    xw = xc * (decay_to_end.transpose(0, 1, 3, 2) * dtc)[..., None]
    S_chunk = jnp.einsum("bckn,bckhp->bchpn", Bc, xw)     # (B,nc,H,P,N)

    # 3) inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[..., -1])                # (B,nc,H)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(state, inp):
        s_c, g_c = inp                                    # (B,H,P,N), (B,H)
        prev = state
        state = g_c[..., None, None] * state + s_c
        return state, prev

    final, prev_states = jax.lax.scan(
        step, init_state.astype(jnp.float32),
        (S_chunk.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (B,nc,H,P,N)

    # 4) inter-chunk output
    state_decay = jnp.exp(dA_cum)                          # (B,nc,H,Q)
    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cc, prev_states) * \
        state_decay.transpose(0, 1, 3, 2)[..., None]

    y = y_intra + y_inter + D[None, None, None, :, None] * xc
    y = y.reshape(Bsz, nc * chunk, H, P)[:, :S]
    return y.astype(xh.dtype), final


def mamba2_block(p: Dict[str, Array], x: Array, cfg, dist: DistConfig,
                 conv_state=None, ssm_state=None, return_state: bool = False):
    """Full Mamba2 block (train / prefill). x (B,S,d) -> (B,S,d)."""
    N, K = cfg.ssm_state, cfg.ssm_conv
    hd = cfg.ssm_head_dim
    xi = region_in(x, dist)
    z = xi @ p["w_z"]                                      # (B,S,d_in_l)
    xr = xi @ p["w_x"]
    bc = xi @ tp_shared(p["w_bc"], dist.tp)                # (B,S,2N)
    dt = xi @ p["w_dt"] + p["dt_bias"][None, None, :]      # (B,S,H_l)
    dt = jax.nn.softplus(dt.astype(jnp.float32))

    cx0 = conv_state[0] if conv_state is not None else None
    cbc0 = conv_state[1] if conv_state is not None else None
    xr, new_cx = _causal_conv(xr, p["conv_x"], cx0)
    bc, new_cbc = _causal_conv(bc, tp_shared(p["conv_bc"], dist.tp), cbc0)
    xr = jax.nn.silu(xr)
    bc = jax.nn.silu(bc)
    Bm, Cm = bc[..., :N], bc[..., N:]

    H_l = p["A_log"].shape[0]
    xh = xr.reshape(*xr.shape[:2], H_l, hd)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, final_state = ssd_chunked(xh, dt, A, Bm, Cm,
                                 p["D"].astype(jnp.float32), cfg.ssm_chunk,
                                 init_state=ssm_state)
    y = rmsnorm(y, p["norm_g"].reshape(H_l, hd), cfg.norm_eps)
    y = y.reshape(*xr.shape) * jax.nn.silu(z)
    out = region_out(y @ p["w_out"], dist)
    if return_state:
        return out, ((new_cx, new_cbc), final_state)
    return out


def mamba2_decode(p: Dict[str, Array], x: Array, conv_state, ssm_state,
                  cfg, dist: DistConfig):
    """One-token recurrent step. x (B,1,d); conv_state = (cx (B,K-1,d_in_l),
    cbc (B,K-1,2N)); ssm_state (B,H_l,P,N). Returns (out, new_states)."""
    N = cfg.ssm_state
    hd = cfg.ssm_head_dim
    xi = tp_region_in(x, dist.tp)
    z = xi @ p["w_z"]
    xr = xi @ p["w_x"]
    bc = xi @ tp_shared(p["w_bc"], dist.tp)
    dt = xi @ p["w_dt"] + p["dt_bias"][None, None, :]
    dt = jax.nn.softplus(dt.astype(jnp.float32))[:, 0]     # (B,H_l)

    xr, new_cx = _causal_conv(xr, p["conv_x"], conv_state[0])
    bc, new_cbc = _causal_conv(bc, tp_shared(p["conv_bc"], dist.tp),
                               conv_state[1])
    xr = jax.nn.silu(xr)[:, 0]                             # (B,d_in_l)
    bc = jax.nn.silu(bc)[:, 0]
    Bm, Cm = bc[..., :N], bc[..., N:]                      # (B,N)

    H_l = p["A_log"].shape[0]
    xh = xr.reshape(-1, H_l, hd).astype(jnp.float32)       # (B,H,P)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    g = jnp.exp(dt * A[None, :])                           # (B,H)
    upd = jnp.einsum("bn,bh,bhp->bhpn", Bm.astype(jnp.float32), dt, xh)
    new_state = g[..., None, None] * ssm_state + upd
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), new_state)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
    y = rmsnorm(y.astype(x.dtype), p["norm_g"].reshape(H_l, hd),
                cfg.norm_eps)
    y = y.reshape(x.shape[0], 1, -1) * jax.nn.silu(z)
    out = tp_region_out(y @ p["w_out"], dist.tp)
    return out, ((new_cx, new_cbc), new_state)
