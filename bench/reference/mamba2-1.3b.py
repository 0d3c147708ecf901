"""Plain float32 reference of the mamba2 language model as the program
configures it (bench/configs/mamba2-1.3b.json lists where that departs
from the published Mamba-2).

Per layer, with x the residual stream (b, S, d):
  h  = RMSNorm(x)
  z  = h W_z,  u = h W_x,  bc = h W_bc,  dt = softplus(h W_dt + dt_bias)
  u  = silu(causal depthwise conv(u)),  bc = silu(causal conv(bc))
  B, C = bc[:N], bc[N:]
  y  = SSD(u as H heads of P, dt, A = -exp(A_log), B, C) + D u
  x += (RMSNorm per head(y) * silu(z)) W_out
then logits = RMSNorm(x) E^T over the first `vocab_size` rows of the
tied embedding E, and the mean cross-entropy of the next token.

SSD is the chunked algorithm of the Mamba-2 paper's minimal listing
(ssd_minimal_discrete), with the stable segment sum, in chunks of 128.

Gradients are taken layer by layer: a forward pass keeps each layer's
input, then each layer's vector-Jacobian product runs from the top, with
the layer's parameters upcast to float32 alone. So the reference needs the
bf16 parameters, one float32 gradient and the stored layer inputs of a
block of rows, which fits one chip beside nothing else.
"""
from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference._common import rmsnorm, xent_sum

#: rows of the batch that one forward/backward block holds
BLOCK_ROWS = 2
#: SSD chunk length of the reference (the program uses the config's)
CHUNK = 128

LAYER_LEAVES = ("norm_in_g", "w_z", "w_x", "w_bc", "w_dt", "conv_x",
                "conv_bc", "A_log", "D", "dt_bias", "norm_g", "w_out")


def dims(c):
    d = c["d_model"]
    di = c["expand"] * d
    return d, di, di // c["headdim"], c["headdim"], c["d_state"], c["d_conv"]


def param_specs(c):
    """{path: (shape, init, std)} of the program's parameter tree."""
    d, di, H, P, N, K = dims(c)
    L, G = c["n_layer"], c["ngroups"]
    r = lambda n: 1.0 / math.sqrt(n)
    return {
        "embed": ((c["vocab_padded"], d), "normal", r(d)),
        "final_norm_g": ((d,), "ones", 0.0),
        "blocks/norm_in_g": ((L, d), "ones", 0.0),
        "blocks/w_z": ((L, d, di), "normal", r(d)),
        "blocks/w_x": ((L, d, di), "normal", r(d)),
        "blocks/w_bc": ((L, d, 2 * G * N), "normal", r(d)),
        "blocks/w_dt": ((L, d, H), "normal", r(d)),
        "blocks/conv_x": ((L, di, K), "normal", 0.5 * r(K)),
        "blocks/conv_bc": ((L, 2 * G * N, K), "normal", 0.5 * r(K)),
        "blocks/A_log": ((L, H), "a_log", 0.0),
        "blocks/D": ((L, H), "ones", 0.0),
        "blocks/dt_bias": ((L, H), "dt_bias", 0.0),
        "blocks/norm_g": ((L, di), "ones", 0.0),
        "blocks/w_out": ((L, di, d), "normal", r(di)),
    }


def segsum(x):
    """(..., T) -> (..., T, T): out[i, j] = x[j+1] + ... + x[i] for i >= j,
    -inf above the diagonal (the stable form: a masked cumulative sum)."""
    T = x.shape[-1]
    xr = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    low = jnp.tril(jnp.ones((T, T), bool), -1)
    cs = jnp.cumsum(jnp.where(low, xr, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), cs, -jnp.inf)


def ssd(X, A, B, C, dot, chunk):
    """Discrete SSD: X (b,S,H,P) already times dt, A (b,S,H) = dt * A,
    B, C (b,S,N) -> Y (b,S,H,P), from a zero state."""
    b, S, H, P = X.shape
    N = B.shape[-1]
    c = S // chunk
    X = X.reshape(b, c, chunk, H, P)
    A = A.reshape(b, c, chunk, H).transpose(0, 3, 1, 2)      # (b,H,c,l)
    B = B.reshape(b, c, chunk, N)
    C = C.reshape(b, c, chunk, N)
    A_cum = jnp.cumsum(A, axis=-1)
    # 1. outputs within each chunk
    Lm = jnp.exp(segsum(A))                                  # (b,H,c,l,s)
    CB = dot("bcln,bcsn->bcls", C, B)
    Y_diag = dot("bhcls,bcshp->bclhp", CB[:, None] * Lm, X)
    # 2. the state each chunk ends with
    decay = jnp.exp(A_cum[..., -1:] - A_cum)                 # (b,H,c,l)
    Bw = B[:, None] * decay[..., None]                       # (b,H,c,l,N)
    states = dot("bhcln,bclhp->bchpn", Bw, X)
    # 3. the recurrence between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    dchunk = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                     (1, 0)))))
    states = dot("bhzc,bchpn->bzhpn", dchunk, states)[:, :-1]
    # 4. state -> output within each chunk
    Y_off = dot("bcln,bchpn->bclhp", C, states) * \
        jnp.exp(A_cum).transpose(0, 2, 3, 1)[..., None]
    return (Y_diag + Y_off).reshape(b, S, H, P)


def causal_conv(u, w):
    """Depthwise causal conv: u (b,S,C), w (C,K); tap K-1 is the current
    position."""
    K = w.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(up[:, k:k + u.shape[1]] * w[:, k] for k in range(K))


def layer(p, x, c, dot):
    d, di, H, P, N, K = dims(c)
    eps = c["norm_eps"]
    b, S, _ = x.shape
    h = rmsnorm(x, p["norm_in_g"], eps)
    z = dot("bsd,de->bse", h, p["w_z"])
    u = dot("bsd,de->bse", h, p["w_x"])
    bc = dot("bsd,de->bse", h, p["w_bc"])
    dt = jax.nn.softplus(dot("bsd,de->bse", h, p["w_dt"]) + p["dt_bias"])
    u = jax.nn.silu(causal_conv(u, p["conv_x"]))
    bc = jax.nn.silu(causal_conv(bc, p["conv_bc"]))
    Bm, Cm = bc[..., :N], bc[..., N:]
    A = -jnp.exp(p["A_log"])
    uh = u.reshape(b, S, H, P)
    y = ssd(uh * dt[..., None], dt * A, Bm, Cm, dot, math.gcd(CHUNK, S))
    y = y + p["D"][:, None] * uh
    y = rmsnorm(y, p["norm_g"].reshape(H, P), eps).reshape(b, S, di)
    return dot.store(x + dot("bse,ed->bsd", y * jax.nn.silu(z), p["w_out"]))


def _layer_params(params, l):
    return {n: params["blocks/" + n][l].astype(jnp.float32)
            for n in LAYER_LEAVES}


@functools.lru_cache(maxsize=None)
def _programs(cjson: str, dot):
    """The jitted pieces of one forward/backward, for one configuration
    and one contraction."""
    c = json.loads(cjson)
    V = c["vocab_size"]

    @jax.jit
    def embed(params, tokens):
        return dot.store(params["embed"][tokens].astype(jnp.float32))

    @jax.jit
    def fwd(params, l, x):
        return layer(_layer_params(params, l), x, c, dot)

    @partial(jax.jit, donate_argnums=(4,))
    def bwd(params, l, x, gx, acc):
        _, vjp = jax.vjp(lambda p, x_: layer(p, x_, c, dot),
                         _layer_params(params, l), x)
        gp, gx_in = vjp(gx)
        acc = dict(acc)
        for n in LAYER_LEAVES:
            k = "blocks/" + n
            acc[k] = acc[k].at[l].add(gp[n])
        return gx_in, acc

    @jax.jit
    def head(params, x, targets):
        def f(E, g, x_):
            h = rmsnorm(x_, g, c["norm_eps"])
            return xent_sum(dot("bsd,vd->bsv", h, E[:V]), targets)
        E = params["embed"].astype(jnp.float32)
        g = params["final_norm_g"].astype(jnp.float32)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(E, g, x)

    @partial(jax.jit, donate_argnums=(0,))
    def embed_bwd(acc, gE, gg, tokens, gx0):
        acc = dict(acc)
        acc["embed"] = (acc["embed"] + gE).at[tokens].add(gx0)
        acc["final_norm_g"] = acc["final_norm_g"] + gg
        return acc

    return embed, fwd, bwd, head, embed_bwd


def grad_sum(params, batch, c, dot):
    """Gradient of the summed token loss over the rows of `batch`, in
    float32 -> (grads {path: array}, loss sum, tokens)."""
    embed, fwd, bwd, head, embed_bwd = _programs(
        json.dumps(c, sort_keys=True), dot)
    acc = {p: jnp.zeros(v.shape, jnp.float32) for p, v in params.items()}
    rows = batch["tokens"].shape[0]
    total = 0.0
    for r0 in range(0, rows, BLOCK_ROWS):
        tok = jnp.asarray(batch["tokens"][r0:r0 + BLOCK_ROWS])
        tgt = jnp.asarray(batch["targets"][r0:r0 + BLOCK_ROWS])
        xs = [embed(params, tok)]
        for l in range(c["n_layer"]):
            xs.append(fwd(params, jnp.int32(l), xs[-1]))
        s, (gE, gg, gx) = head(params, xs.pop(), tgt)
        total += float(s)
        for l in reversed(range(c["n_layer"])):
            gx, acc = bwd(params, jnp.int32(l), xs.pop(), gx, acc)
        acc = embed_bwd(acc, gE, gg, tok, gx)
    return acc, total, int(batch["tokens"].size)
