"""Plain float32 reference of Whisper's encoder-decoder transformer as the
program configures it (bench/configs/whisper-base.json lists where that
departs from the published Whisper-base).

Encoder, over the frame embeddings f (b, T, d) that stand in for the conv
front end:
  x  = f + sinusoids(T, d)                    Whisper's fixed positions
  per layer:  x += Attn(LN(x), LN(x))         bidirectional
              x += MLP(LN(x))
  m  = LN(x)
Decoder, over tokens t (b, S):
  y  = E[t] + P[:S]                           P the learned 448-row table
  per layer:  y += Attn(LN(y), LN(y))         causal
              y += Attn(LN(y), m)             cross-attention
              y += MLP(LN(y))
  logits = LN(y) E^T over the first `vocab_size` rows of the tied E,
and the mean cross-entropy of the next token. LN is LayerNorm with gain
and bias; Attn(h, s) = softmax((h Wq + bq)(s Wk)^T / sqrt(64))(s Wv + bv)
Wo + bo over heads of 64 (no bias on k); MLP(h) = GELU(h W_in + b_in)
W_out + b_out with the exact (erf) GELU.

Where the program keeps bf16, so does the reference: the parameters are
stored in bf16 between steps (bench/reference/_common.py) and the frame
embeddings enter rounded to bf16, as the program casts them. Everything
else, the residual streams included, is float32 here.

Gradients are taken over blocks of rows, the whole model at once, with
the parameters upcast to float32: a block's attention probabilities and
logits fit one chip beside nothing else.
"""
from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference._common import xent_sum

#: rows of the batch that one forward/backward block holds
BLOCK_ROWS = 4

ATTN = ("attn_norm_g", "attn_norm_b", "wq", "bq", "wk", "wv", "bv", "wo",
        "bo")
CROSS = ("cross_norm_g", "cross_norm_b", "cwq", "cbq", "cwk", "cwv", "cbv",
         "cwo", "cbo")
MLP = ("mlp_norm_g", "mlp_norm_b", "w_in", "b_in", "w_out", "b_out")


def param_specs(c):
    """{path: (shape, init, std)} of the program's parameter tree. The
    matrices are normal with std 1/sqrt(fan-in); biases, LayerNorm biases
    and the decoder's position table normal with std 0.02 (so that each
    takes part in the comparison); LayerNorm gains 1."""
    d, V = c["d_model"], c["vocab_padded"]
    ff = c["encoder_ffn_dim"]
    r = lambda n: 1.0 / math.sqrt(n)
    out = {"embed": ((V, d), "normal", r(d)),
           "dec_pos": ((c["max_target_positions"], d), "normal", 0.02),
           "enc_final_norm_g": ((d,), "ones", 0.0),
           "enc_final_norm_b": ((d,), "normal", 0.02),
           "final_norm_g": ((d,), "ones", 0.0),
           "final_norm_b": ((d,), "normal", 0.02)}
    for stack, L, leaves in (
            ("encoder_blocks", c["encoder_layers"], ATTN + MLP),
            ("decoder_blocks", c["decoder_layers"], ATTN + CROSS + MLP)):
        shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
                  "cwq": (d, d), "cwk": (d, d), "cwv": (d, d),
                  "cwo": (d, d), "w_in": (d, ff), "w_out": (ff, d),
                  "b_in": (ff,)}
        for n in leaves:
            shape = (L,) + shapes.get(n, (d,))
            if n.endswith("norm_g"):
                out[f"{stack}/{n}"] = (shape, "ones", 0.0)
            elif len(shape) == 3:
                out[f"{stack}/{n}"] = (shape, "normal", r(shape[1]))
            else:
                out[f"{stack}/{n}"] = (shape, "normal", 0.02)
    return out


def sinusoids(length: int, channels: int, max_timescale: float = 10000.0):
    """Whisper's encoder positions (whisper/model.py `sinusoids`), in
    float64 and then float32."""
    inc = np.log(max_timescale) / (channels // 2 - 1)
    inv = np.exp(-inc * np.arange(channels // 2))
    t = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)], axis=1).astype(np.float32)


def layernorm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def attention(p, h, s, heads, causal, dot, names):
    """Multi-head attention of queries from h over keys and values from
    s; `names` are the leaves of q, its bias, k, v, v's bias, out and its
    bias."""
    wq, bq, wk, wv, bv, wo, bo = (p[n] for n in names)
    b, S, d = h.shape
    T = s.shape[1]
    dh = d // heads
    q = (dot("bsd,de->bse", h, wq) + bq).reshape(b, S, heads, dh)
    k = dot("btd,de->bte", s, wk).reshape(b, T, heads, dh)
    v = (dot("btd,de->bte", s, wv) + bv).reshape(b, T, heads, dh)
    logits = dot("bshe,bthe->bhst", q, k) / math.sqrt(dh)
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((S, T), bool)), logits,
                           -jnp.inf)
    o = dot("bhst,bthe->bshe", jax.nn.softmax(logits, axis=-1), v)
    return dot("bse,ed->bsd", o.reshape(b, S, d), wo) + bo


def mlp(p, h, dot):
    u = jax.nn.gelu(dot("bsd,df->bsf", h, p["w_in"]) + p["b_in"],
                    approximate=False)
    return dot("bsf,fd->bsd", u, p["w_out"]) + p["b_out"]


def encoder_layer(p, x, c, dot):
    eps, H = c["norm_eps"], c["encoder_attention_heads"]
    h = layernorm(x, p["attn_norm_g"], p["attn_norm_b"], eps)
    x = dot.store(x + attention(p, h, h, H, False, dot, ATTN[2:]))
    h = layernorm(x, p["mlp_norm_g"], p["mlp_norm_b"], eps)
    return dot.store(x + mlp(p, h, dot))


def decoder_layer(p, y, m, c, dot):
    eps, H = c["norm_eps"], c["decoder_attention_heads"]
    h = layernorm(y, p["attn_norm_g"], p["attn_norm_b"], eps)
    y = dot.store(y + attention(p, h, h, H, True, dot, ATTN[2:]))
    h = layernorm(y, p["cross_norm_g"], p["cross_norm_b"], eps)
    y = dot.store(y + attention(p, h, m, H, False, dot, CROSS[2:]))
    h = layernorm(y, p["mlp_norm_g"], p["mlp_norm_b"], eps)
    return dot.store(y + mlp(p, h, dot))


def _layer(p, stack, l):
    pre = stack + "/"
    return {k[len(pre):]: v[l] for k, v in p.items() if k.startswith(pre)}


def loss_sum(p, tokens, targets, frames, c, dot):
    """Summed token loss of a block of rows, parameters in float32."""
    eps = c["norm_eps"]
    T = frames.shape[1]
    x = dot.store(frames + jnp.asarray(sinusoids(T, c["d_model"])))
    for l in range(c["encoder_layers"]):
        x = encoder_layer(_layer(p, "encoder_blocks", l), x, c, dot)
    m = layernorm(x, p["enc_final_norm_g"], p["enc_final_norm_b"], eps)
    S = tokens.shape[1]
    y = dot.store(p["embed"][tokens] + p["dec_pos"][:S])
    for l in range(c["decoder_layers"]):
        y = decoder_layer(_layer(p, "decoder_blocks", l), y, m, c, dot)
    h = layernorm(y, p["final_norm_g"], p["final_norm_b"], eps)
    E = p["embed"][:c["vocab_size"]]
    return xent_sum(dot("bsd,vd->bsv", h, E), targets)


@functools.lru_cache(maxsize=None)
def _block_grad(cjson: str, dot):
    """The jitted gradient of one block of rows, for one configuration
    and one contraction: (params, acc, tokens, targets, frames) ->
    (loss sum, acc + gradient)."""
    c = json.loads(cjson)
    frames_dtype = jnp.dtype(c["dtype"])

    @partial(jax.jit, donate_argnums=(1,))
    def step(params, acc, tokens, targets, frames):
        # the program casts the frame embeddings to its dtype
        f = frames.astype(frames_dtype).astype(jnp.float32)
        p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
        s, g = jax.value_and_grad(
            lambda p: loss_sum(p, tokens, targets, f, c, dot))(p32)
        return s, {k: acc[k] + g[k] for k in acc}
    return step


def grad_sum(params, batch, c, dot):
    """Gradient of the summed token loss over the rows of `batch`, in
    float32 -> (grads {path: array}, loss sum, tokens)."""
    step = _block_grad(json.dumps(c, sort_keys=True), dot)
    acc = {p: jnp.zeros(v.shape, jnp.float32) for p, v in params.items()}
    rows = batch["tokens"].shape[0]
    total = 0.0
    for r0 in range(0, rows, BLOCK_ROWS):
        rs = slice(r0, r0 + BLOCK_ROWS)
        s, acc = step(params, acc, jnp.asarray(batch["tokens"][rs]),
                      jnp.asarray(batch["targets"][rs]),
                      jnp.asarray(batch["frames"][rs]))
        total += float(s)
    return acc, total, int(batch["tokens"].size)
