"""What every plain reference shares: contractions at a stated precision,
norms, QSGD and the training driver that follows the program's first
steps.

Nothing here imports the program. The reference computes every operation
in float32, with matrix products at HIGHEST precision, and stores the
parameters between steps in the configuration's dtype (bf16), because the
state after each step is what is compared. The control works one
precision below the configuration's (fp8 e4m3 with one scale per tensor
for bf16, bf16 for float32), as the configuration's precision covers both
what is stored and what is computed: it keeps the parameters in that
precision, from the seed's weights on, and rounds every contraction's
operands and result and every layer's output, forward and backward, to
it; everything else is computed as the reference does.
"""
from __future__ import annotations

import sys
import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
#: the largest value of an IEEE-style float of 4 exponent and 3 mantissa
#: bits (fp8 e4m3 keeping its top exponent for infinities)
E4M3_MAX = 240.0
STACKED = ("blocks", "layers", "encoder_blocks", "decoder_blocks")

# The rounding is lax.reduce_precision and not a cast to the narrow type
# and back: XLA on the TPU may drop such a pair of casts inside a fused
# program, and a control whose weights it kept that way read as if they
# were float32 (PERF.md, section 6).


def _q8(x):
    """fp8 e4m3 with one scale per tensor."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / E4M3_MAX)
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rounded(quant):
    """Identity that rounds its value, and its cotangent, by `quant`."""
    @jax.custom_vjp
    def store(x):
        return quant(x)

    store.defvjp(lambda x: (quant(x), None), lambda _, g: (quant(g),))
    return store


class Contraction:
    """How the reference computes: `dot(spec, a, b)` is an einsum of two
    operands, `dot.store(x)` what a layer's output is kept in. The
    reference computes in float32 at HIGHEST precision and keeps float32;
    a control rounds every contraction's operands and result and every
    layer's output, forward and backward, to its precision."""

    def __init__(self, quant=None):
        self.quant = quant
        self.store = (lambda x: x) if quant is None else _rounded(quant)
        if quant is not None:
            @partial(jax.custom_vjp, nondiff_argnums=(0,))
            def ein(spec, a, b):
                return _ein(spec, quant(a), quant(b))

            def fwd(spec, a, b):
                qa, qb = quant(a), quant(b)
                return _ein(spec, qa, qb), (qa, qb)

            def bwd(spec, res, g):
                _, vjp = jax.vjp(lambda a, b: _ein(spec, a, b), *res)
                return vjp(quant(g))

            ein.defvjp(fwd, bwd)
            self._ein = ein
        else:
            self._ein = _ein

    def __call__(self, spec, a, b):
        if self.quant is None:
            return self._ein(spec, a, b)
        return self.store(self._ein(spec, a, b))


_CONTRACTIONS = {"f32": Contraction(), "bf16": Contraction(_bf16),
                 "fp8": Contraction(_q8)}


def contraction(precision: str) -> Contraction:
    """"f32" (the reference), or the control's "bf16" or "fp8"."""
    if precision not in _CONTRACTIONS:
        raise ValueError(f"unknown precision {precision!r}")
    return _CONTRACTIONS[precision]


def control_precision(dtype: str) -> str:
    """The precision one step below the configuration's: fp8 for bf16
    parameters and compute, bf16 for float32."""
    return {"bfloat16": "fp8", "float32": "bf16"}[dtype]


def own_precision(dtype: str) -> str:
    """The configuration's own precision, as a contraction: "bf16" for
    bf16 parameters and compute, "f32" (the reference) for float32."""
    return {"bfloat16": "bf16", "float32": "f32"}[dtype]


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def xent_sum(logits, targets):
    """Sum over tokens of -log softmax(logits)[target]."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


# --------------------------------------------------------------------------
# the worker compressor (QSGD, Alistarh et al. 2017) over layer-wise units
# --------------------------------------------------------------------------

#: the program's step key: jax.random.key(42) with the step folded in
STEP_KEY_SEED = 42


def qsgd(x, key, levels: int):
    """One unit: ||x|| sign(x_i) xi_i / s, xi_i the stochastic rounding of
    s |x_i| / ||x||, with u_i = uniform(key, (d,))."""
    nrm = jnp.sqrt(jnp.sum(x * x))
    y = jnp.abs(x) / jnp.where(nrm > 0, nrm, 1.0) * levels
    lo = jnp.floor(y)
    lev = lo + (jax.random.uniform(key, x.shape) < (y - lo))
    return jnp.sign(x) * lev * (nrm / levels)


@partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
def _compress_leaf(g, step_key, uid, worker, stacked: bool, levels: int):
    """A leaf's units through QSGD. The unit keys follow the program's
    protocol for layer-wise units: units are numbered through the leaves
    in tree order; a stacked leaf's unit i folds (number of its first
    unit, i), any other leaf's unit folds its number; the worker's index
    is folded last. With the same key, jax's partitionable threefry gives
    the same uniform draws the program's kernels reproduce."""
    if stacked:
        kb = jax.random.fold_in(step_key, uid)
        keys = jax.vmap(lambda i: jax.random.fold_in(
            jax.random.fold_in(kb, i), worker))(jnp.arange(g.shape[0]))
        flat = g.reshape(g.shape[0], -1)
        return jax.vmap(lambda x, k: qsgd(x, k, levels))(flat, keys
                                                          ).reshape(g.shape)
    key = jax.random.fold_in(jax.random.fold_in(step_key, uid), worker)
    return qsgd(g.reshape(-1), key, levels).reshape(g.shape)


def tree_order(paths) -> List[str]:
    """The paths in jax's leaf order of the nested parameter tree."""
    leaves = jax.tree_util.tree_flatten_with_path(
        weights.nest({p: p for p in paths}))[0]
    return [v for _, v in leaves]


def compress(grads: Dict[str, jax.Array], codec: Optional[dict],
             step: int, worker: int) -> Dict[str, jax.Array]:
    """The codec's round trip of one worker's gradient at `step`, leaf by
    leaf in place (`grads` is consumed)."""
    if codec is None:
        return grads
    if codec["name"] != "qsgd" or codec["granularity"] != "layerwise":
        raise ValueError(f"no reference for codec {codec}")
    step_key = jax.random.fold_in(jax.random.key(STEP_KEY_SEED), step)
    out, uid = {}, 0
    for path in tree_order(list(grads)):
        g = grads.pop(path)
        stacked = path.split("/")[0] in STACKED
        out[path] = _compress_leaf(g, step_key, jnp.int32(uid),
                                   jnp.int32(worker), stacked,
                                   int(codec["levels"]))
        uid += g.shape[0] if stacked else 1
    return out


# --------------------------------------------------------------------------
# the training driver
# --------------------------------------------------------------------------

def lr_at(job: dict, step: int) -> float:
    """The launcher's schedule: linear warm-up over a tenth of the steps,
    then linear decay to 0 (piecewise_linear(lr, steps, steps // 10))."""
    peak, total = float(job["lr"]), int(job["steps"])
    warm = max(1, total // 10)
    up = peak * step / warm
    down = peak * (total - step) / max(1, total - warm)
    return float(np.float32(min(max(min(up, down), 0.0), peak)))


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def _kept(params, quant):
    """The parameters as the control keeps them: rounded by `quant`, held
    in float32."""
    return {p: quant(v.astype(jnp.float32)) for p, v in params.items()}


@partial(jax.jit, donate_argnums=(0,))
def _sgd(params, grads, lr):
    """p <- bf16(f32(p) - lr g): the configuration stores bf16."""
    return {p: (params[p].astype(jnp.float32) - lr * grads[p]
                ).astype(params[p].dtype) for p in params}


@partial(jax.jit, donate_argnums=(0,))
def _axpy(acc, g, a):
    return {p: acc[p] + a * g[p] for p in acc}


@partial(jax.jit, donate_argnums=(0,))
def _scale(t, a):
    return {p: v * a for p, v in t.items()}


@jax.jit
def _norms(tree):
    return {p: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for p, v in tree.items()}


def train_readings(ref, config: dict, traffic: dict, workers: int,
                   batches: List[dict], seed: int, first_step: int,
                   precision: str = "f32", steps: int = 3,
                   fault: Optional[str] = None) -> dict:
    """Follow the program's first `steps` steps from the seed's weights
    on batches[0..steps-1]: each worker's rows give a gradient of its mean
    loss, the cell's codec compresses it, the workers' results are
    averaged and SGD updates the parameters, which are kept in the
    configuration's dtype (by the control, in its own precision).

    Returns the per-step losses (mean over all tokens), per leaf the norm
    of the first gradient as the optimizer gets it (||p_1 - p_0|| / lr),
    the norm of the change after `steps` steps (||p_n - p_0||), and the
    norm of the first step's uncompressed mean gradient (which leaves
    count).

    `fault` plants a fault of the program in the reference put in its
    place: "half_batch" (each worker's gradient from half its rows),
    "no_exchange" (each worker keeps its own gradient: worker 0's is the
    update).
    """
    dot = contraction(precision)
    specs = ref.param_specs(config)
    dtype = jnp.dtype(config["dtype"])
    params = weights.flatten(weights.make(specs, seed, dtype))
    low = (dot.quant if precision == control_precision(config["dtype"])
           else None)
    if low is not None:
        params = _kept(params, low)
    codec = traffic.get("codec")
    losses, g1, gref = [], None, None
    lr0 = lr_at(traffic, first_step)
    t0 = time.perf_counter()
    for k in range(steps):
        batch = batches[k]
        rows = next(iter(batch.values())).shape[0]
        per = rows // workers
        agg, tsum, nll, ntok = None, None, 0.0, 0
        for w in range(workers):
            take = per // 2 if fault == "half_batch" else per
            sub = {n: v[w * per:w * per + take] for n, v in batch.items()}
            g, s, n = ref.grad_sum(params, sub, config, dot)
            g = _scale(g, jnp.float32(1.0 / n))
            nll, ntok = nll + s, ntok + n
            if k == 0 and workers == 1:
                gref = {p: float(v) for p, v in _norms(g).items()}
            elif k == 0:
                tsum = (_scale({p: v + 0 for p, v in g.items()},
                               jnp.float32(1.0 / workers)) if tsum is None
                        else _axpy(tsum, g, jnp.float32(1.0 / workers)))
            q = compress(g, codec, first_step + k, w)
            del g
            if fault == "no_exchange":
                agg = q if w == 0 else agg
            elif workers == 1:
                agg = q
            elif agg is None:
                agg = _scale(q, jnp.float32(1.0 / workers))
            else:
                agg = _axpy(agg, q, jnp.float32(1.0 / workers))
            del q
        if tsum is not None:
            gref = {p: float(v) for p, v in _norms(tsum).items()}
            tsum = None
        losses.append(float(nll) / ntok)
        params = _sgd(params, agg, jnp.float32(lr_at(traffic,
                                                     first_step + k)))
        if low is not None:
            params = _kept(params, low)
        del agg
        if k == 0:
            g1 = {p: float(v) / lr0 for p, v in _delta(params, specs, dtype,
                                                       seed).items()}
        print(f"bench: reference {precision} step {k + 1} "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
              flush=True)
    dn = {p: float(v) for p, v in _delta(params, specs, dtype,
                                         seed).items()}
    return {"losses": losses, "grad_norms": g1, "update_norms": dn,
            "true_grad_norms": gref}


def _delta(params, specs, dtype, seed):
    return weights.delta_norms(specs, dtype)(weights.nest(params),
                                             weights.seed_key_data(seed))
