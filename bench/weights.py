"""Weights made from --seed, on the device, in one jitted call.

A configuration's reference module declares every parameter of the
program's tree as `param_specs(config) -> {path: (shape, init, std)}`,
with init "normal" (times std), "zeros", "ones", or Mamba-2's own
initialisation of its decay and step: "a_log" (A_log = log A, A uniform
in [1, 16]) and "dt_bias" (the inverse softplus of a step drawn
log-uniform in [0.001, 0.1]). Each leaf draws from
its own key, folded from the seed by the path's CRC-32, so a leaf's
values do not depend on which other leaves exist. The program and the
reference both take these weights; neither makes its own.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.data import seed_key

Spec = Tuple[Tuple[int, ...], str, float]


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _leaf(key, path: str, spec: Spec, dtype):
    shape, init, std = spec
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "a_log":
        u = jax.random.uniform(leaf_key(key, path), shape, jnp.float32,
                               1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            leaf_key(key, path), shape, jnp.float32,
            jnp.log(1e-3), jnp.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if init != "normal":
        raise ValueError(f"{path}: unknown init {init!r}")
    return (std * jax.random.normal(leaf_key(key, path), shape,
                                    jnp.float32)).astype(dtype)


def nest(flat: Dict[str, object]) -> Dict:
    """{"a/b": x} -> {"a": {"b": x}}"""
    out: Dict = {}
    for path, v in flat.items():
        d = out
        *head, last = path.split("/")
        for k in head:
            d = d.setdefault(k, {})
        d[last] = v
    return out


def flatten(tree: Dict, prefix: str = "") -> Dict[str, object]:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def _frozen(specs: Dict[str, Spec]):
    return tuple((p, tuple(s[0]), s[1], float(s[2]))
                 for p, s in specs.items())


def maker(specs: Dict[str, Spec], dtype):
    """A jitted fn(key_data) -> nested params, placed on the default
    device. The seed is an argument, so every seed runs one compiled
    program, and the program's weights, the reference's and the initial
    weights that a change is measured from all come out of that one
    program, bit for bit alike. (Drawn anew inside another jitted
    program they need not round alike: on the TPU, drawn inside the
    program that took the norms of a change, they did not, and a leaf
    that had not moved read a change of up to 0.73.)"""
    return _maker(_frozen(specs), jnp.dtype(dtype))


@functools.lru_cache(maxsize=None)
def _maker(frozen, dtype):
    specs = {p: (shape, init, std) for p, shape, init, std in frozen}

    def make(kd):
        key = jax.random.wrap_key_data(kd)
        return nest({p: _leaf(key, p, s, dtype) for p, s in specs.items()})
    return jax.jit(make)


def make(specs: Dict[str, Spec], seed: int, dtype):
    return maker(specs, dtype)(seed_key_data(seed))


def placed_like(tree, like):
    """`tree` on the devices and shardings of the same-shaped `like`."""
    return jax.device_put(tree, jax.tree_util.tree_map(
        lambda x: x.sharding, like))


def delta_norms(specs: Dict[str, Spec], dtype):
    """fn(params, key_data) -> {path: ||params - params_0||} in float32,
    where params_0 are the seed's initial weights, made anew by maker()
    (they are not kept on the device beside the step)."""
    mk = maker(specs, dtype)

    def fn(params, kd):
        return _diff_norms(params, placed_like(mk(kd), params))
    return fn


@jax.jit
def _diff_norms(params, params0):
    a, b = flatten(params), flatten(params0)
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        a[p].astype(jnp.float32) - b[p].astype(jnp.float32)))) for p in a}


def seed_key_data(seed: int):
    return jax.random.key_data(seed_key(seed, stream=1))
