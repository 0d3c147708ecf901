"""Executable checks of the paper's theory (Lemma 1, the Trace(A) vs
L·max noise bound, Lemma 2 instances)."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import make_compressor
from repro.core.theory import (empirical_omega, entire_model_bound,
                               layerwise_tighter, lemma1_check, trace_A)

KEY = jax.random.key(3)


def test_lemma1_inequality_chain():
    """E||Q(x)||^2 <= sum_j (1+Om_j)||x_j||^2 <= max_j(1+Om_j)||x||^2."""
    parts = [jax.random.normal(jax.random.fold_in(KEY, j), (64 * (j + 1),))
             for j in range(4)]
    c = make_compressor("qsgd", levels=4)
    lhs, mid, rhs = lemma1_check(c, parts, KEY, trials=96)
    assert lhs <= mid * 1.15  # Monte-Carlo slack on the expectation
    assert mid <= rhs + 1e-6


@settings(max_examples=30)
@given(st.lists(st.floats(min_value=0.0, max_value=50.0), min_size=2,
                max_size=12),
       st.lists(st.floats(min_value=0.0, max_value=50.0), min_size=2,
                max_size=12),
       st.integers(min_value=1, max_value=10_000))
def test_property_layerwise_bound_tighter(oms_w, oms_m, seed):
    """The paper's headline claim: Trace(A) <= d * max_j(1+Om_W)(1+Om_M)
    for ANY per-layer omegas and dimensions."""
    L = min(len(oms_w), len(oms_m))
    oms_w, oms_m = oms_w[:L], oms_m[:L]
    import numpy as np
    rng = np.random.default_rng(seed)
    dims = rng.integers(1, 1000, size=L).tolist()
    assert layerwise_tighter(oms_w, oms_m, dims)
    assert trace_A(oms_w, oms_m, dims) <= entire_model_bound(
        oms_w, oms_m, dims) + 1e-6


def test_layerwise_noise_strictly_smaller_when_heterogeneous():
    """With heterogeneous per-layer omegas the layer-wise factor is
    STRICTLY smaller — the quantitative advantage the paper proves."""
    oms_w = [0.1, 5.0, 0.5]
    oms_m = [0.0, 0.0, 0.0]
    dims = [1000, 10, 100]
    t = trace_A(oms_w, oms_m, dims)
    e = entire_model_bound(oms_w, oms_m, dims)
    assert t < 0.5 * e


def test_lemma2_randomk_scaling():
    """Lemma 2(ii): unscaled Random-k gives E[q^T g] = (k/d)||g||^2 per
    side (k_M k_W / d^2 bidirectionally)."""
    d, ratio = 600, 0.2
    g = jax.random.normal(KEY, (d,))
    c = make_compressor("randomk", ratio=ratio)
    keys = jax.random.split(KEY, 512)
    vals = jax.vmap(lambda k: jnp.dot(c.sim(g, k), g))(keys)
    expect = ratio * float(jnp.sum(g * g))
    assert float(jnp.mean(vals)) == pytest.approx(expect, rel=0.1)


def test_omega_identity_zero():
    c = make_compressor("identity")
    x = jax.random.normal(KEY, (128,))
    assert abs(empirical_omega(c, x, KEY, trials=4)) < 1e-6
