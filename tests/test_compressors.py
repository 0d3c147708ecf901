"""Unit + property tests for the compression operators (Assumption 5,
unbiasedness, payload consistency, sparsity counts)."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import index_bits, make_compressor
from repro.core.theory import (check_unbiasedness, empirical_omega,
                               empirical_descent_alignment)

KEY = jax.random.key(0)

ALL_SPECS = [
    ("identity", {}),
    ("randomk", {"ratio": 0.1}),
    ("randomk", {"ratio": 0.1, "scale": True}),
    ("topk", {"ratio": 0.1}),
    ("threshold_v", {"v": 0.8}),
    ("adaptive_threshold", {"alpha": 0.3}),
    ("terngrad", {}),
    ("qsgd", {"levels": 16}),
    ("signsgd", {}),
    ("natural", {}),
]


@pytest.mark.parametrize("name,kw", ALL_SPECS)
def test_encode_decode_matches_sim(name, kw):
    """Wire format and mathematical operator agree (threshold ops: wire is
    capacity-bounded, so only where the count fits)."""
    c = make_compressor(name, **kw)
    x = jax.random.normal(KEY, (777,))
    y = c.sim(x, KEY)
    z = c.decode(c.encode(x, KEY), 777)
    if name in ("threshold_v", "adaptive_threshold"):
        # capacity cap may drop smallest-magnitude qualifying entries
        kept = jnp.sum(z != 0)
        assert kept <= jnp.sum(y != 0) + 1
        nz = z != 0
        assert jnp.allclose(z[nz], y[nz])
    else:
        assert jnp.allclose(y, z, atol=1e-6), name


@pytest.mark.parametrize("name,kw", ALL_SPECS)
def test_assumption5(name, kw):
    """E||Q(x)||^2 <= (1+Omega)||x||^2 for the analytic Omega (when known)."""
    c = make_compressor(name, **kw)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (512,))
    om_emp = empirical_omega(c, x, KEY, trials=128)
    om = c.omega(512)
    if om is not None:
        assert om_emp <= om + 0.25 * (1 + abs(om)), (name, om_emp, om)
    if not c.unbiased and name != "signsgd":
        # biased sparsifiers never grow the norm
        assert om_emp <= 1e-3, (name, om_emp)


@pytest.mark.parametrize("name,kw", [
    ("randomk", {"ratio": 0.25, "scale": True}),
    ("terngrad", {}),
    ("qsgd", {"levels": 8}),
    ("natural", {}),
])
def test_unbiasedness(name, kw):
    c = make_compressor(name, **kw)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (256,))
    rel = check_unbiasedness(c, x, KEY, trials=3000)
    assert rel < 0.12, (name, rel)


def test_topk_randomk_keep_exact_k():
    x = jax.random.normal(KEY, (1000,))
    for name in ("topk", "randomk"):
        c = make_compressor(name, ratio=0.05)
        y = c.sim(x, KEY)
        assert int(jnp.sum(y != 0)) == 50, name


def test_topk_picks_largest():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 0.0, 1.0, -1.5])
    c = make_compressor("topk", ratio=0.25)
    y = c.sim(x, KEY)
    assert set(jnp.nonzero(y)[0].tolist()) == {1, 3}


def test_signsgd_values():
    x = jnp.asarray([0.5, -2.0, 0.0, 3.0])
    y = make_compressor("signsgd").sim(x, KEY)
    assert jnp.array_equal(y, jnp.asarray([1.0, -1.0, 1.0, 1.0]))


def test_natural_powers_of_two():
    x = jax.random.normal(KEY, (256,)) * 10
    y = make_compressor("natural").sim(x, KEY)
    nz = y[y != 0]
    e = jnp.log2(jnp.abs(nz))
    assert jnp.allclose(e, jnp.round(e), atol=1e-5)


def test_payload_bits_sane():
    d = 10000
    assert make_compressor("signsgd").payload_bits(d) == d
    assert make_compressor("terngrad").payload_bits(d) == 2 * d + 32
    # sparse records: 32-bit value + dim-dependent index width
    # (ceil(log2(10000)) = 14 bits — what the packed wire format uses)
    assert index_bits(d) == 14
    assert make_compressor("topk", ratio=0.01).payload_bits(d) == \
        100 * (32 + 14)
    assert make_compressor("qsgd", levels=16).payload_bits(d) < 32 * d
    assert index_bits(1) == 1 and index_bits(2) == 1 and index_bits(8) == 3
    assert index_bits(9) == 4


@settings(max_examples=15)
@given(st.sampled_from([8, 31, 32, 33, 100, 511, 512, 513, 1000, 2048]),
       st.sampled_from(["topk", "randomk", "terngrad", "qsgd", "signsgd",
                        "natural"]),
       st.integers(min_value=0, max_value=10_000))
def test_property_assumption5_holds(d, name, seed):
    """Hypothesis: Assumption 5 with the operator's worst-case Omega holds
    on random inputs of random dimension (the paper's eq. (5))."""
    kw = {"ratio": 0.2} if name in ("topk", "randomk") else {}
    c = make_compressor(name, **kw)
    key = jax.random.key(seed)
    x = jax.random.normal(key, (d,)) * jax.random.uniform(key, (), minval=0.1,
                                                          maxval=10)
    norm2 = float(jnp.sum(x * x))
    keys = jax.random.split(key, 32)
    qn = float(jnp.mean(jax.vmap(
        lambda k: jnp.sum(jnp.square(c.sim(x, k))))(keys)))
    om = c.omega(d)
    if name == "signsgd":
        q1 = c.sim(x, key)
        assert float(jnp.sum(q1 * q1)) == pytest.approx(d, rel=1e-4)
    elif c.unbiased:
        if om is not None:
            # 32-draw mean of E||Q||^2 vs the Assumption-5 bound (+MC slack)
            assert qn <= (1 + om) * norm2 * 1.8 + 1e-6
        else:
            # TernGrad: E||Q||^2 = s*||x||_1 <= sqrt(d)*||x||^2/||x||*...
            # use the loose sqrt(d) worst case
            assert qn <= (1 + d ** 0.5) * norm2 * 1.8 + 1e-6
    else:
        assert qn <= norm2 * (1 + 1e-5)  # biased sparsifiers contract


@settings(max_examples=10)
@given(st.sampled_from([16, 31, 32, 33, 100, 255, 256, 257, 512]),
       st.integers(min_value=0, max_value=10_000))
def test_property_descent_alignment_unbiased(d, seed):
    """Assumption 6 / Lemma 2(i): unbiased ops align with the gradient:
    E[Q(g)^T g] == ||g||^2 (alpha=2)."""
    key = jax.random.key(seed)
    g = jax.random.normal(key, (d,))
    c = make_compressor("qsgd", levels=32)
    a = empirical_descent_alignment(c, g, key, trials=256)
    assert a == pytest.approx(float(jnp.sum(g * g)), rel=0.2)
