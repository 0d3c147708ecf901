"""Flash attention and chunked-SSD against their pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.flash import flash_attention
from repro.models.layers import chunked_attention
from repro.models.mamba2 import segsum, ssd_chunked

KEY = jax.random.key(0)


@pytest.mark.parametrize("causal,window,off", [
    (True, 0, 0), (True, 7, 0), (False, 0, 0), (True, 0, 5), (True, 3, 11)])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_flash_matches_oracle(causal, window, off, chunk):
    B, S, H, dh, dv = 2, 40, 3, 16, 12
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H, dv))
    a = flash_attention(q, k, v, jnp.float32(window), causal, off, chunk)
    b = chunked_attention(q, k, v, causal=causal, window=window,
                          q_offset=off, q_chunk=chunk, kv_chunk=chunk)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_grads_match_oracle():
    B, S, H, dh = 1, 24, 2, 8
    q = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, H, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, H, dh))

    def f1(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(q, k, v, jnp.float32(0),
                                                True, 0, 8)))

    def f2(q, k, v):
        return jnp.sum(jnp.tanh(chunked_attention(q, k, v, causal=True,
                                                  q_chunk=8, kv_chunk=8)))

    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


def _naive_ssd(xh, dt, A, Bm, Cm, D):
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        g = jnp.exp(dt_t * A[None, :])
        h = g[..., None, None] * h + jnp.einsum("bn,bh,bhp->bhpn",
                                                B_t, dt_t, x_t)
        return h, jnp.einsum("bn,bhpn->bhp", C_t, h)

    h0 = jnp.zeros((Bsz, H, P, N))
    hT, ys = jax.lax.scan(step, h0, (xh.transpose(1, 0, 2, 3),
                                     dt.transpose(1, 0, 2),
                                     Bm.transpose(1, 0, 2),
                                     Cm.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3) + D[None, None, :, None] * xh, hT


@settings(max_examples=6)
@given(st.integers(min_value=1, max_value=3),
       st.sampled_from([4, 7, 8, 9, 16, 17, 30, 40]),
       st.sampled_from([2, 4, 8]),
       st.integers(min_value=0, max_value=1000))
def test_property_ssd_equals_recurrence(B, S, chunk, seed):
    """State-space duality: the chunked quadratic form equals the linear
    recurrence for any (B, S, chunk)."""
    key = jax.random.key(seed)
    H, P, N = 2, 4, 3
    xh = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2),
                                           (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 5), (B, S, N))
    D = jax.random.normal(jax.random.fold_in(key, 6), (H,))
    y1, s1 = ssd_chunked(xh, dt, A, Bm, Cm, D, chunk=chunk)
    y2, s2 = _naive_ssd(xh, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4)


def test_ssd_respects_initial_state():
    B, S, H, P, N = 1, 12, 2, 4, 3
    key = jax.random.key(1)
    xh = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H)))
    A = -jnp.exp(jnp.zeros((H,)))
    Bm = jax.random.normal(key, (B, S, N))
    Cm = jax.random.normal(key, (B, S, N))
    D = jnp.zeros((H,))
    # split S into two halves, thread the state
    y_a, s_a = ssd_chunked(xh[:, :6], dt[:, :6], A, Bm[:, :6], Cm[:, :6],
                           D, chunk=4)
    y_b, s_b = ssd_chunked(xh[:, 6:], dt[:, 6:], A, Bm[:, 6:], Cm[:, 6:],
                           D, chunk=4, init_state=s_a)
    y_full, s_full = ssd_chunked(xh, dt, A, Bm, Cm, D, chunk=4)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y_a, y_b], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_full),
                               atol=1e-4)


def test_segsum():
    x = jnp.asarray([1.0, 2.0, 3.0])
    out = segsum(x)
    assert out[0, 0] == 0 and out[2, 1] == 3 and out[2, 0] == 5
    assert jnp.isneginf(out[0, 2])
