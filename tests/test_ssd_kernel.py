"""The SSD kernel pair (`kernels/ssd.py`), interpreted on CPU, against the
jnp form of `ssd_chunked` (its oracle) and the linear recurrence.

Fixed shapes: S a multiple of the chunk and not, 1 and 3 head blocks,
state 16 and 128, head dim 32 and 64. Each case checks y, the final
state, an initial state threaded through two calls, and the gradients in
x, dt, A, B, C, D and the initial state. With float32 inputs the kernel's
MXU operands are float32, so it agrees with the oracle to rounding; the
bf16 case rounds its MXU operands to bf16 as the model does on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ssd as K
from repro.models.mamba2 import ssd_chunked
from test_flash_ssd import _naive_ssd

# (B, S, H, P, N, chunk, heads per block)
SHAPES = {
    "S-multiple-1block-N16-P32": (1, 24, 4, 32, 16, 8, 4),
    "S-ragged-3blocks-N16-P32": (2, 21, 12, 32, 16, 8, 4),
    "S-ragged-3blocks-N128-P64": (1, 40, 6, 64, 128, 16, 2),
    "S-multiple-1block-N128-P64": (1, 32, 2, 64, 128, 16, 2),
}


def _inputs(B, S, H, P, N, dtype=jnp.float32, seed=0):
    key = jax.random.key(seed)

    def k(i):
        return jax.random.fold_in(key, i)
    xh = jax.random.normal(k(1), (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k(2), (B, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(k(3), (H,)))
    Bm = jax.random.normal(k(4), (B, S, N)).astype(dtype)
    Cm = jax.random.normal(k(5), (B, S, N)).astype(dtype)
    D = jax.random.normal(k(6), (H,))
    init = jax.random.normal(k(7), (B, H, P, N))
    return xh, dt, A, Bm, Cm, D, init


def _kernel(chunk, hb):
    def f(xh, dt, A, Bm, Cm, D, init=None):
        B, S, H, P = xh.shape
        y, s = K.ssd(xh.reshape(B, S, H * P), dt, A, Bm, Cm, D, chunk, init,
                     hb=hb, interpret=True)
        return y.reshape(B, S, H, P), s
    return f


def _oracle(chunk):
    def f(xh, dt, A, Bm, Cm, D, init=None):
        return ssd_chunked(xh, dt, A, Bm, Cm, D, chunk, init_state=init)
    return f


def _close(got, want, rel):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * scale)


def _grads(fn, args, seed=11):
    xh, init = args[0], args[-1]
    wy = jax.random.normal(jax.random.key(seed), xh.shape)
    ws = jax.random.normal(jax.random.key(seed + 1), init.shape)

    def loss(*a):
        y, s = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * wy) + jnp.sum(s * ws)
    return jax.jit(jax.grad(loss, argnums=tuple(range(7))))(*args)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_kernel_matches_oracle_and_recurrence(name):
    B, S, H, P, N, chunk, hb = SHAPES[name]
    assert H // hb in (1, 3)
    args = _inputs(B, S, H, P, N)
    kern, orac = jax.jit(_kernel(chunk, hb)), jax.jit(_oracle(chunk))
    y, s = kern(*args)
    y_o, s_o = orac(*args)
    _close(y, y_o, 2e-5)
    _close(s, s_o, 2e-5)
    # from a zero state, the linear recurrence
    y0, s0 = kern(*args[:-1])
    y_n, s_n = _naive_ssd(*args[:-1])
    _close(y0, y_n, 2e-5)
    _close(s0, s_n, 2e-5)
    # an initial state threaded through two calls, split mid-chunk
    cut = S // 2 + 1
    first = [a[:, :cut] for a in (args[0], args[1])] + [args[2]] + \
        [a[:, :cut] for a in (args[3], args[4])] + [args[5], args[6]]
    second = [a[:, cut:] for a in (args[0], args[1])] + [args[2]] + \
        [a[:, cut:] for a in (args[3], args[4])] + [args[5]]
    y_a, s_a = kern(*first)
    y_b, s_b = kern(*second, s_a)
    _close(jnp.concatenate([y_a, y_b], 1), y, 2e-5)
    _close(s_b, s, 2e-5)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_kernel_gradients_match_oracle(name):
    B, S, H, P, N, chunk, hb = SHAPES[name]
    args = _inputs(B, S, H, P, N, seed=3)
    got = _grads(_kernel(chunk, hb), args)
    want = _grads(_oracle(chunk), args)
    for what, g, w in zip(("x", "dt", "A", "B", "C", "D", "init"), got,
                          want):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        _close(g, w, 2e-5)


def test_bf16_inputs_round_mxu_operands_only():
    """bf16 x, B, C: outputs and cotangents keep the inputs' dtypes and
    agree with the float32 oracle to bf16 operand rounding."""
    B, S, H, P, N, chunk, hb = SHAPES["S-ragged-3blocks-N128-P64"]
    args = _inputs(B, S, H, P, N, dtype=jnp.bfloat16, seed=5)
    y, s = jax.jit(_kernel(chunk, hb))(*args)
    y_o, s_o = jax.jit(_oracle(chunk))(*args)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    _close(y, y_o, 2e-2)
    _close(s, s_o, 2e-2)
    got = _grads(_kernel(chunk, hb), args)
    want = _grads(_oracle(chunk), args)
    for what, g, w in zip(("x", "dt", "A", "B", "C", "D", "init"), got,
                          want):
        assert g.dtype == w.dtype, what
        _close(g, w, 3e-2)


def test_head_block_dispatch():
    """The kernel takes mamba2-1.3b's and zamba2-7b's SSD (and their TP
    shards); shapes it does not tile fall back to the jnp form."""
    assert K.head_block(64, 64, 128, 64) == 64       # mamba2-1.3b
    assert K.head_block(32, 64, 128, 64) == 32       # the same, TP 2
    assert K.head_block(112, 64, 64, 64) == 56       # zamba2-7b
    assert K.head_block(2, 64, 128, 64) == 2         # every head, one block
    assert K.head_block(8, 32, 16, 8) is None        # smoke: chunk 8
    assert K.head_block(64, 48, 128, 64) is None     # P does not divide 128
    assert K.head_block(64, 64, 128, 100) is None    # chunk off the tiles
    assert K.head_block(4096, 64, 128, 64) is None   # state over VMEM


def _ssd_calls(xh, dt, A, Bm, Cm, D, chunk):
    return ops.count_pallas_calls(ssd_chunked, xh, dt, A, Bm, Cm, D,
                                  chunk=chunk)


def test_cpu_runs_the_jnp_form_and_tpu_the_kernel(monkeypatch):
    args = _inputs(1, 64, 8, 64, 128)[:-1]
    assert _ssd_calls(*args, 16) == 0                 # CPU: the jnp form
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    assert _ssd_calls(*args, 16) == 1                 # TPU: the kernel
    assert _ssd_calls(*args, 8) == 0                  # chunk 8 does not tile
