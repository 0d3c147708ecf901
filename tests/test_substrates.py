"""Optimizers, schedules, data pipeline, checkpointing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.data import classification_batch, lm_batches, make_markov, \
    markov_lm_batch
from repro.optim import (OptConfig, adam, apply_updates, init_opt_state,
                         momentum, piecewise_linear, sgd)

KEY = jax.random.key(0)


def test_sgd_closed_form():
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -1.0])}
    cfg = OptConfig(name="sgd")
    p2, _ = apply_updates(cfg, p, g, {}, jnp.float32(0.1))
    np.testing.assert_allclose(np.asarray(p2["w"]), [0.95, 2.1], atol=1e-6)


def test_momentum_matches_reference():
    cfg = OptConfig(name="momentum", beta1=0.9)
    p = {"w": jnp.zeros(3)}
    st = init_opt_state(cfg, p)
    g = {"w": jnp.ones(3)}
    m_ref, w_ref = np.zeros(3), np.zeros(3)
    for _ in range(4):
        p, st = apply_updates(cfg, p, g, st, jnp.float32(0.1))
        m_ref = 0.9 * m_ref + 1.0
        w_ref = w_ref - 0.1 * m_ref
    np.testing.assert_allclose(np.asarray(p["w"]), w_ref, atol=1e-6)


def test_nesterov_differs_from_plain():
    g = {"w": jnp.ones(2)}
    p = {"w": jnp.zeros(2)}
    outs = []
    for nes in (False, True):
        cfg = OptConfig(name="momentum", nesterov=nes)
        st = init_opt_state(cfg, p)
        q, _ = apply_updates(cfg, p, g, st, jnp.float32(0.1))
        outs.append(float(q["w"][0]))
    assert outs[0] != outs[1]


def test_adam_bias_correction_first_step():
    cfg = OptConfig(name="adam", eps=0.0)
    p = {"w": jnp.zeros(2)}
    st = init_opt_state(cfg, p)
    g = {"w": jnp.asarray([0.3, -7.0])}
    p2, st2 = apply_updates(cfg, p, g, st, jnp.float32(0.01))
    # first Adam step is -lr * sign(g) after bias correction
    np.testing.assert_allclose(np.asarray(p2["w"]), [-0.01, 0.01], atol=1e-5)
    assert int(st2["count"]) == 1


def test_grad_clip():
    cfg = OptConfig(name="sgd", grad_clip=1.0)
    p = {"w": jnp.zeros(4)}
    g = {"w": jnp.full(4, 100.0)}
    p2, _ = apply_updates(cfg, p, g, {}, jnp.float32(1.0))
    assert np.linalg.norm(np.asarray(p2["w"])) == pytest.approx(1.0, rel=1e-4)


def test_piecewise_linear_schedule():
    s = piecewise_linear(0.4, 100, 20)
    assert float(s(0)) == 0.0
    assert float(s(20)) == pytest.approx(0.4)
    assert float(s(100)) == pytest.approx(0.0, abs=1e-6)
    assert 0 < float(s(60)) < 0.4


def test_lm_batches_deterministic_and_learnable():
    a = next(lm_batches(64, 4, 16, seed=5))
    b = next(lm_batches(64, 4, 16, seed=5))
    assert jnp.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 16)
    # targets are the next-token shift of the same chain
    succ, logp = make_markov(64, 5)
    batch = markov_lm_batch(jax.random.key(1), (succ, logp), 8, 32)
    tok = batch["tokens"].reshape(-1)
    tgt = batch["targets"].reshape(-1)
    # probability of each sampled transition (a successor may repeat)
    probs = jnp.sum(jnp.where(succ[tok] == tgt[:, None],
                              jnp.exp(logp[tok]), 0.0), axis=1)
    assert bool((probs > 0).all())       # every target is a successor
    # sampled transitions concentrate on high-probability entries
    assert float(jnp.mean(probs)) > 1.0 / 64 * 2


def test_classification_batch_shapes():
    b = classification_batch(KEY, 8, classes=10)
    assert b["images"].shape == (8, 32, 32, 3)
    assert b["labels"].shape == (8,)
    assert int(b["labels"].max()) < 10


def test_checkpoint_roundtrip_bf16():
    tree = {"a": jnp.ones((3, 4), jnp.bfloat16) * 1.5,
            "b": {"c": jnp.arange(5, dtype=jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 12, tree)
        save_checkpoint(d, 30, tree)
        path = latest_checkpoint(d)
        assert "00000030" in path
        step, out = load_checkpoint(path, tree)
        assert step == 30
        assert out["a"].dtype == jnp.bfloat16
        assert jnp.array_equal(out["b"]["c"], tree["b"]["c"])
        assert jnp.allclose(out["a"].astype(jnp.float32), 1.5)


def test_checkpoint_missing_key_raises():
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"a": jnp.ones(2)})
        with pytest.raises(ValueError):
            load_checkpoint(latest_checkpoint(d),
                            {"a": jnp.ones(2), "b": jnp.ones(2)})
