"""The benchmark's batch stream, made from --seed.

A copy of the program's synthetic data (src/repro/data/synthetic.py:
make_markov, markov_lm_batch, frames_stub), kept here so that a change to
the program cannot move the yardstick. Token sequences follow a fixed
random first-order Markov chain in which every token has 64 successors
with Gumbel-peaked probabilities (concentration 0.3, as the program draws
them); where a traffic mix names "frames", an audio model's front end is
a stub of (B, frames, d_model) frame embeddings. About one seed in 25
gives a batch with a row that falls into a short cycle of tokens (50-800
distinct tokens of 2048); PERF.md says what such a row does to the
comparison with the reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MARKOV_SUCCESSORS = 64


def seed_key(seed: int, stream: int = 0):
    """A jax key from any whole-number seed (also past 32 bits)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_markov(vocab: int, seed: int, concentration: float = 0.3):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    succ = rng.integers(0, vocab, size=(vocab, MARKOV_SUCCESSORS))
    logits = rng.gumbel(size=(vocab, MARKOV_SUCCESSORS)) / concentration
    logp = logits - logits.max(axis=1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(axis=1, keepdims=True))
    return (jnp.asarray(succ, jnp.int32), jnp.asarray(logp, jnp.float32))


@partial(jax.jit, static_argnums=(2, 3))
def markov_lm_batch(key, trans, batch: int, seq: int):
    """(tokens, targets) of `batch` sequences of length `seq`."""
    succ, logp = trans
    vocab = succ.shape[0]
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, vocab)

    def step(tok, k):
        j = jax.random.categorical(k, logp[tok])
        nxt = jnp.take_along_axis(succ[tok], j[:, None], axis=1)[:, 0]
        return nxt, nxt

    _, seqs = jax.lax.scan(step, first, jax.random.split(k1, seq))
    seqs = jnp.concatenate([first[None], seqs], axis=0).T
    return {"tokens": seqs[:, :-1].astype(jnp.int32),
            "targets": seqs[:, 1:].astype(jnp.int32)}


@partial(jax.jit, static_argnums=(1, 2, 3))
def frames_stub(key, batch: int, frames: int, d_model: int):
    """Audio front-end stub: precomputed frame embeddings."""
    return 0.02 * jax.random.normal(key, (batch, frames, d_model),
                                    jnp.float32)


def batch_ring(config: dict, traffic: dict, seed: int):
    """traffic["ring"] distinct batches, as host numpy arrays. The first
    steps of a run, which the reference follows, each get their own."""
    vocab = int(config["vocab_size"])
    trans = make_markov(vocab, seed)
    key = seed_key(seed, stream=2)
    out = []
    for i in range(int(traffic["ring"])):
        k = jax.random.fold_in(key, i)
        b = markov_lm_batch(k, trans, int(traffic["batch"]),
                            int(traffic["seq"]))
        if traffic.get("frames"):
            b["frames"] = frames_stub(jax.random.fold_in(k, 7),
                                      int(traffic["batch"]),
                                      int(traffic["frames"]),
                                      int(config["d_model"]))
        out.append({k_: np.asarray(v) for k_, v in b.items()})
    return out
