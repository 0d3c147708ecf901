"""Deterministic synthetic data pipelines (no data gates in this container).

Language modeling: sequences sampled from a fixed random first-order Markov
chain over the vocab — a task with nonzero learnable structure, so loss
decreases measurably within a few hundred steps (the convergence experiments
need a signal, not white noise).

Classification: Gaussian class prototypes + noise at CIFAR-like shapes for
the paper's CNN study.

Everything is a pure function of (seed, step) — shardable by slicing the
batch dimension, reproducible across hosts.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


#: successors of each token in the Markov chain: a dense (vocab, vocab)
#: transition matrix would take 10-90 GB at a published LM vocabulary
#: (~50k-150k tokens)
MARKOV_SUCCESSORS = 64


def make_markov(vocab: int, seed: int = 0, concentration: float = 0.3):
    """Low-entropy (learnable) first-order Markov chain over the vocab:
    each token moves to one of MARKOV_SUCCESSORS random successors with
    Gumbel-peaked probabilities -> (successors (vocab, MARKOV_SUCCESSORS)
    int32, their log-probabilities f32)."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, MARKOV_SUCCESSORS))
    logits = rng.gumbel(size=(vocab, MARKOV_SUCCESSORS)) / concentration
    logp = logits - logits.max(axis=1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(axis=1, keepdims=True))
    return (jnp.asarray(succ, jnp.int32), jnp.asarray(logp, jnp.float32))


def _markov_step(trans, tok: Array, key: Array) -> Array:
    """Next token of every sequence in `tok` (B,) under the chain."""
    succ, logp = trans
    j = jax.random.categorical(key, logp[tok])
    return jnp.take_along_axis(succ[tok], j[:, None], axis=1)[:, 0]


@partial(jax.jit, static_argnums=(2, 3))
def markov_lm_batch(key: Array, trans, batch: int, seq: int):
    """Sample (tokens, targets) from the Markov chain of make_markov;
    targets = next token."""
    vocab = trans[0].shape[0]
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, vocab)

    def step(tok, k):
        nxt = _markov_step(trans, tok, k)
        return nxt, nxt

    keys = jax.random.split(k1, seq)
    _, seqs = jax.lax.scan(step, first, keys)
    seqs = jnp.concatenate([first[None], seqs], axis=0).T  # (B, S+1)
    return {"tokens": seqs[:, :-1].astype(jnp.int32),
            "targets": seqs[:, 1:].astype(jnp.int32)}


def lm_batches(vocab: int, batch: int, seq: int, seed: int = 0
               ) -> Iterator[Dict[str, Array]]:
    """Infinite deterministic LM batch stream."""
    trans = make_markov(vocab, seed)
    step = 0
    base = jax.random.key(seed)
    while True:
        yield markov_lm_batch(jax.random.fold_in(base, step), trans, batch,
                              seq)
        step += 1


def _class_prototypes(classes: int, hw: int, channels: int) -> Array:
    """The fixed smooth class prototypes both the IID and the skewed
    classification samplers draw from (same constants => same task)."""
    coarse = jax.random.normal(jax.random.key(1234),
                               (classes, 4, 4, channels))
    return jax.image.resize(coarse, (classes, hw, hw, channels),
                            method="bilinear") * 2.0


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def classification_batch(key: Array, batch: int, classes: int = 10,
                         hw: int = 32, channels: int = 3, noise: float = 0.5):
    """(images (B,hw,hw,C), labels (B,)) — smooth (low-frequency) class
    prototypes + pixel noise. Prototypes are 4x4 random grids bilinearly
    upsampled so convolutional nets can detect them locally (white-noise
    prototypes are only separable by pixel-exact templates = MLPs)."""
    kp, kl, kn = jax.random.split(key, 3)
    protos = _class_prototypes(classes, hw, channels)
    labels = jax.random.randint(kl, (batch,), 0, classes)
    x = protos[labels] + noise * jax.random.normal(kn, (batch, hw, hw,
                                                        channels))
    return {"images": x.astype(jnp.float32), "labels": labels.astype(jnp.int32)}


# --------------------------------------------------------------------------
# non-IID worker shards (Dirichlet skew — the federated-learning standard)
# --------------------------------------------------------------------------

def dirichlet_proportions(key: Array, n_workers: int, categories: int,
                          alpha: float) -> Array:
    """(n_workers, categories) row-stochastic shard proportions: each
    worker's category distribution is an independent Dirichlet(alpha)
    draw. Small alpha => near-one-hot shards (hostile skew); large alpha
    => near-uniform (approaches IID). Pure function of the key."""
    conc = jnp.full((categories,), jnp.float32(alpha))
    return jax.random.dirichlet(key, conc, shape=(n_workers,))


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def noniid_classification_batch(key: Array, proportions: Array,
                                per_worker: int, classes: int = 10,
                                hw: int = 32, channels: int = 3,
                                noise: float = 0.5):
    """Skewed per-worker classification batches: labels of worker w are
    drawn from Categorical(proportions[w]) instead of uniform — same
    prototypes, same noise model as classification_batch, different
    shard composition. Returns {"images": (n, per, hw, hw, C),
    "labels": (n, per)} with the leading worker axis the simulated-
    worker aggregation path expects."""
    n = proportions.shape[0]
    protos = _class_prototypes(classes, hw, channels)

    def worker(wkey, props):
        kl, kn = jax.random.split(wkey)
        labels = jax.random.categorical(kl, jnp.log(props + 1e-9),
                                        shape=(per_worker,))
        x = protos[labels] + noise * jax.random.normal(
            kn, (per_worker, hw, hw, channels))
        return x.astype(jnp.float32), labels.astype(jnp.int32)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    images, labels = jax.vmap(worker)(keys, proportions)
    return {"images": images, "labels": labels}


@partial(jax.jit, static_argnums=(3, 4))
def noniid_markov_lm_batch(key: Array, trans, proportions: Array,
                           per_worker: int, seq: int):
    """Skewed per-worker LM batches: worker w's sequences START from
    Categorical(proportions[w]) over the vocab instead of uniform, then
    evolve by the shared Markov chain — each worker sees a different
    slice of the chain's state space (shard skew) while the learnable
    transition structure stays the task. Returns {"tokens": (n, per,
    S), "targets": (n, per, S)}."""
    n = proportions.shape[0]

    def worker(wkey, props):
        k0, k1 = jax.random.split(wkey)
        first = jax.random.categorical(k0, jnp.log(props + 1e-9),
                                       shape=(per_worker,))

        def step(tok, k):
            nxt = _markov_step(trans, tok, k)
            return nxt, nxt

        keys = jax.random.split(k1, seq)
        _, seqs = jax.lax.scan(step, first, keys)
        seqs = jnp.concatenate([first[None], seqs], axis=0).T
        return (seqs[:, :-1].astype(jnp.int32),
                seqs[:, 1:].astype(jnp.int32))

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    tokens, targets = jax.vmap(worker)(keys, proportions)
    return {"tokens": tokens, "targets": targets}


def frames_stub(key: Array, batch: int, frames: int, d_model: int) -> Array:
    """Audio frontend stub: precomputed frame embeddings (whisper carve-out)."""
    return 0.02 * jax.random.normal(key, (batch, frames, d_model),
                                    jnp.float32)


def patches_stub(key: Array, batch: int, patches: int, d_model: int) -> Array:
    """Vision frontend stub: projected patch embeddings (VLM carve-out)."""
    return 0.02 * jax.random.normal(key, (batch, patches, d_model),
                                    jnp.float32)
