"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned arch family — one forward/train step + one decode step on CPU,
asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow

from repro.configs.registry import ARCH_NAMES, get_smoke
from repro.data import frames_stub, patches_stub
from repro.models import DistConfig, Model

KEY = jax.random.key(0)
B, S = 2, 16


def _batch(cfg):
    b = {"tokens": jnp.ones((B, S), jnp.int32) * 3,
         "targets": jnp.ones((B, S), jnp.int32) * 5}
    if cfg.arch_type == "vlm":
        b["patch_embeds"] = patches_stub(KEY, B, cfg.frontend_seq,
                                         cfg.d_model)
    if cfg.arch_type == "audio":
        b["frames"] = frames_stub(KEY, B, cfg.frontend_seq, cfg.d_model)
    return b


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    assert cfg.n_layers <= 5 and cfg.d_model <= 512 and cfg.n_experts <= 4
    m = Model(cfg, DistConfig())
    params = jax.jit(m.init)(KEY)
    batch = _batch(cfg)

    def loss_fn(p):
        return m.loss(p, batch, jax.random.key(1))

    # jitted: one program per step instead of one per operation
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert loss.shape == () and jnp.isfinite(loss)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert not bool(jnp.isnan(leaf).any())
    # one SGD step decreases nothing catastrophically
    p2 = jax.tree_util.tree_map(lambda p, g: p - 0.01 * g, params, grads)
    loss2 = jax.jit(loss_fn)(p2)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_prefill_then_decode(arch):
    cfg = get_smoke(arch)
    m = Model(cfg, DistConfig())
    params = jax.jit(m.init)(KEY)
    batch = _batch(cfg)
    prefill = jax.jit(m.prefill, static_argnames=("cache_len",))
    logits, cache = prefill(params, batch, jax.random.key(2),
                            cache_len=S + 4)
    vocab_padded = ((cfg.vocab + 127) // 128) * 128
    assert logits.shape == (B, vocab_padded)
    assert not bool(jnp.isnan(logits).any())
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    decode_step = jax.jit(m.decode_step)
    lg, cache = decode_step(params, tok, jnp.int32(S), cache)
    assert lg.shape == (B, vocab_padded)
    assert not bool(jnp.isnan(lg).any())
    # a second decode step continues from the updated cache
    lg2, _ = decode_step(params, jnp.argmax(lg, -1).astype(jnp.int32),
                         jnp.int32(S + 1), cache)
    assert not bool(jnp.isnan(lg2).any())


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "qwen3-moe-235b-a22b",
                                  "whisper-base"])
def test_smoke_wire_train_step(arch):
    """The non-transformer-dense registry families (SSM, MoE, audio
    encoder-decoder) each run one ENGINE train step over the wire path —
    compressed gradients materialized as packed payloads, not just
    sim()'d — so the scenario campaign's config zoo is exercised
    end-to-end before the campaign prices it."""
    from repro.core import CompressionConfig, Granularity, make_compressor
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke(arch)
    comp = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                             granularity=Granularity("layerwise"))
    eng = Engine(cfg, make_host_mesh(1, 1), comp=comp)
    batch = {"tokens": jnp.ones((4, S), jnp.int32) * 3,
             "targets": jnp.ones((4, S), jnp.int32) * 5}
    if cfg.arch_type == "audio":
        batch["frames"] = frames_stub(KEY, 4, cfg.frontend_seq,
                                      cfg.d_model).astype(
                                          jnp.dtype(cfg.dtype))
    step = eng.build_train_step(wire=True, collective="allgather")
    params, opt_state = eng.init_state(0)
    params, opt_state, m = step(params, opt_state, batch, jnp.int32(0))
    assert jnp.isfinite(m["loss"])
    for leaf in jax.tree_util.tree_leaves(params):
        assert not bool(jnp.isnan(leaf).any())


def test_decode_matches_prefill_continuation():
    """Teacher-forced decode after prefill reproduces the prefill logits
    of the next position (cache consistency, dense arch)."""
    cfg = get_smoke("llama3-405b")
    m = Model(cfg, DistConfig())
    params = m.init(KEY)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    full = {"tokens": toks, "targets": toks}
    # prefill on the first S-1 tokens, then decode token S-1
    short = {"tokens": toks[:, :S - 1]}
    _, cache = m.prefill(params, short, jax.random.key(2), cache_len=S + 1)
    lg_dec, _ = m.decode_step(params, toks[:, S - 1], jnp.int32(S - 1), cache)
    # reference: last-position logits of the full prefill
    lg_full, _ = m.prefill(params, {"tokens": toks}, jax.random.key(2))
    assert jnp.allclose(lg_dec, lg_full, atol=2e-2), \
        float(jnp.max(jnp.abs(lg_dec - lg_full)))
