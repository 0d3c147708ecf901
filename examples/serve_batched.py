"""Batched serving example: prefill a batch of prompts, then decode new
tokens with the sequence-sharded KV cache on a device mesh (the same
serve_step the decode_32k / long_500k dry-run shapes lower).

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python examples/serve_batched.py
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402

from repro.launch.engine import Engine       # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import ModelConfig          # noqa: E402
from repro.models.config import InputShape    # noqa: E402

CFG = ModelConfig(name="serve-lm", arch_type="dense", n_layers=4,
                  d_model=128, vocab=512, n_heads=8, n_kv_heads=2,
                  d_head=16, d_ff=256, dtype="float32")
BATCH, PROMPT, GEN, CACHE = 8, 24, 12, 64


def main():
    mesh = make_host_mesh(data=4, model=2)
    eng = Engine(CFG, mesh)
    params, _ = eng.init_state(seed=1)
    serve = eng.build_serve_step(InputShape("d", CACHE, BATCH, "decode"))
    # the engine's shard_map'd prefill — a bare jit(model.prefill) has no
    # bound TP axes — with the cache sized for the generation budget
    prefill = eng.build_prefill(InputShape("p", PROMPT, BATCH, "prefill"),
                                cache_len=CACHE)

    prompts = jax.random.randint(jax.random.key(0), (BATCH, PROMPT), 0,
                                 CFG.vocab)
    with mesh:
        logits, cache = prefill(params, {"tokens": prompts})
        # now decode greedily with the seq-sharded cache
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out = [toks]
        for t in range(GEN):
            logits, cache = serve(params, {"token": toks,
                                           "pos": jnp.int32(PROMPT + t)},
                                  cache)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(toks)
    gen = jnp.stack(out, axis=1)
    print("prompts:", prompts[:2])
    print("generated continuations:", jax.device_get(gen)[:2])
    print(f"served {BATCH} sequences x {GEN} tokens on "
          f"{mesh.devices.size} devices (seq-sharded KV cache)")


if __name__ == "__main__":
    main()
