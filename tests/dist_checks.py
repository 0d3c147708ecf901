"""Distributed correctness checks, run in a SUBPROCESS with 8 virtual CPU
devices (tests/test_distributed.py drives this; the flag must be set before
jax initializes, which pytest's main process must not do).

Checks:
  1. mesh train grads == single-device reference for every arch family,
     with sequence parallelism ON and OFF
  2. compression strategies (simulated / allgather / rs_compress_ag /
     shared_random) produce the correct aggregation semantics
  3. end-to-end: compressed training decreases the loss on a mesh
  4. serve path: prefill -> decode on a mesh
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import sys          # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (CompressionConfig, Granularity, Identity,  # noqa
                        make_compressor, stacked_mask)
from repro.core.aggregation import compressed_allreduce  # noqa: E402
from repro.data import lm_batches  # noqa: E402
from repro.launch.engine import Engine, shard_map  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import DistConfig, Model, ModelConfig  # noqa: E402
from repro.models.config import InputShape  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

FAMILIES = {
    "dense": ModelConfig(name="dense", arch_type="dense", n_layers=2,
                         d_model=64, vocab=256, n_heads=4, n_kv_heads=2,
                         d_head=16, d_ff=128, dtype="float32"),
    "moe": ModelConfig(name="moe", arch_type="moe", n_layers=2, d_model=64,
                       vocab=256, n_heads=4, n_kv_heads=2, d_head=16,
                       d_ff=96, n_experts=4, experts_per_token=2,
                       moe_capacity_factor=8.0, dtype="float32"),
    "mla": ModelConfig(name="mla", arch_type="dense", attention="mla",
                       n_layers=2, d_model=64, vocab=256, n_heads=4,
                       n_kv_heads=4, d_head=48, d_ff=128, q_lora_rank=48,
                       kv_lora_rank=32, qk_nope_dim=32, qk_rope_dim=16,
                       v_head_dim=32, dtype="float32"),
    "ssm": ModelConfig(name="ssm", arch_type="ssm", attention="none",
                       n_layers=2, d_model=64, vocab=256, d_ff=0,
                       ssm_state=16, ssm_expand=2, ssm_head_dim=16,
                       ssm_chunk=8, dtype="float32"),
    "hybrid": ModelConfig(name="hybrid", arch_type="hybrid", n_layers=5,
                          d_model=64, vocab=256, n_heads=4, n_kv_heads=4,
                          d_head=16, d_ff=128, ssm_state=16, ssm_expand=2,
                          ssm_head_dim=16, ssm_chunk=8, attn_every=2,
                          dtype="float32"),
}

TOL = {"dense": 1e-4, "moe": 2e-2, "mla": 1e-4, "ssm": 1e-4, "hybrid": 1e-4}


def check_grad_equivalence():
    batch = next(lm_batches(256, 16, 32, seed=3))
    key = jax.random.key(7)
    for fam, cfg in FAMILIES.items():
        m0 = Model(cfg, DistConfig())
        params = jax.jit(m0.init)(jax.random.key(0))
        g0 = jax.jit(jax.grad(lambda p: m0.loss(p, batch, key)))(params)
        for SP in (False, True):
            mesh = make_host_mesh(data=4, model=2)
            eng = Engine(cfg, mesh, comp=CompressionConfig(strategy="dense"),
                         opt=OptConfig())
            if not SP:
                eng.dist = dataclasses.replace(eng.dist, sp=False)
                eng.model.dist = eng.dist
            model = eng.model

            def gfn(p, b):
                g = jax.grad(lambda pp: model.loss(pp, b, key))(p)
                return eng._aggregate_grads(g, key)

            pp = model.param_pspecs()
            bs = eng.batch_pspecs(InputShape("t", 32, 16, "train"))
            mapped = shard_map(gfn, mesh, in_specs=(pp, bs), out_specs=pp)
            with mesh:
                g1 = jax.jit(mapped)(params, batch)
            worst = 0.0
            for a, b in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(g0)):
                a, b = np.asarray(a), np.asarray(b)
                rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))
                worst = max(worst, rel)
            assert worst < TOL[fam], (fam, SP, worst)
            print(f"grad-equiv {fam} SP={SP}: worst rel {worst:.2e} OK")


def check_strategies():
    """allgather / rs / shared_random reproduce correct aggregation.

    identity compressor: every strategy must equal the plain mean.
    shared_random: the output support is the shared index set.
    """
    mesh = make_host_mesh(data=8, model=1)
    g = {"blocks": {"w": jax.random.normal(jax.random.key(1), (3, 8, 16))},
         "head": jax.random.normal(jax.random.key(2), (8, 4))}
    sm = stacked_mask(g)
    ref = None
    for strat in ("dense", "simulated", "allgather", "rs_compress_ag"):
        cfg = CompressionConfig(qw=Identity(), strategy=strat)

        def f(gl):
            out, _ = compressed_allreduce(gl, sm, cfg, ("data",),
                                          jax.random.key(0), 8)
            return out

        specs = {"blocks": {"w": P(None, "data", None)},
                 "head": P("data", None)}
        with mesh:
            out = jax.jit(shard_map(f, mesh, in_specs=(specs,),
                                    out_specs=specs))(g)
        if ref is None:
            ref = out
        else:
            for a, b in zip(jax.tree_util.tree_leaves(out),
                            jax.tree_util.tree_leaves(ref)):
                assert jnp.allclose(a, b, atol=1e-5), strat
        print(f"strategy {strat}: identity == mean OK")

    cfg = CompressionConfig(qw=make_compressor("randomk", ratio=0.25),
                            strategy="shared_random")

    def f2(gl):
        out, _ = compressed_allreduce(gl, sm, cfg, ("data",),
                                      jax.random.key(0), 8)
        return out

    specs = {"blocks": {"w": P(None, "data", None)},
             "head": P("data", None)}
    with mesh:
        out = jax.jit(shard_map(f2, mesh, in_specs=(specs,),
                                out_specs=specs))(g)
    frac = float(jnp.mean((out["blocks"]["w"] != 0).astype(jnp.float32)))
    assert 0.1 < frac <= 0.35, frac
    print(f"strategy shared_random: sparsity {frac:.2f} OK")


def check_training_decreases_loss():
    cfg = FAMILIES["dense"]
    mesh = make_host_mesh(data=4, model=2)
    comp = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                             granularity=Granularity("layerwise"),
                             strategy="allgather")
    eng = Engine(cfg, mesh, comp=comp, opt=OptConfig(name="momentum", lr=0.3))
    step = eng.build_train_step()
    params, opt_state = eng.init_state()
    it = lm_batches(256, 16, 32, seed=3)
    losses = []
    with mesh:
        for i in range(12):
            params, opt_state, m = step(params, opt_state, next(it),
                                        jnp.int32(i))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
    print(f"training: loss {losses[0]:.3f} -> {losses[-1]:.3f} OK")


def check_serve():
    cfg = FAMILIES["dense"]
    mesh = make_host_mesh(data=4, model=2)
    eng = Engine(cfg, mesh)
    params, _ = eng.init_state()
    pshape = InputShape("p", 64, 8, "prefill")
    dshape = InputShape("d", 64, 8, "decode")
    pre = eng.build_prefill(pshape)
    srv = eng.build_serve_step(dshape)
    with mesh:
        lg, cache = pre(params, {"tokens": jnp.ones((8, 32), jnp.int32)})
        lg2, cache = srv(params, {"token": jnp.ones((8,), jnp.int32),
                                  "pos": jnp.int32(32)}, cache)
    assert lg2.shape[0] == 8 and not bool(jnp.isnan(lg2).any())
    print("serve: prefill->decode OK")


if __name__ == "__main__":
    check_grad_equivalence()
    check_strategies()
    check_training_decreases_loss()
    check_serve()
    print("ALL DISTRIBUTED CHECKS PASSED")
