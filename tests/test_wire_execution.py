"""WireCodec: wire execution == unpacked execution through the
aggregation stack — error feedback over five steps for six compressors x
{per-bucket, fused} messages, the collective strategies inside
shard_map, and the sharded engine train step. The EF sweep's topk case
at ratio 0.1 runs unmarked; the rest of it carries the `wire` marker
(tier-1 only, excluded by `make verify-fast`).
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from _sweep_helpers import (KEY, SIX, assert_steps_bitwise,
                            assert_trees_bitwise, grad_tree, smoke_engine,
                            sweep)
from repro.core import (CompressionConfig, FUSE_ALL, Granularity,
                        aggregate_simulated_workers, compressed_allreduce,
                        make_compressor, stacked_mask)


def _run_ef_steps(name, kw, wire, fusion_bytes=None, steps=5):
    t = grad_tree()
    sm = stacked_mask(t)
    n = 2
    cfg = CompressionConfig(qw=make_compressor(name, **kw),
                            granularity=Granularity("layerwise"),
                            error_feedback=True,
                            fusion_bytes=fusion_bytes)
    ef = jax.tree_util.tree_map(
        lambda x: jnp.zeros((n,) + x.shape, jnp.float32), t)
    out = None
    for i in range(steps):
        wg = jax.tree_util.tree_map(
            lambda x: jnp.stack([x * (1.0 + 0.1 * i), -0.5 * x]), t)
        out, ef = aggregate_simulated_workers(
            wg, sm, cfg, jax.random.fold_in(KEY, i), ef_state=ef,
            wire=wire)
    return out, ef


@functools.lru_cache(maxsize=None)
def _unpacked_ef_steps(name, kw_items):
    """The unpacked reference, shared by a codec's wire cases."""
    return _run_ef_steps(name, dict(kw_items), wire=False)


_EF_CASES = [(name, kw, fb) for name, kw in SIX for fb in (None, FUSE_ALL)]


@pytest.mark.parametrize("name,kw,fb", sweep(
    [("topk", {"ratio": 0.1}, None)] + _EF_CASES,
    lambda name, kw, fb: kw == {"ratio": 0.1}, pytest.mark.wire,
    ids=["topk0.1-per_bucket"] + [f"{name}-{'fused' if fb else 'per_bucket'}"
                                  for name, _, fb in _EF_CASES]))
def test_wire_matches_unpacked_ef_full(name, kw, fb):
    """5 steps of Algorithm 1 with error-feedback threading, all six
    compressors x {per-bucket, fused} wire messages: the wire path's
    outputs AND residual memories stay bit-identical to the unpacked
    path. The unmarked case: topk at ratio 0.1, per-bucket messages."""
    ref = _unpacked_ef_steps(name, tuple(sorted(kw.items())))
    got = _run_ef_steps(name, kw, wire=True, fusion_bytes=fb)
    assert_trees_bitwise(ref, got, (name, kw, fb))


def test_collective_wire_paths_bit_identical():
    """compressed_allreduce inside shard_map: wire=True matches the
    unpacked path for BOTH strategies — under `allgather` the packed
    uint8 payload itself crosses the collective."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh
    t = grad_tree()
    sm = stacked_mask(t)
    mesh = make_host_mesh(1, 1)
    for strat in ("simulated", "allgather"):
        cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                                granularity=Granularity("layerwise"),
                                strategy=strat)

        def run(wire):
            def f(g):
                out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY,
                                              1, wire=wire)
                return out
            return jax.jit(shard_map(f, mesh, in_specs=(P(),),
                                     out_specs=P()))(t)

        assert_trees_bitwise(run(False), run(True), strat)


def test_engine_wire_step_bit_identical():
    """Acceptance: the sharded train step with wire=True is bit-for-bit
    the unpacked step (real message buffers in the compiled graph)."""
    eng = smoke_engine()
    assert_steps_bitwise(eng, eng.build_train_step(),
                         eng.build_train_step(wire=True), "engine-wire")
