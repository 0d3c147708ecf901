"""Streaming collectives conserve error feedback, bit for bit.

Five steps of Algorithm 1 with threaded error feedback on a 1-worker
mesh: the `ring` and `rs_stream` paths' outputs AND residual memories
stay bit-identical to the serialized allgather wire path at every step,
for all six codecs x {per-bucket, fused} messages — EF is neither
dropped nor double-applied by the ring reordering. The whole sweep
carries the `stream` marker (tier-1 only, excluded by `make
verify-fast`).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from _sweep_helpers import KEY, SIX, SIX_IDS, assert_trees_bitwise, grad_tree
from repro.core import (CompressionConfig, FUSE_ALL, Granularity,
                        compressed_allreduce, make_compressor, stacked_mask)
from repro.launch.engine import shard_map
from repro.launch.mesh import make_host_mesh


def _run_ef_steps(strat, name, fb, chunk=None, steps=5):
    cfg = CompressionConfig(qw=make_compressor(name, **dict(SIX)[name]),
                            granularity=Granularity("layerwise"),
                            strategy=strat, error_feedback=True,
                            fusion_bytes=fb)
    t = grad_tree()
    sm = stacked_mask(t)
    mesh = make_host_mesh(1, 1)

    def f(g, m, key):
        return compressed_allreduce(g, sm, cfg, ("data",), key, 1,
                                    wire=True, ef_state=m,
                                    stream_chunk_bytes=chunk)
    fn = jax.jit(shard_map(f, mesh, in_specs=(P(), P(), P()),
                           out_specs=(P(), P())))
    # the residual enters with the sharding it leaves with, so the step
    # compiles once rather than again at step 1
    m = jax.device_put(jax.tree_util.tree_map(jnp.zeros_like, t),
                       NamedSharding(mesh, P()))
    outs = []
    for i in range(steps):
        g = jax.tree_util.tree_map(lambda x: x * (1.0 + 0.1 * i), t)
        out, m = fn(g, m, jax.random.fold_in(KEY, i))
        outs.append(out)
    return outs, m


@functools.lru_cache(maxsize=None)
def _allgather_ef_steps(name, fb):
    """The serialized wire path's trajectory, shared by both streaming
    strategies' cases."""
    return _run_ef_steps("allgather", name, fb)


@pytest.mark.stream
@pytest.mark.parametrize("strat", ["ring", "rs_stream"])
@pytest.mark.parametrize("name", SIX_IDS)
@pytest.mark.parametrize("fb", [0.0, FUSE_ALL], ids=["per_bucket", "fused"])
def test_stream_ef_conservation_full(name, fb, strat):
    ref_outs, ref_m = _allgather_ef_steps(name, fb)
    got_outs, got_m = _run_ef_steps(strat, name, fb, chunk=64.0)
    for i, (r, g) in enumerate(zip(ref_outs, got_outs)):
        assert_trees_bitwise(r, g, (name, fb, strat, "step", i))
    assert_trees_bitwise(ref_m, got_m, (name, fb, strat, "m"))
