"""Multi-device streaming-collective checks (subprocess body).

Run by tests/test_stream.py with 4 virtual CPU devices — XLA device
count must be set before jax initializes, hence the subprocess. What a
single-device run cannot witness, this does:

  1. ring == allgather BITWISE at n_workers = 4 — distinct per-worker
     gradients, with and without chunked hops, including 5 steps of
     threaded error feedback. The streaming correctness contract on a
     real ring.
  2. the double-buffer jaxpr proof: in the traced program the first
     `ppermute` (message 0's first hop) appears AFTER the first
     `optimization_barrier` (message 1's gathers ordered on message 0's
     buffer) — compress(i+1) interleaves before collective(i) — and the
     ppermute count is exactly sum_msgs (n-1) x n_chunks(msg).
  3. per-hop observability: measure_stream reports hop-span count ==
     n_messages x (n-1) for both modes (its trace validates against the
     Chrome schema internally; multi-device stamps collapse under
     finalize_step(dedupe=True)).
  4. the rs paths on NON-DIVISIBLE dims: rs_stream (wire) and
     rs_compress_ag (unpacked) with the identity compressor reproduce
     the dense mean — the padding-mask fix (phantom capacity-tail
     values used to leak into encode).
  5. `_mean_psum` static-n bit-identity: psum(x)/n_static equals the
     legacy psum(x)/psum(ones) bitwise (psum of ones is exactly
     float(n)).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from _sweep_helpers import (assert_trees_bitwise, per_worker,  # noqa: E402
                            ring_tree)
from repro.core import (CompressionConfig, FUSE_ALL, Granularity,  # noqa: E402
                        build_plan, build_schedule, compressed_allreduce,
                        make_compressor, stacked_mask)
from repro.core.wire import layout_chunks, message_layouts, wire_codec  # noqa: E402
from repro.launch.engine import shard_map  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

KEY = jax.random.key(7)
N = jax.local_device_count()
assert N == 4, f"expected 4 virtual devices, got {N}"
MESH = make_host_mesh(N, 1)


def _run(strat, qw, fb, *, ef_steps=0, chunk=None):
    t = ring_tree()
    sm = stacked_mask(t)
    cfg = CompressionConfig(qw=qw, granularity=Granularity("layerwise"),
                            strategy=strat, error_feedback=ef_steps > 0,
                            fusion_bytes=fb)

    def f(g, ef, key):
        g = per_worker(g)
        if ef_steps:
            return compressed_allreduce(g, sm, cfg, ("data",), key, N,
                                        wire=True, ef_state=ef,
                                        stream_chunk_bytes=chunk)
        out, _ = compressed_allreduce(g, sm, cfg, ("data",), key, N,
                                      wire=True, stream_chunk_bytes=chunk)
        return out

    fn = jax.jit(shard_map(f, MESH, in_specs=(P(), P(), P()),
                           out_specs=(P(), P()) if ef_steps else P()))
    if not ef_steps:
        return fn(t, t, KEY)   # ef arg unused
    ef = jax.tree_util.tree_map(jnp.zeros_like, t)
    outs = []
    for i in range(ef_steps):
        out, ef = fn(t, ef, jax.random.fold_in(KEY, i))
        outs.append(out)
    return outs, ef


def check_ring_bitwise():
    for name, kw in (("topk", {"ratio": 0.25}), ("qsgd", {"levels": 16}),
                     ("natural", {})):
        qw = make_compressor(name, **kw)
        for fb in (0.0, FUSE_ALL):
            ref = _run("allgather", qw, fb)
            for chunk in (None, 64.0):
                got = _run("ring", qw, fb, chunk=chunk)
                assert_trees_bitwise(ref, got, ("ring", name, fb, chunk))
    print("ring == allgather bitwise at n=4: OK")


def check_ring_ef_bitwise():
    qw = make_compressor("topk", ratio=0.25)
    for fb in (0.0, FUSE_ALL):
        ref_outs, ref_ef = _run("allgather", qw, fb, ef_steps=5)
        got_outs, got_ef = _run("ring", qw, fb, ef_steps=5, chunk=64.0)
        for i, (r, g) in enumerate(zip(ref_outs, got_outs)):
            assert_trees_bitwise(r, g, ("ring-ef", fb, "step", i))
        assert_trees_bitwise(ref_ef, got_ef, ("ring-ef-state", fb))
    print("ring 5-step EF == allgather at n=4: OK")


def _prim_seq(jx, out):
    for eqn in jx.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for u in vs:
                if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                    _prim_seq(u.jaxpr, out)
                elif hasattr(u, "eqns"):
                    _prim_seq(u, out)


def check_double_buffer_jaxpr():
    t = ring_tree()
    sm = stacked_mask(t)
    comp = make_compressor("qsgd", levels=16)
    plan = build_plan(t, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)
    codec = wire_codec(comp)
    assert sched.num_messages > 1, "need >= 2 messages for the pipeline"

    def f(g):
        out, _ = sched.execute_streaming(None, g, KEY, wire=codec,
                                         axis_names=("data",), n_workers=N)
        return out

    jaxpr = jax.make_jaxpr(shard_map(f, MESH, in_specs=(P(),),
                                     out_specs=P()))(t)
    seq = []
    _prim_seq(jaxpr.jaxpr, seq)
    assert "ppermute" in seq and "optimization_barrier" in seq, seq[:20]
    i_ob = seq.index("optimization_barrier")
    i_pp = seq.index("ppermute")
    # message 1's gathers are barriered on message 0's buffer BEFORE
    # message 0's first hop: compress(i+1) precedes collective(i).
    assert i_ob < i_pp, (i_ob, i_pp)
    expected = sum((N - 1) * len(layout_chunks(l, None))
                   for l in message_layouts(sched, codec))
    got = sum(1 for p in seq if p == "ppermute")
    assert got == expected, (got, expected)
    print(f"double-buffer jaxpr: barrier@{i_ob} < ppermute@{i_pp}, "
          f"{got} ppermutes: OK")


def check_hop_spans():
    from repro.obs.calibrate import measure_stream
    t = ring_tree()
    sm = stacked_mask(t)
    comp = make_compressor("qsgd", levels=16)
    for mode in ("ring", "rs"):
        r = measure_stream(t, sm, comp, 0.0, mode=mode, reps=2, warmup=1,
                           chunk_bytes=64.0)
        assert r["n_workers"] == N, r
        assert r["n_hops"] == r["n_messages"] * (N - 1), r
        assert r["n_hop_spans_measured"] == r["n_hops"], r
        assert r["hop_bytes_total"] == (N - 1) * r["wire_bytes"], r
    print("per-hop spans (counts, bytes, chrome-trace schema): OK")


def check_rs_nondivisible():
    t = ring_tree()   # 'odd' (7,) and blocks dim 10: both non-divisible by 4
    sm = stacked_mask(t)
    qw = make_compressor("identity")
    mesh = MESH

    def dense_mean(g):
        g = per_worker(g)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, ("data",)) / 4.0, g)

    ref = jax.jit(shard_map(dense_mean, mesh, in_specs=(P(),),
                            out_specs=P()))(t)
    for strat, wire in (("rs_stream", True), ("rs_compress_ag", False)):
        cfg = CompressionConfig(qw=qw,
                                granularity=Granularity("layerwise"),
                                strategy=strat)

        def f(g):
            g = per_worker(g)
            out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY, N,
                                          wire=wire)
            return out

        got = jax.jit(shard_map(f, mesh, in_specs=(P(),),
                                out_specs=P()))(t)
        for x, y in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            err = float(jnp.max(jnp.abs(x - y)))
            # identity codec: any deviation beyond reduction reordering
            # means padding leaked into the payloads (the fixed bug)
            assert err <= 1e-5 * (1.0 + float(jnp.max(jnp.abs(x)))), (
                strat, err)
    print("rs paths on non-divisible dims == dense mean (identity): OK")


def check_mean_psum_static():
    from repro.core.aggregation import _mean_psum
    x = jax.random.normal(jax.random.key(9), (64,))

    def new(v):
        return _mean_psum(v, ("data",), N)

    def legacy(v):
        return jax.lax.psum(v, ("data",)) / jax.lax.psum(
            jnp.ones((), v.dtype), ("data",))

    a = jax.jit(shard_map(new, MESH, in_specs=(P(),), out_specs=P()))(x)
    b = jax.jit(shard_map(legacy, MESH, in_specs=(P(),), out_specs=P()))(x)
    assert bool((a == b).all()), float(jnp.max(jnp.abs(a - b)))
    print("_mean_psum static-n == legacy psum-of-ones bitwise: OK")


if __name__ == "__main__":
    check_ring_bitwise()
    check_ring_ef_bitwise()
    check_double_buffer_jaxpr()
    check_hop_spans()
    check_rs_nondivisible()
    check_mean_psum_static()
    print("ALL STREAM CHECKS PASSED")
