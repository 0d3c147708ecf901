"""Streaming ring collectives: the collective-path contracts.

The correctness contract: `CommSchedule.execute_streaming` (chunked-
ppermute ring, mode='ring') is BIT-identical to the serialized allgather
wire path — same packed payloads, same decode-then-mean in the same
worker order — for every codec, granularity, fusion threshold and
chunking, including threaded error feedback; and `rs_stream` (compress →
reduce-scatter → allgather) degenerates to exactly the same contract at
one worker. The single-device sweeps holding that differential are in
tests/test_stream_allgather_{quantizers,fields}.py and
tests/test_stream_ef.py; the genuinely-multi-worker properties (ring ==
allgather at n=4, the double-buffer jaxpr interleave proof, per-hop span
counts, rs padding on non-divisible dims, `_mean_psum` static-n
bit-identity) run in a 4-virtual-device subprocess
(tests/stream_checks.py — XLA device count must be set before jax
initializes).

Also here: the engine routing its step through the ring, and the
collective-path bugfix regressions the streaming work flushed out — rs
bits accounted on the true d (hand-computed non-divisible case),
`_mean_psum`'s psum-of-ones collective dropped from every message, and
`fit_alpha_beta`'s degenerate-fit clamp.

The `stream`-marked tests run in tier-1 (`make verify`) only, excluded
from the `make verify-fast` inner loop.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from _sweep_helpers import KEY, assert_steps_bitwise, grad_tree, smoke_engine
from repro.core import (CompressionConfig, Granularity, build_plan,
                        comm_report, compressed_allreduce, make_compressor,
                        stacked_mask)
from repro.launch.engine import shard_map
from repro.launch.mesh import make_host_mesh


def test_stream_requires_wire_and_single_axis():
    t = grad_tree()
    sm = stacked_mask(t)
    cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                            granularity=Granularity("layerwise"),
                            strategy="ring")
    with pytest.raises(ValueError, match="wire"):
        compressed_allreduce(t, sm, cfg, ("data",), KEY, 1, wire=False)

    mesh = make_host_mesh(1, 1)

    def f(g):
        out, _ = compressed_allreduce(g, sm, cfg, ("data", "model"), KEY,
                                      1, wire=True)
        return out

    with pytest.raises(ValueError, match="ONE data-parallel axis"):
        jax.jit(shard_map(f, mesh, in_specs=(P(),), out_specs=P()))(t)


# ---------------------------------------------------------------------------
# multi-device properties (4 virtual devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.stream
@pytest.mark.timeout(630)
def test_stream_multidevice_checks(run_check_script):
    """Drives tests/stream_checks.py: ring == allgather bitwise at n=4
    (incl. 5-step EF), the double-buffer jaxpr interleave proof, per-hop
    span counts, rs padding on non-divisible dims, `_mean_psum`
    static-n bit-identity."""
    run_check_script("stream_checks.py", "ALL STREAM CHECKS PASSED",
                     timeout=600)


# ---------------------------------------------------------------------------
# engine integration: --collective routes the step through the ring
# ---------------------------------------------------------------------------

@pytest.mark.stream
def test_engine_collective_ring_bit_identical():
    """build_train_step(collective='ring') is bit-for-bit the
    collective='allgather' step — the streaming ring behind the engine
    changes program order, never numerics."""
    eng = smoke_engine()
    assert_steps_bitwise(
        eng, eng.build_train_step(wire=True, collective="allgather"),
        eng.build_train_step(wire=True, collective="ring"),
        "engine-collective-ring")


def test_engine_collective_validation():
    from repro.configs.registry import get_smoke
    from repro.launch.engine import Engine

    cfg = get_smoke("mamba2-1.3b")
    mesh = make_host_mesh(1, 1)
    comp = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                             granularity=Granularity("layerwise"))
    eng = Engine(cfg, mesh, comp=comp)
    with pytest.raises(ValueError, match="wire=True"):
        eng.build_train_step(collective="ring")           # needs wire
    with pytest.raises(ValueError, match="collective"):
        eng.build_train_step(wire=True, collective="butterfly")
    dense = Engine(cfg, mesh, comp=None)
    with pytest.raises(ValueError, match="compression config"):
        dense.build_train_step(wire=True, collective="ring")


# ---------------------------------------------------------------------------
# bugfix regressions the streaming work flushed out
# ---------------------------------------------------------------------------

def test_rs_bits_true_d_hand_computed():
    """rs accounting on the TRUE d, hand-computed: d=10 on n=4 workers
    shards as ceil(10/4)=3 -> per-worker shard sizes (3, 3, 3, 1). TopK
    ratio 0.5 payload_bits: k(3)=2 keeps 2x(32+2)=68 bits, k(1)=1 keeps
    1x(32+1)=33 -> payload_all = 3x68 + 33 = 237. Worker average: own
    contribute leg ceil(237/4) = 60, receive leg 237 - 60 = 177; dense
    reduce-scatter leg 32x10 = 320. The legacy formula charged every
    worker floor(10/4)=2 entries — neither the wire nor the data."""
    qw = make_compressor("topk", ratio=0.5)
    assert qw.payload_bits(3) == 68 and qw.payload_bits(1) == 33
    for strat in ("rs_compress_ag", "rs_stream"):
        cfg = CompressionConfig(qw=qw,
                                granularity=Granularity("layerwise"),
                                strategy=strat)
        r = comm_report(cfg, [10], 4)
        assert r.uplink_bits_per_worker == 32 * 10 + 60, strat
        assert r.downlink_bits_per_worker == 237 - 60, strat

    # divisible case unchanged by the fix: d=8, n=4 -> shards all 2
    r = comm_report(CompressionConfig(
        qw=qw, granularity=Granularity("layerwise"),
        strategy="rs_compress_ag"), [8], 4)
    per_shard = qw.payload_bits(2)
    own = -(-4 * per_shard // 4)
    assert r.uplink_bits_per_worker == 32 * 8 + own
    assert r.downlink_bits_per_worker == 4 * per_shard - own


def _count_psums(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx):
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "psum":
                n += 1
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else [v]
                for u in vs:
                    if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                        n += walk(u.jaxpr)
                    elif hasattr(u, "eqns"):
                        n += walk(u)
        return n

    return walk(jaxpr.jaxpr)


def test_mean_psum_static_drops_collective():
    """`_mean_psum` resolves the world size statically: the simulated
    strategy's traced graph carries exactly ONE psum per compressor
    dispatch, where the legacy psum-of-ones mean carried two — one
    whole collective per message gone, proven on the jaxpr."""
    from repro.core.aggregation import _mean_psum
    t = grad_tree()
    sm = stacked_mask(t)
    qw = make_compressor("qsgd", levels=16)
    plan = build_plan(t, sm, Granularity("layerwise"))
    n_dispatch = plan.num_dispatches
    mesh = make_host_mesh(1, 1)
    cfg = CompressionConfig(qw=qw, granularity=Granularity("layerwise"),
                            strategy="simulated")

    def prod(g):
        out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY, 1,
                                      plan=plan)
        return out

    def legacy_unit(x, k):
        s = jax.lax.psum(qw.sim(x, k), ("data",))
        return s / jax.lax.psum(jnp.ones((), s.dtype), ("data",))

    def legacy(g):
        return plan.execute(legacy_unit, g, KEY)

    n_prod = _count_psums(shard_map(prod, mesh, in_specs=(P(),),
                                    out_specs=P()), t)
    n_leg = _count_psums(shard_map(legacy, mesh, in_specs=(P(),),
                                   out_specs=P()), t)
    assert n_dispatch > 0
    assert n_prod == n_dispatch, (n_prod, n_dispatch)
    assert n_leg == 2 * n_dispatch, (n_leg, n_dispatch)


def test_fit_alpha_beta_degenerate_clamps_to_prior():
    """Fewer than two distinct message sizes cannot identify alpha AND
    beta: the fit returns the default prior with an explicit flag
    instead of silently dumping the whole duration into alpha."""
    from repro.obs.calibrate import fit_alpha_beta

    one = fit_alpha_beta([(4096, 120.0), (4096, 130.0), (4096, 125.0)])
    assert one["fit_degenerate"] is True
    assert one["alpha_us"] == 50.0 and one["gbps"] == 12.5
    assert one["resid_rms_us"] > 0.0          # honest misfit vs the prior

    custom = fit_alpha_beta([(4096, 120.0)], prior_alpha_us=10.0,
                            prior_gbps=100.0)
    assert custom["fit_degenerate"] is True
    assert custom["alpha_us"] == 10.0 and custom["gbps"] == 100.0

    bad = fit_alpha_beta([(1e3, float("nan")), (1e6, 50.0)])
    assert bad["fit_degenerate"] is True and bad["gbps"] == 12.5

    # legacy shapes preserved: empty -> gbps None (flagged degenerate);
    # two DISTINCT sizes with a flat line is a VALID latency-dominated
    # fit, not a degenerate one
    empty = fit_alpha_beta([])
    assert empty["gbps"] is None and empty["fit_degenerate"] is True
    flat = fit_alpha_beta([(1e3, 50.0), (1e6, 50.0)])
    assert flat["fit_degenerate"] is False
    assert flat["gbps"] is None and flat["alpha_us"] == 50.0

    good = fit_alpha_beta([(1e3, 51.0), (1e6, 130.0)])
    assert good["fit_degenerate"] is False and good["gbps"] is not None


def test_chunk_runs():
    """The hop-granularity grouping: greedy fusion of consecutive
    regions under the chunk budget; regions never split; edge cases."""
    from repro.kernels.ops import chunk_runs

    assert chunk_runs([10, 20, 30], None) == ((0, 1, 2),)
    assert chunk_runs([10, 20, 30], math.inf) == ((0, 1, 2),)
    assert chunk_runs([10, 20, 30], 0) == ((0,), (1,), (2,))
    assert chunk_runs([10, 20, 30], 30.0) == ((0, 1), (2,))
    assert chunk_runs([100, 20, 30], 30.0) == ((0,), (1, 2))
    assert chunk_runs([], 64.0) == ()
    with pytest.raises(ValueError):
        chunk_runs([10], -1.0)


def test_trace_dedupe_collapses_multidevice_stamps():
    """finalize_step(dedupe=True): n-device shard_map stamps each mark
    once per device; dedupe keeps the LAST arrival per mark, restoring
    the one-stamp-per-stage timeline."""
    from repro.obs.trace import TraceRecorder

    rec = TraceRecorder()
    for stage in ("compress", "pack", "collective"):
        rec._meta.append({"stage": stage, "message": 0})
    # 3 marks x 4 "devices", interleaved arrivals
    t = 1000
    for rep in range(4):
        for mid in range(3):
            rec._marks.append((mid, t + mid * 100 + rep))
            t += 1
    s = rec.finalize_step(0, dedupe=True)
    assert s["n_spans"] == 3, s
    spans = [e for e in rec.span_events(step=0) if e["cat"] == "stage"]
    assert len(spans) == 3

    rec2 = TraceRecorder()
    for stage in ("compress", "pack", "collective"):
        rec2._meta.append({"stage": stage, "message": 0})
    for rep in range(4):
        for mid in range(3):
            rec2._marks.append((mid, 1000 + mid * 100 + rep))
    s2 = rec2.finalize_step(0)        # without dedupe: every stamp a span
    assert s2["n_spans"] == 12
