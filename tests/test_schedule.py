"""CommSchedule: the equivalence harness locking down comm scheduling.

The load-bearing property: scheduling NEVER changes numerics. A
CommSchedule reorders the plan's per-bucket dispatches into backward-ready
fused wire messages and pins that order with barriers — but every bucket
runs the identical batched compressor call with the identical PRNG keys,
so `schedule.execute` must be BIT-identical to `UnitPlan.execute` /
`apply_unitwise` for every compressor, granularity, fusion threshold and
key-derivation mode. Plus: error-feedback state is neither dropped nor
double-applied under fusion/reordering, message construction invariants,
the alpha-beta model's sanity, and the comm_report message/latency
accounting against hand-computed values.

The full sweep (six compressors x granularities x thresholds x key modes)
carries the `sched` marker: it runs in tier-1 (`make verify`) and is
excluded from the `make verify-fast` inner loop.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from _sweep_helpers import (GRANS, KEY, SIX, assert_steps_bitwise,
                            assert_trees_bitwise, grad_tree, smoke_engine,
                            sweep)
from repro.core import (CompressionConfig, FUSE_ALL, Granularity,
                        aggregate_simulated_workers, build_plan,
                        build_schedule, comm_report, compressed_allreduce,
                        make_compressor, message_wire_bits,
                        simulate_schedule, stacked_mask)
from repro.core.granularity import apply_unitwise


# fusion thresholds the harness sweeps: per-bucket messages, Horovod-ish
# small buffer, large buffer, one fused message.
THRESHOLDS = (0.0, 4096.0, float(1 << 20), FUSE_ALL)

# the paper's six operators (ISSUE: "all six compressors")


def _fns(comp, key_mode):
    """The per-unit closure under both PRNG disciplines: `per_unit` uses
    the plan-derived unit key (the production path), `shared` ignores it
    and closes over ONE key (frameworks that seed per step, not per
    tensor). Equivalence must hold for both."""
    if key_mode == "per_unit":
        return lambda x, k: comp.sim(x, k)
    shared = jax.random.fold_in(KEY, 0xF00D)
    return lambda x, k: comp.sim(x, shared)


def _check_equivalence(tree, sm, comp, gran, fusion_bytes, key_mode,
                       key=KEY):
    plan = build_plan(tree, sm, gran)
    sched = build_schedule(plan, fusion_bytes)
    fn = _fns(comp, key_mode)
    ref = plan.execute(fn, tree, key)
    got = sched.execute(fn, tree, key)
    assert_trees_bitwise(ref, got,
                         (comp.name, gran.kind, fusion_bytes, key_mode))


# ---------------------------------------------------------------------------
# equivalence: scheduled execution == UnitPlan reference, bit for bit
# ---------------------------------------------------------------------------

_SCHED_CASES = [(name, kw, gran, key_mode, fb) for name, kw in SIX
                for gran in GRANS for key_mode in ("per_unit", "shared")
                for fb in THRESHOLDS]


@pytest.mark.parametrize("name,kw,gran,key_mode,fb", sweep(
    _SCHED_CASES,
    lambda name, kw, gran, key_mode, fb: name in ("qsgd", "topk")
    and gran.kind == "layerwise" and key_mode == "per_unit"
    and fb in (0.0, FUSE_ALL),
    pytest.mark.sched))
def test_schedule_matches_plan_full(name, kw, gran, key_mode, fb):
    """The acceptance sweep: all six compressors x {layerwise,
    entire_model} x fusion thresholds {0, 4KiB, 1MiB, inf} x
    {per-unit, shared} PRNG keys — bit-identical everywhere. The
    unmarked subset: qsgd and topk x layerwise x {no fusion, full
    fusion} x per-unit keys."""
    t = grad_tree()
    _check_equivalence(t, stacked_mask(t), make_compressor(name, **kw),
                       gran, fb, key_mode)


def test_schedule_matches_plan_blockwise():
    """Beyond the ISSUE matrix: blockwise plans schedule too (single
    size-class bucket — scheduling degenerates to one message)."""
    t = grad_tree()
    sm = stacked_mask(t)
    _check_equivalence(t, sm, make_compressor("qsgd", levels=8),
                       Granularity("blockwise", 100), 0.0, "per_unit")


def test_schedule_matches_plan_raw_key():
    """Old-style uint32 keys take the raw fold path through the schedule
    exactly as through the plan."""
    t = grad_tree()
    sm = stacked_mask(t)
    rk = jax.random.PRNGKey(11)
    _check_equivalence(t, sm, make_compressor("qsgd", levels=8),
                       Granularity("layerwise"), 4096.0, "per_unit", key=rk)


def test_schedule_matches_apply_unitwise():
    """The harness's second oracle: `apply_unitwise` (the public plan
    entry point) agrees with scheduled execution under jit."""
    t = grad_tree()
    sm = stacked_mask(t)
    g = Granularity("layerwise")
    c = make_compressor("natural")
    plan = build_plan(t, sm, g)
    sched = build_schedule(plan, FUSE_ALL)
    fn = lambda x, k: c.sim(x, k)  # noqa: E731
    ref = jax.jit(lambda tt: apply_unitwise(fn, g, tt, sm, KEY))(t)
    got = jax.jit(lambda tt: sched.execute(fn, tt, KEY))(t)
    assert_trees_bitwise(ref, got, "apply_unitwise-vs-schedule")


# ---------------------------------------------------------------------------
# error feedback: state neither dropped nor double-applied
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fb", [0.0, 4096.0, FUSE_ALL],
                         ids=["per_bucket", "fuse4k", "fuse_all"])
def test_schedule_with_state_matches_plan(fb):
    """Outputs AND residual memories are bit-identical when buckets are
    fused or reordered."""
    t = grad_tree()
    sm = stacked_mask(t)
    m0 = jax.tree_util.tree_map(lambda x: 0.3 * jnp.ones_like(x), t)
    c = make_compressor("topk", ratio=0.1)

    def ef(x, m, k):
        e = x + m
        q = c.sim(e, k)
        return q, e - q

    for gran in GRANS:
        plan = build_plan(t, sm, gran)
        sched = build_schedule(plan, fb)
        y_p, m_p = plan.execute_with_state(ef, t, m0, KEY)
        y_s, m_s = sched.execute_with_state(ef, t, m0, KEY)
        assert_trees_bitwise(y_p, y_s, (gran.kind, fb, "out"))
        assert_trees_bitwise(m_p, m_s, (gran.kind, fb, "mem"))


def test_ef_conservation_over_steps():
    """5 steps of Algorithm 1 with error feedback, fused vs unscheduled:
    the EF residual trees stay bit-identical step after step (nothing
    dropped, nothing double-applied), so their element sums match the
    unscheduled reference exactly."""
    t = grad_tree()
    sm = stacked_mask(t)
    n = 2

    def run(fusion_bytes):
        cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.1),
                                granularity=Granularity("layerwise"),
                                error_feedback=True,
                                fusion_bytes=fusion_bytes)
        ef = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, jnp.float32), t)
        out = None
        for i in range(5):
            gkey = jax.random.fold_in(KEY, 100 + i)
            wg = jax.tree_util.tree_map(
                lambda x: jnp.stack([x * (1.0 + 0.1 * i), -0.5 * x]), t)
            out, ef = aggregate_simulated_workers(
                wg, sm, cfg, jax.random.fold_in(gkey, i), ef_state=ef)
        return out, ef

    out_ref, ef_ref = run(None)
    for fb in (0.0, 4096.0, FUSE_ALL):
        out_s, ef_s = run(fb)
        assert_trees_bitwise(out_s, out_ref, (fb, "out"))
        assert_trees_bitwise(ef_s, ef_ref, (fb, "ef"))
        ref_sum = sum(float(jnp.sum(l))
                      for l in jax.tree_util.tree_leaves(ef_ref))
        s_sum = sum(float(jnp.sum(l))
                    for l in jax.tree_util.tree_leaves(ef_s))
        assert s_sum == ref_sum, fb


# ---------------------------------------------------------------------------
# scheduling semantics: order, fusion, barriers
# ---------------------------------------------------------------------------

def test_readiness_order_streams_backward():
    """Scheduled tracing dispatches buckets in backward-readiness order
    (late layers first — head before embed before the stacked blocks),
    NOT in the plan's size-class discovery order; and every bucket still
    traces exactly once (dispatch count preserved)."""
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)

    seen = []

    def counting(x, k):
        seen.append(x.shape[-1])
        return x

    jax.make_jaxpr(lambda tt: sched.execute(counting, tt, KEY))(t)
    expect = [plan.buckets[i].dim for m in sched.messages
              for i in m.bucket_ids]
    plan_order = [b.dim for b in plan.buckets]
    assert seen == expect
    assert seen != plan_order           # scheduling really reorders
    assert len(seen) == plan.num_dispatches
    # the tree's last leaves (head dim 8 via the shared dim-8 bucket is
    # held back by blocks.b at leaf 0 — the scalar and embed go first)
    ready = [plan.buckets[i].ready for i in sched.order]
    assert ready == sorted(ready)       # ascending readiness


def test_message_construction_invariants():
    """Messages partition the buckets exactly once, in readiness order;
    fusion is monotone in the threshold; 0 => one message per bucket;
    inf => one message; dense bytes add up."""
    t = grad_tree()
    sm = stacked_mask(t)
    for gran in GRANS + [Granularity("blockwise", 100)]:
        plan = build_plan(t, sm, gran)
        prev_n = None
        for fb in (0.0, 1024.0, 4096.0, float(1 << 20), FUSE_ALL):
            sched = build_schedule(plan, fb)
            ids = [bi for m in sched.messages for bi in m.bucket_ids]
            assert sorted(ids) == list(range(len(plan.buckets)))
            assert tuple(ids) == sched.order
            assert sum(m.nbytes for m in sched.messages) == \
                sum(b.nbytes for b in plan.buckets)
            for m in sched.messages:
                assert m.ready == max(plan.buckets[bi].ready
                                      for bi in m.bucket_ids)
            if prev_n is not None:      # larger threshold never splits
                assert sched.num_messages <= prev_n
            prev_n = sched.num_messages
        assert build_schedule(plan, 0.0).num_messages == len(plan.buckets)
        assert build_schedule(plan, FUSE_ALL).num_messages == 1
    with pytest.raises(ValueError):
        build_schedule(build_plan(t, sm, GRANS[0]), -1.0)


def test_build_schedule_cached_and_hashable():
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    s1 = build_schedule(plan, 4096.0)
    assert build_schedule(plan, 4096.0) is s1       # lru_cache hit
    assert build_schedule(plan, 0.0) is not s1
    assert len({s1, build_schedule(plan, 4096.0)}) == 1  # hashable key
    assert "messages" in s1.summary() or "message" in s1.summary()


def test_streaming_barriers_in_jaxpr():
    """One ordering barrier between consecutive messages — message i+1's
    gathers depend on message i's output, which is what forbids the
    compiler from hoisting later compression above earlier collectives.
    The unscheduled plan path has none."""
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    c = make_compressor("signsgd")
    fn = lambda x, k: c.sim(x, k)  # noqa: E731

    def count_barriers(jaxpr):
        return sum(1 for eq in jaxpr.eqns
                   if eq.primitive.name == "optimization_barrier")

    for fb, want_msgs in ((0.0, len(plan.buckets)), (FUSE_ALL, 1)):
        sched = build_schedule(plan, fb)
        assert sched.num_messages == want_msgs
        jx = jax.make_jaxpr(lambda tt: sched.execute(fn, tt, KEY))(t)
        assert count_barriers(jx.jaxpr) == sched.num_messages - 1
    jx = jax.make_jaxpr(lambda tt: plan.execute(fn, tt, KEY))(t)
    assert count_barriers(jx.jaxpr) == 0


# ---------------------------------------------------------------------------
# alpha-beta cost model
# ---------------------------------------------------------------------------

def test_simulate_schedule_model():
    """Deterministic sanity of the cost model: entire-model (one late
    message) exposes ALL its comm; per-bucket layerwise streaming
    overlaps some of it behind backward; the alpha term makes many small
    messages expensive when latency dominates. JSON-exportable."""
    t = grad_tree()
    sm = stacked_mask(t)
    lw = build_plan(t, sm, Granularity("layerwise"))
    em = build_plan(t, sm, Granularity("entire_model"))
    qw = make_compressor("topk", ratio=0.1)
    kw = dict(qw=qw, alpha_us=50.0, gbps=12.5, compress_gbps=25.0,
              backward_us=500.0)

    sim_em = simulate_schedule(build_schedule(em, FUSE_ALL), **kw)
    sim_pb = simulate_schedule(build_schedule(lw, 0.0), **kw)
    sim_fa = simulate_schedule(build_schedule(lw, FUSE_ALL), **kw)

    # identical inputs => identical outputs (pure function of statics)
    assert sim_pb == simulate_schedule(build_schedule(lw, 0.0), **kw)
    for s in (sim_em, sim_pb, sim_fa):
        json.dumps(s)
        assert 0.0 <= s["overlap_frac"] <= 1.0
        assert s["exposed_comm_us"] <= s["comm_us_total"] + 1e-9
    # the entire-model message departs only at backward end: zero overlap
    assert sim_em["overlap_frac"] == 0.0
    assert sim_em["n_messages"] == 1
    # per-bucket streaming starts mid-backward: it finishes no later than
    # waiting for the whole gradient would
    assert sim_pb["t_total_us"] <= sim_em["t_total_us"] + \
        (sim_pb["n_messages"] - 1) * kw["alpha_us"] + 1e-6
    # alpha scaling: with latency 100x, fewer messages must not lose
    hi = dict(kw, alpha_us=5000.0)
    assert simulate_schedule(build_schedule(lw, FUSE_ALL), **hi)[
        "t_total_us"] < simulate_schedule(build_schedule(lw, 0.0), **hi)[
        "t_total_us"]


def test_message_wire_bits_accounting():
    """Per-message wire bits = sum of member buckets' payload bits, under
    the compressor view, the measured-override view, and the dense
    fallback."""
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    sched = build_schedule(plan, FUSE_ALL)
    qw = make_compressor("topk", ratio=0.25)
    total = sum(b.n * qw.payload_bits(b.dim) for b in plan.buckets)
    assert message_wire_bits(sched, qw=qw) == [total]
    dense = sum(32 * b.n * b.dim for b in plan.buckets)
    assert message_wire_bits(sched) == [dense]
    override = [7] * len(plan.buckets)
    assert message_wire_bits(sched, bucket_bits=override) == \
        [7 * len(plan.buckets)]
    with pytest.raises(ValueError):
        message_wire_bits(sched, bucket_bits=[1])


# ---------------------------------------------------------------------------
# bits.comm_report: message count + alpha (latency) line, hand-computed
# ---------------------------------------------------------------------------

def test_comm_report_messages_and_alpha_hand_computed():
    """Regression against hand-computed values on a 3-unit partition:
    dims (8, 8, 4), Top-k ratio 0.5, allgather, 2 workers.

      per-unit k = max(1, round(0.5*d)) -> (4, 4, 2)
      record = 32-bit value + ceil(log2(d))-bit index (the packed wire
      format's dim-dependent index width): 35 bits at d=8, 34 at d=4
      uplink   = 4*35 + 4*35 + 2*34     = 348
      downlink = (n-1)*uplink           = 348
      unscheduled: one message per unit -> 3; alpha=1000 -> latency 3000
      fully fused:  one message         -> 1; alpha=1000 -> latency 1000
    """
    t = {"a": jnp.zeros((2, 8)), "c": jnp.zeros((4,))}
    sm = jax.tree_util.tree_map(lambda _: False, t)
    sm["a"] = True  # stacked: two dim-8 units
    g = Granularity("layerwise")
    plan = build_plan(t, sm, g)
    assert list(plan.unit_dims) == [8, 8, 4]
    qw = make_compressor("topk", ratio=0.5)

    cfg = CompressionConfig(qw=qw, granularity=g, strategy="allgather")
    rep = comm_report(cfg, plan, 2, alpha_bits_per_message=1000)
    assert rep.uplink_bits_per_worker == 348
    assert rep.downlink_bits_per_worker == 348
    assert rep.n_messages == 3
    assert rep.latency_bits() == 3000
    assert rep.total_bits_with_latency() == 348 + 348 + 3000
    assert rep.dense_bits == 2 * 32 * 20

    fused = CompressionConfig(qw=qw, granularity=g, strategy="allgather",
                              fusion_bytes=FUSE_ALL)
    repf = comm_report(fused, plan, 2, alpha_bits_per_message=1000)
    assert repf.n_messages == 1
    assert repf.latency_bits() == 1000
    # payload (beta) terms are schedule-independent
    assert repf.uplink_bits_per_worker == rep.uplink_bits_per_worker
    assert repf.total_bits_with_latency() == 348 + 348 + 1000
    # entire-model vs layerwise vs fused layerwise are now distinguishable
    em = comm_report(
        CompressionConfig(qw=qw, granularity=Granularity("entire_model"),
                          strategy="allgather"),
        build_plan(t, sm, Granularity("entire_model")), 2,
        alpha_bits_per_message=1000)
    assert em.n_messages == 1
    assert (em.n_messages, rep.n_messages, repf.n_messages) == (1, 3, 1)
    # entire-model pays WIDER indices (5 bits at d=20 -> 10*37 = 370
    # uplink vs layerwise's 348) but one alpha; latency dominates here
    assert em.uplink_bits_per_worker == 370
    assert em.total_bits_with_latency() < rep.total_bits_with_latency()


def test_comm_report_schedule_from_plan_only():
    """The schedule auto-build needs a UnitPlan; a plain dim list keeps
    the per-unit message count even when the config asks for fusion."""
    dims = [8, 8, 4]
    qw = make_compressor("topk", ratio=0.5)
    cfg = CompressionConfig(qw=qw, strategy="allgather",
                            fusion_bytes=FUSE_ALL)
    rep = comm_report(cfg, dims, 2)
    assert rep.n_messages == 3  # no plan -> no schedule -> per-unit


# ---------------------------------------------------------------------------
# aggregation + engine integration
# ---------------------------------------------------------------------------

def test_scheduled_allreduce_collective_path():
    """compressed_allreduce inside shard_map: cfg.fusion_bytes routes
    through the schedule without changing the aggregate (1-device mesh,
    psum-bearing closures under the ordering barriers)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh
    t = grad_tree()
    sm = stacked_mask(t)
    mesh = make_host_mesh(1, 1)

    def run(fusion_bytes):
        cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                                granularity=Granularity("layerwise"),
                                fusion_bytes=fusion_bytes)

        def f(g):
            out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY, 1)
            return out
        return jax.jit(shard_map(f, mesh, in_specs=(P(),),
                                 out_specs=P()))(t)

    ref = run(None)
    for fb in (0.0, FUSE_ALL):
        assert_trees_bitwise(run(fb), ref, fb)


def test_aggregate_simulated_workers_schedule_arg():
    """An explicit prebuilt CommSchedule is honored (and equals the
    cfg.fusion_bytes route)."""
    t = grad_tree()
    sm = stacked_mask(t)
    wg = jax.tree_util.tree_map(lambda x: jnp.stack([x, 2.0 * x]), t)
    cfg = CompressionConfig(qw=make_compressor("terngrad"),
                            granularity=Granularity("layerwise"))
    plan = build_plan(t, sm, cfg.granularity)
    sched = build_schedule(plan, 4096.0)
    a, _ = aggregate_simulated_workers(wg, sm, cfg, KEY)
    b, _ = aggregate_simulated_workers(wg, sm, cfg, KEY, schedule=sched)
    import dataclasses as _dc
    c, _ = aggregate_simulated_workers(
        wg, sm, _dc.replace(cfg, fusion_bytes=4096.0), KEY)
    assert_trees_bitwise(a, b, "explicit-schedule")
    assert_trees_bitwise(b, c, "fusion-bytes-route")


def test_resolve_schedule_validation():
    from repro.launch.comm_sched import resolve_schedule
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    other = build_plan(t, sm, Granularity("entire_model"))
    s = build_schedule(plan, 0.0)
    assert resolve_schedule(None, None) is None
    assert resolve_schedule(plan, 4096) is build_schedule(plan, 4096.0)
    assert resolve_schedule(plan, s) is s
    assert resolve_schedule(None, 4096.0) is None  # nothing to schedule
    with pytest.raises(ValueError):
        resolve_schedule(other, s)  # schedule from a different plan


def test_engine_scheduled_step_bit_identical():
    """Acceptance: the sharded train step with schedule= (and with the
    decision-carried fusion_bytes) is bit-for-bit the unscheduled step."""
    eng = smoke_engine()
    assert_steps_bitwise(eng, eng.build_train_step(),
                         eng.build_train_step(schedule=4096.0),
                         "engine-schedule")
    # schedule_report joins message accounting + the cost model
    from repro.launch.comm_sched import engine_schedule, schedule_report
    s = engine_schedule(eng, 4096.0)
    rep = schedule_report(s, eng.comp, eng.dp_size)
    assert rep["n_messages"] <= rep["n_dispatches"]
    assert rep["latency_bits"] == rep["n_messages"] * int(50.0 * 12.5 * 8e3)
    json.dumps(rep)


def test_resnet9_fused_messages_below_dispatches():
    """The benchmark acceptance property, statically: on the resnet9
    gradient tree, a 1 MiB fusion buffer yields strictly fewer wire
    messages than the per-bucket dispatch count."""
    from repro.configs.resnet9_cifar import RESNET9
    from repro.models.cnn import init_cnn
    shapes = jax.eval_shape(lambda k: init_cnn(RESNET9, k),
                            jax.random.key(0))
    sm = stacked_mask(shapes)
    plan = build_plan(shapes, sm, Granularity("layerwise"))
    sched = build_schedule(plan, float(1 << 20))
    assert sched.num_messages < plan.num_dispatches
    assert build_schedule(plan, 0.0).num_messages == plan.num_dispatches


# ---------------------------------------------------------------------------
# property test
# ---------------------------------------------------------------------------

# Shapes come from fixed lists: each new shape compiles new programs.
# Stacked rows of 4: one row, an odd count and the largest; loose dims:
# 1, 4 (the size of a one-row layer, so the two share a size class) and
# the largest.
@settings(max_examples=8)
@given(st.sampled_from([1, 2, 6]),
       st.sampled_from([1, 17, 40]),
       st.sampled_from([1, 4, 30]),
       st.integers(min_value=0, max_value=10_000),
       st.sampled_from(THRESHOLDS))
def test_property_schedule_equivalence(L, rows, loose, seed, fb):
    """Random stacked/loose shapes x any threshold: scheduled == planned
    for both ISSUE granularities, bit for bit."""
    key = jax.random.key(seed)
    t = {"blocks": {"w": jax.random.normal(key, (L, rows, 4))},
         "head": jax.random.normal(jax.random.fold_in(key, 1), (loose,))}
    sm = stacked_mask(t)
    c = make_compressor("qsgd", levels=8)
    for gran in GRANS:
        _check_equivalence(t, sm, c, gran, fb, "per_unit", key=key)
