"""Adaptive compression controller: telemetry, policies, decision cache.

Load-bearing properties:
  * Controller + StaticPolicy is BIT-FOR-BIT the plain Engine path.
  * The decision -> compiled-step cache never retraces a revisited
    decision, and a fresh decision matches a from-scratch Engine.
  * GranularitySwitchPolicy switches to entire-model on a workload whose
    measured omegas favor it (the paper's "framework should choose").
  * VarianceBudgetPolicy is monotone: tighter budget => never fewer bits.
  * Telemetry payload-bit accounting agrees with bits.comm_report.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from _sweep_helpers import smoke_batch, smoke_engine
from repro.control import (BitBudgetPolicy, CompressionDecision, Controller,
                           GranularitySwitchPolicy, PerDimRatio,
                           StaticPolicy, VarianceBudgetPolicy, accumulate,
                           init_telemetry, make_policy, measure,
                           measurement_plan, payload_bits_per_step,
                           summarize, unit_omegas)
from repro.core import (CompressionConfig, Granularity, Identity,
                        aggregate_simulated_workers, comm_report,
                        make_compressor, stacked_mask)
from repro.core.theory import noise_bounds_from_plan

KEY = jax.random.key(0)


def _tree(key=KEY):
    ks = [jax.random.fold_in(key, i) for i in range(3)]
    return {"blocks": {"w": jax.random.normal(ks[0], (3, 16, 8))},
            "embed": jax.random.normal(ks[1], (20, 4)),
            "head": jax.random.normal(ks[2], (16, 4))}


def _summary(qw, tree=None, ratio_cfg=None):
    t = tree if tree is not None else _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    inc = measure(mplan, qw, t, KEY)
    return summarize(accumulate(init_telemetry(mplan), inc), mplan, qw=qw), \
        mplan


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_telemetry_identity_is_lossless():
    s, mplan = _summary(Identity())
    assert s["steps"] == 1.0
    for b in s["buckets"]:
        assert abs(b["omega_hat"]) < 1e-5
        assert b["rel_err"] < 1e-10
    assert s["entire_model"]["rel_err"] < 1e-10
    json.dumps(s)  # exportable


def test_telemetry_accumulates_and_jits():
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    qw = make_compressor("qsgd", levels=8)
    inc_fn = jax.jit(lambda g, k: measure(mplan, qw, g, k))
    st_ = init_telemetry(mplan)
    for i in range(3):
        st_ = accumulate(st_, inc_fn(t, jax.random.fold_in(KEY, i)))
    s = summarize(st_, mplan, qw=qw)
    assert s["steps"] == 3.0
    for b in s["buckets"]:
        assert b["grad_var"] >= 0.0
        assert b["grad_norm_sq"] > 0.0


def test_telemetry_entire_model_leg_is_gated():
    """entire_model=False skips the flat counterfactual: em_* stay zero,
    summarize omits the entire_model block, and GranularitySwitchPolicy
    falls back to the current decision instead of misreading zeros."""
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    qw = make_compressor("topk", ratio=0.1)
    inc = measure(mplan, qw, t, KEY, entire_model=False)
    assert float(inc.em_sumsq) == 0.0 and float(inc.em_errsq) == 0.0
    s = summarize(accumulate(init_telemetry(mplan), inc), mplan, qw=qw)
    assert not s.get("entire_model")
    base = CompressionDecision(qw=qw)
    assert GranularitySwitchPolicy().decide(s, base, mplan) == base
    assert VarianceBudgetPolicy().needs_entire_model is False
    assert BitBudgetPolicy().needs_entire_model is False
    assert GranularitySwitchPolicy().needs_entire_model is True


def test_telemetry_payload_bits_match_comm_report():
    """Telemetry's bucket-wise payload sum equals comm_report's per-unit
    walk — for a plain config AND a decision with per-bucket ratio
    overrides (the allgather uplink is exactly the payload)."""
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    qw = make_compressor("topk", ratio=0.1)
    cfg = CompressionConfig(qw=qw, granularity=Granularity("layerwise"),
                            strategy="allgather")
    assert payload_bits_per_step(mplan, qw, measured=False) == \
        comm_report(cfg, mplan, 4).uplink_bits_per_worker
    # the measured (real packed bytes) legs agree the same way
    assert payload_bits_per_step(mplan, qw) == \
        comm_report(cfg, mplan, 4, measured=True).uplink_bits_per_worker

    dec = CompressionDecision(qw=qw, granularity=Granularity("layerwise"),
                              strategy="allgather",
                              ratio_overrides=((8, 0.5), (128, 0.02)))
    rep = comm_report(dec, mplan, 4)
    assert payload_bits_per_step(mplan, dec.to_config().qw,
                                 measured=False) == \
        rep.uplink_bits_per_worker
    assert rep.uplink_bits_per_worker != \
        comm_report(cfg, mplan, 4).uplink_bits_per_worker
    assert dec.payload_bits(mplan.unit_dims) == rep.uplink_bits_per_worker


def test_compressed_allreduce_telemetry_wiring():
    """The collective path also grows a TelemetryState increment (device
    mesh of 1, shard_map like the engine) without changing the output."""
    from jax.sharding import PartitionSpec as P
    from repro.core import compressed_allreduce
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                            granularity=Granularity("layerwise"))
    mesh = make_host_mesh(1, 1)

    def plain(g):
        out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY, 1)
        return out

    def with_telem(g):
        out, _, inc = compressed_allreduce(g, sm, cfg, ("data",), KEY, 1,
                                           telemetry_plan=mplan)
        return out, inc

    a = jax.jit(shard_map(plain, mesh, in_specs=(P(),), out_specs=P()))(t)
    b, inc = jax.jit(shard_map(with_telem, mesh, in_specs=(P(),),
                               out_specs=(P(), P())))(t)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.allclose(la, lb)
    assert float(inc.steps) == 1.0
    assert float(jnp.sum(inc.grad_sumsq)) > 0.0
    assert float(inc.em_sumsq) > 0.0


def test_aggregation_telemetry_wiring():
    """aggregate_simulated_workers grows a TelemetryState increment when
    given a telemetry_plan, without changing the aggregate."""
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    wg = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, 2.0 * x]), t)
    cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                            granularity=Granularity("layerwise"))
    a, _ = aggregate_simulated_workers(wg, sm, cfg, KEY)
    b, _, inc = aggregate_simulated_workers(wg, sm, cfg, KEY,
                                            telemetry_plan=mplan)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.allclose(la, lb)
    assert float(inc.steps) == 1.0
    assert bool(jnp.all(jnp.isfinite(inc.grad_sumsq)))


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

def test_decision_roundtrip_and_hashability():
    d = CompressionDecision(qw=make_compressor("topk", ratio=0.05),
                            granularity=Granularity("entire_model"),
                            ratio_overrides=((128, 0.5),))
    cfg = d.to_config()
    assert isinstance(cfg.qw, PerDimRatio)
    assert cfg.qw.for_dim(128).ratio == 0.5
    assert cfg.qw.for_dim(64).ratio == 0.05
    assert CompressionDecision.from_config(cfg) == d
    assert len({d, d}) == 1  # hashable cache key


def test_per_dim_ratio_compressor_semantics():
    base = make_compressor("topk", ratio=0.5)
    c = PerDimRatio(base=base, table=((8, 0.25),))
    x = jnp.arange(8.0)
    # dim 8 -> ratio 0.25 -> k=2 survivors
    assert int(jnp.sum(c.sim(x, KEY) != 0)) == 2
    y = jnp.arange(16.0) + 1.0
    # dim 16 -> base ratio 0.5 -> k=8 survivors
    assert int(jnp.sum(c.sim(y, KEY) != 0)) == 8
    # records are 32-bit value + ceil(log2(d))-bit index: 35 bits at
    # d=8 (k=2), 36 bits at d=16 (k=8)
    assert c.payload_bits(8) == 2 * 35 and c.payload_bits(16) == 8 * 36


def test_shared_random_decision_ignores_ratio_overrides():
    """shared_random needs the bare RandomK (isinstance check in
    CompressionConfig): a decision carrying overrides must still
    materialize, and the ratio policies decline to emit overrides for
    it in the first place."""
    from repro.core import RandomK
    qw = make_compressor("randomk", ratio=0.1)
    d = CompressionDecision(qw=qw, strategy="shared_random",
                            ratio_overrides=((128, 0.5),))
    assert isinstance(d.to_config().qw, RandomK)  # no PerDimRatio wrap
    summary, mplan = _summary(qw)
    base = CompressionDecision(qw=qw, strategy="shared_random")
    assert VarianceBudgetPolicy(budget=0.01).decide(
        summary, base, mplan) == base
    assert BitBudgetPolicy(bits_per_step=1 << 20).decide(
        summary, base, mplan) == base


def test_noise_bounds_from_plan_measured():
    t = _tree()
    mplan = measurement_plan(t, stacked_mask(t))
    n = mplan.num_units
    tr, em = noise_bounds_from_plan(mplan, measured_w=[0.5] * n)
    assert tr == pytest.approx(1.5 * mplan.total)
    assert em == pytest.approx(1.5 * mplan.total)
    with pytest.raises(ValueError):
        noise_bounds_from_plan(mplan, measured_w=[0.5] * (n + 1))
    with pytest.raises(ValueError):  # no closed form, no measurement
        noise_bounds_from_plan(mplan, make_compressor("signsgd"))


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def _vb_bits(summary, mplan, base, budget):
    d = VarianceBudgetPolicy(budget=budget).decide(summary, base, mplan)
    return d.payload_bits(mplan.unit_dims)


def test_variance_budget_monotone_deterministic():
    qw = make_compressor("topk", ratio=0.1)
    base = CompressionDecision(qw=qw)
    summary, mplan = _summary(qw)
    prev = None
    for budget in (0.8, 0.4, 0.2, 0.1, 0.05, 0.01, 0.002):
        bits = _vb_bits(summary, mplan, base, budget)
        assert prev is None or bits >= prev, budget
        prev = bits


@settings(max_examples=25)
@given(st.floats(min_value=1e-4, max_value=1.0),
       st.floats(min_value=1e-4, max_value=1.0))
def test_property_variance_budget_monotone(b1, b2):
    """tighter budget => >= bits (any budget pair, either order)."""
    qw = make_compressor("topk", ratio=0.1)
    base = CompressionDecision(qw=qw)
    summary, mplan = _summary(qw)
    lo, hi = min(b1, b2), max(b1, b2)
    assert _vb_bits(summary, mplan, base, lo) >= \
        _vb_bits(summary, mplan, base, hi)


def test_bit_budget_policy_respects_budget():
    qw = make_compressor("topk", ratio=0.1)
    base = CompressionDecision(qw=qw)
    summary, mplan = _summary(qw)
    dims = mplan.unit_dims
    min_bits = BitBudgetPolicy(bits_per_step=0).decide(
        summary, base, mplan).payload_bits(dims)
    for budget in (min_bits, 4 * min_bits, 64 * min_bits):
        d = BitBudgetPolicy(bits_per_step=budget).decide(summary, base,
                                                         mplan)
        assert d.payload_bits(dims) <= budget
    # a looser budget never captures less
    loose = BitBudgetPolicy(bits_per_step=64 * min_bits).decide(
        summary, base, mplan)
    assert loose.payload_bits(dims) >= min_bits


def test_make_policy_factory():
    assert make_policy("static").name == "static"
    assert make_policy("variance_budget", budget=0.2).budget == 0.2
    with pytest.raises(ValueError):
        make_policy("nope")


# ---------------------------------------------------------------------------
# controller cache + granularity switching (simulated-worker harness)
# ---------------------------------------------------------------------------

def _sim_harness(tree, sm, mplan, collect=True):
    """build_step factory: a jitted Algorithm-1 aggregation step over
    fixed 2-worker gradients, threading telemetry."""
    def build(decision):
        cfg = decision.to_config()

        @jax.jit
        def step(wg, key, telem):
            if collect:
                out, _, inc = aggregate_simulated_workers(
                    wg, sm, cfg, key, telemetry_plan=mplan)
                return out, accumulate(telem, inc)
            out, _ = aggregate_simulated_workers(wg, sm, cfg, key)
            return out, telem
        return step
    return build


def _switch_tree(key=KEY):
    """Measured omegas favor entire-model: one leaf with its mass in a
    few spikes (global top-k captures it), one pure-noise leaf (per-layer
    top-k burns its budget on noise). Distinct sizes, so each leaf is its
    own size-class bucket (telemetry resolution is per size class)."""
    spiky = jnp.zeros((512,)).at[:8].set(100.0)
    noise = 0.1 * jax.random.normal(key, (448,))
    return {"spiky": spiky, "noise": noise}


def test_granularity_switch_policy_switches_and_reuses_cache():
    t = _switch_tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    base = CompressionDecision(qw=make_compressor("topk", ratio=0.1),
                               granularity=Granularity("layerwise"))
    ctrl = Controller(GranularitySwitchPolicy(margin=0.05),
                      _sim_harness(t, sm, mplan), base, mplan,
                      replan_every=2)
    wg = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), t)
    fns = []
    for i in range(6):
        fn = ctrl.step_fn()
        fns.append(fn)
        _, telem = fn(wg, jax.random.fold_in(KEY, i), ctrl.telemetry)
        ctrl.observe(telem, i)
    # the switch happened, to entire_model, at the first boundary
    assert ctrl.switches and ctrl.switches[0]["step"] == 1
    assert ctrl.decision.granularity.kind == "entire_model"
    # exactly two compiled steps ever built (layerwise + entire_model):
    # the post-switch steps reuse the cached compile, no retrace
    assert ctrl.builds == 2
    assert fns[2] is fns[3] is fns[4] is fns[5]
    # and the decision stays entire_model at later boundaries (its
    # measured trace really is smaller on this workload)
    s = ctrl.windows[-1]["summary"]
    em = s["entire_model"]
    lw_trace, _ = noise_bounds_from_plan(
        mplan, measured_w=unit_omegas(s, mplan))
    assert em["dim"] * (1.0 + em["rel_err"]) < lw_trace


def test_controller_same_decision_same_object_no_retrace():
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    base = CompressionDecision(qw=make_compressor("qsgd", levels=16))
    alt = CompressionDecision(qw=make_compressor("qsgd", levels=16),
                              granularity=Granularity("entire_model"))
    ctrl = Controller(StaticPolicy(), _sim_harness(t, sm, mplan, False),
                      base, mplan, replan_every=10,
                      collect_telemetry=False)
    f1 = ctrl.step_fn()
    assert ctrl.step_fn() is f1 and ctrl.builds == 1
    ctrl.set_decision(alt)
    f2 = ctrl.step_fn()
    assert f2 is not f1 and ctrl.builds == 2
    ctrl.set_decision(base)
    assert ctrl.step_fn() is f1 and ctrl.builds == 2  # cache hit, no build


def test_fusion_decision_revisit_hits_cache():
    """A decision that only changes `fusion_bytes` (the comm schedule's
    fusion threshold) is a distinct cache key the first time, but
    REVISITING a prior threshold must hit the compiled-step cache — no
    retrace. And every scheduled step stays bit-identical to the
    unscheduled base (scheduling never changes numerics)."""
    import dataclasses
    import math
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    base = CompressionDecision(qw=make_compressor("topk", ratio=0.25))
    a = dataclasses.replace(base, fusion_bytes=4096.0)
    b = dataclasses.replace(base, fusion_bytes=math.inf)
    assert len({base, a, b}) == 3           # hashable, distinct keys
    ctrl = Controller(StaticPolicy(), _sim_harness(t, sm, mplan, False),
                      base, mplan, collect_telemetry=False)
    wg = jax.tree_util.tree_map(lambda x: jnp.stack([x, 2.0 * x]), t)
    f_base = ctrl.step_fn()
    out_base, _ = f_base(wg, KEY, None)
    assert ctrl.builds == 1
    ctrl.set_decision(a)
    f_a = ctrl.step_fn()
    assert f_a is not f_base and ctrl.builds == 2
    ctrl.set_decision(b)
    f_b = ctrl.step_fn()
    assert f_b is not f_a and ctrl.builds == 3
    ctrl.set_decision(a)                     # revisit: cache hit
    assert ctrl.step_fn() is f_a and ctrl.builds == 3
    ctrl.set_decision(base)                  # and back to unscheduled
    assert ctrl.step_fn() is f_base and ctrl.builds == 3
    for fn in (f_a, f_b):
        out, _ = fn(wg, KEY, None)
        for la, lb in zip(jax.tree_util.tree_leaves(out_base),
                          jax.tree_util.tree_leaves(out)):
            assert bool((la == lb).all())


def test_fusion_policy_picks_threshold_from_model():
    """FusionPolicy prices the telemetry window's payload bits through
    the alpha-beta pipeline model: a latency-dominated link fuses
    everything into one message, a zero-latency link streams per bucket;
    non-layerwise decisions pass through untouched."""
    from repro.control import FusionPolicy
    from repro.core import build_schedule
    qw = make_compressor("topk", ratio=0.1)
    summary, mplan = _summary(qw)
    base = CompressionDecision(qw=qw)
    hi = FusionPolicy(alpha_us=1e5).decide(summary, base, mplan)
    assert hi.fusion_bytes is not None
    assert build_schedule(mplan, hi.fusion_bytes).num_messages == 1
    lo = FusionPolicy(alpha_us=0.0).decide(summary, base, mplan)
    assert lo.fusion_bytes == 0.0            # per-bucket streaming
    # pure: same window, same decision -> same result (and a revisit of
    # the emitted decision would be a cache hit, per the test above)
    assert FusionPolicy(alpha_us=1e5).decide(summary, base, mplan) == hi
    em = CompressionDecision(qw=qw, granularity=Granularity("entire_model"))
    assert FusionPolicy().decide(summary, em, mplan) == em
    assert make_policy("fusion", alpha_us=3.0).alpha_us == 3.0


# ---------------------------------------------------------------------------
# engine integration: the acceptance regression
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    eng = smoke_engine()
    return eng, eng.comp, smoke_batch()


def _run_steps(step_fn, eng, batch, n=2, telem=None):
    params, opt_state = eng.init_state(0)
    for i in range(n):
        if telem is not None:
            params, opt_state, m, telem = step_fn(params, opt_state, batch,
                                                  jnp.int32(i), telem)
        else:
            params, opt_state, m = step_fn(params, opt_state, batch,
                                           jnp.int32(i))
    return params, m


def test_static_controller_bit_identical_to_engine(engine_setup):
    """Acceptance: Controller + StaticPolicy == the plain Engine path,
    bit for bit."""
    from repro.control import engine_controller
    eng, comp, batch = engine_setup
    p_ref, m_ref = _run_steps(eng.build_train_step(), eng, batch)
    ctrl = engine_controller(eng, StaticPolicy())
    assert ctrl.decision == CompressionDecision.from_config(comp)
    p_ctl, m_ctl = _run_steps(ctrl.step_fn(), eng, batch)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_ctl)):
        assert bool((a == b).all())
    assert float(m_ref["loss"]) == float(m_ctl["loss"])


def test_new_decision_matches_fresh_engine(engine_setup):
    """A decision the controller compiles on the fly is numerically the
    Engine you would have built from scratch with that config."""
    from repro.control import engine_controller
    from repro.launch.engine import Engine
    eng, comp, batch = engine_setup
    alt = CompressionDecision(qw=make_compressor("topk", ratio=0.25),
                              granularity=Granularity("entire_model"))
    ctrl = engine_controller(eng, StaticPolicy(), collect_telemetry=False)
    ctrl.set_decision(alt)
    p_ctl, _ = _run_steps(ctrl.step_fn(), eng, batch)
    fresh = Engine(eng.cfg, eng.mesh, comp=alt.to_config())
    p_ref, _ = _run_steps(fresh.build_train_step(), fresh, batch)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_ctl)):
        assert jnp.allclose(a, b, atol=1e-6)


def test_engine_telemetry_step_threads_state(engine_setup):
    """The telemetry leg measures without disturbing training: finite
    stats, steps counted, loss still finite."""
    from repro.control import engine_controller
    eng, comp, batch = engine_setup
    ctrl = engine_controller(eng, GranularitySwitchPolicy(),
                             replan_every=2)
    params, opt_state = eng.init_state(0)
    for i in range(2):
        fn = ctrl.step_fn()
        params, opt_state, m, telem = fn(params, opt_state, batch,
                                         jnp.int32(i), ctrl.telemetry)
        ctrl.observe(telem, i)
    assert jnp.isfinite(jnp.asarray(float(m["loss"])))
    assert len(ctrl.windows) == 1
    s = ctrl.windows[0]["summary"]
    assert s["steps"] == 2.0
    assert all(jnp.isfinite(jnp.asarray(b["omega_hat"]))
               for b in s["buckets"])
    json.dumps(ctrl.report())  # --telemetry-out payload is serializable


# ---------------------------------------------------------------------------
# AdaptiveKPolicy (Shi et al. 1911.08727: layer-wise adaptive-k)
# ---------------------------------------------------------------------------

def _energy_split_tree(key=KEY):
    """Two size-class buckets with a lopsided energy split: the (512,)
    leaf carries ~1e4x the gradient norm of the (448,) leaf."""
    hot = 10.0 * jax.random.normal(key, (512,))
    cold = 0.01 * jax.random.normal(jax.random.fold_in(key, 1), (448,))
    return {"hot": hot, "cold": cold}


def test_adaptive_k_policy_allocates_ratio_by_energy():
    from repro.control import AdaptiveKPolicy
    qw = make_compressor("topk", ratio=0.05)
    summary, mplan = _summary(qw, tree=_energy_split_tree())
    base = CompressionDecision(qw=qw)
    d = AdaptiveKPolicy(avg_ratio=0.05).decide(summary, base, mplan)
    ratios = dict(d.ratio_overrides)
    assert set(ratios) == {512, 448}
    assert ratios[512] > ratios[448]  # energy buys ratio
    # pure: same summary in, equal (hashable) decision out
    d2 = AdaptiveKPolicy(avg_ratio=0.05).decide(summary, base, mplan)
    assert d == d2 and hash(d) == hash(d2)
    # guards: no telemetry / ratio-less operator / shared_random
    assert AdaptiveKPolicy().decide({}, base, mplan) is base
    sign = CompressionDecision(qw=make_compressor("signsgd"))
    assert AdaptiveKPolicy().decide(summary, sign, mplan) is sign
    shared = CompressionDecision(qw=make_compressor("randomk", ratio=0.1),
                                 strategy="shared_random")
    assert AdaptiveKPolicy().decide(summary, shared, mplan) is shared


def test_adaptive_k_zero_energy_falls_back_to_flat_ratio():
    from repro.control import AdaptiveKPolicy
    qw = make_compressor("topk", ratio=0.05)
    summary, mplan = _summary(qw, tree=_energy_split_tree())
    dead = dict(summary)
    dead["buckets"] = [dict(b, grad_norm_sq=0.0) for b in summary["buckets"]]
    d = AdaptiveKPolicy(avg_ratio=0.05).decide(
        dead, CompressionDecision(qw=qw), mplan)
    assert all(r == 0.05 for _, r in d.ratio_overrides)


def test_adaptive_k_decision_revisit_hits_cache():
    """Revisiting an adaptive-k allocation (same summary => the SAME
    frozen decision) must hit the controller's compiled-step cache — the
    cache-no-retrace contract for the new policy."""
    from repro.control import AdaptiveKPolicy
    t = _energy_split_tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    qw = make_compressor("topk", ratio=0.05)
    summary, _ = _summary(qw, tree=t)
    base = CompressionDecision(qw=qw)
    policy = AdaptiveKPolicy(avg_ratio=0.05)
    d1 = policy.decide(summary, base, mplan)
    assert d1 != base
    ctrl = Controller(StaticPolicy(), _sim_harness(t, sm, mplan, False),
                      base, mplan, collect_telemetry=False)
    f_base = ctrl.step_fn()
    assert ctrl.builds == 1
    ctrl.set_decision(d1)
    f_d1 = ctrl.step_fn()
    assert f_d1 is not f_base and ctrl.builds == 2
    ctrl.set_decision(base)
    assert ctrl.step_fn() is f_base and ctrl.builds == 2
    ctrl.set_decision(policy.decide(summary, base, mplan))  # re-decided
    assert ctrl.step_fn() is f_d1 and ctrl.builds == 2      # no retrace


def test_adaptive_k_factory():
    p = make_policy("adaptive_k", avg_ratio=0.1)
    assert p.name == "adaptive_k" and p.avg_ratio == 0.1
    assert p.needs_telemetry and not p.needs_entire_model
