"""Observability: TraceRecorder span structure, the zero-overhead
contract, the metrics registry round-trip, the retrace watchdog, and the
measured-vs-modeled calibration machinery.

The load-bearing properties:

  * span STRUCTURE mirrors the executed schedule — per step, the
    per-message span count equals `schedule.num_messages` and the bucket
    attribution concatenates to `plan.readiness_order()` (per-bucket
    threshold), on the simulated path AND the wire path;
  * recording disabled is FREE — the traced graph is bit-identical to
    the uninstrumented one (jaxpr equality, zero debug_callback
    equations) and enabled recording never changes numerics;
  * exports validate: chrome-trace JSON against the schema subset,
    metrics JSON-lines round-trip equal to the in-memory snapshot;
  * the controller's retrace watchdog warns (and counts) exactly when a
    previously-built decision rebuilds, and stays silent on healthy
    cache revisits.

The full sweep (compressors x fusion thresholds x both execution paths)
and the engine-level trace carry the `obs` marker: tier-1 only, excluded
from `make verify-fast`.
"""
import json
import math
import warnings

import jax
import jax.numpy as jnp
import pytest

from _sweep_helpers import (KEY, assert_trees_bitwise, grad_tree, smoke_batch,
                            smoke_engine)
from repro.core import (Granularity, build_plan, build_schedule,
                        make_compressor, stacked_mask, wire_codec)
from repro.obs import (METRICS_SCHEMA_VERSION, TRACE_SCHEMA_VERSION,
                       MetricsRegistry, TraceRecorder, calibrate,
                       count_debug_callbacks, fit_alpha_beta,
                       format_step_summary, measure_schedule, read_jsonl,
                       validate_chrome_trace)


def _run_recorded(sched, fn, tree, rec, *, wire=None):
    if wire is not None:
        jit = jax.jit(lambda t, k: sched.execute(None, t, k, wire=wire,
                                                 recorder=rec))
        out, bufs = jit(tree, KEY)
        jax.block_until_ready(bufs)
    else:
        jit = jax.jit(lambda t, k: sched.execute(fn, t, k, recorder=rec))
        out = jit(tree, KEY)
    jax.block_until_ready(out)
    return out


# ---------------------------------------------------------------------------
# span structure == schedule message layout
# ---------------------------------------------------------------------------

def test_message_spans_match_schedule():
    """Per-bucket threshold, simulated path: one message span per
    schedule message, bucket attribution == plan.readiness_order()."""
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("qsgd", levels=16)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)
    rec = TraceRecorder()
    _run_recorded(sched, lambda x, k: comp.sim(x, k), tree, rec)
    summary = rec.finalize_step(0)
    spans = rec.message_spans(step=0)
    assert len(spans) == sched.num_messages == summary["n_message_spans"]
    ordered = sorted(spans, key=lambda e: e["args"]["message"])
    concat = [b for e in ordered for b in e["args"]["bucket_ids"]]
    assert tuple(concat) == plan.readiness_order()
    for e, msg in zip(ordered, sched.messages):
        assert tuple(e["args"]["bucket_ids"]) == msg.bucket_ids
        assert e["args"]["n_units"] == sum(plan.buckets[bi].n
                                           for bi in msg.bucket_ids)
        assert e["args"]["step"] == 0
        assert e["args"]["schema_version"] == TRACE_SCHEMA_VERSION


def test_plan_dispatch_spans():
    """Bare UnitPlan execution records one dispatch span per bucket."""
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("signsgd")
    plan = build_plan(tree, sm, Granularity("layerwise"))
    rec = TraceRecorder()
    jit = jax.jit(lambda t, k: plan.execute(
        lambda x, kk: comp.sim(x, kk), t, k, recorder=rec))
    jax.block_until_ready(jit(tree, KEY))
    rec.finalize_step(0)
    spans = rec.span_events(cat="dispatch", step=0)
    assert len(spans) == plan.num_dispatches
    assert sorted(b for e in spans for b in e["args"]["bucket_ids"]) == \
        list(range(plan.num_dispatches))


def test_wire_stage_spans_and_synthesized_messages():
    """Wire path: per-stage spans carry codec attribution and finalize
    synthesizes exactly num_messages umbrella message spans."""
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("qsgd", levels=16)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, float(1 << 10))
    codec = wire_codec(comp)
    rec = TraceRecorder()
    _run_recorded(sched, None, tree, rec, wire=codec)
    summary = rec.finalize_step(0)
    msgs = rec.message_spans(step=0)
    assert len(msgs) == sched.num_messages == summary["n_message_spans"]
    stages = rec.span_events(cat="stage", step=0)
    per_msg_stages = {}
    for e in stages:
        assert e["args"]["codec"] == codec.name
        per_msg_stages.setdefault(e["args"]["message"], set()).add(
            e["args"]["stage"])
    assert set(per_msg_stages) == set(range(sched.num_messages))
    for mi, st in per_msg_stages.items():
        assert {"compress", "pack", "decode"} <= st, (mi, st)
    # the synthesized umbrellas cover their stage spans
    for e in msgs:
        assert e["args"]["stages"] == sorted(
            per_msg_stages[e["args"]["message"]])


def test_multi_step_and_summary_format():
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("randomk", ratio=0.5)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, math.inf)
    rec = TraceRecorder()
    fn = lambda x, k: comp.sim(x, k)  # noqa: E731
    for i in range(3):
        _run_recorded(sched, fn, tree, rec)
        s = rec.finalize_step(i)
        assert s["step"] == i and s["n_message_spans"] == 1
        assert "message spans" in format_step_summary(s)
    assert [s["step"] for s in rec.steps] == [0, 1, 2]
    assert len(rec.message_spans()) == 3
    assert len(rec.message_spans(step=1)) == 1


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def test_chrome_trace_valid_and_exportable(tmp_path):
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("qsgd", levels=16)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)
    rec = TraceRecorder()
    _run_recorded(sched, lambda x, k: comp.sim(x, k), tree, rec)
    rec.finalize_step(0)
    with rec.host_span("compile", note="host side"):
        pass
    obj = rec.chrome_trace()
    assert validate_chrome_trace(obj)
    assert obj["metadata"]["schema_version"] == TRACE_SCHEMA_VERSION
    assert obj["metadata"]["steps"] == rec.steps
    path = tmp_path / "trace.json"
    rec.export(str(path))
    assert validate_chrome_trace(json.loads(path.read_text()))
    # the validator actually rejects malformed traces
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "Z", "name": "x",
                                                "pid": 0, "tid": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x",
                                                "pid": 0, "tid": 0,
                                                "ts": -1.0, "dur": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace([])


def test_metrics_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("train/steps")
    reg.inc("train/steps", 2)
    reg.gauge("engine/n_messages", 7)
    for v in (1.0, 5.0, 3.0, 9.0, 7.0):
        reg.observe("serve/decode_us", v)
    line = reg.record(step=0)
    assert line["schema_version"] == METRICS_SCHEMA_VERSION
    assert line["counters"]["train/steps"] == 3.0
    assert line["gauges"]["engine/n_messages"] == 7.0
    h = line["histograms"]["serve/decode_us"]
    assert h["count"] == 5 and h["min"] == 1.0 and h["max"] == 9.0
    assert h["p50"] == 5.0 and h["sum"] == 25.0
    path = tmp_path / "metrics.jsonl"
    assert reg.export_jsonl(str(path)) == 1
    parsed = read_jsonl(str(path))
    assert parsed == [line] == [reg.snapshot(step=0)]
    # a registry with no recorded lines exports one final snapshot
    reg2 = MetricsRegistry()
    reg2.inc("a")
    assert reg2.export_jsonl(str(path)) == 1
    assert read_jsonl(str(path))[0]["labels"] == {"final": True}


def test_disabled_metrics_noop(tmp_path):
    reg = MetricsRegistry(enabled=False)
    reg.inc("a")
    reg.gauge("b", 1.0)
    reg.observe("c", 2.0)
    reg.record(step=0)
    assert reg.counters == {} and reg.gauges == {} and reg.histograms == {}
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["kind"] == "snapshot"


# ---------------------------------------------------------------------------
# the zero-overhead contract
# ---------------------------------------------------------------------------

def test_zero_overhead_when_disabled():
    """recorder=None, recorder=disabled, and no recorder at all stage
    IDENTICAL jaxprs with zero debug_callback equations; enabling the
    recorder adds callbacks but never changes numerics."""
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("qsgd", levels=16)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, float(1 << 10))
    fn = lambda x, k: comp.sim(x, k)  # noqa: E731
    off = TraceRecorder(enabled=False)

    bare = lambda t, k: sched.execute(fn, t, k)                 # noqa: E731
    none = lambda t, k: sched.execute(fn, t, k, recorder=None)  # noqa: E731
    dis = lambda t, k: sched.execute(fn, t, k, recorder=off)    # noqa: E731
    jx_bare = str(jax.make_jaxpr(bare)(tree, KEY))
    assert jx_bare == str(jax.make_jaxpr(none)(tree, KEY))
    assert jx_bare == str(jax.make_jaxpr(dis)(tree, KEY))
    assert count_debug_callbacks(bare, tree, KEY) == 0
    assert count_debug_callbacks(dis, tree, KEY) == 0

    rec = TraceRecorder()
    on = lambda t, k: sched.execute(fn, t, k, recorder=rec)  # noqa: E731
    # begin + one mark per message
    assert count_debug_callbacks(on, tree, KEY) == 1 + sched.num_messages
    ref = jax.jit(bare)(tree, KEY)
    got = jax.jit(on)(tree, KEY)
    jax.block_until_ready(got)
    rec.finalize_step(0)
    assert_trees_bitwise(ref, got, "recorded-vs-bare")


def test_zero_overhead_wire_path():
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("signsgd")
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)
    codec = wire_codec(comp)
    off = TraceRecorder(enabled=False)
    bare = lambda t, k: sched.execute(None, t, k, wire=codec)  # noqa: E731
    dis = lambda t, k: sched.execute(None, t, k, wire=codec,   # noqa: E731
                                     recorder=off)
    assert str(jax.make_jaxpr(bare)(tree, KEY)) == \
        str(jax.make_jaxpr(dis)(tree, KEY))
    assert count_debug_callbacks(dis, tree, KEY) == 0
    ref, refb = jax.jit(bare)(tree, KEY)
    rec = TraceRecorder()
    on = jax.jit(lambda t, k: sched.execute(None, t, k, wire=codec,
                                            recorder=rec))
    got, gotb = on(tree, KEY)
    jax.block_until_ready(got)
    rec.finalize_step(0)
    assert_trees_bitwise(ref, got, "wire-recorded-vs-bare")
    assert_trees_bitwise(refb, gotb, "wire-buffers")


def test_audio_model_scopes_name_ops_only():
    """The audio model's encoder stack and cross-attention are named
    scopes: the names reach the lowered program's debug info alone (the
    program text without it is free of them) and stage no callback."""
    from repro.configs.registry import get_smoke
    from repro.data import frames_stub
    from repro.models import DistConfig, Model
    cfg = get_smoke("whisper-base")
    m = Model(cfg, DistConfig())
    params = m.init(KEY)
    batch = {"tokens": jnp.ones((2, 8), jnp.int32),
             "targets": jnp.ones((2, 8), jnp.int32),
             "frames": frames_stub(KEY, 2, cfg.frontend_seq, cfg.d_model)}

    def loss(p):
        return m.loss(p, batch, KEY)

    lowered = jax.jit(loss).lower(params)
    text = lowered.as_text(debug_info=True)
    assert "repro/encoder" in text and "repro/cross_attention" in text
    bare = lowered.as_text()
    assert "repro/encoder" not in bare
    assert "repro/cross_attention" not in bare
    assert count_debug_callbacks(loss, params) == 0


# ---------------------------------------------------------------------------
# retrace watchdog
# ---------------------------------------------------------------------------

def _tiny_controller(metrics=None):
    from repro.control import CompressionDecision, Controller, StaticPolicy
    tree = grad_tree()
    sm = stacked_mask(tree)
    mplan = build_plan(tree, sm, Granularity("layerwise"))
    base = CompressionDecision(qw=make_compressor("randomk", ratio=0.5),
                               granularity=Granularity("layerwise"))
    build = lambda decision: jax.jit(lambda x: x + 1)  # noqa: E731
    return Controller(StaticPolicy(), build, base, mplan,
                      collect_telemetry=False, metrics=metrics)


def test_retrace_watchdog_silent_on_healthy_revisits():
    from repro.control import CompressionDecision
    reg = MetricsRegistry()
    ctrl = _tiny_controller(metrics=reg)
    base = ctrl.decision
    alt = CompressionDecision(qw=make_compressor("randomk", ratio=0.5),
                              granularity=Granularity("entire_model"))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        f_base = ctrl.step_fn()
        ctrl.set_decision(alt)
        f_alt = ctrl.step_fn()
        # revisits of both decisions: cache hits, no warning, no build
        ctrl.set_decision(base)
        assert ctrl.step_fn() is f_base
        ctrl.set_decision(alt)
        assert ctrl.step_fn() is f_alt
    assert ctrl.builds == 2
    assert ctrl.retraces_unexpected == 0
    assert ctrl.check_retraces() == 0
    assert reg.counters["controller/builds"] == 2.0
    assert "controller/retraces_unexpected" not in reg.counters


def test_retrace_watchdog_fires_on_evicted_cache():
    reg = MetricsRegistry()
    ctrl = _tiny_controller(metrics=reg)
    ctrl.step_fn()
    assert ctrl.builds == 1
    ctrl._cache.clear()  # simulate eviction behind the controller's back
    with pytest.warns(RuntimeWarning, match="unexpected retrace"):
        ctrl.step_fn()
    assert ctrl.builds == 2
    assert ctrl.retraces_unexpected == 1
    assert ctrl.check_retraces() == 1
    assert reg.counters["controller/retraces_unexpected"] == 1.0
    assert reg.gauges["controller/retraces_unexpected_total"] == 1.0


def test_controller_report_self_describing():
    ctrl = _tiny_controller()
    rep = ctrl.report()
    from repro.control.telemetry import TELEMETRY_SCHEMA_VERSION
    assert rep["schema_version"] == TELEMETRY_SCHEMA_VERSION == 2
    act = rep["active"]
    assert act["policy"] == "static"
    assert act["compressor"] == "randomk"
    assert act["granularity"] == "layerwise"
    assert act["fusion_bytes"] is None
    assert act["ratio"] == 0.5
    assert isinstance(act["ratio_overrides"], dict)
    assert rep["retraces_unexpected"] == 0
    assert "jit_recompiles" in rep
    json.dumps(rep)


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def test_fit_alpha_beta():
    # exact synthetic line: t = 100 + b / (10 gbps * 1e3)
    beta = 1.0 / (10.0 * 1e3)
    samples = [(b, 100.0 + b * beta)
               for b in (1e3, 1e4, 1e5, 1e6)]
    fit = fit_alpha_beta(samples)
    assert fit["n_samples"] == 4
    assert abs(fit["alpha_us"] - 100.0) < 1.0
    assert abs(fit["gbps"] - 10.0) < 0.1
    assert fit["resid_rms_us"] < 1.0
    # degenerate inputs stay well-defined
    assert fit_alpha_beta([])["gbps"] is None
    flat = fit_alpha_beta([(1e3, 50.0), (1e6, 50.0)])
    assert flat["gbps"] is None and flat["alpha_us"] == 50.0


def test_calibration_smoke():
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor("qsgd", levels=16)
    meas = measure_schedule(tree, sm, comp, 0.0, reps=1, warmup=1)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, 0.0)
    assert meas["n_messages"] == sched.num_messages
    assert len(meas["per_message"]) == sched.num_messages
    assert meas["total_us"] > 0.0
    assert all(m["wire_bytes"] > 0 for m in meas["per_message"])

    cal = calibrate("tiny", tree, sm, comp, reps=1)
    assert cal["codec"] == "qsgd"
    assert set(cal["thresholds"]) == {"per_bucket", "fused_64kib",
                                      "one_shot"}
    for label, t in cal["thresholds"].items():
        for k in ("model_error_ratio_default", "model_error_ratio_fitted"):
            assert t[k] > 0.0 and math.isfinite(t[k]), (label, k, t[k])
        assert t["exposed_comm_us_measured"] > 0.0
    fit = next(iter(cal["fit_by_host"].values()))
    assert fit["n_samples"] == sum(
        t["n_messages"] for t in cal["thresholds"].values())
    json.dumps(cal)


# ---------------------------------------------------------------------------
# engine-level trace + the full sweep (`obs` marker: tier-1 only)
# ---------------------------------------------------------------------------

@pytest.mark.obs
def test_engine_train_step_trace_and_zero_overhead():
    """The sharded train step on a 1-device mesh: enabled tracing yields
    exactly schedule.num_messages message spans per step, static metrics
    gauges match the schedule, and a disabled tracer keeps the step
    bit-identical with zero staged callbacks."""
    from repro.launch.comm_sched import engine_schedule
    eng = smoke_engine()
    sched = engine_schedule(eng, 0.0)
    batch = smoke_batch()
    rec, reg = TraceRecorder(), MetricsRegistry()
    fn = eng.build_train_step(schedule=sched, tracer=rec, metrics=reg)
    params, opt_state = eng.init_state(0)
    for i in range(2):
        params, opt_state, m = fn(params, opt_state, batch, jnp.int32(i))
        jax.block_until_ready(m["loss"])
        s = rec.finalize_step(i)
        assert s["n_message_spans"] == sched.num_messages
        assert len(rec.message_spans(step=i)) == sched.num_messages
    assert reg.gauges["engine/n_messages"] == sched.num_messages
    assert reg.gauges["engine/n_dispatches"] == \
        eng.comm_plans(eng.comp)[0].num_dispatches
    assert validate_chrome_trace(rec.chrome_trace())

    # zero overhead: disabled tracer == no tracer, bit for bit
    fn_bare = eng.build_train_step(schedule=sched)
    fn_off = eng.build_train_step(
        schedule=sched, tracer=TraceRecorder(enabled=False))
    p0, o0 = eng.init_state(0)
    p_bare, _, m_bare = fn_bare(p0, o0, batch, jnp.int32(0))
    p1, o1 = eng.init_state(0)
    p_off, _, m_off = fn_off(p1, o1, batch, jnp.int32(0))
    assert_trees_bitwise(p_bare, p_off, "engine-disabled-tracer")
    assert float(m_bare["loss"]) == float(m_off["loss"])


@pytest.mark.obs
@pytest.mark.parametrize("cname,kw", [("qsgd", {"levels": 16}),
                                      ("terngrad", {}),
                                      ("signsgd", {})])
@pytest.mark.parametrize("fb", [0.0, 4096.0, math.inf])
def test_obs_sweep_span_counts(cname, kw, fb):
    """Full sweep: on both execution paths, per-step message-span count
    == schedule.num_messages and recording never changes numerics."""
    tree, sm = grad_tree(), stacked_mask(grad_tree())
    comp = make_compressor(cname, **kw)
    plan = build_plan(tree, sm, Granularity("layerwise"))
    sched = build_schedule(plan, fb)
    fn = lambda x, k: comp.sim(x, k)  # noqa: E731

    rec = TraceRecorder()
    got = _run_recorded(sched, fn, tree, rec)
    assert rec.finalize_step(0)["n_message_spans"] == sched.num_messages
    ref = jax.jit(lambda t, k: sched.execute(fn, t, k))(tree, KEY)
    assert_trees_bitwise(ref, got, (cname, fb, "sim"))

    codec = wire_codec(comp)
    recw = TraceRecorder()
    goww = _run_recorded(sched, None, tree, recw, wire=codec)
    assert recw.finalize_step(0)["n_message_spans"] == sched.num_messages
    refw, _ = jax.jit(
        lambda t, k: sched.execute(None, t, k, wire=codec))(tree, KEY)
    assert_trees_bitwise(refw, goww, (cname, fb, "wire"))
