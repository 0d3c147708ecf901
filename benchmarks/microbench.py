"""Compression-operator microbenchmarks: us per invocation on a 1M-element
gradient, per operator x granularity, plus the Pallas-kernel wrappers and
the per-leaf-vs-UnitPlan dispatch benchmark (BENCH_unitplan.json)."""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line
from repro.core import (Granularity, apply_unitwise, build_plan,
                        make_compressor, stacked_mask)
from repro.core.granularity import apply_unitwise_reference
from repro.kernels import ops

D = 1 << 20
KEY = jax.random.key(0)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _time(fn, *args, iters=5):
    fn(*args).block_until_ready() if hasattr(fn(*args), "block_until_ready") \
        else fn(*args)
    t0 = time.time()
    for _ in range(iters):
        r = fn(*args)
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, r)
    return (time.time() - t0) / iters * 1e6


def _block(r):
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, r)
    return r


def _time_median(fn, *args, reps=5, warmup=2):
    """us per call: `warmup` discarded calls, then the median of `reps`
    timed calls — the controller bench's noise discipline (single-shot
    numbers on a shared container are meaningless)."""
    for _ in range(warmup):
        _block(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.time()
        _block(fn(*args))
        ts.append(time.time() - t0)
    return sorted(ts)[len(ts) // 2] * 1e6


def operators():
    x = jax.random.normal(KEY, (D,))
    tree = {"blocks": {"w": x.reshape(64, -1, 128)}}
    sm = stacked_mask(tree)
    for name, kw in [("topk", {"ratio": 0.01}), ("randomk", {"ratio": 0.01}),
                     ("terngrad", {}), ("qsgd", {"levels": 16}),
                     ("signsgd", {}), ("natural", {}),
                     ("threshold_v", {"v": 0.5}),
                     ("adaptive_threshold", {})]:
        c = make_compressor(name, **kw)
        for gran in ("layerwise", "entire_model"):
            g = Granularity(gran)
            fn = jax.jit(lambda t, k: apply_unitwise(
                lambda v, kk: c.sim(v, kk), g, t, sm, k))
            us = _time(fn, tree, KEY)
            csv_line(f"op_{name}_{gran}", us, f"d={D}")


def kernels():
    x = jax.random.normal(KEY, (D,))
    for name, fn in [
        ("kernel_qsgd", lambda: ops.qsgd_compress(x, KEY, 16)),
        ("kernel_terngrad", lambda: ops.terngrad_compress(x, KEY)),
        ("kernel_topk_block", lambda: ops.blockwise_topk(x, 5)),
    ]:
        us = _time(lambda _: fn(), None, iters=3)
        csv_line(name, us, f"interpret={ops.interpret_mode()}")


# --------------------------------------------------------------------------
# per-leaf vs UnitPlan dispatch benchmark
# --------------------------------------------------------------------------

def _grad_trees():
    """(name, grads pytree, stacked mask) for the two reference configs."""
    from repro.configs.registry import get_smoke
    from repro.configs.resnet9_cifar import RESNET9
    from repro.models import DistConfig, Model
    from repro.models.cnn import init_cnn

    cnn = init_cnn(RESNET9, KEY)
    yield "resnet9", cnn, stacked_mask(cnn)

    m = Model(get_smoke("phi4-mini-3.8b"), DistConfig())
    params = m.init(jax.random.fold_in(KEY, 1))
    yield "phi4-mini", params, m.stacked()


def _traced_compressor_calls(apply, comp, gran, tree, sm) -> int:
    """How many times the compressor body is traced in ONE jit trace —
    the operator-launch count the paper's granularity discussion (and
    Agarwal et al.) care about."""
    count = 0

    def counting(x, k):
        nonlocal count
        count += 1
        return comp.sim(x, k)

    jax.make_jaxpr(lambda t: apply(counting, gran, t, sm, KEY))(tree)
    return count


def unitplan(out_path: str = None):
    """Units compressed per traced call + wall clock: legacy per-leaf loop
    vs the UnitPlan bucketed path, on the resnet9 and phi4-mini gradient
    pytrees (layerwise granularity — the ragged case). Emits
    BENCH_unitplan.json next to the repo root for CI tracking."""
    gran = Granularity("layerwise")
    comp = make_compressor("qsgd", levels=16)
    report = {}
    for name, tree, sm in _grad_trees():
        plan = build_plan(tree, sm, gran)
        legacy_calls = _traced_compressor_calls(
            apply_unitwise_reference, comp, gran, tree, sm)
        plan_calls = _traced_compressor_calls(
            apply_unitwise, comp, gran, tree, sm)

        fn = lambda x, k: comp.sim(x, k)  # noqa: E731
        legacy_jit = jax.jit(
            lambda t, k: apply_unitwise_reference(fn, gran, t, sm, k))
        plan_jit = jax.jit(
            lambda t, k: apply_unitwise(fn, gran, t, sm, k))
        legacy_us = _time(legacy_jit, tree, KEY, iters=20)
        plan_us = _time(plan_jit, tree, KEY, iters=20)

        report[name] = {
            "num_leaves": len(jax.tree_util.tree_leaves(tree)),
            "num_units": plan.num_units,
            "num_size_classes": plan.num_dispatches,
            "legacy_traced_calls": legacy_calls,
            "plan_traced_calls": plan_calls,
            "legacy_us": round(legacy_us, 1),
            "plan_us": round(plan_us, 1),
            "speedup": round(legacy_us / max(plan_us, 1e-9), 2),
        }
        csv_line(f"unitplan_{name}_legacy", legacy_us,
                 f"traced_calls={legacy_calls}")
        csv_line(f"unitplan_{name}_planned", plan_us,
                 f"traced_calls={plan_calls}")
        # the acceptance property: O(#size-classes) dispatches, not O(#leaves)
        assert plan_calls == plan.num_dispatches <= legacy_calls, report[name]

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_unitplan.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# comm-schedule benchmark: message fusion counts + modeled exposed comm
# --------------------------------------------------------------------------

def schedule(out_path: str = None):
    """BENCH_schedule.json: wire-message counts and the alpha-beta model's
    exposed-vs-overlapped comm picture for the resnet9 and phi4-mini
    gradient trees, per fusion threshold (0 = one message per size-class
    bucket, 1/4 MiB Horovod-style buffers, inf = one fused message),
    plus the wall clock of the scheduled vs unscheduled jitted execution.

    The stable signals are the COUNTS (messages vs dispatches vs units)
    and the deterministic model numbers; the `*_us` wall clocks are
    single-container noise — see CHANGES.md's benchmarking conventions.
    The `exposed_comm_us_measured` column is the TraceRecorder-measured
    wire-stream wall (obs.calibrate.measure_schedule — single-process, so
    nothing overlaps and "exposed" equals the stream total) and
    `model_error_ratio` divides it by the alpha-beta model's exposed
    prediction: the measured-vs-modeled discrepancy headline.
    The acceptance property asserted here: fusing strictly reduces the
    resnet9 message count below its per-bucket dispatch count."""
    from math import inf
    from repro.core import build_schedule, simulate_schedule
    from repro.obs import measure_schedule

    gran = Granularity("layerwise")
    comp = make_compressor("qsgd", levels=16)
    cfg_kw = dict(alpha_us=50.0, gbps=12.5, compress_gbps=25.0)
    thresholds = [("per_bucket", 0.0), ("fused_64kib", float(1 << 16)),
                  ("fused_1mib", float(1 << 20)), ("one_shot", inf)]
    report = {}
    for name, tree, sm in _grad_trees():
        plan = build_plan(tree, sm, gran)
        entry = {"num_leaves": len(jax.tree_util.tree_leaves(tree)),
                 "num_units": plan.num_units,
                 "num_dispatches": plan.num_dispatches}
        fn = lambda x, k: comp.sim(x, k)  # noqa: E731
        plan_jit = jax.jit(lambda t, k: plan.execute(fn, t, k))
        entry["plan_us"] = round(_time_median(plan_jit, tree, KEY), 1)
        for label, fb in thresholds:
            sched = build_schedule(plan, fb)
            sim = simulate_schedule(sched, qw=comp, **cfg_kw)
            sched_jit = jax.jit(lambda t, k: sched.execute(fn, t, k))
            us = _time_median(sched_jit, tree, KEY)
            meas = measure_schedule(tree, sm, comp, fb, reps=3, warmup=1)
            entry[label] = {
                "n_messages": sched.num_messages,
                "exposed_comm_us_model": sim["exposed_comm_us"],
                "exposed_comm_us_measured": meas["total_us"],
                "model_error_ratio": round(
                    meas["total_us"] / max(sim["exposed_comm_us"], 1e-9),
                    3),
                "comm_us_total_model": sim["comm_us_total"],
                "overlap_frac_model": sim["overlap_frac"],
                "wire_bits": sim["wire_bits_total"],
                "sched_us": round(us, 1),
            }
            assert meas["n_messages"] == sched.num_messages, (name, label)
            csv_line(f"schedule_{name}_{label}", us,
                     f"messages={sched.num_messages} "
                     f"exposed_model={sim['exposed_comm_us']}us "
                     f"measured={meas['total_us']}us")
        # acceptance: fusion strictly reduces resnet9's message count
        # below the per-bucket dispatch count
        if name == "resnet9":
            assert (entry["fused_64kib"]["n_messages"]
                    < entry["num_dispatches"]), entry
            assert (entry["fused_1mib"]["n_messages"]
                    < entry["num_dispatches"]), entry
        assert entry["per_bucket"]["n_messages"] == entry["num_dispatches"]
        assert entry["one_shot"]["n_messages"] == 1
        report[name] = entry
    path = out_path or os.path.join(_REPO_ROOT, "BENCH_schedule.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# wire benchmark: accounted vs MEASURED bits per config x codec x fusion
# --------------------------------------------------------------------------

def wire(out_path: str = None):
    """BENCH_wire.json: the accounted-vs-measured wire study — for the
    resnet9 and phi4-mini gradient trees x six codecs x fusion
    thresholds: analytic payload bits (bits.comm_report's accounting),
    MEASURED packed-payload bits (8 x the real codec bytes — what
    schedule wire execution materializes; the differential suite proves
    the equality), the per-codec word-padding slack separating them, and
    the fused-message buffer/header bytes. All numbers are static counts
    — deterministic and immune to the container's wall-clock noise — plus
    one timed row for the 1M-element qsgd pack hot path (pallas vs jnp;
    noisy, trust the counts)."""
    from math import inf
    from repro.core import (build_schedule, make_compressor,
                            message_layouts, wire_codec)

    gran = Granularity("layerwise")
    thresholds = [("per_bucket", 0.0), ("fused_64kib", float(1 << 16)),
                  ("one_shot", inf)]
    codecs = [("topk", {"ratio": 0.01}), ("randomk", {"ratio": 0.01}),
              ("qsgd", {"levels": 16}), ("terngrad", {}), ("signsgd", {}),
              ("natural", {})]
    report = {}
    for name, tree, sm in _grad_trees():
        plan = build_plan(tree, sm, gran)
        entry = {"num_units": plan.num_units,
                 "num_dispatches": plan.num_dispatches,
                 "dense_bits": 32 * plan.total}
        for cname, kw in codecs:
            c = make_compressor(cname, **kw)
            codec = wire_codec(c)
            acct = sum(c.payload_bits(d) for d in plan.unit_dims)
            meas = sum(codec.wire_bits(d) for d in plan.unit_dims)
            centry = {"accounted_bits": acct, "measured_bits": meas,
                      "padding_bits": meas - acct,
                      "compression_x": round(32 * plan.total / meas, 1)}
            for label, fb in thresholds:
                sched = build_schedule(plan, fb)
                lays = message_layouts(sched, codec)
                payload = 8 * sum(l.payload_nbytes for l in lays)
                # the acceptance property: the fused buffers carry
                # exactly the measured payload, never more
                assert payload == meas, (name, cname, label)
                centry[label] = {
                    "n_messages": sched.num_messages,
                    "buffer_bytes": sum(l.total_nbytes for l in lays),
                    "header_bytes": sum(l.header_nbytes for l in lays),
                }
            entry[cname] = centry
            csv_line(f"wire_{name}_{cname}", 0.0,
                     f"accounted={acct} measured={meas} "
                     f"padding={meas - acct}")
        report[name] = entry

    # the pack hot path, timed (entire-model single unit: no vmap, so
    # the pallas kernel path is exercised end to end). Wall-clocks on
    # this interpret-mode container measure Python, so the row records
    # the interpret flag and the DETERMINISTIC bytes-moved numbers from
    # the kernel specs — the gated signal (see kernels_bench).
    x = jax.random.normal(KEY, (D,))
    c = make_compressor("qsgd", levels=16)
    width = c.entry_bits
    enc_entry = {"interpret": ops.interpret_mode()}
    for label, fused in (("fused_pallas", True), ("legacy", False)):
        codec = wire_codec(c, fused=fused)
        enc = jax.jit(lambda v, k: codec.encode_batch(v[None], k[None])[0])
        us = _time_median(enc, x, KEY, reps=3, warmup=1)
        enc_entry[label] = round(us, 1)
        csv_line(f"wire_encode_1m_qsgd_{label}", us,
                 f"payload_bytes={codec.nbytes(D)}")
    for label, fused in (("fused", True), ("legacy", False)):
        spec = ops.pack_bytes_moved(width, fused=fused)
        enc_entry[f"{label}_bytes_moved_per_elt"] = round(
            spec["read_bytes_per_elt"] + spec["write_bytes_per_elt"]
            + spec["intermediate_bytes_per_elt"], 4)
        enc_entry[f"{label}_launches"] = spec["launches_per_bucket"]
    # the gate lives on bytes-moved, not the noisy wall clocks
    fspec = ops.pack_bytes_moved(width, fused=True)
    assert fspec["read_bytes_per_elt"] <= 4.0 + 12 / 512, fspec
    assert fspec["write_bytes_per_elt"] == width / 8.0, fspec
    assert fspec["intermediate_bytes_per_elt"] == 0.0, fspec
    report["encode_1m_qsgd_us"] = enc_entry

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_wire.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# fused-kernel benchmark: bytes moved + dispatch counts, jnp vs fused
# --------------------------------------------------------------------------

def kernels_bench(out_path: str = None):
    """BENCH_kernels.json: per-codec encode/decode memory traffic of the
    fused single-launch compress+pack kernels vs the legacy three-pass
    pipeline, from the kernel specs (ops.pack_bytes_moved /
    ops.unpack_bytes_moved), plus MEASURED pallas dispatch counts
    (ops.count_pallas_calls on a ragged (5, 1300) bucket — d not a
    multiple of 512, so the word-padding path is exercised too).

    All numbers are deterministic; this bench never reads a wall clock.
    The acceptance gates asserted here are the ISSUE's: fused
    qsgd/terngrad/signsgd encode moves <= 1 f32 read (+ the 12-byte
    per-row key/stat columns) + 1 packed-word write per element with
    zero intermediates in ONE launch, and majority-vote runs on packed
    words without ever materializing the {0,1} bit tensor."""
    n, d = 5, 1300
    x2d = jax.random.normal(KEY, (n, d))
    keys = jax.random.key_data(jax.random.split(KEY, n)).astype(jnp.uint32)
    e2d = jax.random.normal(jax.random.fold_in(KEY, 7), (n, d))
    qw = make_compressor("qsgd", levels=16).entry_bits

    codecs = {
        "qsgd": dict(
            width=qw, stochastic=True,
            pack=lambda: ops.count_pallas_calls(
                lambda x, k: ops.qsgd_pack_units(x, k, 16, qw)[0],
                x2d, keys),
            words=lambda: ops.qsgd_pack_units(x2d, keys, 16, qw),
            unpack=lambda w, s: ops.count_pallas_calls(
                lambda a, b: ops.qsgd_unpack_units(a, b, d, 16, qw), w, s),
            unpack_ef=lambda w, s: ops.count_pallas_calls(
                lambda a, b, e: ops.qsgd_unpack_ef_units(
                    a, b, e, d, 16, qw), w, s, e2d)),
        "terngrad": dict(
            width=2, stochastic=True,
            pack=lambda: ops.count_pallas_calls(
                lambda x, k: ops.terngrad_pack_units(x, k)[0], x2d, keys),
            words=lambda: ops.terngrad_pack_units(x2d, keys),
            unpack=lambda w, s: ops.count_pallas_calls(
                lambda a, b: ops.terngrad_unpack_units(a, b, d), w, s),
            unpack_ef=lambda w, s: ops.count_pallas_calls(
                lambda a, b, e: ops.terngrad_unpack_ef_units(a, b, e, d),
                w, s, e2d)),
        "signsgd": dict(
            width=1, stochastic=False,
            pack=lambda: ops.count_pallas_calls(
                lambda x: ops.sign_pack_units(x), x2d),
            words=lambda: (ops.sign_pack_units(x2d), None),
            unpack=lambda w, s: ops.count_pallas_calls(
                lambda a: ops.sign_unpack_units(a, d), w),
            unpack_ef=lambda w, s: ops.count_pallas_calls(
                lambda a, e: ops.sign_unpack_ef_units(a, e, d), w, e2d)),
    }

    report = {"interpret": ops.interpret_mode(),
              "bucket": {"n_units": n, "d": d}}
    for cname, spec in codecs.items():
        width = spec["width"]
        entry = {"width_bits": width}
        for label, fused in (("fused", True), ("legacy", False)):
            entry[f"encode_{label}"] = ops.pack_bytes_moved(
                width, fused=fused, stochastic=spec["stochastic"])
            entry[f"decode_{label}"] = ops.unpack_bytes_moved(
                width, fused=fused)
            entry[f"decode_ef_{label}"] = ops.unpack_bytes_moved(
                width, fused=fused, ef=True)
        words, stat = spec["words"]()
        entry["measured_dispatches"] = {
            "encode": spec["pack"](),
            "decode": spec["unpack"](words, stat),
            "decode_ef": spec["unpack_ef"](words, stat),
        }
        # the ISSUE's acceptance gate, per codec: fused encode <= 1 f32
        # read + key/stat columns, exactly 1 packed-word write, zero
        # intermediates, one launch on every fused op
        fe = entry["encode_fused"]
        assert fe["read_bytes_per_elt"] <= 4.0 + 12 / 512, (cname, fe)
        assert fe["write_bytes_per_elt"] == width / 8.0, (cname, fe)
        assert fe["intermediate_bytes_per_elt"] == 0.0, (cname, fe)
        assert fe["launches_per_bucket"] == 1, (cname, fe)
        assert entry["decode_fused"]["intermediate_bytes_per_elt"] == 0.0
        for op, cnt in entry["measured_dispatches"].items():
            assert cnt == 1, (cname, op, cnt)
        csv_line(f"kernels_{cname}_encode_fused", 0.0,
                 f"bytes/elt={fe['read_bytes_per_elt'] + fe['write_bytes_per_elt']:.4f} "
                 f"launches={fe['launches_per_bucket']}")
        report[cname] = entry

    # majority vote on packed words: one launch over the (workers, W)
    # word matrix, word-wide bit-plane counters — the bit tensor that a
    # pack(maj(unpack)) pipeline would materialize (32x the words) never
    # exists on either path.
    workers = 8
    g = jax.random.normal(jax.random.fold_in(KEY, 9), (workers, d))
    wmat = ops.sign_pack_units(g)
    maj_calls = ops.count_pallas_calls(
        lambda w: ops.majority_words(w, use_pallas=True), wmat)
    report["majority_vote"] = {
        "n_workers": workers,
        "launches": maj_calls,
        "read_bytes_per_word": 4 * workers,
        "write_bytes_per_word": 4,
        "unpacked_bit_tensor_bytes": 0,
    }
    assert maj_calls == 1, maj_calls

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# adaptive-controller benchmark: telemetry overhead + replan/retrace cost
# --------------------------------------------------------------------------

def controller(out_path: str = None, steps: int = 20):
    """BENCH_controller.json: (1) per-step cost of the in-step telemetry
    leg (median-of-5, warmup discarded), (2) the cost of a policy switch
    — cold build+compile of a new decision's step vs re-fetching a cached
    one, (3) steps/s of a full training loop under StaticPolicy vs
    VarianceBudgetPolicy (re-plan every 5)."""
    from benchmarks.common import (MODELS, cnn_controller,
                                   train_cnn_with_controller)
    from repro.control import (CompressionDecision, StaticPolicy,
                               VarianceBudgetPolicy)
    from repro.data import classification_batch
    from repro.models.cnn import init_cnn

    model, workers, batch = "resnet9", 4, 32
    base = CompressionDecision(qw=make_compressor("topk", ratio=0.05),
                               granularity=Granularity("layerwise"))
    alt = CompressionDecision(qw=make_compressor("topk", ratio=0.05),
                              granularity=Granularity("entire_model"))
    cfg = MODELS[model]
    params = init_cnn(cfg, KEY)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    b = classification_batch(KEY, batch)
    lr = jnp.float32(0.01)
    report = {}

    # (1) telemetry overhead: the same decision's step with/without the
    # telemetry leg.
    off = cnn_controller(model, StaticPolicy(), base=base, workers=workers,
                         collect_telemetry=False)
    on = cnn_controller(model, StaticPolicy(), base=base, workers=workers,
                        collect_telemetry=True)
    f_off, f_on = off.step_fn(), on.step_fn()
    us_off = _time_median(f_off, params, vel, b, KEY, lr, off.telemetry)
    us_on = _time_median(f_on, params, vel, b, KEY, lr, on.telemetry)
    report["telemetry"] = {
        "step_us_off": round(us_off, 1),
        "step_us_on": round(us_on, 1),
        "overhead_pct": round(100.0 * (us_on - us_off) / max(us_off, 1e-9),
                              1),
    }
    csv_line("controller_step_no_telemetry", us_off, "resnet9 median-of-5")
    csv_line("controller_step_telemetry", us_on, "resnet9 median-of-5")

    # (2) replan cost: switching to a NEW decision pays one build+compile;
    # switching BACK to a cached decision pays a dict lookup + dispatch.
    t0 = time.time()
    off.set_decision(alt)
    _block(off.step_fn()(params, vel, b, KEY, lr, None))
    cold_ms = (time.time() - t0) * 1e3
    builds_after_cold = off.builds
    t0 = time.time()
    off.set_decision(base)
    _block(off.step_fn()(params, vel, b, KEY, lr, None))
    cached_ms = (time.time() - t0) * 1e3
    assert off.builds == builds_after_cold == 2, off.builds  # no retrace
    report["replan"] = {"cold_build_ms": round(cold_ms, 1),
                        "cached_switch_ms": round(cached_ms, 1)}
    csv_line("controller_replan_cold", cold_ms * 1e3, "new decision")
    csv_line("controller_replan_cached", cached_ms * 1e3, "cached decision")

    # (3) steps/s: static vs adaptive policy end to end.
    for name, policy in [("static", StaticPolicy()),
                         ("variance_budget",
                          VarianceBudgetPolicy(budget=0.3))]:
        ctrl = cnn_controller(model, policy, base=base, workers=workers,
                              replan_every=5)
        t0 = time.time()
        train_cnn_with_controller(model, ctrl, steps=steps, batch=batch)
        dt = time.time() - t0
        report.setdefault("policies", {})[name] = {
            "steps_per_s": round(steps / dt, 2),
            "builds": ctrl.builds,
            "switches": len(ctrl.switches),
        }
        csv_line(f"controller_policy_{name}", dt / steps * 1e6,
                 f"builds={ctrl.builds}")

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_controller.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


# --------------------------------------------------------------------------
# observability benchmark: measured vs modeled comm + fitted alpha/beta
# --------------------------------------------------------------------------

def obs_bench(out_path: str = None):
    """BENCH_obs.json: the measured-vs-modeled comm calibration study
    (obs.calibrate) for the resnet9 and phi4-mini gradient trees x
    fusion thresholds {0, 64 KiB, inf}. Per threshold: TraceRecorder-
    measured exposed comm of the REAL wire stream (encode -> packed
    uint8 buffers -> decode) next to the alpha-beta model under the
    default parameters AND under the per-host least-squares fit, with
    both model-error ratios.

    Honesty caveat (recorded into the report): this is a single-process
    serialized stream — no network, nothing overlaps, so measured
    "exposed" comm equals the stream total, and the fitted alpha/beta
    describe THIS host, not an interconnect. Wall-clocks on a shared
    container are noisy; the stable signals are the counts, the byte
    totals, and the RELATIVE shape of the ratios across thresholds."""
    from repro.obs import calibrate

    comp = make_compressor("qsgd", levels=16)
    report = {"caveat": "single-process serialized wire stream: no "
                        "network, zero overlap; measured exposed == "
                        "stream total. Counts and bytes are stable, "
                        "wall-clocks are container noise.",
              "configs": {}}
    for name, tree, sm in _grad_trees():
        cal = calibrate(name, tree, sm, comp)
        ts = cal["thresholds"]
        assert len(ts) == 3, sorted(ts)
        for label, t in ts.items():
            for k in ("model_error_ratio_default",
                      "model_error_ratio_fitted"):
                r = t[k]
                assert r > 0.0 and r == r and r != float("inf"), \
                    (name, label, k, r)
            csv_line(f"obs_{name}_{label}",
                     t["exposed_comm_us_measured"],
                     f"model={t['exposed_comm_us_model']}us "
                     f"ratio_default={t['model_error_ratio_default']} "
                     f"ratio_fitted={t['model_error_ratio_fitted']}")
        report["configs"][name] = cal

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


def stream(out_path: str = None):
    """BENCH_stream.json: the streaming ring collective vs the serialized
    allgather stream, on a host ring of all local devices (run via `make
    bench-stream`, which forces 8 virtual CPU devices — XLA_FLAGS must
    be set before jax initializes). Per config x fusion threshold:
    ring and rs hop/byte structure (hop-span count, bytes circulated per
    hop) next to the serialized stream's measured total.

    The serialized baseline is the allgather wire path under the SAME
    8-device mesh (obs.calibrate.measure_collective) — the only honest
    comparison; the single-device measure_schedule stream does 1/n of a
    ring's decode work.

    The GATES are the deterministic counts: hop spans per step ==
    n_messages x (n_workers - 1) for both modes, and message counts
    agreeing with the serialized path. The ring-vs-serialized wall
    clocks (measured exposed hop time vs serialized stream total) are
    recorded — and the ring must come in below the serialized total on
    at least one config — but on a shared container they are noisy;
    trust the counts and bytes, read the clocks as shape (the report
    embeds the caveat)."""
    from repro.obs.calibrate import measure_collective, measure_stream

    n = jax.local_device_count()
    comp = make_compressor("qsgd", levels=16)
    report = {"caveat": "host-ring measurement on virtual CPU devices: "
                        "hop/message COUNTS and bytes are deterministic "
                        "gates; the ring-vs-serialized wall clocks are "
                        "container-noise-limited shape, not truth.",
              "n_workers": n, "configs": {}}
    ring_below_serialized = []
    for name, tree, sm in _grad_trees():
        per_threshold = {}
        for label, fb in (("fused_64kib", float(1 << 16)),
                          ("one_shot", float("inf"))):
            ser = measure_collective(tree, sm, comp, fb, reps=3)
            entry = {"serialized_total_us": ser["total_us"],
                     "serialized_stage_us": ser["stage_us"],
                     "n_messages": ser["n_messages"]}
            for mode in ("ring", "rs"):
                m = measure_stream(tree, sm, comp, fb, mode=mode, reps=3,
                                   warmup=1, chunk_bytes=float(1 << 16))
                assert m["n_workers"] == n, m
                assert m["n_messages"] == ser["n_messages"], (m, ser)
                assert m["n_hop_spans_measured"] == \
                    m["n_messages"] * (n - 1), m
                entry[mode] = {k: m[k] for k in (
                    "n_hops", "n_hop_spans_measured", "wire_bytes",
                    "hop_bytes_total", "hop_us", "total_us", "stage_us")}
            ring_below_serialized.append(
                entry["ring"]["hop_us"] < ser["total_us"])
            csv_line(f"stream_{name}_{label}", entry["ring"]["hop_us"],
                     f"ring_hops={entry['ring']['n_hop_spans_measured']} "
                     f"hop_bytes={entry['ring']['hop_bytes_total']} "
                     f"serialized={ser['total_us']}us "
                     f"rs_bytes={entry['rs']['hop_bytes_total']}")
            per_threshold[label] = entry
        report["configs"][name] = per_threshold
    # the overlap acceptance: measured exposed ring comm strictly below
    # the serialized stream total on at least one config
    assert any(ring_below_serialized), report
    report["ring_below_serialized_configs"] = sum(ring_below_serialized)

    path = out_path or os.path.join(_REPO_ROOT, "BENCH_stream.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    return report


def run():
    operators()
    kernels()
    unitplan()
    schedule()
    wire()
    kernels_bench()
    controller()
    obs_bench()
