"""Measured-vs-modeled comm calibration.

`core.schedule.simulate_schedule` is an alpha-beta MODEL: per message,
comm = alpha_us + bytes/(gbps*1e3), overlapped against a modeled
backward. Nothing in the repo validated those parameters against the
pipeline we actually execute — the ROADMAP gap this module closes.

`measure_schedule` runs the REAL scheduled wire pipeline (encode →
packed uint8 buffer → decode, the exact graph `--wire` training steps
execute) under a TraceRecorder and reports per-message measured
durations. `fit_alpha_beta` least-squares fits the model's two
parameters to the measured (bytes, duration) samples, per host.
`calibrate` sweeps fusion thresholds for one gradient tree and reports,
per threshold, measured exposed comm next to the model's prediction
under BOTH the default parameters and the fitted ones — the model-error
ratios BENCH_obs.json records.

Honesty note (the repo's standing convention): this is a single-process
measurement of the serialized compress/pack/decode stream — there is no
real network and nothing overlaps, so measured "exposed" comm equals the
measured stream total. Wall-clocks on a shared container are noisy;
reps take medians, and the stable signals remain the counts and byte
totals. The fitted alpha/beta describe THIS host's executed stream, not
a cluster interconnect.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.obs.trace import TraceRecorder, validate_chrome_trace

__all__ = ["measure_schedule", "measure_stream", "measure_collective",
           "fit_alpha_beta", "calibrate", "DEFAULT_THRESHOLDS"]

#: the acceptance sweep: per-bucket, 64 KiB Horovod-style buffers, one shot
DEFAULT_THRESHOLDS: Tuple[Tuple[str, float], ...] = (
    ("per_bucket", 0.0),
    ("fused_64kib", float(1 << 16)),
    ("one_shot", math.inf),
)


def _median(vals: Sequence[float]) -> float:
    sv = sorted(vals)
    return sv[len(sv) // 2] if sv else 0.0


def measure_schedule(tree, stacked, comp, fusion_bytes: float, *,
                     granularity: str = "layerwise", reps: int = 3,
                     warmup: int = 1, seed: int = 0) -> Dict:
    """Execute the real wire schedule for (tree, comp, fusion_bytes)
    under a TraceRecorder; return measured per-message durations plus
    stage totals.

    Returns {"n_messages", "wire_bytes" (buffer bytes incl. headers),
    "total_us" (median step wall), "stage_us" {stage: median},
    "per_message": [{"message", "wire_bytes", "dur_us"}]}."""
    from repro.core import build_plan, build_schedule, wire_codec
    from repro.core.granularity import Granularity
    from repro.core.wire import message_layouts

    plan = build_plan(tree, stacked, Granularity(granularity))
    sched = build_schedule(plan, float(fusion_bytes))
    codec = wire_codec(comp)
    layouts = message_layouts(sched, codec)
    rec = TraceRecorder()
    key = jax.random.key(seed)

    fn = jax.jit(lambda t, k: sched.execute(None, t, k, wire=codec,
                                            recorder=rec))
    for _ in range(warmup):
        out, bufs = fn(tree, key)
        jax.block_until_ready(bufs)
        rec.finalize_step()
    rec.events, rec.steps = [], []  # keep only the timed reps

    per_rep_msgs: List[Dict[int, float]] = []
    totals, stage_accum = [], {}
    for r in range(reps):
        out, bufs = fn(tree, key)
        jax.block_until_ready(bufs)
        jax.block_until_ready(out)
        summary = rec.finalize_step(r)
        totals.append(summary["wall_us"])
        for k, v in summary["stage_us"].items():
            stage_accum.setdefault(k, []).append(v)
        durs = {}
        for e in rec.message_spans(step=r):
            durs[int(e["args"]["message"])] = float(e["dur"])
        per_rep_msgs.append(durs)

    per_message = []
    for mi, layout in enumerate(layouts):
        ds = [d[mi] for d in per_rep_msgs if mi in d]
        per_message.append({"message": mi,
                            "wire_bytes": int(layout.total_nbytes),
                            "dur_us": round(_median(ds), 3)})
    return {
        "n_messages": sched.num_messages,
        "wire_bytes": int(sum(l.total_nbytes for l in layouts)),
        "total_us": round(_median(totals), 3),
        "stage_us": {k: round(_median(v), 3)
                     for k, v in sorted(stage_accum.items())},
        "per_message": per_message,
    }


def measure_stream(tree, stacked, comp, fusion_bytes: float, *,
                   mode: str = "ring", granularity: str = "layerwise",
                   chunk_bytes: Optional[float] = None, reps: int = 3,
                   warmup: int = 1, seed: int = 0) -> Dict:
    """Execute the STREAMING ring collective for (tree, comp,
    fusion_bytes) over every local device and report per-hop structure
    plus measured exposed comm.

    Unlike `measure_schedule` (the serialized single-process stream,
    where exposed comm == stream total by construction), this runs
    `CommSchedule.execute_streaming` under a real multi-device
    ``shard_map`` — the chunked-ppermute ring with double-buffered
    compress — and aggregates the recorder's per-hop spans. The stable,
    gateable signals are the COUNTS (hop spans per step ==
    n_messages x (n_workers - 1), deterministic) and BYTES per hop (the
    full message buffer circulates each hop in mode='ring'; packed
    shards in mode='rs'); `hop_us` — the measured exposed-comm proxy the
    ring-vs-serialized comparison in BENCH_stream.json uses — is a
    host-clock wall measurement and carries the usual shared-container
    noise caveat.

    Uses ALL local devices (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for an
    N-worker host ring); with a single device the ring degenerates to
    the serialized wire path (0 hops). The recorder's multi-device
    stamps are collapsed with ``finalize_step(dedupe=True)`` and the
    resulting trace is validated against the Chrome trace-event schema
    before returning.

    Returns {"mode", "n_workers", "n_messages", "n_hops",
    "n_hop_spans_measured", "wire_bytes", "hop_bytes_total",
    "hop_us", "total_us", "stage_us", "per_message":
    [{"message", "wire_bytes", "n_chunks", "hop_bytes"}]}."""
    from jax.sharding import PartitionSpec as P
    from repro.core import build_plan, build_schedule, wire_codec
    from repro.core.granularity import Granularity
    from repro.core.wire import (layout_chunks, message_layouts,
                                 shard_message_layouts)
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh

    n = jax.local_device_count()
    mesh = make_host_mesh(n, 1)
    plan = build_plan(tree, stacked, Granularity(granularity))
    sched = build_schedule(plan, float(fusion_bytes))
    codec = wire_codec(comp)
    layouts = (message_layouts(sched, codec) if mode == "ring"
               else shard_message_layouts(sched, codec, n))
    rec = TraceRecorder()
    key = jax.random.key(seed)

    def f(t):
        return sched.execute_streaming(
            None, t, key, wire=codec, axis_names=("data",), n_workers=n,
            mode=mode, chunk_bytes=chunk_bytes, recorder=rec)

    fn = jax.jit(shard_map(f, mesh, in_specs=(P(),), out_specs=P()))
    for _ in range(warmup):
        out, bufs = fn(tree)
        jax.block_until_ready(bufs)
        rec.finalize_step(dedupe=True)
    rec.events, rec.steps = [], []  # keep only the timed reps

    totals, stage_accum, hop_counts = [], {}, []
    for r in range(reps):
        out, bufs = fn(tree)
        jax.block_until_ready(bufs)
        jax.block_until_ready(out)
        summary = rec.finalize_step(r, dedupe=True)
        totals.append(summary["wall_us"])
        for k, v in summary["stage_us"].items():
            stage_accum.setdefault(k, []).append(v)
        hop_counts.append(sum(1 for e in rec.span_events(step=r)
                              if e["args"].get("stage") == "hop"))
    validate_chrome_trace(rec.chrome_trace())

    per_message = [{"message": mi,
                    "wire_bytes": int(l.total_nbytes),
                    "n_chunks": len(layout_chunks(l, chunk_bytes)),
                    "hop_bytes": int((n - 1) * l.total_nbytes)}
                   for mi, l in enumerate(layouts)]
    stage_us = {k: round(_median(v), 3)
                for k, v in sorted(stage_accum.items())}
    return {
        "mode": mode,
        "n_workers": n,
        "n_messages": sched.num_messages,
        "n_hops": sched.num_messages * (n - 1),
        "n_hop_spans_measured": int(_median(hop_counts)),
        "wire_bytes": int(sum(l.total_nbytes for l in layouts)),
        "hop_bytes_total": int(sum(m["hop_bytes"] for m in per_message)),
        "hop_us": stage_us.get("hop", 0.0),
        "total_us": round(_median(totals), 3),
        "stage_us": stage_us,
        "per_message": per_message,
    }


def measure_collective(tree, stacked, comp, fusion_bytes: float, *,
                       strategy: str = "allgather",
                       granularity: str = "layerwise", reps: int = 3,
                       warmup: int = 1, seed: int = 0) -> Dict:
    """The SERIALIZED wire collective under the same multi-device mesh
    as `measure_stream`: compressed_allreduce(strategy='allgather',
    wire=True) over every local device — compress, pack, one blocking
    gather-everything collective, decode, per message in sequence. Its
    `total_us` is the serialized-stream total that the ring's measured
    exposed hop time is compared against in BENCH_stream.json (same
    device count, same process — the only honest baseline; the
    single-device `measure_schedule` stream is NOT comparable to a ring
    doing n_workers x the decode work). Returns {"n_workers",
    "n_messages", "wire_bytes", "total_us", "stage_us"}."""
    from jax.sharding import PartitionSpec as P
    from repro.core import build_plan, build_schedule, wire_codec
    from repro.core.aggregation import (CompressionConfig,
                                        compressed_allreduce)
    from repro.core.granularity import Granularity
    from repro.core.wire import message_layouts
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh

    n = jax.local_device_count()
    mesh = make_host_mesh(n, 1)
    gran = Granularity(granularity)
    plan = build_plan(tree, stacked, gran)
    sched = build_schedule(plan, float(fusion_bytes))
    layouts = message_layouts(sched, wire_codec(comp))
    cfg = CompressionConfig(qw=comp, granularity=gran, strategy=strategy,
                            fusion_bytes=float(fusion_bytes))
    rec = TraceRecorder()
    key = jax.random.key(seed)

    def f(t):
        out, _ = compressed_allreduce(t, stacked, cfg, ("data",), key, n,
                                      plan=plan, wire=True, recorder=rec)
        return out

    fn = jax.jit(shard_map(f, mesh, in_specs=(P(),), out_specs=P()))
    for _ in range(warmup):
        out = fn(tree)
        jax.block_until_ready(out)
        rec.finalize_step(dedupe=True)
    rec.events, rec.steps = [], []  # keep only the timed reps

    totals, stage_accum = [], {}
    for r in range(reps):
        out = fn(tree)
        jax.block_until_ready(out)
        summary = rec.finalize_step(r, dedupe=True)
        totals.append(summary["wall_us"])
        for k, v in summary["stage_us"].items():
            stage_accum.setdefault(k, []).append(v)
    return {
        "n_workers": n,
        "n_messages": sched.num_messages,
        "wire_bytes": int(sum(l.total_nbytes for l in layouts)),
        "total_us": round(_median(totals), 3),
        "stage_us": {k: round(_median(v), 3)
                     for k, v in sorted(stage_accum.items())},
    }


def fit_alpha_beta(samples: Sequence[Tuple[float, float]],
                   prior_alpha_us: float = 50.0,
                   prior_gbps: float = 12.5) -> Dict:
    """Least-squares fit t_us = alpha_us + nbytes/(gbps*1e3) over
    measured (nbytes, dur_us) samples. Slope is clamped non-negative
    (a negative slope just means latency dominates at these sizes);
    alpha is clamped non-negative likewise.

    Degenerate inputs — fewer than two DISTINCT message sizes (e.g.
    fusion=inf produces exactly one message, so every sample shares one
    x) or non-finite samples — cannot identify two parameters: the
    legacy fit silently dumped the whole duration into alpha (or worse,
    propagated NaN into BENCH_obs.json's model-error ratios). Now such
    inputs return the PRIOR (`prior_alpha_us`, `prior_gbps` — the
    model's defaults) with an explicit ``fit_degenerate: True`` flag,
    and `resid_rms_us` honestly reports the misfit of the prior against
    the samples. Empty samples keep the legacy {alpha 0, gbps None}
    shape (there is nothing to misfit), flagged degenerate likewise."""
    n = len(samples)
    if n == 0:
        return {"alpha_us": 0.0, "gbps": None, "n_samples": 0,
                "resid_rms_us": 0.0, "fit_degenerate": True}
    xs = [float(b) for b, _ in samples]
    ys = [float(t) for _, t in samples]
    finite = all(math.isfinite(v) for v in xs + ys)
    mx = sum(xs) / n if finite else 0.0
    my = sum(ys) / n if finite else 0.0
    sxx = sum((x - mx) ** 2 for x in xs) if finite else 0.0
    degenerate = (not finite or len(set(xs)) < 2 or sxx <= 0.0)
    if degenerate:
        slope = 1.0 / (prior_gbps * 1e3)
        alpha = float(prior_alpha_us)
        gbps = float(prior_gbps)
    else:
        sxy = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
        slope = max(sxy / sxx, 0.0)   # us per byte
        alpha = max(0.0, my - slope * mx)
        gbps = (1.0 / (slope * 1e3)) if slope > 1e-12 else None
        if not all(math.isfinite(v) for v in
                   (slope, alpha) + (() if gbps is None else (gbps,))):
            degenerate, slope, alpha, gbps = (
                True, 1.0 / (prior_gbps * 1e3), float(prior_alpha_us),
                float(prior_gbps))
    resid = [y - (alpha + slope * x) for x, y in zip(xs, ys)
             if math.isfinite(x) and math.isfinite(y)]
    rms = (math.sqrt(sum(r * r for r in resid) / len(resid))
           if resid else 0.0)
    return {"alpha_us": round(alpha, 3),
            "gbps": float(f"{gbps:.4g}") if gbps is not None else None,
            "us_per_byte": round(slope, 6),
            "n_samples": n,
            "resid_rms_us": round(rms, 3),
            "fit_degenerate": degenerate}


def _predict_us(n_messages: int, nbytes: int, alpha_us: float,
                gbps: Optional[float]) -> float:
    beta = 0.0 if gbps is None else 1.0 / (gbps * 1e3)
    return n_messages * alpha_us + nbytes * beta


def calibrate(name: str, tree, stacked, comp, *,
              thresholds: Sequence[Tuple[str, float]] = DEFAULT_THRESHOLDS,
              granularity: str = "layerwise", reps: int = 3,
              alpha_us: float = 50.0, gbps: float = 12.5,
              compress_gbps: float = 25.0) -> Dict:
    """Measured-vs-modeled calibration report for one gradient tree.

    Per fusion threshold: the measured wire-schedule stream next to the
    alpha-beta model's comm prediction under the DEFAULT parameters and
    under parameters FITTED to this host's measurements (error ratio =
    measured / predicted; the fitted ratio should sit near 1 — that gap
    is the model error the paper's discrepancy argument is about)."""
    from repro.core import build_plan, build_schedule, simulate_schedule
    from repro.core.granularity import Granularity

    plan = build_plan(tree, stacked, Granularity(granularity))
    per_threshold: Dict[str, Dict] = {}
    samples: List[Tuple[float, float]] = []
    for label, fb in thresholds:
        meas = measure_schedule(tree, stacked, comp, fb,
                                granularity=granularity, reps=reps)
        sched = build_schedule(plan, float(fb))
        sim = simulate_schedule(sched, qw=comp, alpha_us=alpha_us,
                                gbps=gbps, compress_gbps=compress_gbps)
        samples.extend((m["wire_bytes"], m["dur_us"])
                       for m in meas["per_message"])
        per_threshold[label] = {
            "fusion_bytes": None if math.isinf(fb) else fb,
            "n_messages": meas["n_messages"],
            "wire_bytes_measured": meas["wire_bytes"],
            "wire_bits_model": sim["wire_bits_total"],
            "exposed_comm_us_measured": meas["total_us"],
            "exposed_comm_us_model": sim["exposed_comm_us"],
            "comm_us_total_model": sim["comm_us_total"],
            "stage_us_measured": meas["stage_us"],
            "per_message_measured": meas["per_message"],
        }

    fit = fit_alpha_beta(samples, prior_alpha_us=alpha_us, prior_gbps=gbps)
    host = str(jax.process_index())
    for label, _ in thresholds:
        t = per_threshold[label]
        pred_default = _predict_us(t["n_messages"], t["wire_bytes_measured"],
                                   alpha_us, gbps)
        pred_fitted = _predict_us(t["n_messages"], t["wire_bytes_measured"],
                                  fit["alpha_us"], fit["gbps"])
        meas_us = t["exposed_comm_us_measured"]
        t["model_error_ratio_default"] = round(
            meas_us / max(pred_default, 1e-9), 3)
        t["model_error_ratio_fitted"] = round(
            meas_us / max(pred_fitted, 1e-9), 3)
    return {
        "config": name,
        "codec": comp.name,
        "granularity": granularity,
        "model_defaults": {"alpha_us": alpha_us, "gbps": gbps,
                           "compress_gbps": compress_gbps},
        "fit_by_host": {host: fit},
        "thresholds": per_threshold,
    }
