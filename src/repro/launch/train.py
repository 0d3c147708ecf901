"""Training launcher: compressed data-parallel training of any --arch on
the current device set (host CPU mesh for development; the same code path
lowers on the production mesh via dryrun.py).

Example:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b --smoke \\
      --steps 50 --data 4 --model 2 --compressor topk --ratio 0.1 \\
      --granularity layerwise
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import Any, Iterator, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_NAMES, get_config, get_smoke
from repro.control import POLICIES, engine_controller, make_policy
from repro.core import CompressionConfig, Granularity, make_compressor
from repro.data import lm_batches, frames_stub, patches_stub
from repro.launch.engine import Engine
from repro.launch.mesh import make_host_mesh
from repro.ckpt import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.optim import OptConfig, piecewise_linear


#: root of the checkout this module runs from (src/repro/launch/ -> ../../..)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """Where compiled programs are kept across processes:
    $JAX_COMPILATION_CACHE_DIR where it is set, else a fixed directory
    inside the checkout (a fixed path, so a later run hits the cache)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache at compile_cache_dir().
    Call before the process compiles anything."""
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


def build_controller(args, eng, sched, *, metrics=None, tracer=None):
    kw = {}
    if args.policy == "variance_budget":
        kw["budget"] = args.variance_budget
    if args.policy == "bit_budget":
        kw["bits_per_step"] = args.bit_budget
    if args.policy == "fusion":
        kw["alpha_us"] = args.alpha_us
    policy = make_policy(args.policy, **kw)
    collect = policy.needs_telemetry or bool(args.telemetry_out)
    return engine_controller(eng, policy, lr_schedule=sched,
                             replan_every=args.replan_every,
                             collect_telemetry=collect,
                             metrics=metrics, tracer=tracer)


def build_compression(args) -> CompressionConfig:
    if args.compressor == "none":
        return CompressionConfig(strategy="dense")
    kw = {}
    if args.compressor in ("randomk", "topk"):
        kw["ratio"] = args.ratio
    if args.compressor == "qsgd":
        kw["levels"] = args.levels
    return CompressionConfig(
        qw=make_compressor(args.compressor, **kw),
        qm=make_compressor(args.qm),
        granularity=Granularity(args.granularity, args.block_size),
        strategy=args.strategy,
        error_feedback=args.error_feedback,
        fusion_bytes=args.fusion_bytes)


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's command line -> validated args."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="mamba2-1.3b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--compressor", default="none")
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--levels", type=int, default=16)
    ap.add_argument("--qm", default="identity")
    ap.add_argument("--granularity", default="layerwise",
                    choices=["layerwise", "entire_model", "blockwise"])
    ap.add_argument("--block-size", type=int, default=65536)
    ap.add_argument("--strategy", default="simulated")
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--fusion-bytes", type=float, default=None,
                    help="comm-schedule fusion threshold in bytes: stream "
                         "aggregation through the backward-ordered "
                         "CommSchedule, fusing buckets below this size "
                         "into one wire message (0 = per-bucket messages, "
                         "inf = one message; default: unscheduled)")
    ap.add_argument("--alpha-us", type=float, default=50.0,
                    help="per-message link latency for the fusion policy "
                         "and the modeled comm report")
    ap.add_argument("--wire", action="store_true",
                    help="materialize compression as real bit-packed wire "
                         "payloads (core.wire): every message is an actual "
                         "uint8 buffer, bit-identical numerics; prints "
                         "accounted vs measured wire bits (static path "
                         "only — not combined with --policy)")
    ap.add_argument("--collective", default=None,
                    choices=("allgather", "ring"),
                    help="wire-collective topology (requires --wire): "
                         "'allgather' = the serialized gather-everything "
                         "stream, 'ring' = the streaming chunked-ppermute "
                         "ring with per-hop decode-accumulate — "
                         "bit-identical numerics, real compress/collective "
                         "overlap in program order")
    ap.add_argument("--policy", default=None, choices=list(POLICIES),
                    help="adaptive compression policy; routes the run "
                         "through the control.Controller (default: the "
                         "static engine path without telemetry)")
    ap.add_argument("--replan-every", type=int, default=20,
                    help="policy re-plan boundary, in steps")
    ap.add_argument("--telemetry-out", default="",
                    help="write the controller's per-window telemetry "
                         "summaries + switch log as JSON (implies "
                         "--policy static when no policy is given)")
    ap.add_argument("--trace-out", default="",
                    help="record per-step/per-message spans with the "
                         "obs.TraceRecorder and write a Chrome trace-event "
                         "JSON (open in Perfetto). Forces per-step host "
                         "sync — timings are honest, throughput is not")
    ap.add_argument("--metrics-out", default="",
                    help="write engine/controller/train counters and "
                         "gauges as JSON lines (obs.MetricsRegistry)")
    ap.add_argument("--variance-budget", type=float, default=0.1,
                    help="variance_budget policy: max relative "
                         "compression error per bucket")
    ap.add_argument("--bit-budget", type=int, default=1 << 22,
                    help="bit_budget policy: uplink payload bits/step")
    ap.add_argument("--optimizer", default="momentum")
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--nesterov", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore params/optimizer from the newest "
                         "checkpoint in --ckpt-dir and continue from its "
                         "step; the data stream is replayed to that step, "
                         "so an uninterrupted run and a killed-and-resumed "
                         "run produce bitwise-identical states")
    ap.add_argument("--step-guard", action="store_true",
                    help="drop any update whose loss or aggregated "
                         "gradient is non-finite (params/optimizer keep "
                         "their pre-step values); skipped steps are "
                         "counted under resil/steps_skipped when "
                         "--metrics-out is set")
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume restores from --ckpt-dir; set it")
    if args.telemetry_out and not args.policy:
        args.policy = "static"  # telemetry collection needs the controller
    if args.wire and args.policy:
        ap.error("--wire is the static engine path; drop --policy")
    if args.step_guard and args.policy:
        ap.error("--step-guard is the static engine path; drop --policy")
    if args.collective and not args.wire:
        ap.error("--collective picks the wire collective's topology; "
                 "add --wire")
    if args.collective and args.compressor == "none":
        ap.error("--collective needs a compressor (the dense path has no "
                 "wire messages to stream); add --compressor")
    return args


@dataclasses.dataclass
class Job:
    """A launched run before its first step: the engine, the jitted step
    (or the controller that builds steps), the initial state and the
    batch source."""
    args: argparse.Namespace
    cfg: Any
    mesh: Any
    eng: Engine
    sched: Any
    step_fn: Any
    ctrl: Any
    rec: Any
    reg: Any
    params: Any
    opt_state: Any
    start: int

    def batches(self) -> Iterator[Tuple[int, dict]]:
        """(step, batch) from the resume point on — the exact stream an
        uninterrupted run sees."""
        args, cfg = self.args, self.cfg
        it = lm_batches(cfg.vocab, args.batch, args.seq, seed=args.seed)
        for _ in range(self.start):
            next(it)
        key = jax.random.key(args.seed)
        for i in range(self.start, args.steps):
            batch = next(it)
            if cfg.arch_type == "vlm":
                batch["patch_embeds"] = patches_stub(
                    jax.random.fold_in(key, i), args.batch,
                    cfg.frontend_seq, cfg.d_model)
            if cfg.arch_type == "audio":
                batch["frames"] = frames_stub(
                    jax.random.fold_in(key, i), args.batch,
                    cfg.frontend_seq, cfg.d_model)
            yield i, batch


@dataclasses.dataclass
class Result:
    """What a run leaves: every step's loss, the wall clock at each
    progress line as (step, seconds since the first step began — the
    line reads the loss, so the step has finished), and the final state."""
    losses: List[float]
    clock: List[Tuple[int, float]]
    params: Any
    opt_state: Any


def prepare(args: argparse.Namespace) -> Job:
    """Build the engine, the step and the initial (or resumed) state."""
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(data=args.data, model=args.model)
    comp = build_compression(args)
    opt = OptConfig(name=args.optimizer, lr=args.lr, nesterov=args.nesterov)
    eng = Engine(cfg, mesh, comp=comp, opt=opt)
    sched = piecewise_linear(args.lr, args.steps, max(1, args.steps // 10))
    rec = reg = None
    if args.trace_out or args.metrics_out:
        from repro.obs import MetricsRegistry, TraceRecorder
        rec = TraceRecorder() if args.trace_out else None
        reg = MetricsRegistry() if args.metrics_out else None
    ctrl = (build_controller(args, eng, sched, metrics=reg, tracer=rec)
            if args.policy else None)
    step_fn = None if ctrl else eng.build_train_step(
        sched, wire=args.wire, collective=args.collective, tracer=rec,
        metrics=reg, step_guard=args.step_guard)
    params, opt_state = eng.init_state(args.seed)
    start = 0
    if args.resume:
        ck = latest_checkpoint(args.ckpt_dir)
        if ck is not None:
            start, state = load_checkpoint(
                ck, like={"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"resume: {ck} -> step {start}")
        else:
            print(f"resume: no checkpoint under {args.ckpt_dir!r}, "
                  f"starting fresh")
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.2f}M mesh={dict(eng.sizes)} "
          f"comp={comp.strategy}/{comp.qw.name}/{comp.granularity.kind}"
          + (f" collective={args.collective}" if args.collective else "")
          + (f" policy={args.policy}/replan={args.replan_every}"
             if ctrl else ""))
    # the static compression-execution plan the jitted step will run with
    # (same cached object: built here from ShapeDtypeStructs, reused at
    # trace time by Engine._aggregate_grads)
    rest_plan, fsdp_plan = eng.comm_plans()
    for tag, p in (("dp", rest_plan), ("fsdp", fsdp_plan)):
        if p is not None:
            print(f"plan[{tag}]: {p.summary()}")
    if args.wire and rest_plan is not None and comp.strategy != "dense":
        # accounted vs measured wire bits of the active codec (the
        # differential suite holds these equal modulo word padding)
        from repro.core.wire import wire_codec
        codec = wire_codec(comp.qw)
        acct = sum(comp.qw.payload_bits(d) for d in rest_plan.unit_dims)
        meas = sum(codec.wire_bits(d) for d in rest_plan.unit_dims)
        print(f"wire[dp]: codec={codec.name} accounted={acct} bits "
              f"measured={meas} bits (padding {meas - acct})")
    if args.fusion_bytes is not None and rest_plan is not None:
        from repro.launch.comm_sched import engine_schedule, schedule_report
        s = engine_schedule(eng, args.fusion_bytes)
        rep = schedule_report(s, comp, eng.dp_size, alpha_us=args.alpha_us)
        print(f"schedule[dp]: {s.summary()}")
        print(f"schedule[dp]: modeled exposed comm "
              f"{rep['model']['exposed_comm_us']:.0f}us of "
              f"{rep['model']['comm_us_total']:.0f}us "
              f"(overlap {rep['model']['overlap_frac']:.0%}; model, not "
              f"measurement — trust the message counts)")

    return Job(args, cfg, mesh, eng, sched, step_fn, ctrl, rec, reg,
               params, opt_state, start)


def train(job: Job) -> Result:
    """Run the job's steps; print progress, write the requested exports."""
    args, ctrl, rec, reg = job.args, job.ctrl, job.rec, job.reg
    params, opt_state = job.params, job.opt_state
    losses, clock = [], []
    with job.mesh:
        t0 = time.time()
        for i, batch in job.batches():
            if ctrl is not None:
                fn = ctrl.step_fn()
                if ctrl.collect:
                    params, opt_state, m, telem = fn(
                        params, opt_state, batch, jnp.int32(i),
                        ctrl.telemetry)
                else:
                    params, opt_state, m = fn(params, opt_state, batch,
                                              jnp.int32(i))
                    telem = None
                if ctrl.observe(telem, i):
                    print(f"step {i:5d} replan -> "
                          f"{ctrl.decision.describe()}")
            else:
                params, opt_state, m = job.step_fn(params, opt_state, batch,
                                                   jnp.int32(i))
            losses.append(m["loss"])
            if rec is not None:
                # span stamps arrive via host callbacks — close the step
                # before cutting it (honest timings, serialized steps)
                jax.block_until_ready(m["loss"])
                rec.finalize_step(i)
            if reg is not None:
                reg.inc("train/steps")
                if args.step_guard:
                    reg.inc("resil/steps_skipped", float(m["skipped"]))
                reg.record(step=i)
            if i % max(1, args.steps // 20) == 0 or i == args.steps - 1:
                loss = float(m["loss"])
                clock.append((i, time.time() - t0))
                print(f"step {i:5d} loss {loss:.4f} "
                      f"lr {float(m['lr']):.4f} "
                      f"({clock[-1][1]:.1f}s)")
            if args.ckpt_dir and args.ckpt_every and \
                    (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, i + 1,
                                {"params": params, "opt": opt_state})
    if ctrl is not None:
        print(f"controller: decision={ctrl.decision.describe()} "
              f"builds={ctrl.builds} switches={len(ctrl.switches)}")
        if args.telemetry_out:
            ctrl.export(args.telemetry_out)
            print(f"telemetry -> {args.telemetry_out}")
    if rec is not None:
        from repro.obs import format_step_summary
        if rec.steps:
            print(format_step_summary(rec.steps[-1]))
        rec.export(args.trace_out)
        print(f"trace -> {args.trace_out} "
              f"({len(rec.events)} events, {len(rec.steps)} steps)")
    if reg is not None:
        if ctrl is not None:
            ctrl.check_retraces()  # stamp the final retrace gauge
        n_lines = reg.export_jsonl(args.metrics_out)
        print(f"metrics -> {args.metrics_out} ({n_lines} lines)")
    return Result([float(v) for v in losses], clock, params, opt_state)


def main(argv=None) -> int:
    enable_compile_cache()
    train(prepare(parse_args(argv)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
