"""The timed path: the program's launcher builds the job, the benchmark
gives it the seed's weights and batches, drives its first steps for the
comparison and then the measured window.

The program is imported only here, and only for the entry under test:
repro.launch.train (parse_args -> prepare -> Job.step_fn) and its compile
cache.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import data, weights
from bench.cells import CHECKOUT, Cell
from bench.reference._common import lr_at

CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Timer:
    """Seconds of each named phase, logged as it ends."""
    def __init__(self):
        self.t = time.perf_counter()

    def lap(self, what: str) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        log(f"{what} {dt:.3f} s")
        return dt


def enable_compile_cache(persistent: bool = True) -> None:
    """JAX's persistent compilation cache at <checkout>/.jax_cache, a fixed
    path, also for the program (which takes $JAX_COMPILATION_CACHE_DIR).
    The program's source is put on the path here too."""
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if not persistent:
        return
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.launch.train import enable_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_check(chips: int, require_tpu: bool = True):
    """The devices of the cell; no TPU, or too few chips, ends the run."""
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is on "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def launcher_argv(cell: Cell, seed: int) -> List[str]:
    """The launcher's arguments for the cell. The codec's come from the
    traffic's "codec" (which the reference and the byte count read too):
    its name is --compressor, each other key a flag of its own name;
    "launcher" adds flags that change no number, such as --wire."""
    t, c = cell.traffic, cell.config
    argv = ["--arch", c["arch"], "--batch", str(t["batch"]),
            "--seq", str(t["seq"]), "--data", str(cell.chips),
            "--steps", str(t["steps"]), "--seed", str(seed % 2 ** 31),
            "--optimizer", t["optimizer"], "--lr", repr(float(t["lr"]))]
    if c.get("smoke"):
        argv.append("--smoke")
    codec = t["codec"] or {"name": "none"}
    argv += ["--compressor", codec["name"]]
    for k, v in codec.items():
        if k != "name":
            argv += ["--" + k.replace("_", "-"), str(v)]
    return argv + list(t["launcher"])


@dataclasses.dataclass
class Run:
    """One built job and its state, from one seed."""
    cell: Cell
    seed: int
    job: object
    step: Callable
    specs: Dict
    params: object
    opt_state: object
    batches: List[Dict]          # on the device, sharded as the step wants
    host_batches: List[Dict]
    step_index: int = 0          # steps taken so far

    @property
    def dtype(self):
        return jnp.dtype(self.cell.config["dtype"])

    def next_step(self):
        """Dispatch the next step (asynchronously) -> its metrics."""
        t = self.cell.traffic
        b = self.batches[self.step_index % len(self.batches)]
        self.params, self.opt_state, m = self.step(
            self.params, self.opt_state, b,
            jnp.int32(int(t["first_step"]) + self.step_index))
        self.step_index += 1
        return m


def build(cell: Cell, seed: int, *, break_step=None, job=None) -> Run:
    """The job through the launcher, with the seed's weights (made by
    bench/weights.py on the device) in place of the program's own, and the
    seed's batch ring on the device. `job` reuses a built job (one
    compile for many seeds); `break_step` wraps the step (fault tests)."""
    from repro.launch import train as launcher
    from repro.models.config import InputShape
    clock = Timer()
    if job is None:
        with contextlib.redirect_stdout(sys.stderr):
            job = launcher.prepare(launcher.parse_args(
                launcher_argv(cell, seed)))
    specs = cell.reference().param_specs(cell.config)
    got = weights.flatten(job.params) if job.params is not None else None
    if got is not None:
        want = {p: (tuple(s[0]), jnp.dtype(cell.config["dtype"]))
                for p, s in specs.items()}
        have = {p: (tuple(v.shape), v.dtype) for p, v in got.items()}
        if have != want:
            raise SystemExit(f"the program's parameters differ from the "
                             f"reference's: {sorted(set(have.items()) ^ set(want.items()))[:6]}")
        job.shardings = weights.nest({p: v.sharding for p, v in got.items()})
    job.params = None
    del got
    gc.collect()
    clock.lap("launcher prepare")
    params = jax.device_put(
        weights.make(specs, seed, jnp.dtype(cell.config["dtype"])),
        job.shardings)
    jax.block_until_ready(params)
    clock.lap("weights from the seed")
    t = cell.traffic
    host = data.batch_ring(cell.config, t, seed)
    shape = InputShape("train", int(t["seq"]), int(t["batch"]), "train")
    specs_b = job.eng.batch_pspecs(shape)
    from jax.sharding import NamedSharding
    dev = [{k: jax.device_put(v, NamedSharding(job.mesh, specs_b[k]))
            for k, v in b.items()} for b in host]
    jax.block_until_ready(dev)
    clock.lap("batch ring")
    step = job.step_fn if break_step is None else break_step(job.step_fn)
    return Run(cell, seed, job, step, specs, params, job.opt_state, dev,
               host)


def first_steps(run: Run) -> dict:
    """The steps the reference follows, through the window's own call on
    batches that all differ -> the program's readings. Also the warm-up:
    the first call compiles."""
    t = run.cell.traffic
    n = int(t["reference_steps"])
    if n > len(run.batches):
        raise SystemExit("the batch ring is shorter than the steps the "
                         "reference follows")
    norms = weights.delta_norms(run.specs, run.dtype)
    kd = weights.seed_key_data(run.seed)
    lr0 = lr_at(t, int(t["first_step"]))
    losses, g1 = [], None
    clock = Timer()
    for k in range(n):
        losses.append(run.next_step()["loss"])
        if k == 0:
            jax.block_until_ready(losses[-1])
            clock.lap("first step (compiles in a cold checkout)")
            g1 = {p: float(v) / lr0 for p, v in
                  norms(run.params, kd).items()}
            clock.lap("norms of the first change")
    d = {p: float(v) for p, v in norms(run.params, kd).items()}
    clock.lap(f"steps 2-{n} and norms")
    return {"losses": [float(x) for x in losses], "grad_norms": g1,
            "update_norms": d}


def window(run: Run, seconds: float, max_steps: Optional[int] = None,
           annotate: bool = False) -> dict:
    """Steps back to back for `seconds`, one step in flight beyond the one
    waited on; the window ends when the last dispatched step completes.
    -> steps, seconds, losses."""
    losses = []
    ctx = (jax.profiler.TraceAnnotation if annotate
           else lambda _n: contextlib.nullcontext())
    t0 = time.perf_counter()
    with ctx("bench_dispatch"):
        losses.append(run.next_step()["loss"])
    while max_steps is None or len(losses) < max_steps:
        with ctx("bench_dispatch"):
            nxt = run.next_step()["loss"]
        with ctx("bench_wait"):
            losses[-1].block_until_ready()
        losses.append(nxt)
        if time.perf_counter() - t0 >= seconds:
            break
    with ctx("bench_wait"):
        jax.block_until_ready((run.params, run.opt_state, losses[-1]))
    t1 = time.perf_counter()
    return {"steps": len(losses), "seconds": t1 - t0,
            "losses": [float(x) for x in losses]}


def free(run: Run) -> None:
    """Drop the program's state from the device before the reference."""
    run.params = run.opt_state = run.batches = run.step = None
    run.job = None
    free_all()


def free_all() -> None:
    gc.collect()


def peak_bytes(devices) -> int:
    """HBM at its fullest on the fullest chip. The TPU runtime reserves a
    program's scratch apart from the buffers it allocates, so the step's
    footprint is what is allocated beside it (the state, after the
    window) plus the peak reservation; set-up's allocations alone may
    have been higher."""
    def one(d):
        m = d.memory_stats() or {}
        return max(int(m.get("peak_bytes_in_use", 0)),
                   int(m.get("bytes_in_use", 0))
                   + int(m.get("peak_bytes_reserved", 0)))
    return max(one(d) for d in devices)

