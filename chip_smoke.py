#!/usr/bin/env python3
"""On-chip smoke test of the compressed data-parallel train step.

Run from the root of a checkout, on a host with TPU chips:

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4    # four chips: the DP exchange only

One chip, three phases, all in this one process:

  (a) device   JAX must see a TPU; prints its kind and the device count.
  (b) kernels  every fused wire kernel runs once, compiled by Mosaic, on
               one real mamba2-1.3b bucket (48 x 524288: the per-layer
               w_bc gradients). Its payload words equal the pure-jnp
               reference's on the chip, and decode(encode(x)) equals the
               compressor's sim(x), bit for bit.
  (c) train    mamba2-1.3b at its published width through the launcher
               (repro.launch.train), sgd, sequence 2048, batch 8: three
               steps each of dense, qsgd(16) layerwise --wire and
               qsgd(16) entire_model. Every loss must be finite.

--chips 4 runs only what exists across chips: whisper-base on a data=4
mesh, qsgd(16) layerwise --wire, --collective ring against --collective
allgather from one seed, three steps each. The parameters must be the
published set (biases, learned decoder positions); losses and parameters
must be bitwise equal, and every parameter must live on all four devices.

Any failed check raises; the process then exits non-zero without the
verdict. The last line of standard output is the JSON verdict.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import gc
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))

#: one real mamba2-1.3b layerwise bucket: 48 layers x (2048 x 256) w_bc
BUCKET = (48, 524288)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"[a] device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d0.platform == "tpu",
          f"no TPU: JAX's first device is on {d0.platform!r}")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _bitwise(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        bool(np.array_equal(a, b))


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro.core import make_compressor, wire_codec
    from repro.core.compressors import index_bits
    from repro.kernels import ops

    check(ops.interpret_mode() is False,
          "kernels would run in interpret mode on this platform")
    n, d = BUCKET
    key = jax.random.key(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    kd = jax.random.key_data(keys).astype(jnp.uint32)

    def both(name, fn, *args):
        """The kernel's words == the jnp reference's, on the chip. Both
        paths run in ONE compiled program, so a unit statistic computed
        by XLA outside the kernel (whose summation order depends on the
        surrounding program) is one value; where fn returns (words,
        statistic), the words are compared."""
        prog = jax.jit(lambda *a: (fn(*a, use_pallas=True),
                                   fn(*a, use_pallas=False)))
        c = prog.lower(*args).compile()
        check("tpu_custom_call" in c.as_text(),
              f"{name}: no compiled Pallas kernel in the program")
        got, want = c(*args)
        pick = (lambda t: t[0]) if isinstance(got, tuple) else (lambda t: t)
        check(_bitwise(pick(got), pick(want)),
              f"{name}: kernel output differs from the jnp reference")
        print(f"[b] {name}: kernel == jnp reference", flush=True)
        return got

    qsgd = make_compressor("qsgd", levels=16)
    width = qsgd.entry_bits
    words, nrms = both("qsgd_pack", lambda a, k, use_pallas: ops.qsgd_pack_units(
        a, k, 16, width, use_pallas=use_pallas), x, kd)
    both("qsgd_unpack", lambda w, s, use_pallas: ops.qsgd_unpack_units(
        w, s, d, 16, width, use_pallas=use_pallas), words, nrms)
    words, scales = both("terngrad_pack", lambda a, k, use_pallas:
                         ops.terngrad_pack_units(a, k, use_pallas=use_pallas),
                         x, kd)
    both("terngrad_unpack", lambda w, s, use_pallas: ops.terngrad_unpack_units(
        w, s, d, use_pallas=use_pallas), words, scales)
    signs = both("sign_pack", lambda a, use_pallas: ops.sign_pack_units(
        a, use_pallas=use_pallas), x)
    both("sign_unpack", lambda w, use_pallas: ops.sign_unpack_units(
        w, d, use_pallas=use_pallas), signs)
    both("majority", lambda w, use_pallas: ops.majority_words(
        w, use_pallas=use_pallas), signs[:4])
    ib = index_bits(d)
    idx = jax.random.randint(key, (n, d // 100), 0, d, jnp.int32)
    fields = both("fields_pack", lambda f, use_pallas: ops.fields_pack_units(
        f, ib, use_pallas=use_pallas), idx)
    both("fields_unpack", lambda w, use_pallas: ops.fields_unpack_units(
        w, d // 100, ib, use_pallas=use_pallas), fields)

    for name, kw in (("qsgd", {"levels": 16}), ("terngrad", {}),
                     ("signsgd", {}), ("natural", {}),
                     ("topk", {"ratio": 0.01})):
        comp = make_compressor(name, **kw)
        codec = wire_codec(comp)
        # one program, for the same reason as in both()
        got, want = jax.jit(lambda a, k: (
            codec.decode_batch(codec.encode_batch(a, k), d),
            jax.vmap(comp.sim)(a, k)))(x, keys)
        check(_bitwise(got, want), f"{name}: decode(encode(x)) != sim(x)")
        print(f"[b] {name}: decode(encode(x)) == sim(x) on {n}x{d}",
              flush=True)


def _run(argv):
    """One launcher run in this process -> (losses, clock)."""
    from repro.launch import train
    res = train.train(train.prepare(train.parse_args(argv)))
    return res.losses, res.clock


def phase_train():
    base = ["--arch", "mamba2-1.3b", "--optimizer", "sgd", "--lr", "0.01",
            "--seq", "2048", "--batch", "8", "--steps", "3", "--seed", "0"]
    qsgd = ["--compressor", "qsgd", "--levels", "16"]
    runs = (("dense", ["--compressor", "none"]),
            ("qsgd16 layerwise wire", qsgd + ["--granularity", "layerwise",
                                              "--wire"]),
            ("qsgd16 entire_model", qsgd + ["--granularity",
                                            "entire_model"]))
    for name, extra in runs:
        losses, clock = _run(base + extra)
        gc.collect()                       # free this run's state on device
        check(len(losses) == 3 and all(map(math.isfinite, losses)),
              f"{name}: losses {losses}")
        (_, t0), (_, t1), (_, t2) = clock
        print(f"[c] {name}: losses {losses} first step (with compile) "
              f"{t0:.2f}s, step time after the first step "
              f"{(t2 - t0) / 2:.4f}s ({t1 - t0:.4f}s, {t2 - t1:.4f}s)",
              flush=True)


def published_whisper(params) -> bool:
    """Whisper-base's published parameter set: a learned table of 448
    decoder positions and no encoder table (its positions are fixed
    sinusoids); biases on q, v and out of every attention and on both
    MLP matrices, none on k."""
    enc, dec = params["encoder_blocks"], params["decoder_blocks"]
    biases = {"bq", "bv", "bo", "b_in", "b_out"}
    return ("enc_pos" not in params and params["dec_pos"].shape[0] == 448
            and biases <= set(enc) and (biases | {"cbq", "cbv", "cbo"})
            <= set(dec) and not {"bk", "cbk"} & (set(enc) | set(dec)))


def phase_four_chips():
    import jax
    import jax.numpy as jnp
    from repro.launch import train

    argv = ["--arch", "whisper-base", "--data", "4", "--optimizer", "sgd",
            "--lr", "0.01", "--seq", "448", "--batch", "32", "--steps", "3",
            "--seed", "0", "--compressor", "qsgd", "--levels", "16",
            "--granularity", "layerwise", "--wire"]
    jobs = {c: train.prepare(train.parse_args(argv + ["--collective", c]))
            for c in ("ring", "allgather")}
    for c, job in jobs.items():
        devs = set(job.mesh.devices.flat)
        check(len(devs) == 4, f"{c}: mesh spans {len(devs)} devices")
        check(published_whisper(job.params),
              f"{c}: whisper-base's parameters are not the published set")
    # the two programs compile at once (the jitted steps then reuse them);
    # they run one after the other
    lowered = []
    for job in jobs.values():
        i, batch = next(job.batches())
        lowered.append(job.step_fn.lower(job.params, job.opt_state, batch,
                                         jnp.int32(i)))
    with concurrent.futures.ThreadPoolExecutor(len(lowered)) as ex:
        for f in [ex.submit(lw.compile) for lw in lowered]:
            f.result()
    results = {c: train.train(job) for c, job in jobs.items()}
    ring, ag = results["ring"], results["allgather"]
    for c, res in results.items():
        check(all(map(math.isfinite, res.losses)),
              f"{c}: losses {res.losses}")
        for leaf in jax.tree_util.tree_leaves(res.params):
            check(len(leaf.sharding.device_set) == 4,
                  f"{c}: a parameter lives on {leaf.sharding.device_set}")
    print(f"[4] losses ring {ring.losses} allgather {ag.losses}",
          flush=True)
    check(ring.losses == ag.losses, "losses differ between ring and allgather")
    differ = [(jax.tree_util.keystr(path), float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(ring.params),
            jax.tree_util.tree_leaves(ag.params)) if not _bitwise(a, b)]
    check(not differ, f"parameters differ between ring and allgather "
                      f"(leaf, max |diff|): {differ}")
    (_, r0), _, (_, r2) = ring.clock
    (_, a0), _, (_, a2) = ag.clock
    print(f"[4] whisper-base data=4 qsgd16 layerwise wire: ring == "
          f"allgather bitwise over 3 steps, losses {ring.losses}; step "
          f"time after the first step ring {(r2 - r0) / 2:.4f}s "
          f"allgather {(a2 - a0) / 2:.4f}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: device, kernels and train phases on one chip; "
                         "4: only the ring-vs-allgather exchange on four")
    args = ap.parse_args(argv)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py needs the repository around it ({src}/repro "
              f"not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()

    device = phase_device(args.chips)
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_kernels()
        gc.collect()
        phase_train()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
