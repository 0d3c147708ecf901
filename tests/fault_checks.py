"""Multi-device fault-injection checks (subprocess body).

Run by tests/test_resil.py with 4 virtual CPU devices — XLA device count
must be set before jax initializes, hence the subprocess. What a
single-device run cannot witness — corruption of REAL ring hops between
distinct workers:

  1. bit flips on every ring hop (prob=1) are all detected by the
     Fletcher-32 header word, and resend recovers the clean aggregate
     BITWISE (chunked and unchunked hops, and the serialized allgather
     wire path for the same contract off the ring);
  2. dropped (zeroed) hops are all detected — the init=1 checksum of an
     all-zero span never matches — and resend recovers clean bits;
  3. a duplicated (stale) hop is a VALID message: the checksum passes
     (zero detections), the aggregate silently differs — the documented
     sequence-number gap;
  4. a prob=0 injector is the byte-identical pass-through (det == 0,
     same bits as faults=None);
  5. threaded error feedback under per-hop bit flips with resend stays
     bitwise on the clean trajectory across steps.
"""
import functools
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from _sweep_helpers import (assert_trees_bitwise, per_worker,  # noqa: E402
                            ring_tree)
from repro.core import (CompressionConfig, Granularity,  # noqa: E402
                        compressed_allreduce, make_compressor,
                        stacked_mask)
from repro.launch.engine import shard_map  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.resil import FaultInjector  # noqa: E402
from repro.sim import CorruptionSpec  # noqa: E402

KEY = jax.random.key(7)
N = jax.local_device_count()
assert N == 4, f"expected 4 virtual devices, got {N}"
MESH = make_host_mesh(N, 1)


def _differs(a, b):
    return any(not bool((x == y).all())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _cfg(strat):
    return CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                             granularity=Granularity("layerwise"),
                             strategy=strat, error_feedback=False,
                             integrity=True)


def _run(strat, spec, *, resend=True, chunk=None, ef_steps=0):
    """One shard_map'd compressed_allreduce under an injector. Returns
    (out, total_detections, messages_per_device) — or (outs, ef, det)
    with `ef_steps` threaded error feedback."""
    t = ring_tree()
    sm = stacked_mask(t)
    cfg = _cfg(strat)
    if ef_steps:
        cfg = CompressionConfig(qw=cfg.qw, granularity=cfg.granularity,
                                strategy=strat, error_feedback=True,
                                integrity=True)
    inj = None if spec is None else FaultInjector(spec, resend=resend)

    def f(g, ef, key):
        g = per_worker(g)
        if cfg.error_feedback:
            out, ef = compressed_allreduce(g, sm, cfg, ("data",), key, N,
                                           wire=True, ef_state=ef,
                                           faults=inj,
                                           stream_chunk_bytes=chunk)
        else:
            out, _ = compressed_allreduce(g, sm, cfg, ("data",), key, N,
                                          wire=True, faults=inj,
                                          stream_chunk_bytes=chunk)
        if inj is None:
            det = jnp.zeros((), jnp.int32)
            msgs = jnp.zeros((), jnp.int32)
        else:
            # drain the verdicts INSIDE this trace (they are its tracers)
            flags = inj.take_flags()
            det = (jnp.sum(~flags).astype(jnp.int32) if flags.size
                   else jnp.zeros((), jnp.int32))
            msgs = jnp.asarray(flags.size, jnp.int32)
        det = jax.lax.psum(det, ("data",))
        return out, ef, det, msgs

    fn = jax.jit(shard_map(f, MESH, in_specs=(P(), P(), P()),
                           out_specs=(P(), P(), P(), P())))
    if not ef_steps:
        out, _ef, det, msgs = fn(t, t, KEY)   # ef arg unused
        return out, int(det), int(msgs)
    # the residual enters with the sharding it leaves with, so the step
    # compiles once rather than again at step 1
    ef = jax.device_put(jax.tree_util.tree_map(jnp.zeros_like, t),
                        NamedSharding(MESH, P()))
    outs, det_total = [], 0
    for i in range(ef_steps):
        out, ef, det, _ = fn(t, ef, jax.random.fold_in(KEY, i))
        outs.append(out)
        det_total += int(det)
    return outs, ef, det_total


@functools.lru_cache(maxsize=None)
def _clean_ring():
    """The uninjected ring aggregate every ring check compares to."""
    return _run("ring", None)[0]


def check_ring_bitflip_resend():
    clean = _clean_ring()
    for chunk in (None, 64.0):
        out, det, msgs = _run("ring", CorruptionSpec(prob=1.0, seed=5),
                              resend=True, chunk=chunk)
        assert msgs > 0 and det == N * msgs, (det, msgs, chunk)
        assert_trees_bitwise(out, clean, ("ring-bitflip-resend", chunk))
        # without resend the corrupted hops poison the aggregate
        bad, det2, _ = _run("ring", CorruptionSpec(prob=1.0, seed=5),
                            resend=False, chunk=chunk)
        assert det2 == det and _differs(bad, clean), chunk
    print("ring bit flips: all detected, resend == clean bitwise: OK")


def check_ring_drop_hop():
    clean = _clean_ring()
    out, det, msgs = _run("ring",
                          CorruptionSpec(prob=1.0, mode="drop_hop",
                                         seed=6), resend=True)
    assert msgs > 0 and det == N * msgs, (det, msgs)
    assert_trees_bitwise(out, clean, "ring-drop-resend")
    print("ring dropped hops: init=1 catches zeros, resend == clean: OK")


def check_ring_dup_hop_limitation():
    clean = _clean_ring()
    out, det, msgs = _run("ring",
                          CorruptionSpec(prob=1.0, mode="dup_hop",
                                         seed=8), resend=True)
    assert msgs > 0 and det == 0, (det, msgs)
    assert _differs(out, clean)
    print("ring duplicated hop: valid stale message passes the checksum "
          "(needs sequence numbers) — documented gap holds: OK")


def check_allgather_bitflip_resend():
    clean, _, _ = _run("allgather", None)
    out, det, msgs = _run("allgather", CorruptionSpec(prob=1.0, seed=9),
                          resend=True)
    assert msgs > 0 and det == N * msgs, (det, msgs)
    assert_trees_bitwise(out, clean, "allgather-bitflip-resend")
    print("allgather wire bit flips: all detected, resend == clean: OK")


def check_prob0_passthrough():
    clean = _clean_ring()
    out, det, msgs = _run("ring", CorruptionSpec(prob=0.0))
    assert det == 0 and msgs == 0
    assert_trees_bitwise(out, clean, "prob0-passthrough")
    print("prob=0 injector: byte-identical pass-through: OK")


def check_ring_ef_resend():
    clean_outs, clean_ef, _ = _run("ring", None, ef_steps=3)
    outs, ef, det = _run("ring", CorruptionSpec(prob=1.0, seed=12),
                         resend=True, ef_steps=3)
    assert det > 0
    for i, (r, g) in enumerate(zip(clean_outs, outs)):
        assert_trees_bitwise(r, g, ("ring-ef-resend", i))
    assert_trees_bitwise(ef, clean_ef, "ring-ef-resend-state")
    print("ring 3-step EF under bit flips with resend == clean: OK")


if __name__ == "__main__":
    check_ring_bitflip_resend()
    check_ring_drop_hop()
    check_ring_dup_hop_limitation()
    check_allgather_bitflip_resend()
    check_prob0_passthrough()
    check_ring_ef_resend()
    print("ALL FAULT CHECKS PASSED")
