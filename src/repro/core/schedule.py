"""CommSchedule: fused, backward-ordered streaming of compressed buckets.

A UnitPlan says *what* the compression units are and batches them into
per-size-class dispatches; it says nothing about *when* the wire sees each
one. Real frameworks do two things the plan alone does not model (Agarwal
et al., "On the Utility of Gradient Compression in Distributed Training
Systems"; Horovod fusion buffers; commfuser's fusion/scheduling passes):

  1. they launch communication for LATE layers while EARLY layers are
     still in backward (gradients arrive in reverse leaf order), and
  2. they FUSE small tensors into one wire message so per-message latency
     (the alpha term) is paid once per buffer, not once per tensor.

A `CommSchedule` compiles both decisions from a plan, statically:

  build_schedule(plan, fusion_bytes)
      -> order   : bucket indices by backward-readiness (Bucket.ready,
                   derived from the treedef's reverse leaf order)
      -> messages: consecutive ready buckets greedily packed until a
                   message's dense bytes reach `fusion_bytes`
                   (0 = one message per bucket; math.inf = one message)

and `schedule.execute(fn, grads, key)` runs the plan's per-bucket batched
dispatches message by message in that order, pinning program order with
`lax.optimization_barrier` so message i's compress -> collective ->
decompress pipeline is issued before message i+1's compression begins
(the streaming contract; XLA may still *overlap* them — the barrier only
forbids reordering message i+1's work ahead of message i's).

Numerical contract: scheduling NEVER changes numerics. Every bucket runs
the identical batched dispatch with the identical per-unit PRNG keys as
`UnitPlan.execute`; only program order differs, bucket outputs land in
disjoint regions, and the barrier is an identity — so the scheduled path
is bit-identical to the unscheduled one. tests/test_schedule.py holds
this property over the operator zoo x granularities x fusion thresholds.

`simulate_schedule` is the deterministic alpha-beta cost model: per
message, comm time = alpha + wire_bytes / bandwidth, overlapped against a
backward pass that emits leaves in reverse order and a sequential
compression stream. It reports exposed-vs-overlapped comm time. It is a
MODEL, not a measurement — wall-clocks on a shared container are noisy;
trust the message/dispatch counts and use the model for relative
comparisons (entire-model vs per-bucket vs fused) only.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import Bucket, UnitPlan

Array = jax.Array

#: fusion_bytes sentinel: never close a message — everything fuses into one.
FUSE_ALL = math.inf

def _order_after(xs: List[Array], token: Optional[Array]) -> List[Array]:
    """Identity on `xs` that the compiler may not hoist above `token`
    (the previous message's output): one optimization_barrier tying them
    together. token=None (first message) is a no-op."""
    if token is None:
        return xs
    out = jax.lax.optimization_barrier(tuple(xs) + (token,))
    return list(out[:-1])


@dataclasses.dataclass(frozen=True)
class Message:
    """One wire message: a readiness-ordered group of fused buckets.

    `bucket_ids` index the plan's buckets (dispatch order inside the
    message). `nbytes` is the dense f32 payload the fusion decision was
    made on; `ready` the backward-readiness rank of the LAST bucket to
    become available (the message can only depart then).
    """
    bucket_ids: Tuple[int, ...]
    nbytes: int
    ready: int

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_ids)


@dataclasses.dataclass(frozen=True)
class CommSchedule:
    """Static communication schedule for one (UnitPlan, fusion_bytes).

    Frozen + tuples (and a hashable UnitPlan) => hashable, so a schedule
    — like the plan it wraps — is a valid static argument under jit and a
    safe cache key (the controller's decision -> compiled-step cache keys
    on the decision's `fusion_bytes`, which resolves to one of these).
    """
    plan: UnitPlan
    fusion_bytes: float
    order: Tuple[int, ...]          # bucket indices, backward-ready first
    messages: Tuple[Message, ...]

    @property
    def num_messages(self) -> int:
        return len(self.messages)

    def summary(self) -> str:
        ms = ", ".join(f"{m.n_buckets}b/{m.nbytes >> 10}KiB"
                       for m in self.messages)
        fb = ("inf" if math.isinf(self.fusion_bytes)
              else f"{int(self.fusion_bytes)}")
        return (f"CommSchedule(fuse<{fb}B: {self.num_messages} messages "
                f"over {self.plan.num_dispatches} dispatches [{ms}])")

    # ---- execution -------------------------------------------------------
    def execute(self, fn: Callable[[Array, Array], Array], grads,
                key: Array, *, wire=None, wire_key=None, recorder=None,
                faults=None):
        """UnitPlan.execute, streamed: identical per-bucket dispatches and
        PRNG keys, issued message by message in backward-ready order with
        an ordering barrier between consecutive messages. Bit-identical
        output (the equivalence harness's subject).

        `wire` (a core.wire.WireCodec) switches to REAL wire buffers:
        each message's units are encoded to bit-packed payloads and
        concatenated into ONE uint8 buffer (header table of per-bucket
        byte offsets), decoding reads back out of the buffer, and the
        inter-message barrier pins on the buffer itself. In wire mode
        `fn` is the post-decode closure fn(payload_row, xhat_row,
        unit_key) -> y (None = return the decoded gradient), `wire_key`
        optionally transforms the unit key for the encode leg (the
        worker-key fold), and the return value is (tree, buffers) —
        sum(8 * b.size) over `buffers` is the measured wire truth.
        Because every codec round-trips bit-exactly to its compressor's
        `sim`, wire mode never changes numerics either.

        `recorder` (duck-typed, obs.trace.TraceRecorder) instruments the
        stream with per-message spans (or per-stage spans in wire mode);
        None or a disabled recorder leaves the traced graph untouched —
        the zero-overhead contract tests/test_obs.py compares jaxprs
        over.

        `faults` (duck-typed, resil.FaultInjector; wire mode only)
        corrupts each message's received bytes after pack — see
        core.wire.execute_schedule_wire."""
        if wire is not None:
            from repro.core.wire import execute_schedule_wire
            return execute_schedule_wire(self, wire, fn, grads, key,
                                         wire_key=wire_key,
                                         recorder=recorder, faults=faults)
        if faults is not None:
            raise ValueError("fault injection needs the wire path "
                             "(wire=codec): faults act on packed bytes")
        rec = (recorder if recorder is not None
               and getattr(recorder, "enabled", False) else None)
        plan = self.plan
        leaves = jax.tree_util.tree_leaves(grads)
        flat = plan.flatten(grads) if plan.needs_flat else None
        keys = plan.unit_keys(key)
        out_leaves = [None] * len(leaves)
        out_flat = (jnp.zeros((plan.exec_total,), jnp.float32)
                    if flat is not None else None)
        if rec is not None and leaves:
            rec.begin(leaves[0], label="grads_ready")
        token = None
        for mi, msg in enumerate(self.messages):
            ys: List[Tuple[Bucket, Array]] = []
            xs = [plan._gather_runs(leaves, flat, plan.buckets[bi])
                  for bi in msg.bucket_ids]
            xs = _order_after(xs, token)
            if rec is not None:
                with rec.scope(f"repro/msg{mi}"):
                    for bi, x in zip(msg.bucket_ids, xs):
                        b = plan.buckets[bi]
                        ys.append((b, plan._dispatch(fn, b, x, keys)))
                rec.mark([y for _, y in ys], "message", cat="message",
                         message=mi, bucket_ids=msg.bucket_ids,
                         dims=tuple(plan.buckets[bi].dim
                                    for bi in msg.bucket_ids),
                         n_units=sum(plan.buckets[bi].n
                                     for bi in msg.bucket_ids))
            else:
                for bi, x in zip(msg.bucket_ids, xs):
                    b = plan.buckets[bi]
                    ys.append((b, plan._dispatch(fn, b, x, keys)))
            token = ys[-1][1]
            for b, y in ys:
                out_flat = plan._scatter_runs(out_leaves, out_flat, b, y)
        return plan._assemble(out_leaves, out_flat)

    def execute_with_state(self, fn, grads, state, key: Array, *,
                           wire=None, wire_key=None, recorder=None,
                           faults=None):
        """UnitPlan.execute_with_state, streamed (error-feedback memory
        threads through untouched by ordering/fusion: every unit's state
        row is read and written exactly once, in whichever message its
        bucket landed).

        `wire` routes through real buffers exactly as in `execute`; the
        EF discipline is fixed to e = x + m, m' = e - decode(payload)
        (bit-identical to the unpacked path by the round-trip property),
        `fn` is the post-decode closure (or None), and the return value
        grows to (tree, m_tree, buffers). `faults` (wire mode only)
        corrupts received bytes; the EF residual stays sender-side
        clean — see core.wire.execute_schedule_wire_with_state."""
        if wire is not None:
            from repro.core.wire import execute_schedule_wire_with_state
            return execute_schedule_wire_with_state(
                self, wire, fn, grads, state, key, wire_key=wire_key,
                recorder=recorder, faults=faults)
        if faults is not None:
            raise ValueError("fault injection needs the wire path "
                             "(wire=codec): faults act on packed bytes")
        rec = (recorder if recorder is not None
               and getattr(recorder, "enabled", False) else None)
        plan = self.plan
        leaves = jax.tree_util.tree_leaves(grads)
        need = plan.needs_flat
        flat = plan.flatten(grads) if need else None
        mflat = plan.flatten(state) if need else None
        keys = plan.unit_keys(key)
        out_leaves = [None] * len(leaves)
        mout_leaves = [None] * len(leaves)
        out_flat = (jnp.zeros((plan.exec_total,), jnp.float32)
                    if need else None)
        mout_flat = (jnp.zeros((plan.exec_total,), jnp.float32)
                     if need else None)
        sleaves = jax.tree_util.tree_leaves(state)
        if rec is not None and leaves:
            rec.begin(leaves[0], label="grads_ready")
        token = None
        for mi, msg in enumerate(self.messages):
            pairs = []
            for bi in msg.bucket_ids:
                b = plan.buckets[bi]
                pairs.append(plan._gather_runs(leaves, flat, b))
                pairs.append(plan._gather_runs(sleaves, mflat, b))
            pairs = _order_after(pairs, token)
            ys = []
            if rec is not None:
                with rec.scope(f"repro/msg{mi}"):
                    for j, bi in enumerate(msg.bucket_ids):
                        b = plan.buckets[bi]
                        x, m = pairs[2 * j], pairs[2 * j + 1]
                        y, mn = plan._dispatch_with_state(fn, b, x, m,
                                                          keys)
                        ys.append((b, y, mn))
                rec.mark([y for _, y, _ in ys]
                         + [mn for _, _, mn in ys],
                         "message", cat="message", message=mi,
                         bucket_ids=msg.bucket_ids,
                         dims=tuple(plan.buckets[bi].dim
                                    for bi in msg.bucket_ids),
                         n_units=sum(plan.buckets[bi].n
                                     for bi in msg.bucket_ids))
            else:
                for j, bi in enumerate(msg.bucket_ids):
                    b = plan.buckets[bi]
                    x, m = pairs[2 * j], pairs[2 * j + 1]
                    y, mn = plan._dispatch_with_state(fn, b, x, m, keys)
                    ys.append((b, y, mn))
            token = ys[-1][1]
            for b, y, mn in ys:
                out_flat = plan._scatter_runs(out_leaves, out_flat, b, y)
                mout_flat = plan._scatter_runs(mout_leaves, mout_flat, b,
                                               mn)
        return (plan._assemble(out_leaves, out_flat),
                plan._assemble(mout_leaves, mout_flat))

    def execute_streaming(self, post, grads, key: Array, *, wire,
                          axis_names, n_workers: int, mode: str = "ring",
                          wire_key=None, chunk_bytes=None, recorder=None,
                          faults=None):
        """Execute the schedule through a REAL streaming collective: a
        chunked-ppermute ring (mode='ring') or a compress→reduce-scatter→
        allgather shard stream (mode='rs') under shard_map, double-
        buffered so message i+1's fused compress+pack kernels are emitted
        before message i's hops complete. Must run inside shard_map over
        a single DP axis. `wire` is the WireCodec; `post(xm_row,
        unit_key)` the master-compression closure applied to the
        cross-worker mean (None returns the mean); `chunk_bytes` the
        per-hop dispatch granularity (None = whole-message hops).
        Returns (tree, buffers). mode='ring' is bit-identical to
        `execute(..., wire=...)` under the allgather strategy — the
        correctness contract tests/test_stream.py holds differentially.
        See core.wire.execute_schedule_stream for the full mechanics
        (including `faults`, the per-hop corruption injector)."""
        from repro.core.wire import execute_schedule_stream
        return execute_schedule_stream(
            self, wire, post, grads, None, key, axis_names=axis_names,
            n_workers=n_workers, mode=mode, wire_key=wire_key,
            chunk_bytes=chunk_bytes, recorder=recorder, faults=faults)

    def execute_streaming_with_state(self, post, grads, state, key: Array,
                                     *, wire, axis_names, n_workers: int,
                                     mode: str = "ring", wire_key=None,
                                     chunk_bytes=None, recorder=None,
                                     faults=None):
        """Error-feedback twin of execute_streaming: e = x + m is
        encoded, m' = e - decode(own payload) — the same local EF
        discipline as the serialized wire path (EF never depends on the
        collective topology; under mode='rs' only the owned shard slice
        of each residual row is live). Returns (tree, m_tree,
        buffers)."""
        from repro.core.wire import execute_schedule_stream
        return execute_schedule_stream(
            self, wire, post, grads, state, key, axis_names=axis_names,
            n_workers=n_workers, mode=mode, wire_key=wire_key,
            chunk_bytes=chunk_bytes, recorder=recorder, faults=faults)


# ==========================================================================
# schedule construction
# ==========================================================================

@functools.lru_cache(maxsize=256)
def build_schedule(plan: UnitPlan, fusion_bytes: float) -> CommSchedule:
    """Compile the (cached) CommSchedule for a plan.

    Buckets are taken in backward-readiness order and greedily packed into
    messages Horovod-fusion-buffer style: a message accumulates buckets
    until its dense bytes reach `fusion_bytes`, then closes.

      fusion_bytes == 0        one message per bucket (no fusion; the wire
                               sees exactly the plan's dispatches)
      fusion_bytes == FUSE_ALL one message for everything (the
                               entire-model latency picture even when
                               compression stays layer-wise)

    Free (trace-time) like build_plan: pure Python on static metadata.
    """
    fb = float(fusion_bytes)
    if math.isnan(fb) or fb < 0:
        raise ValueError(f"fusion_bytes must be >= 0, got {fusion_bytes!r}")
    order = plan.readiness_order()
    messages: List[Message] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_ready = 0
    for bi in order:
        b = plan.buckets[bi]
        cur.append(bi)
        cur_bytes += b.nbytes
        cur_ready = max(cur_ready, b.ready)
        if cur_bytes >= fb:
            messages.append(Message(tuple(cur), cur_bytes, cur_ready))
            cur, cur_bytes, cur_ready = [], 0, 0
    if cur:
        messages.append(Message(tuple(cur), cur_bytes, cur_ready))
    return CommSchedule(plan=plan, fusion_bytes=fb, order=order,
                        messages=tuple(messages))


# ==========================================================================
# alpha-beta cost model
# ==========================================================================

def message_wire_bits(schedule: CommSchedule, qw=None,
                      bucket_bits: Optional[Sequence[int]] = None
                      ) -> List[int]:
    """Per-message wire payload bits. With a compressor `qw`, each bucket
    contributes n_units * qw.payload_bits(dim) (the allgather-strategy
    payload); `bucket_bits` overrides with measured/externally-computed
    per-bucket bits (telemetry's view, e.g. under per-dim ratio
    overrides); with neither, dense f32."""
    plan = schedule.plan
    if bucket_bits is not None:
        if len(bucket_bits) != len(plan.buckets):
            raise ValueError(
                f"bucket_bits has {len(bucket_bits)} entries, plan has "
                f"{len(plan.buckets)} buckets")
        per_bucket = [int(v) for v in bucket_bits]
    elif qw is not None:
        per_bucket = [b.n * qw.payload_bits(b.dim) for b in plan.buckets]
    else:
        per_bucket = [32 * b.n * b.dim for b in plan.buckets]
    return [sum(per_bucket[bi] for bi in m.bucket_ids)
            for m in schedule.messages]


def simulate_schedule(schedule: CommSchedule, *, qw=None,
                      bucket_bits: Optional[Sequence[int]] = None,
                      alpha_us: float = 50.0, gbps: float = 12.5,
                      compress_gbps: float = 25.0,
                      backward_us: Optional[float] = None) -> Dict:
    """Deterministic alpha-beta pipeline simulation of one step's comm.

    Model (two streams, one network channel):

      * backward emits gradient leaves in reverse leaf order, uniformly
        over `backward_us` (default: 2x the time to stream the dense
        gradient at `compress_gbps` — a stand-in, not a measurement);
        message m's inputs are complete at backward_us*(ready+1)/n_leaves.
      * the compute stream compresses messages sequentially in schedule
        order: compress(m) = dense_bytes(m) / compress_gbps.
      * the network sends message m for alpha_us + wire_bytes(m)/gbps,
        starting when BOTH its compression is done and the previous
        message has left the wire.

    Returns totals + per-message timelines, including `exposed_comm_us`
    (comm time not hidden behind backward+compression) and
    `overlap_frac`. All numbers are MODEL outputs: on this container
    wall-clocks are too noisy to validate them — trust the message and
    dispatch counts, and use the model only for relative comparisons
    (entire-model vs per-bucket vs fused layer-wise).
    """
    plan = schedule.plan
    n_leaves = max(1, plan.num_leaves)
    dense_bytes = 4 * plan.exec_total
    if backward_us is None:
        backward_us = 2.0 * dense_bytes / (compress_gbps * 1e3)
    wire = message_wire_bits(schedule, qw=qw, bucket_bits=bucket_bits)

    msgs = []
    c = 0.0        # compute-stream head (compression)
    e = 0.0        # network-stream head
    comm_sum = 0.0
    for m, bits in zip(schedule.messages, wire):
        ready_us = backward_us * (m.ready + 1) / n_leaves
        c = max(c, ready_us) + m.nbytes / (compress_gbps * 1e3)
        send_us = alpha_us + (bits / 8.0) / (gbps * 1e3)
        start = max(c, e)
        e = start + send_us
        comm_sum += send_us
        msgs.append({"n_buckets": m.n_buckets, "dense_bytes": m.nbytes,
                     "wire_bits": bits, "ready_rank": m.ready,
                     "ready_us": round(ready_us, 3),
                     "compressed_us": round(c, 3),
                     "sent_us": round(e, 3)})
    compute_end = max(backward_us, c)
    total = max(e, compute_end)
    exposed = max(0.0, total - compute_end)
    return {
        "n_messages": schedule.num_messages,
        "n_dispatches": plan.num_dispatches,
        "fusion_bytes": (None if math.isinf(schedule.fusion_bytes)
                         else schedule.fusion_bytes),
        "alpha_us": alpha_us, "gbps": gbps,
        "compress_gbps": compress_gbps,
        "backward_us": round(backward_us, 3),
        "wire_bits_total": int(sum(wire)),
        "comm_us_total": round(comm_sum, 3),
        "t_total_us": round(total, 3),
        "exposed_comm_us": round(exposed, 3),
        "overlap_frac": round(1.0 - exposed / comm_sum, 4) if comm_sum
        else 1.0,
        "messages": msgs,
    }
