"""WireCodec: the differential suite locking accounting to the wire.

Two load-bearing properties:

  1. ROUND-TRIP: codec.decode(codec.encode(x, key)) is BIT-identical to
     compressor.sim(x, key) for every codec-bearing operator — so
     routing execution through materialized payloads never changes
     numerics (held over granularities, fusion thresholds, error
     feedback, the collective strategies and the engine step).
  2. ACCOUNTING == WIRE: 8 * len(packed payload) equals
     compressor.payload_bits(d) + the documented per-codec word-padding
     slack, EXACTLY, for all six compressors at both granularities —
     the analytic accounting can never silently drift from the bytes a
     deployment would put on the links again.

The full sweeps carry the `wire` marker (tier-1 only; `make verify-fast`
keeps the unmarked smoke subset).
"""
import math

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CompressionConfig, FUSE_ALL, Granularity,
                        aggregate_simulated_workers, build_plan,
                        build_schedule, comm_report, compressed_allreduce,
                        index_bits, make_compressor,
                        measured_bits_from_payloads, message_layouts,
                        stacked_mask, wire_codec, word_padding)
from repro.core.compressors import _k_of
from repro.core.wire import has_wire_codec

KEY = jax.random.key(0)

# the paper's six operators (ISSUE: "all six compressors"), one codec each
SIX = [
    ("topk", {"ratio": 0.25}),
    ("randomk", {"ratio": 0.3, "scale": True}),
    ("qsgd", {"levels": 16}),
    ("terngrad", {}),
    ("signsgd", {}),
    ("natural", {}),
]

GRANS = [Granularity("layerwise"), Granularity("entire_model")]

# ISSUE fusion matrix: per-bucket messages, 64 KiB buffers, one message
THRESHOLDS = (0.0, float(1 << 16), FUSE_ALL)


def _tree(key=KEY):
    """Mixed pytree: scan-stacked + loose leaves of several size classes
    (odd dims exercise word-boundary padding)."""
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    return {"blocks": {"w": jax.random.normal(ks[0], (3, 16, 8)),
                       "b": jax.random.normal(ks[1], (3, 8))},
            "embed": jax.random.normal(ks[2], (20, 4)),
            "head": jax.random.normal(ks[3], (4, 2)),
            "scalar_gain": jax.random.normal(ks[4], ())}


def _assert_trees_bitwise(a, b, ctx):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert la.shape == lb.shape and la.dtype == lb.dtype, ctx
        assert bool((la == lb).all()), (
            ctx, float(jnp.max(jnp.abs(la - lb))))


def _packed_leg_bits(name, kw, d):
    """The documented packed-leg width per codec (what word-padding
    rounds up): b-bit levels, 2-bit ternary, 1-bit signs, 9-bit natural
    codes, k * ceil(log2(d))-bit sparse indices."""
    if name == "qsgd":
        return max(2, math.ceil(math.log2(2 * kw["levels"] + 1))) * d
    if name == "terngrad":
        return 2 * d
    if name == "signsgd":
        return d
    if name == "natural":
        return 9 * d
    if name in ("topk", "randomk"):
        return _k_of(kw["ratio"], d) * index_bits(d)
    raise AssertionError(name)


# ---------------------------------------------------------------------------
# round-trip: decode(encode(x)) == sim(x), bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kw", SIX + [("identity", {})])
def test_roundtrip_bitexact(name, kw):
    c = make_compressor(name, **kw)
    codec = wire_codec(c)
    for d in (1, 33, 777):  # word-aligned and word-straddling sizes
        x = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
        payload = codec.encode(x, KEY)
        assert payload.dtype == jnp.uint8
        assert payload.shape == (codec.nbytes(d),)
        y = codec.decode(payload, d)
        _assert_trees_bitwise(y, c.sim(x, KEY), (name, d))


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=1025),
       st.integers(min_value=0, max_value=10_000),
       st.sampled_from([s[0] for s in SIX]),
       st.sampled_from([0.03, 0.25, 0.9]))
def test_property_roundtrip_bitexact(d, seed, name, ratio):
    """Random shapes (incl. odd sizes straddling uint32 word boundaries)
    and ratios: the packed wire round-trip is the simulated operator."""
    kw = {"ratio": ratio} if name in ("topk", "randomk") else {}
    c = make_compressor(name, **kw)
    codec = wire_codec(c)
    key = jax.random.key(seed)
    x = jax.random.normal(key, (d,)) * 3.0
    y = codec.decode(codec.encode(x, key), d)
    _assert_trees_bitwise(y, c.sim(x, key), (name, d, ratio))


def test_threshold_codecs_are_the_theory_practice_gap():
    """threshold_v / adaptive_threshold: the static wire format is
    capacity-bounded while sim is exact masking — the codec exists
    (round-tripping the compressor's own payload bit-exactly, i.e. the
    allgather wire), is flagged exact_sim=False, and the simulated-
    strategy wire path refuses it instead of silently changing numerics.
    """
    t = _tree()
    sm = stacked_mask(t)
    for name in ("threshold_v", "adaptive_threshold"):
        c = make_compressor(name)
        codec = wire_codec(c)
        assert codec.exact_sim is False
        x = jax.random.normal(KEY, (100,))
        y = codec.decode(codec.encode(x, KEY), 100)
        _assert_trees_bitwise(y, c.decode(c.encode(x, KEY), 100), name)
        cfg = CompressionConfig(qw=c, granularity=Granularity("layerwise"),
                                strategy="simulated")
        with pytest.raises(ValueError, match="capacity-bounded"):
            compressed_allreduce(t, sm, cfg, ("data",), KEY, 1, wire=True)
    assert has_wire_codec(make_compressor("topk"))
    from repro.core.compressors import Compressor
    assert not has_wire_codec(Compressor(name="mystery"))


# ---------------------------------------------------------------------------
# accounting == measured, exactly (modulo documented word padding)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kw", SIX)
def test_accounted_vs_measured_per_unit(name, kw):
    """8 * len(packed payload) == payload_bits(d) + word_padding(packed
    leg bits), for word-aligned and straddling dims — the slack is never
    anything but the documented pad-to-uint32 rule (< 32 bits/leg)."""
    c = make_compressor(name, **kw)
    codec = wire_codec(c)
    for d in (1, 5, 31, 32, 33, 64, 100, 511, 512, 777):
        x = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
        measured = measured_bits_from_payloads(codec.encode(x, KEY))
        slack = word_padding(_packed_leg_bits(name, kw, d))
        assert measured == c.payload_bits(d) + slack, (name, d)
        assert measured == codec.wire_bits(d), (name, d)
        assert codec.padding_bits(d) == slack < 32, (name, d)


def test_dense_codec_has_zero_padding():
    codec = wire_codec(make_compressor("identity"))
    for d in (1, 37, 512):
        assert codec.padding_bits(d) == 0
        assert codec.wire_bits(d) == 32 * d


def test_comm_report_measured_flag():
    """comm_report(measured=True) - comm_report() == the summed per-unit
    padding slack — the accounting and the wire agree exactly."""
    t = _tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    for name, kw in SIX:
        c = make_compressor(name, **kw)
        codec = wire_codec(c)
        cfg = CompressionConfig(qw=c, granularity=Granularity("layerwise"),
                                strategy="allgather")
        acct = comm_report(cfg, plan, 4)
        meas = comm_report(cfg, plan, 4, measured=True)
        slack = sum(codec.padding_bits(d) for d in plan.unit_dims)
        assert meas.uplink_bits_per_worker == \
            acct.uplink_bits_per_worker + slack, name
        assert meas.downlink_bits_per_worker == \
            acct.downlink_bits_per_worker + 3 * slack, name


# ---------------------------------------------------------------------------
# the differential suite: executed fused messages vs the accounting
# ---------------------------------------------------------------------------

def _check_differential(name, kw, gran, fb):
    t = _tree()
    sm = stacked_mask(t)
    c = make_compressor(name, **kw)
    codec = wire_codec(c)
    plan = build_plan(t, sm, gran)
    sched = build_schedule(plan, fb)

    # numerics: wire streaming == the unscheduled unpacked reference
    ref = plan.execute(lambda x, k: c.sim(x, k), t, KEY)
    got, bufs = sched.execute(None, t, KEY, wire=codec)
    _assert_trees_bitwise(ref, got, (name, gran.kind, fb))

    # wire truth: executed buffer bytes == static layouts == accounting
    layouts = message_layouts(sched, codec)
    assert len(bufs) == sched.num_messages
    for buf, lay in zip(bufs, layouts):
        assert buf.size == lay.total_nbytes
        # the header is readable back out of the buffer
        header = jax.lax.bitcast_convert_type(
            buf[:lay.header_nbytes].reshape(-1, 4), jnp.uint32)
        assert int(header[0]) == len(lay.bucket_ids)
        assert tuple(int(v) for v in header[1:]) == lay.offsets
    measured = measured_bits_from_payloads(bufs)
    header_bits = 8 * sum(l.header_nbytes for l in layouts)
    payload_bits = 8 * sum(l.payload_nbytes for l in layouts)
    assert measured == payload_bits + header_bits

    # accounted == measured payload, exactly (modulo documented padding)
    cfg = CompressionConfig(qw=c, granularity=gran, strategy="allgather")
    acct = comm_report(cfg, plan, 2).uplink_bits_per_worker
    slack = sum(codec.padding_bits(d) for d in plan.unit_dims)
    assert payload_bits == acct + slack, (name, gran.kind, fb)
    assert payload_bits == comm_report(
        cfg, plan, 2, measured=True).uplink_bits_per_worker


def test_differential_smoke():
    """Inner-loop subset of the full `wire`-marked sweep."""
    for name, kw in (("qsgd", {"levels": 16}), ("topk", {"ratio": 0.25})):
        for fb in (0.0, FUSE_ALL):
            _check_differential(name, kw, Granularity("layerwise"), fb)


@pytest.mark.wire
@pytest.mark.parametrize("name,kw", SIX)
def test_differential_full(name, kw):
    """The acceptance sweep: all six compressors x {layerwise,
    entire_model} x fusion {0, 64KiB, inf} — accounted payload bits ==
    measured packed bytes, and wire numerics == unpacked numerics,
    everywhere."""
    for gran in GRANS:
        for fb in THRESHOLDS:
            _check_differential(name, kw, gran, fb)


# ---------------------------------------------------------------------------
# wire execution == unpacked execution through the aggregation stack
# ---------------------------------------------------------------------------

def _run_ef_steps(name, kw, wire, fusion_bytes=None, steps=5):
    t = _tree()
    sm = stacked_mask(t)
    n = 2
    cfg = CompressionConfig(qw=make_compressor(name, **kw),
                            granularity=Granularity("layerwise"),
                            error_feedback=True,
                            fusion_bytes=fusion_bytes)
    ef = jax.tree_util.tree_map(
        lambda x: jnp.zeros((n,) + x.shape, jnp.float32), t)
    out = None
    for i in range(steps):
        wg = jax.tree_util.tree_map(
            lambda x: jnp.stack([x * (1.0 + 0.1 * i), -0.5 * x]), t)
        out, ef = aggregate_simulated_workers(
            wg, sm, cfg, jax.random.fold_in(KEY, i), ef_state=ef,
            wire=wire)
    return out, ef


def test_wire_matches_unpacked_ef_smoke():
    """5 steps of Algorithm 1 with error-feedback threading: the wire
    path's outputs AND residual memories stay bit-identical."""
    ref = _run_ef_steps("topk", {"ratio": 0.1}, wire=False)
    got = _run_ef_steps("topk", {"ratio": 0.1}, wire=True)
    _assert_trees_bitwise(ref, got, "ef-wire-smoke")


@pytest.mark.wire
@pytest.mark.parametrize("name,kw", SIX)
def test_wire_matches_unpacked_ef_full(name, kw):
    """All six compressors x 5 EF steps x {per-bucket, fused} wire
    messages: bit-identical to the unpacked path."""
    ref = _run_ef_steps(name, kw, wire=False)
    for fb in (None, FUSE_ALL):
        got = _run_ef_steps(name, kw, wire=True, fusion_bytes=fb)
        _assert_trees_bitwise(ref, got, (name, fb))


def test_collective_wire_paths_bit_identical():
    """compressed_allreduce inside shard_map: wire=True matches the
    unpacked path for BOTH strategies — under `allgather` the packed
    uint8 payload itself crosses the collective."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh
    t = _tree()
    sm = stacked_mask(t)
    mesh = make_host_mesh(1, 1)
    for strat in ("simulated", "allgather"):
        cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                                granularity=Granularity("layerwise"),
                                strategy=strat)

        def run(wire):
            def f(g):
                out, _ = compressed_allreduce(g, sm, cfg, ("data",), KEY,
                                              1, wire=wire)
                return out
            return jax.jit(shard_map(f, mesh, in_specs=(P(),),
                                     out_specs=P()))(t)

        _assert_trees_bitwise(run(False), run(True), strat)


def test_engine_wire_step_bit_identical():
    """Acceptance: the sharded train step with wire=True is bit-for-bit
    the unpacked step (real message buffers in the compiled graph)."""
    from repro.configs.registry import get_smoke
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh
    cfg = get_smoke("mamba2-1.3b")
    mesh = make_host_mesh(1, 1)
    comp = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                             granularity=Granularity("layerwise"))
    eng = Engine(cfg, mesh, comp=comp)
    batch = {"tokens": jnp.ones((4, 16), jnp.int32) * 3,
             "targets": jnp.ones((4, 16), jnp.int32) * 5}

    def run(step_fn):
        params, opt_state = eng.init_state(0)
        for i in range(2):
            params, opt_state, m = step_fn(params, opt_state, batch,
                                           jnp.int32(i))
        return params, m

    p_ref, m_ref = run(eng.build_train_step())
    p_w, m_w = run(eng.build_train_step(wire=True))
    _assert_trees_bitwise(p_ref, p_w, "engine-wire")
    assert float(m_ref["loss"]) == float(m_w["loss"])


# ---------------------------------------------------------------------------
# codec specifics
# ---------------------------------------------------------------------------

def test_signsgd_majority_vote_on_packed_words():
    """The real signSGD aggregation protocol: majority vote computed on
    packed payloads (dense worker vectors never materialize on the
    master) equals the dense sign-of-sum, ties resolving to +1."""
    codec = wire_codec(make_compressor("signsgd"))
    d = 77
    for n in (2, 3, 5):  # even n exercises the tie
        xs = jax.random.normal(jax.random.fold_in(KEY, n), (n, d))
        payloads = jax.vmap(lambda x: codec.encode(x, KEY))(xs)
        assert payloads.shape == (n, codec.nbytes(d))
        maj = codec.decode(codec.majority_vote(payloads, d), d)
        signs = jnp.where(xs >= 0, 1.0, -1.0)
        dense = jnp.where(jnp.sum(signs, axis=0) >= 0, 1.0, -1.0)
        _assert_trees_bitwise(maj, dense, n)


def test_pallas_pack_kernels_match_oracle():
    """kernels/pack.py at width 1 vs the kernels/ref.py bit oracle:
    bit-for-bit, both directions, and the ops wrappers' pallas/jnp paths
    agree on odd lengths."""
    from repro.kernels import ops
    from repro.kernels.pack import fields_pack_pallas, fields_unpack_pallas
    from repro.kernels.ref import pack_bits_ref, unpack_bits_ref
    bits = jax.random.bernoulli(KEY, 0.4, (64, 512)).astype(jnp.int32)
    w_ref = pack_bits_ref(bits).reshape(8, 128)
    w_pal = fields_pack_pallas(bits, 1, interpret=True)
    assert bool((w_ref == w_pal).all())
    assert bool((fields_unpack_pallas(w_pal, 1, interpret=True)
                 == bits).all())
    assert bool((unpack_bits_ref(w_ref) == bits.reshape(8, -1)).all())
    for n in (1, 31, 33, 777, 4096):
        flat = jax.random.bernoulli(jax.random.fold_in(KEY, n), 0.5,
                                    (n,)).astype(jnp.int32)
        a = ops.pack_fields(flat, 1, use_pallas=False)
        b = ops.pack_fields(flat, 1, use_pallas=True)
        assert a.shape == (-(-n // 32),) and bool((a == b).all()), n
        assert bool((ops.unpack_fields(a, n, 1) == flat).all())


def test_pallas_codec_entire_model():
    """The Pallas codec through the 1-unit entire-model schedule (the
    non-vmapped hot path): still bit-identical to sim."""
    t = _tree()
    sm = stacked_mask(t)
    c = make_compressor("qsgd", levels=16)
    codec = wire_codec(c)
    plan = build_plan(t, sm, Granularity("entire_model"))
    sched = build_schedule(plan, 0.0)
    ref = plan.execute(lambda x, k: c.sim(x, k), t, KEY)
    got, bufs = sched.execute(None, t, KEY, wire=codec)
    _assert_trees_bitwise(ref, got, "pallas-codec")
    assert measured_bits_from_payloads(bufs) == \
        8 * message_layouts(sched, codec)[0].total_nbytes


def test_telemetry_wire_bits_leg():
    """summarize() reports both the accounted and the measured
    (wire_bits) payload legs; payload_bits_per_step defaults to the
    measured one and the two differ by exactly the padding slack."""
    from repro.control.telemetry import (measure, measurement_plan,
                                         payload_bits_per_step, summarize)
    t = _tree()
    sm = stacked_mask(t)
    mplan = measurement_plan(t, sm)
    qw = make_compressor("signsgd")
    codec = wire_codec(qw)
    inc = measure(mplan, qw, t, KEY)
    s = summarize(inc, mplan, qw=qw)
    slack = sum(b.n * codec.padding_bits(b.dim) for b in mplan.buckets)
    assert s["wire_bits_per_step"] == s["payload_bits_per_step"] + slack
    for e in s["buckets"]:
        assert e["wire_bits"] >= e["payload_bits"]
    assert payload_bits_per_step(mplan, qw) == s["wire_bits_per_step"]
    assert payload_bits_per_step(mplan, qw, measured=False) == \
        s["payload_bits_per_step"]


def test_wire_refuses_unwireable_configs():
    t = _tree()
    sm = stacked_mask(t)
    cfg = CompressionConfig(qw=make_compressor("randomk", ratio=0.1),
                            strategy="shared_random")
    with pytest.raises(ValueError, match="simulated/allgather"):
        compressed_allreduce(t, sm, cfg, ("data",), KEY, 1, wire=True)
    # bf16 value legs exist only on the dense/sparse codecs — a
    # quantized-code codec has no f32 records to halve
    with pytest.raises(ValueError, match="bfloat16"):
        wire_codec(make_compressor("qsgd", levels=16),
                   wire_dtype="bfloat16")
    # and the lossy cast breaks strategy='simulated''s exact-operator
    # promise (allgather carries it fine — see the bf16 suite below)
    bf_sim = CompressionConfig(qw=make_compressor("topk", ratio=0.1),
                               strategy="simulated",
                               wire_dtype="bfloat16")
    with pytest.raises(ValueError, match="bit-exact"):
        compressed_allreduce(t, sm, bf_sim, ("data",), KEY, 1, wire=True)
    with pytest.raises(ValueError, match="dense"):  # not silently ignored
        compressed_allreduce(t, sm, CompressionConfig(strategy="dense"),
                             ("data",), KEY, 1, wire=True)


# ==========================================================================
# bfloat16 wire payloads (wire_dtype="bfloat16"): the value legs of the
# dense and sparse codecs ship as bf16 — HALF the f32 value bits — via
# the to_f32/to_bf16 cast idiom. The wire contract becomes decode(x) ==
# sim(x).astype(bf16).astype(f32) BIT for bit (a well-defined lossy
# reference), and the accounting contract stays exact: 8 * packed bytes
# == wire_bits(d) with the bf16 leg padded to the uint32 word.
# ==========================================================================

BF16_CODECS = [
    ("identity", {}),                       # dense f32 values -> bf16
    ("topk", {"ratio": 0.25}),              # sparse values + f32 indices
    ("randomk", {"ratio": 0.3, "scale": True}),
]


def _bf16_reference(comp, x, key):
    return comp.sim(x, key).astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("d", [8, 33, 256])
@pytest.mark.parametrize("name,kw", BF16_CODECS, ids=[n for n, _ in
                                                      BF16_CODECS])
def test_bf16_roundtrip_is_the_cast_reference(name, kw, d):
    comp = make_compressor(name, **kw)
    c16 = wire_codec(comp, wire_dtype="bfloat16")
    x = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
    p = c16.encode(x, KEY)
    y = c16.decode(p, d)
    ref = _bf16_reference(comp, x, KEY)
    assert y.dtype == jnp.float32
    assert bool((y == ref).all()), (name, d,
                                    float(jnp.max(jnp.abs(y - ref))))
    # accounting == wire, exactly, at the halved width
    assert 8 * p.size == c16.wire_bits(d)
    # the lossy cast stays within bf16 precision of the f32 operator
    exact = comp.sim(x, KEY)
    tol = 2.0 ** -8 * jnp.abs(exact) + 1e-30
    assert bool((jnp.abs(y - exact) <= tol).all())


@pytest.mark.parametrize("name,kw", BF16_CODECS, ids=[n for n, _ in
                                                      BF16_CODECS])
def test_bf16_halves_value_payload_bits(name, kw):
    d = 256
    comp = make_compressor(name, **kw)
    c32 = wire_codec(comp)
    c16 = wire_codec(comp, wire_dtype="bfloat16")
    assert c32.exact_sim and not c16.exact_sim
    if name == "identity":
        assert c16.payload_bits(d) == 16 * d == c32.payload_bits(d) // 2
    else:
        k = _k_of(kw["ratio"], d)
        assert c32.payload_bits(d) == k * (32 + index_bits(d))
        assert c16.payload_bits(d) == k * (16 + index_bits(d))
    assert c16.wire_bits(d) < c32.wire_bits(d)


@pytest.mark.wire
@pytest.mark.parametrize("name,kw", BF16_CODECS, ids=[n for n, _ in
                                                      BF16_CODECS])
def test_bf16_batch_entry_points_match_per_unit(name, kw):
    comp = make_compressor(name, **kw)
    c16 = wire_codec(comp, wire_dtype="bfloat16")
    d, n = 48, 5
    xs = jax.random.normal(KEY, (n, d))
    keys = jax.vmap(lambda i: jax.random.fold_in(KEY, i))(jnp.arange(n))
    pb = c16.encode_batch(xs, keys)
    yb = c16.decode_batch(pb, d)
    for i in range(n):
        p = c16.encode(xs[i], keys[i])
        assert bool((pb[i] == p).all()), (name, i)
        assert bool((yb[i] == c16.decode(p, d)).all()), (name, i)


def test_bf16_collective_matches_f32_path_cast():
    """End-to-end: the allgather collective with wire_dtype='bfloat16'
    returns exactly the bf16-cast of the f32 wire path's output on a
    1-worker mesh (mean over one worker is the identity, so the cast is
    the ONLY difference)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.engine import shard_map
    from repro.launch.mesh import make_host_mesh
    t = _tree()
    sm = stacked_mask(t)
    mesh = make_host_mesh(1, 1)
    qw = make_compressor("topk", ratio=0.25)

    def run(cfg):
        def f(g, key):
            out, _ = compressed_allreduce(g, sm, cfg, ("data",), key, 1,
                                          wire=True)
            return out
        return jax.jit(shard_map(f, mesh, in_specs=(P(), P()),
                                 out_specs=P()))(t, KEY)

    o32 = run(CompressionConfig(qw=qw, strategy="allgather"))
    o16 = run(CompressionConfig(qw=qw, strategy="allgather",
                                wire_dtype="bfloat16"))
    for l32, l16 in zip(jax.tree_util.tree_leaves(o32),
                        jax.tree_util.tree_leaves(o16)):
        ref = l32.astype(jnp.bfloat16).astype(jnp.float32)
        assert bool((ref == l16).all()), \
            float(jnp.max(jnp.abs(ref - l16)))


def test_bf16_cast_helpers_round_trip_exact_on_bf16_grid():
    """to_f32(to_bf16(x)) is exact when x already sits on the bf16 grid
    (the idiom's contract: casting down then up is a projection)."""
    from repro.core import to_bf16, to_f32
    t = _tree()
    once = to_f32(to_bf16(t))
    twice = to_f32(to_bf16(once))
    _assert_trees_bitwise(once, twice, "bf16 projection idempotent")
