"""Fused single-launch compress+pack kernels: the bit-exactness contract.

Three load-bearing properties:

  1. BYTE IDENTITY: the fused batch paths (kernels/ops.py *_pack_units /
     *_unpack_units, and the WireCodec fused=True batch entry points)
     produce payload bytes and decoded gradients BIT-identical to the
     legacy three-pass per-unit pipeline — on both the pallas and the
     pure-jnp fallback paths, at word-aligned and word-straddling sizes.
  2. SINGLE LAUNCH: a whole bucket's encode (or decode) is ONE
     pallas_call in the jaxpr — asserted structurally via
     ops.count_pallas_calls, not inferred from timings.
  3. TRAFFIC GATE: the kernel-spec bytes-moved accounting says the fused
     encode moves <= 1 f32 read + 1 packed-word write per element with
     ZERO intermediate bytes (the {0,1} bit tensor of the legacy path
     never exists), and the majority vote never unpacks.

Smoke subsets run unmarked; the full sweeps carry the `wire` marker
(tier-1 only, excluded by `make verify-fast`).
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import make_compressor, wire_codec
from repro.kernels import ops, prng, ref

KEY = jax.random.key(3)

# word-straddling and word-aligned unit dims, odd bucket sizes
SMOKE_SHAPES = [(64, 4), (513, 2), (700, 3)]
FULL_SHAPES = SMOKE_SHAPES + [(1, 1), (31, 7), (512, 1), (1300, 5),
                              (4096, 2)]

SIX = [
    ("topk", {"ratio": 0.25}),
    ("randomk", {"ratio": 0.3, "scale": True}),
    ("qsgd", {"levels": 16}),
    ("terngrad", {}),
    ("signsgd", {}),
    ("natural", {}),
]


def _bucket(d, n, seed=7):
    x = jax.random.normal(jax.random.fold_in(KEY, seed), (n, d))
    keys = jax.vmap(lambda i: jax.random.fold_in(KEY, i))(jnp.arange(n))
    return x, keys


def _legacy(codec):
    import dataclasses
    return dataclasses.replace(codec, fused=False)


def _assert_bitwise(a, b, ctx):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (ctx, a.shape, b.shape)
    assert np.array_equal(a, b), ctx


# ---------------------------------------------------------------------------
# in-kernel PRNG == jax.random (the uniforms the pack kernels draw)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 31, 512, 513, 1025])
def test_uniform_at_matches_jax_random(d):
    key = jax.random.fold_in(KEY, d)
    kd = jax.random.key_data(key).astype(jnp.uint32)
    pos = jnp.arange(d, dtype=jnp.int32)[None, :]
    u = prng.uniform_at(kd[0][None, None], kd[1][None, None], pos)
    _assert_bitwise(u[0], jax.random.uniform(key, (d,)), d)


# ---------------------------------------------------------------------------
# word-wise field packing == the legacy bit-expansion oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 2, 4, 9, 17])
@pytest.mark.parametrize("k", [5, 31, 32, 33, 700])
def test_pack_fields_matches_bitexpand(width, k):
    vals = jax.random.randint(jax.random.fold_in(KEY, k), (k,), 0,
                              1 << min(width, 30), dtype=jnp.int32)
    oracle = ref.pack_fields_bitexpand_ref(vals, width)
    for up in (False, True):
        words = ops.pack_fields(vals, width, use_pallas=up)
        _assert_bitwise(words, oracle, (width, k, up))
        _assert_bitwise(ops.unpack_fields(words, k, width, use_pallas=up),
                        vals, (width, k, up))


@pytest.mark.parametrize("width", [1, 2, 6, 9, 19])
def test_pack_fields_tile_matches_bitexpand(width):
    """A whole (64, 512) tile through the per-128-lane transposes every
    kernel body runs: its words, read row-major, are the bit-expansion
    oracle's stream of the fields read row-major, and unpacking gives
    the fields back."""
    f = jax.random.randint(jax.random.fold_in(KEY, width), (64, 512), 0,
                           1 << width, dtype=jnp.int32)
    words = ref.pack_fields_tile(f, width)
    _assert_bitwise(words.reshape(-1),
                    ref.pack_fields_bitexpand_ref(f.reshape(-1), width), width)
    _assert_bitwise(ref.unpack_fields_tile(words, width), f, width)


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=900),
       st.sampled_from([1, 2, 3, 4, 5, 9, 13, 17]),
       st.integers(min_value=0, max_value=10_000))
def test_property_pack_fields_roundtrip(k, width, seed):
    vals = jax.random.randint(jax.random.fold_in(KEY, seed), (k,), 0,
                              1 << min(width, 30), dtype=jnp.int32)
    words = ops.pack_fields(vals, width)
    _assert_bitwise(words, ref.pack_fields_bitexpand_ref(vals, width),
                    (k, width, seed))
    _assert_bitwise(ops.unpack_fields(words, k, width), vals,
                    (k, width, seed))


# ---------------------------------------------------------------------------
# fused ops == legacy per-unit wire pipeline, byte for byte
# ---------------------------------------------------------------------------

#: the kernels/ops.py entry points the codecs call; use_pallas=False
#: swaps each for its pure-jnp twin
_FUSED_OPS = ("qsgd_pack_units", "qsgd_unpack_units", "qsgd_unpack_ef_units",
              "terngrad_pack_units", "terngrad_unpack_units",
              "terngrad_unpack_ef_units", "sign_pack_units",
              "sign_unpack_units", "sign_unpack_ef_units",
              "fields_pack_units", "fields_unpack_units", "majority_words")


@contextlib.contextmanager
def _ops_path(use_pallas):
    """Run the fused codec's ops on the Pallas kernels or on their jnp
    twins."""
    saved = {name: getattr(ops, name) for name in _FUSED_OPS}
    try:
        if not use_pallas:
            for name, fn in saved.items():
                setattr(ops, name, functools.partial(fn, use_pallas=False))
        yield
    finally:
        for name, fn in saved.items():
            setattr(ops, name, fn)


def _unpack_fields_oracle(words, k, width):
    """Inverse of ref.pack_fields_bitexpand_ref through the bit tensor."""
    bits = ref.unpack_bits_ref(words[None])[0][:k * width].reshape(k, width)
    return (bits << jnp.arange(width, dtype=jnp.int32)).sum(axis=1)


@contextlib.contextmanager
def _oracle_packer():
    """The legacy per-unit wire path packs and unpacks its field legs
    through the bit-expansion oracle, so the reference shares no packing
    code with the fused kernels and their jnp twins."""
    saved = ops.pack_fields, ops.unpack_fields
    try:
        ops.pack_fields = ref.pack_fields_bitexpand_ref
        ops.unpack_fields = _unpack_fields_oracle
        yield
    finally:
        ops.pack_fields, ops.unpack_fields = saved


def _codec_roundtrip_identity(name, kw, d, n, use_pallas):
    comp = make_compressor(name, **kw)
    fused = wire_codec(comp, fused=True)
    legacy = wire_codec(comp, fused=False)
    x, keys = _bucket(d, n)
    e = x * 1.5
    with _oracle_packer():
        pay_l = legacy.encode_batch(x, keys)
        xhat_l = legacy.decode_batch(pay_l, d)
        eh_l, m_l = legacy.decode_ef_batch(pay_l, e, d)
    with _ops_path(use_pallas):
        pay_f = fused.encode_batch(x, keys)
        xhat_f = fused.decode_batch(pay_f, d)
        eh_f, m_f = fused.decode_ef_batch(pay_f, e, d)
    _assert_bitwise(pay_f, pay_l, (name, d, n, use_pallas, "payload"))
    _assert_bitwise(xhat_f, xhat_l, (name, d, n, use_pallas, "decode"))
    _assert_bitwise(eh_f, eh_l, (name, d, n, use_pallas, "ef xhat"))
    _assert_bitwise(m_f, m_l, (name, d, n, use_pallas, "ef residual"))


@pytest.mark.parametrize("name,kw", SIX + [("identity", {})])
def test_fused_codec_byte_identity_smoke(name, kw):
    for d, n in SMOKE_SHAPES:
        _codec_roundtrip_identity(name, kw, d, n, use_pallas=False)
    _codec_roundtrip_identity(name, kw, 700, 3, use_pallas=True)


@pytest.mark.wire
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("name,kw", SIX + [("identity", {})])
def test_fused_codec_byte_identity_full(name, kw, use_pallas):
    for d, n in FULL_SHAPES:
        _codec_roundtrip_identity(name, kw, d, n, use_pallas)


@pytest.mark.parametrize("d,n", SMOKE_SHAPES)
def test_fused_ops_byte_identity(d, n):
    """ops-layer identity at odd sizes: pallas path == jnp fallback for
    payload words, statistics, decode, and EF residual."""
    x, keys = _bucket(d, n)
    e = x * 1.5
    wq_p, nr_p = ops.qsgd_pack_units(x, keys, 16, 6, use_pallas=True)
    wq_j, nr_j = ops.qsgd_pack_units(x, keys, 16, 6, use_pallas=False)
    _assert_bitwise(wq_p, wq_j, (d, n, "qsgd words"))
    _assert_bitwise(nr_p, nr_j, (d, n, "qsgd norms"))
    for up in (False, True):
        xh = ops.qsgd_unpack_units(wq_p, nr_p, d, 16, 6, use_pallas=up)
        xh2, m = ops.qsgd_unpack_ef_units(wq_p, nr_p, e, d, 16, 6,
                                          use_pallas=up)
        _assert_bitwise(xh2, xh, (d, n, up, "qsgd ef xhat"))
        _assert_bitwise(m, np.asarray(e) - np.asarray(xh),
                        (d, n, up, "qsgd residual"))
    wt_p, sc_p = ops.terngrad_pack_units(x, keys, use_pallas=True)
    wt_j, sc_j = ops.terngrad_pack_units(x, keys, use_pallas=False)
    _assert_bitwise(wt_p, wt_j, (d, n, "tern words"))
    _assert_bitwise(sc_p, sc_j, (d, n, "tern scales"))
    ws_p = ops.sign_pack_units(x, use_pallas=True)
    ws_j = ops.sign_pack_units(x, use_pallas=False)
    _assert_bitwise(ws_p, ws_j, (d, n, "sign words"))


# ---------------------------------------------------------------------------
# single launch: one pallas_call per bucket encode/decode, structurally
# ---------------------------------------------------------------------------

def test_fused_encode_is_single_launch():
    d, n = 700, 3
    x, keys = _bucket(d, n)
    kd = jax.random.key_data(keys).astype(jnp.uint32)
    assert ops.count_pallas_calls(
        lambda a, k: ops.qsgd_pack_units(a, k, 16, 6, use_pallas=True),
        x, kd) == 1
    assert ops.count_pallas_calls(
        lambda a, k: ops.terngrad_pack_units(a, k, use_pallas=True),
        x, kd) == 1
    assert ops.count_pallas_calls(
        lambda a: ops.sign_pack_units(a, use_pallas=True), x) == 1


def test_fused_decode_is_single_launch():
    d, n = 700, 3
    x, keys = _bucket(d, n)
    w, nr = ops.qsgd_pack_units(x, keys, 16, 6, use_pallas=False)
    assert ops.count_pallas_calls(
        lambda a, s: ops.qsgd_unpack_units(a, s, d, 16, 6,
                                           use_pallas=True), w, nr) == 1
    # decode+EF: one unpack launch, the residual subtract is an
    # elementwise caller-regime op, NOT a second kernel
    e = x * 1.5
    assert ops.count_pallas_calls(
        lambda a, s, ee: ops.qsgd_unpack_ef_units(a, s, ee, d, 16, 6,
                                                  use_pallas=True),
        w, nr, e) == 1
    ws = ops.sign_pack_units(x, use_pallas=False)
    assert ops.count_pallas_calls(
        lambda a: ops.majority_words(a, use_pallas=True),
        jnp.tile(ws[:1], (5, 1))) == 1


# ---------------------------------------------------------------------------
# majority vote on packed words == pack(majority(unpack))
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_workers", [1, 2, 3, 5, 8])
def test_majority_on_packed_words(n_workers):
    d = 700
    xs = jax.random.normal(jax.random.fold_in(KEY, n_workers),
                           (n_workers, d))
    words = ops.sign_pack_units(xs, use_pallas=False)
    bits = np.stack([np.asarray(ref.unpack_bits_ref(w[None]))[0, :d]
                     for w in words])
    maj_dense = (2 * bits.sum(axis=0) >= n_workers).astype(np.int32)
    pad = (-d) % 32
    oracle = ref.pack_bits_ref(jnp.asarray(
        np.pad(maj_dense, (0, pad))).reshape(-1, 32)).reshape(-1)
    for up in (False, True):
        _assert_bitwise(ops.majority_words(words, use_pallas=up), oracle,
                        (n_workers, up))


def test_signsgd_codec_majority_fused_matches_legacy():
    d, n = 777, 6
    comp = make_compressor("signsgd")
    x, keys = _bucket(d, n)
    fused = wire_codec(comp, fused=True)
    legacy = _legacy(fused)
    pays = legacy.encode_batch(x, keys)
    _assert_bitwise(fused.majority_vote(pays, d),
                    legacy.majority_vote(pays, d), d)


# ---------------------------------------------------------------------------
# traffic gate: the acceptance numbers, from the kernel specs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width,stochastic", [(6, True), (2, True),
                                              (1, False)])
def test_fused_encode_traffic_gate(width, stochastic):
    spec = ops.pack_bytes_moved(width, fused=True, stochastic=stochastic)
    # <= 1 f32 read (+ the per-512-lane-row key/stat columns) and exactly
    # 1 packed-word write per element, nothing intermediate, one launch
    assert spec["read_bytes_per_elt"] <= 4.0 + 12 / 512
    assert spec["write_bytes_per_elt"] == width / 8.0
    assert spec["intermediate_bytes_per_elt"] == 0.0
    assert spec["launches_per_bucket"] == 1
    legacy = ops.pack_bytes_moved(width, fused=False, stochastic=stochastic)
    assert legacy["intermediate_bytes_per_elt"] >= 4.0 + 4.0 * width
    assert legacy["launches_per_bucket"] == 3


def test_fused_decode_traffic_gate():
    for width in (1, 2, 6, 9):
        spec = ops.unpack_bytes_moved(width, fused=True)
        assert spec["read_bytes_per_elt"] == width / 8.0
        assert spec["write_bytes_per_elt"] == 4.0
        assert spec["launches_per_bucket"] == 1
        ef = ops.unpack_bytes_moved(width, fused=True, ef=True)
        assert ef["launches_per_bucket"] == 1
        assert ef["passes_over_data"] == 2
