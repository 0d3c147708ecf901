"""Fused single-launch compress+pack kernels: the bit-exactness contract.

Three load-bearing properties:

  1. BYTE IDENTITY: the fused batch paths (kernels/ops.py *_pack_units /
     *_unpack_units, and the WireCodec fused=True batch entry points)
     produce payload bytes and decoded gradients BIT-identical to the
     legacy three-pass per-unit pipeline — on both the pallas and the
     pure-jnp fallback paths, at word-aligned and word-straddling sizes
     (the codec sweep: tests/test_fused_codec_quantizers.py and
     tests/test_fused_codec_fields.py).
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
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from _fused_helpers import KEY, SMOKE_SHAPES, assert_bitwise, bucket
from repro.core import make_compressor, wire_codec
from repro.kernels import ops, prng, ref


def _legacy(codec):
    import dataclasses
    return dataclasses.replace(codec, fused=False)


# ---------------------------------------------------------------------------
# in-kernel PRNG == jax.random (the uniforms the pack kernels draw)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 31, 512, 513, 1025])
def test_uniform_at_matches_jax_random(d):
    key = jax.random.fold_in(KEY, d)
    kd = jax.random.key_data(key).astype(jnp.uint32)
    pos = jnp.arange(d, dtype=jnp.int32)[None, :]
    u = prng.uniform_at(kd[0][None, None], kd[1][None, None], pos)
    assert_bitwise(u[0], jax.random.uniform(key, (d,)), d)


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
        assert_bitwise(words, oracle, (width, k, up))
        assert_bitwise(ops.unpack_fields(words, k, width, use_pallas=up),
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
    assert_bitwise(words.reshape(-1),
                   ref.pack_fields_bitexpand_ref(f.reshape(-1), width), width)
    assert_bitwise(ref.unpack_fields_tile(words, width), f, width)


#: field counts: one field, either side of a 32-bit word and of a
#: 512-lane row, odd counts, and the largest count
PACK_COUNTS = [1, 31, 32, 33, 100, 511, 512, 513, 700, 900]


@settings(max_examples=15)
@given(st.sampled_from(PACK_COUNTS),
       st.sampled_from([1, 2, 3, 4, 5, 9, 13, 17]),
       st.integers(min_value=0, max_value=10_000))
def test_property_pack_fields_roundtrip(k, width, seed):
    vals = jax.random.randint(jax.random.fold_in(KEY, seed), (k,), 0,
                              1 << min(width, 30), dtype=jnp.int32)
    words = ops.pack_fields(vals, width)
    assert_bitwise(words, ref.pack_fields_bitexpand_ref(vals, width),
                   (k, width, seed))
    assert_bitwise(ops.unpack_fields(words, k, width), vals,
                   (k, width, seed))


@pytest.mark.parametrize("d,n", SMOKE_SHAPES)
def test_fused_ops_byte_identity(d, n):
    """ops-layer identity at odd sizes: pallas path == jnp fallback for
    payload words, statistics, decode, and EF residual."""
    x, keys = bucket(d, n)
    e = x * 1.5
    wq_p, nr_p = ops.qsgd_pack_units(x, keys, 16, 6, use_pallas=True)
    wq_j, nr_j = ops.qsgd_pack_units(x, keys, 16, 6, use_pallas=False)
    assert_bitwise(wq_p, wq_j, (d, n, "qsgd words"))
    assert_bitwise(nr_p, nr_j, (d, n, "qsgd norms"))
    for up in (False, True):
        xh = ops.qsgd_unpack_units(wq_p, nr_p, d, 16, 6, use_pallas=up)
        xh2, m = ops.qsgd_unpack_ef_units(wq_p, nr_p, e, d, 16, 6,
                                          use_pallas=up)
        assert_bitwise(xh2, xh, (d, n, up, "qsgd ef xhat"))
        assert_bitwise(m, np.asarray(e) - np.asarray(xh),
                       (d, n, up, "qsgd residual"))
    wt_p, sc_p = ops.terngrad_pack_units(x, keys, use_pallas=True)
    wt_j, sc_j = ops.terngrad_pack_units(x, keys, use_pallas=False)
    assert_bitwise(wt_p, wt_j, (d, n, "tern words"))
    assert_bitwise(sc_p, sc_j, (d, n, "tern scales"))
    ws_p = ops.sign_pack_units(x, use_pallas=True)
    ws_j = ops.sign_pack_units(x, use_pallas=False)
    assert_bitwise(ws_p, ws_j, (d, n, "sign words"))


# ---------------------------------------------------------------------------
# single launch: one pallas_call per bucket encode/decode, structurally
# ---------------------------------------------------------------------------

def test_fused_encode_is_single_launch():
    d, n = 700, 3
    x, keys = bucket(d, n)
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
    x, keys = bucket(d, n)
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
        assert_bitwise(ops.majority_words(words, use_pallas=up), oracle,
                       (n_workers, up))


def test_signsgd_codec_majority_fused_matches_legacy():
    d, n = 777, 6
    comp = make_compressor("signsgd")
    x, keys = bucket(d, n)
    fused = wire_codec(comp, fused=True)
    legacy = _legacy(fused)
    pays = legacy.encode_batch(x, keys)
    assert_bitwise(fused.majority_vote(pays, d),
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
