"""The fused-kernel tests' buckets and bit comparison, and the codec
byte-identity sweep that tests/test_fused_codec_quantizers.py and
tests/test_fused_codec_fields.py split by kernel family."""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sweep_helpers import SIX, sweep
from repro.core import make_compressor, wire_codec
from repro.kernels import ops, ref

KEY = jax.random.key(3)

# word-straddling and word-aligned unit dims, odd bucket sizes
SMOKE_SHAPES = [(64, 4), (513, 2), (700, 3)]
FULL_SHAPES = SMOKE_SHAPES + [(1, 1), (31, 7), (512, 1), (1300, 5),
                              (4096, 2)]

CODECS = dict(SIX + [("identity", {})])


def bucket(d, n, seed=7):
    x = jax.random.normal(jax.random.fold_in(KEY, seed), (n, d))
    keys = jax.vmap(lambda i: jax.random.fold_in(KEY, i))(jnp.arange(n))
    return x, keys


def assert_bitwise(a, b, ctx):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (ctx, a.shape, b.shape)
    assert np.array_equal(a, b), ctx


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


@functools.lru_cache(maxsize=None)
def _legacy_roundtrip(name, d, n):
    """The legacy per-unit wire path's payload, decode and EF decode of
    one bucket: the reference both fused paths are held to."""
    legacy = wire_codec(make_compressor(name, **CODECS[name]), fused=False)
    x, keys = bucket(d, n)
    with _oracle_packer():
        pay = legacy.encode_batch(x, keys)
        return (pay, legacy.decode_batch(pay, d),
                *legacy.decode_ef_batch(pay, x * 1.5, d))


def codec_cases(names):
    """Each codec x bucket shape x ops path; the smoke shapes on the jnp
    path and (700, 3) on the Pallas path run unmarked, the rest carries
    the `wire` marker."""
    return sweep(
        [(name, CODECS[name], d, n, path) for name in names
         for d, n in FULL_SHAPES for path in ("jnp", "pallas")],
        lambda name, kw, d, n, path: (
            ((d, n) in SMOKE_SHAPES and path == "jnp")
            or ((d, n) == (700, 3) and path == "pallas")),
        pytest.mark.wire)


def check_codec_byte_identity(name, kw, d, n, path):
    """The fused codec's payload, decode and EF decode on one ops path
    (Pallas kernels or their jnp twins) are the legacy pipeline's, bit
    for bit."""
    fused = wire_codec(make_compressor(name, **kw), fused=True)
    x, keys = bucket(d, n)
    with _ops_path(path == "pallas"):
        pay = fused.encode_batch(x, keys)
        got = (pay, fused.decode_batch(pay, d),
               *fused.decode_ef_batch(pay, x * 1.5, d))
    for what, a, b in zip(("payload", "decode", "ef xhat", "ef residual"),
                          got, _legacy_roundtrip(name, d, n)):
        assert_bitwise(a, b, (name, d, n, path, what))
