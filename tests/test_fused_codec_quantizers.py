"""Fused codec byte identity for the codecs with their own quantizer
kernels (qsgd, terngrad, signsgd): the fused batch encode, decode and EF
decode equal the legacy per-unit pipeline's bytes and bits at every
bucket shape, on the Pallas kernels and on their jnp twins."""
import pytest

from _fused_helpers import check_codec_byte_identity, codec_cases


@pytest.mark.parametrize("name,kw,d,n,path", codec_cases(
    ["qsgd", "terngrad", "signsgd"]))
def test_fused_codec_byte_identity_full(name, kw, d, n, path):
    check_codec_byte_identity(name, kw, d, n, path)
