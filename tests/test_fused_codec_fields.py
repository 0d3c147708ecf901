"""Fused codec byte identity for the codecs whose legs go through the
generic field packer (natural's 9-bit codes, topk's and randomk's
indices) and for identity's plain values: the fused batch encode,
decode and EF decode equal the legacy per-unit pipeline's bytes and bits
at every bucket shape, on the Pallas kernels and on their jnp twins."""
import pytest

from _fused_helpers import check_codec_byte_identity, codec_cases


@pytest.mark.parametrize("name,kw,d,n,path", codec_cases(
    ["natural", "topk", "randomk", "identity"]))
def test_fused_codec_byte_identity_full(name, kw, d, n, path):
    check_codec_byte_identity(name, kw, d, n, path)
