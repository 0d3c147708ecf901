"""Streaming == serialized allgather wire path, bit for bit, for the
codecs with their own quantizer kernels (qsgd, terngrad, signsgd) across
granularities, fusion thresholds, chunked hops and both streaming
strategies."""
import pytest

from _stream_helpers import check_matches_allgather, gather_cases


@pytest.mark.parametrize("name,gran,fb,chunk,strat", gather_cases(
    ["qsgd", "terngrad", "signsgd"]))
def test_stream_matches_allgather_full(name, gran, fb, chunk, strat):
    check_matches_allgather(name, gran, fb, chunk, strat)
