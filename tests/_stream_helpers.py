"""The streaming == serialized allgather sweep that
tests/test_stream_allgather_quantizers.py and
tests/test_stream_allgather_fields.py split by kernel family.

`ring` (chunked-ppermute) and `rs_stream` (compress -> reduce-scatter ->
allgather) on a 1-worker mesh reproduce the allgather wire path exactly
(rs_stream's shard partition degenerates to the whole unit at n=1)."""
import functools

import jax
import pytest
from jax.sharding import PartitionSpec as P

from _sweep_helpers import (GRANS, KEY, SIX, assert_trees_bitwise, grad_tree,
                            sweep)
from repro.core import (CompressionConfig, FUSE_ALL, compressed_allreduce,
                        make_compressor, stacked_mask)
from repro.launch.engine import shard_map
from repro.launch.mesh import make_host_mesh

THRESHOLDS = (0.0, float(1 << 16), FUSE_ALL)


def _run_once(strat, name, gran, fb, chunk=None):
    """One jitted shard_map'd compressed_allreduce on a 1-worker mesh."""
    cfg = CompressionConfig(qw=make_compressor(name, **dict(SIX)[name]),
                            granularity=gran, strategy=strat,
                            fusion_bytes=fb)
    t = grad_tree()
    sm = stacked_mask(t)

    def f(g, key):
        out, _ = compressed_allreduce(g, sm, cfg, ("data",), key, 1,
                                      wire=True, stream_chunk_bytes=chunk)
        return out
    return jax.jit(shard_map(f, make_host_mesh(1, 1), in_specs=(P(), P()),
                             out_specs=P()))(t, KEY)


@functools.lru_cache(maxsize=None)
def _allgather_once(name, gran, fb):
    """The serialized allgather reference, shared by the cases that
    differ only in chunking or streaming strategy."""
    return _run_once("allgather", name, gran, fb)


def gather_cases(names):
    """Each codec x {layerwise, entire_model} x fusion {0, 64KiB, inf},
    chunked hops on the fused one-shot message (where chunks are real),
    plus qsgd's and topk's fused message unchunked, x {ring, rs_stream}.
    qsgd and topk at layerwise granularity, no fusion or full fusion,
    unchunked, run unmarked; the rest carries the `stream` marker."""
    cases = [(name, gran, fb, 64.0 if fb == FUSE_ALL else None)
             for name in names for gran in GRANS for fb in THRESHOLDS]
    cases += [(name, GRANS[0], FUSE_ALL, None) for name in names
              if name in ("qsgd", "topk")]
    return sweep([case + (strat,) for case in cases
                  for strat in ("ring", "rs_stream")],
                 lambda name, gran, fb, chunk, strat: name in ("qsgd", "topk")
                 and gran.kind == "layerwise" and fb in (0.0, FUSE_ALL)
                 and chunk is None,
                 pytest.mark.stream)


def check_matches_allgather(name, gran, fb, chunk, strat):
    """The streaming strategy equals the serialized allgather wire path
    of the same codec, granularity and fusion threshold, bit for bit."""
    got = _run_once(strat, name, gran, fb, chunk=chunk)
    assert_trees_bitwise(_allgather_once(name, gran, fb), got,
                         (name, gran.kind, fb, chunk, strat))
