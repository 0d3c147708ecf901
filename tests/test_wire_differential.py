"""WireCodec: the differential sweep locking executed messages to the
accounting. For six compressors x granularities x fusion thresholds, the
fused wire messages decode to the unpacked numerics bit for bit, their
headers read back, and their payload bytes equal the accounted bits plus
the documented word padding. qsgd and topk at layerwise granularity, no
fusion or full fusion, run unmarked; the rest carries the `wire` marker
(tier-1 only, excluded by `make verify-fast`).
"""
import jax
import jax.numpy as jnp
import pytest

from _sweep_helpers import (GRANS, KEY, SIX, assert_trees_bitwise, grad_tree,
                            sweep)
from repro.core import (CompressionConfig, FUSE_ALL, build_plan,
                        build_schedule, comm_report, make_compressor,
                        measured_bits_from_payloads, message_layouts,
                        stacked_mask, wire_codec)

# fusion matrix: per-bucket messages, 64 KiB buffers, one message
THRESHOLDS = (0.0, float(1 << 16), FUSE_ALL)


@pytest.mark.parametrize("name,kw,gran,fb", sweep(
    [(name, kw, gran, fb) for name, kw in SIX for gran in GRANS
     for fb in THRESHOLDS],
    lambda name, kw, gran, fb: name in ("qsgd", "topk")
    and gran.kind == "layerwise" and fb in (0.0, FUSE_ALL),
    pytest.mark.wire))
def test_differential_full(name, kw, gran, fb):
    """The acceptance sweep: all six compressors x {layerwise,
    entire_model} x fusion {0, 64KiB, inf} — accounted payload bits ==
    measured packed bytes, and wire numerics == unpacked numerics,
    everywhere. The unmarked subset: qsgd and topk x layerwise x {no
    fusion, full fusion}."""
    t = grad_tree()
    sm = stacked_mask(t)
    c = make_compressor(name, **kw)
    codec = wire_codec(c)
    plan = build_plan(t, sm, gran)
    sched = build_schedule(plan, fb)

    # numerics: wire streaming == the unscheduled unpacked reference
    ref = plan.execute(lambda x, k: c.sim(x, k), t, KEY)
    got, bufs = sched.execute(None, t, KEY, wire=codec)
    assert_trees_bitwise(ref, got, (name, gran.kind, fb))

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
