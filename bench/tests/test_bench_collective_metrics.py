"""The collective readers on hand-made device traces: `collective_ms`
(collective device time a step, averaged over the chips),
`collective_exposed_ms` (the part no other operation overlaps, on the
chip where it is largest) and `ring_link_roofline` (the least time for
the ring's bytes at the chip's interchip bandwidth over that collective
time). A one-chip trace, with nothing exchanged, reads None for all
three."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import cells, ring_bytes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

PERMUTE = "%collective-permute-done.{} = u8[64] collective-permute-done(%p)"
FUSION = "%fusion.{} = f32[8] fusion(%x)"
RING = "whisper-base.qsgd16-layerwise-wire-ring.4chip"


def _device(i, ops, window=(0, 1000)):
    return tr.DeviceTrace(i, [tr.Op(s, e, n.format(k), kind)
                              for k, (s, e, n, kind) in enumerate(ops)],
                          [tr.Op(window[0], window[1], "step", "module")])


def _ctx(devs, steps=2, cell=RING):
    return {"summary": tr.summary(devs), "devices": devs, "steps": steps,
            "chips": len(devs), "peaks": cells.peaks("TPU v5 lite"),
            "cell": cells.load_cell(cell)}


def _read(name, ctx):
    return cells.metric_readers()[name].read(ctx)


def _traces():
    # chip 0: a collective of 200 ns, 150 of it alone, 50 under a fusion;
    # chip 1: 100 ns alone and 100 ns under a fusion
    d0 = _device(0, [(0, 100, FUSION, "other"),
                     (50, 250, PERMUTE, "collective"),
                     (400, 500, FUSION, "other")])
    d1 = _device(1, [(0, 300, PERMUTE, "collective"),
                     (200, 300, FUSION, "other"),
                     (600, 700, FUSION, "other")])
    return [d0, d1]


def test_collective_ms_averages_the_chips():
    assert _read("collective_ms", _ctx(_traces())) == \
        pytest.approx(1e3 * 250e-9 / 2)


def test_collective_exposed_ms_is_the_worst_chip_outside_other_work():
    assert _read("collective_exposed_ms", _ctx(_traces())) == \
        pytest.approx(1e3 * 200e-9 / 2)
    # wholly overlapped: nothing exposed
    hidden = [_device(0, [(0, 100, PERMUTE, "collective"),
                          (0, 200, FUSION, "other")])]
    assert _read("collective_exposed_ms", _ctx(hidden)) == 0.0
    assert _read("collective_ms", _ctx(hidden)) == pytest.approx(1e-4 / 2)


def _least_s(ctx):
    shapes = {p: tuple(s[0]) for p, s in
              ctx["cell"].reference().param_specs(
                  ctx["cell"].config).items()}
    sent = ring_bytes.step_bytes(ctx["cell"].traffic["codec"], shapes,
                                 ctx["chips"])
    return 8 * sent / 1600e9


def test_ring_link_roofline_least_time_over_time_in_flight():
    # synchronous collectives: in flight for their own spans
    ctx = _ctx(_traces())
    assert _read("ring_link_roofline", ctx) == \
        pytest.approx(100 * _least_s(ctx) / (250e-9 / 2))
    # an asynchronous permute is in flight from its start to the end of
    # its done, though the core spends only 20 ns in the two
    start = ("%collective-permute-start.4 = (u8[64], u8[64]) "
             "collective-permute-start(u8[64] %p)")
    done = ("%collective-permute-done.4 = u8[64] collective-permute-done("
            "(u8[64], u8[64]) %collective-permute-start.4)")
    dev = [_device(0, [(100, 110, start, "collective"),
                       (110, 600, FUSION, "other"),
                       (600, 610, done, "collective")])]
    ctx = _ctx(dev, steps=1)
    assert _read("collective_ms", ctx) == pytest.approx(20e-6)
    assert _read("ring_link_roofline", ctx) == \
        pytest.approx(100 * _least_s(ctx) / 510e-9)


def test_ring_link_roofline_done_without_its_start():
    # a done whose start is not among the operations (left on a line the
    # reduction skips): in flight for the done's own span alone
    done = ("%collective-permute-done.4 = u8[64] collective-permute-done("
            "(u8[64], u8[64]) %collective-permute-start.4)")
    dev = [_device(0, [(100, 600, FUSION, "other"),
                       (600, 700, done, "collective")])]
    ctx = _ctx(dev, steps=1)
    assert _read("collective_ms", ctx) == pytest.approx(1e-4)
    assert _read("ring_link_roofline", ctx) == \
        pytest.approx(100 * _least_s(ctx) / 100e-9)


def test_no_collective_reads_none():
    one = [_device(0, [(0, 100, FUSION, "other")])]
    for name in ("collective_ms", "collective_exposed_ms",
                 "ring_link_roofline"):
        assert _read(name, _ctx(one)) is None
        assert _read(name, _ctx(one, cell="mamba2-1.3b.dense")) is None
    # collectives in a cell that runs no ring: no ring roofline
    assert _read("ring_link_roofline",
                 _ctx(_traces(), cell="mamba2-1.3b.dense")) is None
