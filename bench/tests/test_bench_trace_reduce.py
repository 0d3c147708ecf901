"""The reduction from a profiler trace to device intervals: interval
arithmetic on hand-made intervals, and a small trace recorded on one v5e
(bench/tests/record_trace.py: two steps of the smoke mamba2 with QSGD-16
wire messages) reduced to fixed numbers."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace_reduce as tr  # noqa: E402


def test_union_length_and_gaps():
    ivs = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert tr.union(ivs) == [(0, 15), (20, 31)]
    assert tr.length(ivs) == 26
    assert tr.gaps(ivs, (0, 40)) == [(15, 20), (31, 40)]
    assert tr.clip(ivs, (12, 25)) == [(12, 15), (20, 25)]


def test_exposed_time_is_outside_other_work():
    coll = [(0, 10), (20, 30)]
    other = [(5, 12), (22, 24), (26, 28)]
    assert tr.minus(coll, other) == 5 + 2 + 2 + 2
    assert tr.minus(coll, []) == 20
    assert tr.minus([], other) == 0


def test_classify_and_containers():
    assert tr.classify('%custom-call.3 = u32[8] custom-call(f32[8] %a), '
                       'custom_call_target="tpu_custom_call"') == "pallas"
    assert tr.classify("%collective-permute-done.2 = u8[4] "
                       "collective-permute-done(%x)") == "collective"
    assert tr.classify("%all-reduce.1 = f32[4] all-reduce(%x)") == \
        "collective"
    assert tr.classify('%custom-call.30 = bf16[4] custom-call(), '
                       'custom_call_target="AllocateBuffer"') == "other"
    assert tr._CONTAINER.match(tr.short_name("%while.161 = (s32[]) while(")
                               )
    assert not tr._CONTAINER.match(tr.short_name("%fusion.7 = f32[] fusion("))


TRACE = os.path.join(HERE, "data", "mamba2-smoke-wire-2steps.xplane.pb")

#: what the recorded trace reduces to (seconds; one chip, no collective)
RECORDED = {"window_s": 0.001799133, "busy_s": 0.000426711,
            "pallas_s": 0.000166784, "collective_s": 0.0,
            "collective_exposed_s": 0.0, "devices": 1}


def test_recorded_chip_trace():
    devs = tr.load(TRACE)
    assert tr.summary(devs) == RECORDED
    kinds = [o.kind for o in devs[0].ops]
    assert (kinds.count("pallas"), len(kinds)) == (28, 1554)
    top = dict(tr.top_ops(devs))
    assert top["pallas:qsgd_unpack_units"] == pytest.approx(100.826e-6)
    assert top["pallas:qsgd_pack_units"] == pytest.approx(65.958e-6)
