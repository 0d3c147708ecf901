"""The `ssd_kernel_ms` reader: it sums the Pallas kernels named ssd_*
(not the wire kernels, not XLA operations) per traced step, and reads
None on a program without them, such as the one in the small trace
recorded on one v5e (bench/tests/record_trace.py)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import cells  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

TRACE = os.path.join(HERE, "data", "mamba2-smoke-wire-2steps.xplane.pb")
CALL = ' custom-call(f32[8] %a), custom_call_target="tpu_custom_call"'


def test_ssd_kernel_ms_reads_only_the_ssd_kernels():
    ops = [tr.Op(0, 100, "%ssd_fwd_states.3 = f32[8]" + CALL, "pallas"),
           tr.Op(100, 130, "%qsgd_pack_units.2 = u32[8]" + CALL, "pallas"),
           tr.Op(150, 400, "%ssd_bwd.1 = f32[8]" + CALL, "pallas"),
           tr.Op(400, 500, "%fusion.7 = f32[8] fusion(%ssd_bwd.1)", "other")]
    dev = tr.DeviceTrace(0, ops, [tr.Op(0, 1000, "step", "module")])
    ssd = cells.metric_readers()["ssd_kernel_ms"]
    assert ssd.read({"devices": [dev], "steps": 2}) == \
        pytest.approx(1e3 * 350e-9 / 2)
    assert ssd.read({"devices": tr.load(TRACE), "steps": 2}) is None
    assert ssd.read({"devices": [], "steps": 2}) is None
