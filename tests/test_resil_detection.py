"""The Fletcher-32 detection gate, on a REAL ring of 4 virtual devices
and on the serialized wire path across the six-codec zoo.

  1. RING HOPS (the tests/fault_checks.py subprocess): bit flips and
     dropped hops between distinct workers are detected and resend
     recovers the clean bits; a duplicated (stale) hop passes the
     checksum — the documented sequence-number gap. It comes first so
     that a worker starts it as soon as it takes this file.
  2. SERIALIZED PATH: for six codecs x granularities, every clean
     message verifies (zero false positives) and every single-bit-flipped
     or zeroed message fails verification.

topk at layerwise granularity runs unmarked; the rest carries the
`fault` marker (excluded by `make verify-fast`).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _sweep_helpers import KEY, ZOO, sweep, wire_parts
from repro.core.wire import execute_schedule_wire, verify_message


@pytest.mark.fault
@pytest.mark.slow
@pytest.mark.timeout(600)
def test_resil_multidevice_checks(run_check_script):
    """Drives tests/fault_checks.py on 4 virtual devices: bit flips and
    dropped hops on a REAL ring are detected and resend recovers the
    clean bits; a duplicated (stale) hop passes the checksum — the
    documented sequence-number gap."""
    run_check_script("fault_checks.py", "ALL FAULT CHECKS PASSED",
                     timeout=570)


@pytest.mark.parametrize("name,kw,gran", sweep(
    ZOO, lambda name, kw, gran: name == "topk" and gran.kind == "layerwise",
    pytest.mark.fault))
def test_detection_gate_zoo(name, kw, gran):
    t, codec, sched, lays = wire_parts(name, kw, gran)
    _, bufs = execute_schedule_wire(sched, codec, None, t, KEY)
    assert len(bufs) == len(lays) and len(bufs) >= 1
    rng = np.random.default_rng(11)
    for buf, lay in zip(bufs, lays):
        # zero false positives: the clean buffer always verifies
        assert bool(verify_message(buf, lay)), (name, gran.kind)
        b = np.asarray(buf)
        # every sampled single-bit flip in the covered span is caught
        span = b.size - lay.checksum_span_start
        for _ in range(8):
            pos = lay.checksum_span_start + int(rng.integers(span))
            bit = int(rng.integers(8))
            c = b.copy()
            c[pos] ^= np.uint8(1 << bit)
            assert not bool(verify_message(jnp.asarray(c), lay)), \
                (name, gran.kind, pos, bit)
        # a zeroed (dropped) message is caught too
        z = np.zeros_like(b)
        z[:lay.checksum_span_start] = b[:lay.checksum_span_start]
        assert not bool(verify_message(jnp.asarray(z), lay)), \
            (name, gran.kind)
