"""Corruption through the aggregation path across the six-codec zoo x
granularities: corrupted-with-resend aggregation == clean aggregation
bitwise; EF residuals are SENDER-side state and stay bitwise clean under
receive corruption; detection counters cover every message.

topk at layerwise granularity runs unmarked; the rest carries the
`fault` marker (excluded by `make verify-fast`).
"""
import functools

import jax
import pytest

from _sweep_helpers import (KEY, SIX, ZOO, assert_trees_bitwise, grad_tree,
                            sweep, worker_grads)
from repro.core import (CompressionConfig, aggregate_simulated_workers,
                        make_compressor, stacked_mask)
from repro.resil import FaultInjector
from repro.sim import CorruptionSpec, init_ef


def _trees_differ(a, b):
    return any(not bool((x == y).all())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _cfg(name, gran):
    return CompressionConfig(qw=make_compressor(name, **dict(SIX)[name]),
                             granularity=gran, error_feedback=True,
                             integrity=True)


@functools.lru_cache(maxsize=None)
def _clean(name, gran):
    """The uncorrupted aggregate and EF update both legs compare to."""
    return aggregate_simulated_workers(
        worker_grads(), stacked_mask(grad_tree()), _cfg(name, gran), KEY,
        ef_state=init_ef(grad_tree(), 4), wire=True)


@pytest.mark.parametrize("leg", ["corrupt", "resend"])
@pytest.mark.parametrize("name,kw,gran", sweep(
    ZOO, lambda name, kw, gran: name == "topk" and gran.kind == "layerwise",
    pytest.mark.fault))
def test_corruption_resend_zoo(name, kw, gran, leg):
    """prob=1 single-bit flips on every message. Without resend: all are
    detected, the aggregate differs, and the EF residuals (sender-side
    state) stay bitwise clean. With resend: every detection is resent
    and the aggregate and EF are the clean ones, bitwise."""
    clean_out, clean_ef = _clean(name, gran)
    spec = CorruptionSpec(prob=1.0, mode="bitflip", n_bits=1, seed=3)
    out, new_ef, info = aggregate_simulated_workers(
        worker_grads(), stacked_mask(grad_tree()), _cfg(name, gran), KEY,
        ef_state=init_ef(grad_tree(), 4), wire=True,
        faults=FaultInjector(spec, resend=leg == "resend"))
    ctx = (name, gran.kind, leg)
    assert int(info["messages"]) > 0
    assert_trees_bitwise(new_ef, clean_ef, ctx)
    if leg == "corrupt":
        assert int(info["corrupt_detected"]) == int(info["messages"]), ctx
        assert int(info["resends"]) == 0
        assert _trees_differ(out, clean_out), ctx
    else:
        assert int(info["resends"]) == int(info["corrupt_detected"])
        assert_trees_bitwise(out, clean_out, ctx)
