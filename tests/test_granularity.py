"""Granularity partitioning: unit dims, identity roundtrips, semantic
difference between layer-wise and entire-model statistics."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (Granularity, apply_unitwise, make_compressor,
                        stacked_mask, unit_dims)

KEY = jax.random.key(0)


def _tree():
    return {"blocks": {"w": jax.random.normal(KEY, (3, 16, 8)),
                       "b": jax.random.normal(KEY, (3, 8))},
            "embed": jax.random.normal(KEY, (20, 4))}


def test_unit_dims():
    t = _tree()
    sm = stacked_mask(t)
    assert unit_dims(t, sm, Granularity("entire_model")) == [3 * 16 * 8 + 3 * 8
                                                             + 80]
    assert unit_dims(t, sm, Granularity("layerwise")) == [8, 8, 8, 128, 128,
                                                          128, 80]
    bd = unit_dims(t, sm, Granularity("blockwise", 100))
    assert sum(bd) == 488 and all(b == 100 for b in bd[:-1])


@pytest.mark.parametrize("kind", ["entire_model", "layerwise", "blockwise"])
def test_identity_roundtrip(kind):
    t = _tree()
    sm = stacked_mask(t)
    out = apply_unitwise(lambda x, k: x, Granularity(kind, 64), t, sm, KEY)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(t)):
        assert jnp.allclose(a, b)


def test_layerwise_vs_entire_model_differ_for_topk():
    """The paper's Figure 1: with heterogeneous layer magnitudes,
    entire-model Top-k starves the small-magnitude layer while layer-wise
    keeps k% of EVERY layer."""
    big = 100.0 * jax.random.normal(KEY, (1, 64))
    small = 0.01 * jax.random.normal(jax.random.fold_in(KEY, 1), (1, 64))
    t = {"blocks": {"w": jnp.concatenate([big, small])}}
    sm = stacked_mask(t)
    c = make_compressor("topk", ratio=0.5)
    lw = apply_unitwise(lambda x, k: c.sim(x, k), Granularity("layerwise"),
                        t, sm, KEY)["blocks"]["w"]
    em = apply_unitwise(lambda x, k: c.sim(x, k),
                        Granularity("entire_model"), t, sm, KEY)["blocks"]["w"]
    # layer-wise: the small layer keeps 32 of its own entries
    assert int(jnp.sum(lw[1] != 0)) == 32
    # entire-model: ALL kept entries come from the big layer
    assert int(jnp.sum(em[1] != 0)) == 0
    assert int(jnp.sum(em[0] != 0)) == 64


def test_layerwise_statistics_are_per_layer():
    """TernGrad's scale is per-unit: layer-wise output magnitudes match
    each layer's own max (the paper's §5.3 explanation)."""
    t = {"blocks": {"w": jnp.stack([jnp.full((32,), 10.0),
                                    jnp.full((32,), 0.1)])}}
    sm = stacked_mask(t)
    c = make_compressor("terngrad")
    lw = apply_unitwise(lambda x, k: c.sim(x, k), Granularity("layerwise"),
                        t, sm, KEY)["blocks"]["w"]
    nz0 = jnp.abs(lw[0][lw[0] != 0])
    nz1 = jnp.abs(lw[1][lw[1] != 0])
    assert jnp.allclose(nz0, 10.0) and jnp.allclose(nz1, 0.1)
    em = apply_unitwise(lambda x, k: c.sim(x, k),
                        Granularity("entire_model"), t, sm, KEY)["blocks"]["w"]
    nz1e = jnp.abs(em[1][em[1] != 0])
    if nz1e.size:  # entire-model scale is the GLOBAL max
        assert jnp.allclose(nz1e, 10.0)


@settings(max_examples=15)
@given(st.integers(min_value=1, max_value=5),
       st.integers(min_value=1, max_value=64),
       st.integers(min_value=8, max_value=200))
def test_property_unit_dims_partition(L, rows, block):
    """Every granularity partitions the exact total dimension."""
    t = {"blocks": {"w": jnp.zeros((L, rows, 4))},
         "head": jnp.zeros((rows,))}
    sm = stacked_mask(t)
    total = L * rows * 4 + rows
    for g in [Granularity("entire_model"), Granularity("layerwise"),
              Granularity("blockwise", block)]:
        assert sum(unit_dims(t, sm, g)) == total
