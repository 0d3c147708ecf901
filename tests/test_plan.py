"""UnitPlan: the static bucketed compression-execution engine.

The load-bearing property: `plan.execute` (one batched compressor dispatch
per unit size class) is numerically equivalent to the legacy per-leaf path
`apply_unitwise_reference` — same granularity semantics, same PRNG stream —
for every granularity and the whole operator zoo. Plus: plan.unit_dims
matches granularity.unit_dims on every model config, dispatch counts are
O(#size-classes) not O(#leaves), and the bucket Pallas kernels agree with
their jnp oracles.
"""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from _sweep_helpers import KEY, grad_tree
from repro.core import (CompressionConfig, Granularity, build_plan,
                        aggregate_simulated_workers, comm_report,
                        make_compressor, stacked_mask, unit_dims)
from repro.core.granularity import (apply_unitwise, apply_unitwise_reference,
                                    apply_unitwise_with_state,
                                    apply_unitwise_with_state_reference)
from repro.core.theory import noise_bounds_from_plan
from repro.kernels import ops


GRANS = [Granularity("layerwise"), Granularity("entire_model"),
         Granularity("blockwise", 100)]

OPERATORS = [
    ("identity", {}),
    ("topk", {"ratio": 0.25}),
    ("randomk", {"ratio": 0.3, "scale": True}),
    ("terngrad", {}),
    ("qsgd", {"levels": 16}),
    ("signsgd", {}),
    ("natural", {}),
    ("threshold_v", {"v": 0.3}),
    ("adaptive_threshold", {"alpha": 0.2}),
]


def _assert_trees_close(a, b, ctx, atol=1e-6):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert la.shape == lb.shape and la.dtype == lb.dtype, ctx
        assert jnp.allclose(la, lb, atol=atol), (
            ctx, float(jnp.max(jnp.abs(la - lb))))


# ---------------------------------------------------------------------------
# numerical equivalence: plan path == legacy per-leaf path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gran", GRANS, ids=lambda g: g.kind)
@pytest.mark.parametrize("name,kw", OPERATORS)
def test_plan_matches_reference(gran, name, kw):
    t = grad_tree()
    sm = stacked_mask(t)
    c = make_compressor(name, **kw)

    def fn(x, k):
        return c.sim(x, k)

    planned = apply_unitwise(fn, gran, t, sm, KEY)
    legacy = apply_unitwise_reference(fn, gran, t, sm, KEY)
    _assert_trees_close(planned, legacy, (gran.kind, name))


@pytest.mark.parametrize("gran", GRANS, ids=lambda g: g.kind)
def test_plan_matches_reference_with_state(gran):
    """Error-feedback threading: outputs AND residual memories match."""
    t = grad_tree()
    sm = stacked_mask(t)
    m0 = jax.tree_util.tree_map(lambda x: 0.3 * jnp.ones_like(x), t)
    c = make_compressor("topk", ratio=0.1)

    def ef(x, m, k):
        e = x + m
        q = c.sim(e, k)
        return q, e - q

    y_p, m_p = apply_unitwise_with_state(ef, gran, t, m0, sm, KEY)
    y_l, m_l = apply_unitwise_with_state_reference(ef, gran, t, m0, sm, KEY)
    _assert_trees_close(y_p, y_l, gran.kind)
    _assert_trees_close(m_p, m_l, gran.kind)


def test_plan_matches_reference_raw_key():
    """Old-style uint32 keys take the raw fold path."""
    t = grad_tree()
    sm = stacked_mask(t)
    c = make_compressor("qsgd", levels=8)
    rk = jax.random.PRNGKey(11)
    g = Granularity("layerwise")
    planned = apply_unitwise(lambda x, k: c.sim(x, k), g, t, sm, rk)
    legacy = apply_unitwise_reference(lambda x, k: c.sim(x, k), g, t, sm, rk)
    _assert_trees_close(planned, legacy, "raw-key")


def test_plan_under_jit_and_grad():
    """The plan path is traceable and differentiable (psum-free fn)."""
    t = grad_tree()
    sm = stacked_mask(t)
    g = Granularity("layerwise")

    @jax.jit
    def f(t):
        out = apply_unitwise(lambda x, k: 2.0 * x, g, t, sm, KEY)
        return sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(out))

    grads = jax.grad(f)(t)
    for l in jax.tree_util.tree_leaves(grads):
        assert jnp.allclose(l, 2.0)


# ---------------------------------------------------------------------------
# dispatch complexity: O(#size classes), not O(#leaves)
# ---------------------------------------------------------------------------

def _count_traced_calls(fn_apply, t, sm, gran):
    """Number of times the compressor body is traced for one jit trace."""
    count = 0

    def counting_fn(x, k):
        nonlocal count
        count += 1
        return x

    jax.make_jaxpr(
        lambda tree: fn_apply(counting_fn, gran, tree, sm, KEY))(t)
    return count


def test_layerwise_dispatch_count_is_size_classes():
    """A scan-stacked transformer-like tree has few size classes but many
    units; the plan path traces the compressor once per size class while
    the legacy path traces once per leaf."""
    L = 12
    t = {"blocks": {"wq": jnp.ones((L, 32, 32)), "wk": jnp.ones((L, 32, 32)),
                    "wv": jnp.ones((L, 32, 32)), "norm": jnp.ones((L, 32))},
         "embed": jnp.ones((100, 32)), "head": jnp.ones((32, 100))}
    sm = stacked_mask(t)
    g = Granularity("layerwise")
    plan = build_plan(t, sm, g)
    assert plan.num_units == 4 * L + 2
    # size classes: 32*32 (3 stacked tensors), 32, 3200 (embed+head)
    assert plan.num_dispatches == 3
    planned = _count_traced_calls(apply_unitwise, t, sm, g)
    legacy = _count_traced_calls(apply_unitwise_reference, t, sm, g)
    assert planned == plan.num_dispatches == 3
    assert legacy == 6  # one trace per leaf
    assert planned < legacy


def test_stacked_bucket_is_contiguous():
    """Scan-stacked layers tile a contiguous flat range: gather/scatter
    degrade to reshape (no index arrays)."""
    t = {"blocks": {"w": jnp.ones((8, 64))}}
    plan = build_plan(t, stacked_mask(t), Granularity("layerwise"))
    assert plan.num_dispatches == 1
    assert plan.buckets[0].contiguous
    bplan = build_plan(t, stacked_mask(t), Granularity("blockwise", 128))
    assert all(b.contiguous for b in bplan.buckets)


def test_plan_cache_returns_same_object():
    t = grad_tree()
    sm = stacked_mask(t)
    g = Granularity("layerwise")
    assert build_plan(t, sm, g) is build_plan(t, sm, g)
    # ShapeDtypeStructs hit the same cache entry as concrete arrays
    sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    assert build_plan(sds, sm, g) is build_plan(t, sm, g)


# ---------------------------------------------------------------------------
# accounting: plan.unit_dims == granularity.unit_dims everywhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gran", GRANS, ids=lambda g: g.kind)
def test_unit_dims_match_on_synthetic_tree(gran):
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, gran)
    assert list(plan.unit_dims) == unit_dims(t, sm, gran)
    assert sum(plan.unit_dims) == plan.total


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "phi4-mini-3.8b",
                                  "zamba2-7b", "whisper-base",
                                  "internvl2-2b", "granite-20b",
                                  "qwen3-moe-235b-a22b", "minicpm3-4b",
                                  "llama3-405b",
                                  "llama4-maverick-400b-a17b"])
def test_unit_dims_match_on_config_zoo(arch):
    """plan.unit_dims agrees with granularity.unit_dims on every model
    config's parameter tree (shapes only — no allocation)."""
    from repro.configs.registry import get_smoke
    from repro.models import DistConfig, Model
    m = Model(get_smoke(arch), DistConfig())
    shapes = m.param_shapes()
    sm = m.stacked()
    for gran in (Granularity("layerwise"), Granularity("entire_model"),
                 Granularity("blockwise", 1 << 16)):
        plan = build_plan(shapes, sm, gran)
        assert list(plan.unit_dims) == unit_dims(shapes, sm, gran), \
            (arch, gran.kind)
        assert sum(plan.unit_dims) == plan.total


def test_comm_report_accepts_plan():
    t = grad_tree()
    sm = stacked_mask(t)
    g = Granularity("layerwise")
    plan = build_plan(t, sm, g)
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.01),
                            granularity=g, strategy="allgather")
    a = comm_report(cfg, plan, 16)
    b = comm_report(cfg, unit_dims(t, sm, g), 16)
    assert a == b


def test_noise_bounds_from_plan():
    """Theory reads the plan's unit partition: Trace(A) <= d*max bound
    (the paper's headline), with closed-form QSGD omegas."""
    t = grad_tree()
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    qw = make_compressor("qsgd", levels=4)
    tr, em = noise_bounds_from_plan(plan, qw)
    assert tr <= em + 1e-9
    with pytest.raises(ValueError):
        noise_bounds_from_plan(plan, make_compressor("signsgd"))


# ---------------------------------------------------------------------------
# aggregation through the plan
# ---------------------------------------------------------------------------

def test_aggregate_simulated_workers_accepts_plan():
    """Passing a prebuilt plan changes nothing numerically."""
    n = 4
    t = grad_tree()
    wg = jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (i + 1) for i in range(n)]), t)
    sm = stacked_mask(t)
    cfg = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                            granularity=Granularity("layerwise"))
    plan = build_plan(t, sm, cfg.granularity)
    a, _ = aggregate_simulated_workers(wg, sm, cfg, KEY)
    b, _ = aggregate_simulated_workers(wg, sm, cfg, KEY, plan=plan)
    _assert_trees_close(a, b, "agg-plan")


# ---------------------------------------------------------------------------
# bucket kernels: one Pallas dispatch per bucket
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [64, 512, 700, 1024])
def test_qsgd_units_kernel_matches_ref(d):
    x = jax.random.normal(KEY, (5, d))
    keys = jax.random.split(KEY, 5)
    a = ops.qsgd_compress_units(x, keys, 16, use_pallas=True)
    b = ops.qsgd_compress_units(x, keys, 16, use_pallas=False)
    assert jnp.allclose(a, b, atol=1e-6)
    # per-row quantization error bound: |q - x| <= norm/levels elementwise
    norms = jnp.linalg.norm(x, axis=1, keepdims=True)
    assert float(jnp.max(jnp.abs(a - x) / norms)) <= 1.0 / 16 + 1e-6


@pytest.mark.parametrize("d", [64, 512, 700])
def test_terngrad_units_kernel_matches_ref(d):
    x = jax.random.normal(KEY, (3, d))
    keys = jax.random.split(KEY, 3)
    a = ops.terngrad_compress_units(x, keys, use_pallas=True)
    b = ops.terngrad_compress_units(x, keys, use_pallas=False)
    assert jnp.allclose(a, b, atol=1e-6)
    # ternary support: every entry is 0 or +-(row max)
    m = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    ratio = jnp.abs(a) / m
    assert bool(jnp.all((ratio < 1e-6) | (jnp.abs(ratio - 1.0) < 1e-6)))


def test_plan_compress_kernel_path():
    """plan_compress: gather -> one kernel dispatch per bucket -> scatter,
    with the plan's PRNG fold tables."""
    t = {"blocks": {"w": jax.random.normal(KEY, (4, 16, 32))},
         "emb": jax.random.normal(jax.random.fold_in(KEY, 1), (10, 8))}
    sm = stacked_mask(t)
    plan = build_plan(t, sm, Granularity("layerwise"))
    out = ops.plan_compress(plan, t, KEY, kind="qsgd", levels=16)
    for la, lb in zip(jax.tree_util.tree_leaves(out),
                      jax.tree_util.tree_leaves(t)):
        assert la.shape == lb.shape and la.dtype == lb.dtype
    with pytest.raises(ValueError):
        ops.plan_compress(plan, t, KEY, kind="nope")


# ---------------------------------------------------------------------------
# property test
# ---------------------------------------------------------------------------

# Shapes come from fixed lists: each new shape compiles new programs.
# Stacked rows of 4: one row, either side of the 64-wide blockwise unit
# (16, 17 rows) and the largest; loose dims: 1, 4 (the size of a
# one-row layer, so the two share a size class) and the largest.
@settings(max_examples=8)
@given(st.sampled_from([1, 2, 6]),
       st.sampled_from([1, 16, 17, 40]),
       st.sampled_from([1, 4, 30]),
       st.integers(min_value=0, max_value=10_000))
def test_property_plan_equivalence(L, rows, loose, seed):
    """Random stacked/loose shapes: plan == legacy for every granularity."""
    key = jax.random.key(seed)
    t = {"blocks": {"w": jax.random.normal(key, (L, rows, 4))},
         "head": jax.random.normal(jax.random.fold_in(key, 1), (loose,))}
    sm = stacked_mask(t)
    c = make_compressor("qsgd", levels=8)

    def fn(x, k):
        return c.sim(x, k)

    for gran in (Granularity("layerwise"), Granularity("entire_model"),
                 Granularity("blockwise", 64)):
        plan = build_plan(t, sm, gran)
        assert list(plan.unit_dims) == unit_dims(t, sm, gran)
        _assert_trees_close(apply_unitwise(fn, gran, t, sm, key),
                            apply_unitwise_reference(fn, gran, t, sm, key),
                            (gran.kind, "property"))
