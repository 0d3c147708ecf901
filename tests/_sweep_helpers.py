"""What the tests share: the six-codec zoo, the gradient trees, the
smoke engine and its two-step comparison, bitwise tree comparison, and
the sweep parametrisation that keeps a smoke subset unmarked for `make
verify-fast`."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (CompressionConfig, Granularity, build_plan,
                        build_schedule, make_compressor, message_layouts,
                        stacked_mask, wire_codec)

KEY = jax.random.key(0)

# the paper's six operators, one codec each
SIX = [
    ("topk", {"ratio": 0.25}),
    ("randomk", {"ratio": 0.3, "scale": True}),
    ("qsgd", {"levels": 16}),
    ("terngrad", {}),
    ("signsgd", {}),
    ("natural", {}),
]
SIX_IDS = [n for n, _ in SIX]

GRANS = [Granularity("layerwise"), Granularity("entire_model")]

#: (name, kwargs, granularity): the six codecs at both granularities
ZOO = [(name, kw, gran) for name, kw in SIX for gran in GRANS]


def grad_tree(key=KEY):
    """Mixed pytree: scan-stacked + loose leaves of several size classes
    (odd dims exercise word-boundary padding; readiness order differs
    from plan bucket order)."""
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    return {"blocks": {"w": jax.random.normal(ks[0], (3, 16, 8)),
                       "b": jax.random.normal(ks[1], (3, 8))},
            "embed": jax.random.normal(ks[2], (20, 4)),
            "head": jax.random.normal(ks[3], (4, 2)),
            "scalar_gain": jax.random.normal(ks[4], ())}


def ring_tree():
    """The multi-device checks' tree: dims 4 workers do not divide."""
    ks = [jax.random.fold_in(jax.random.key(3), i) for i in range(4)]
    return {"dense": jax.random.normal(ks[0], (8, 16)),
            "blocks": jax.random.normal(ks[1], (3, 4, 10)),
            "odd": jax.random.normal(ks[2], (7,)),
            "scalar": jax.random.normal(ks[3], ())}


def per_worker(g):
    """Distinct gradients per position on the "data" axis."""
    i = jax.lax.axis_index("data").astype(jnp.float32)
    return jax.tree_util.tree_map(lambda x: x * (1.0 + i), g)


def worker_grads(n=4, key=KEY):
    """Per-worker gradient stack: leading worker axis, distinct draws."""
    trees = [grad_tree(jax.random.fold_in(key, 100 + i)) for i in range(n)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def wire_parts(name, kw, gran, integrity=True):
    """grad_tree, the codec, its per-bucket schedule and the message
    layouts that schedule puts on the wire."""
    t = grad_tree()
    codec = wire_codec(make_compressor(name, **kw), integrity=integrity)
    sched = build_schedule(build_plan(t, stacked_mask(t), gran), 0.0)
    return t, codec, sched, message_layouts(sched, codec)


def smoke_engine():
    """The mamba2-1.3b smoke engine, QSGD-16 per layer, 1-device mesh."""
    from repro.configs.registry import get_smoke
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh
    comp = CompressionConfig(qw=make_compressor("qsgd", levels=16),
                             granularity=Granularity("layerwise"))
    return Engine(get_smoke("mamba2-1.3b"), make_host_mesh(1, 1), comp=comp)


def smoke_batch():
    """A fixed 4 x 16-token batch for the smoke engine."""
    return {"tokens": jnp.ones((4, 16), jnp.int32) * 3,
            "targets": jnp.ones((4, 16), jnp.int32) * 5}


def assert_steps_bitwise(eng, ref_step, step, ctx):
    """Two steps of each train step from the engine's initial state give
    the same parameters, bit for bit, and the same loss."""
    batch = smoke_batch()

    def run(step_fn):
        params, opt_state = eng.init_state(0)
        for i in range(2):
            params, opt_state, m = step_fn(params, opt_state, batch,
                                           jnp.int32(i))
        return params, m

    p_ref, m_ref = run(ref_step)
    p, m = run(step)
    assert_trees_bitwise(p_ref, p, ctx)
    assert float(m_ref["loss"]) == float(m["loss"]), ctx


def assert_trees_bitwise(a, b, ctx):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert la.shape == lb.shape and la.dtype == lb.dtype, ctx
        assert bool((la == lb).all()), (
            ctx, float(jnp.max(jnp.abs(la - lb))))


def _case_id(case):
    parts = []
    for v in case:
        if isinstance(v, Granularity):
            parts.append(v.kind)
        elif isinstance(v, float):
            parts.append(f"{v:g}")
        elif not isinstance(v, dict):      # a codec's kwargs follow its name
            parts.append(str(v))
    return "-".join(parts)


def sweep(cases, smoke, mark, ids=None):
    """One `pytest.param` per case (a tuple of the test's arguments). The
    cases `smoke(*case)` accepts run unmarked — the `make verify-fast`
    subset; the others carry `mark`, the sweep's marker."""
    ids = ids or [_case_id(case) for case in cases]
    return [pytest.param(*case, id=i, marks=() if smoke(*case) else (mark,))
            for case, i in zip(cases, ids)]
