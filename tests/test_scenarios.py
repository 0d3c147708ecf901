"""SimCluster: the fault-injection differential suite.

The contract (ISSUE: "every scenario knob at its identity setting is
bit-identical to the un-wrapped path"):

  1. IDENTITY: `SimCluster.aggregate` under an identity scenario (no
     links, zero-delay stragglers, n->n rescales, IID data) returns the
     SAME bits as the bare `aggregate_simulated_workers` — held across
     the six-codec zoo, both granularities, error feedback and the wire
     path. By construction aggregate is a pass-through; this suite pins
     that construction as a regression contract.
  2. ELASTIC EF CONSERVATION: re-bucketing EF residuals 4 -> 2 -> 4
     through a real ckpt/ round-trip conserves residual mass exactly
     (integer-valued residuals => exact fp sums), and a rescale to the
     CURRENT world size is bit-identical (the ckpt round-trip itself is
     lossless).
  3. HAND-COMPUTED ACCOUNTING: straggler delays and heterogeneous link
     alpha/beta feed `simulate_schedule` exactly as the closed-form
     single-message model predicts — exposed = alpha + bits/(8*gbps*1e3)
     per worker, step exposure = max over workers, delays charged on top.
  4. NON-IID DETERMINISM: Dirichlet shard skew is a pure function of the
     key — same key, same shards, bit for bit.

The full codec-zoo sweep carries the `scenario` marker (tier-1 only;
`make verify-fast` keeps the unmarked smoke subset).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sweep_helpers import (GRANS, KEY, SIX, assert_trees_bitwise, grad_tree,
                            sweep, worker_grads)
from repro.core import (CompressionConfig, Granularity,
                        aggregate_simulated_workers, build_plan,
                        make_compressor, stacked_mask)
from repro.data import (dirichlet_proportions, noniid_classification_batch,
                        noniid_markov_lm_batch, make_markov)
from repro.sim import (LinkSpec, RescaleEvent, Scenario, SimCluster,
                       StragglerSpec, init_ef)


#: every knob present, every knob at its identity setting — the hostile
#: shape of the spec with the clean semantics of the default.
IDENTITY = Scenario(
    name="identity", n_workers=4,
    straggler=StragglerSpec(prob=0.5, delay_us=0.0, seed=11),
    rescales=(RescaleEvent(step=3, world_size=4),))


@pytest.mark.parametrize("name,kw,gran,wire", sweep(
    [(name, kw, gran, wire) for wire in ("sim", "wire") for name, kw in SIX
     for gran in GRANS],
    lambda name, kw, gran, wire: name == "topk" and wire == "wire",
    pytest.mark.scenario))
def test_identity_scenario_bitwise_zoo(name, kw, gran, wire):
    """The unmarked subset: topk + EF + wire at both granularities."""
    on_wire = wire == "wire"
    grads = worker_grads()
    stacked = stacked_mask(grad_tree())
    cfg = CompressionConfig(qw=make_compressor(name, **kw),
                            granularity=gran, error_feedback=True)
    ef = init_ef(grad_tree(), 4)
    cluster = SimCluster(IDENTITY, cfg)
    assert IDENTITY.is_identity()
    got = cluster.aggregate(grads, stacked, KEY, ef_state=ef, wire=on_wire)
    want = aggregate_simulated_workers(grads, stacked, cfg, KEY,
                                       ef_state=ef, wire=on_wire)
    ctx = (name, gran.kind, wire)
    assert_trees_bitwise(got[0], want[0], ctx)
    assert_trees_bitwise(got[1], want[1], ctx)


# ==========================================================================
# elastic world size: EF re-bucketing through ckpt/
# ==========================================================================

def _int_ef(n=4):
    """Integer-valued residuals: fp addition on small ints is exact, so
    conservation sums are EQUALITY checks, not tolerances."""
    tree = grad_tree()
    i = [0]

    def fill(p):
        leaf = jnp.arange(n * p.size, dtype=jnp.float32) % 13.0 + i[0]
        i[0] += 1
        return leaf.reshape((n,) + p.shape)
    return jax.tree_util.tree_map(fill, tree)


def test_rescale_to_same_size_is_bitwise_noop(tmp_path):
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                            error_feedback=True)
    cluster = SimCluster(IDENTITY, cfg, ckpt_dir=str(tmp_path))
    ef = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.fold_in(KEY, 7),
                                    (4,) + p.shape), grad_tree())
    back = cluster.rescale_ef(ef, 4, step=0)
    assert_trees_bitwise(back, ef, "n->n rescale through ckpt")


def test_ef_conservation_4_2_4(tmp_path):
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                            error_feedback=True)
    sc = Scenario(name="elastic", n_workers=4,
                  rescales=(RescaleEvent(step=10, world_size=2),
                            RescaleEvent(step=20, world_size=4)))
    cluster = SimCluster(sc, cfg, ckpt_dir=str(tmp_path))
    ef4 = _int_ef(4)

    n, ef2, changed = cluster.maybe_rescale(10, ef4)
    assert (n, changed) == (2, True)
    for l4, l2 in zip(jax.tree_util.tree_leaves(ef4),
                      jax.tree_util.tree_leaves(ef2)):
        assert l2.shape[0] == 2
        # worker i folds into slot i % 2 — exact on integer residuals
        assert bool((l2[0] == l4[0] + l4[2]).all())
        assert bool((l2[1] == l4[1] + l4[3]).all())
        assert bool((l2.sum(0) == l4.sum(0)).all())  # mass conserved

    n, ef4b, changed = cluster.maybe_rescale(20, ef2)
    assert (n, changed) == (4, True)
    for l2, l4b in zip(jax.tree_util.tree_leaves(ef2),
                       jax.tree_util.tree_leaves(ef4b)):
        assert l4b.shape[0] == 4
        assert bool((l4b[:2] == l2).all())      # survivors keep rows
        assert bool((l4b[2:] == 0.0).all())     # joiners start at zero
        assert bool((l4b.sum(0) == l2.sum(0)).all())


def test_maybe_rescale_quiet_between_events(tmp_path):
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25))
    sc = Scenario(name="elastic", n_workers=4,
                  rescales=(RescaleEvent(step=10, world_size=2),))
    cluster = SimCluster(sc, cfg, ckpt_dir=str(tmp_path))
    ef = _int_ef(4)
    for step in (0, 5, 9, 11, 15):  # no event due => untouched object
        n, out, changed = cluster.maybe_rescale(step, ef)
        assert not changed and out is ef
        assert n == (4 if step < 10 else 2)
    assert sc.world_size_at(9) == 4
    assert sc.world_size_at(10) == 2
    assert sc.world_size_at(999) == 2


def test_identity_rescale_event_does_not_touch_state(tmp_path):
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25))
    cluster = SimCluster(IDENTITY, cfg, ckpt_dir=str(tmp_path))
    ef = _int_ef(4)
    n, out, changed = cluster.maybe_rescale(3, ef)  # event due, n->n
    assert (n, changed) == (4, False) and out is ef


# ==========================================================================
# straggler + heterogeneous-link accounting: hand-computed
# ==========================================================================

def _single_message_plan():
    """One leaf, entire-model granularity: exactly one bucket, one
    message — the closed-form case of the alpha-beta model."""
    tree = {"w": jnp.zeros((64,), jnp.float32)}
    return build_plan(tree, stacked_mask(tree), Granularity("entire_model"))


def _expected_exposed(bits, alpha_us, gbps):
    """Single message: exposed = send time = alpha + bits/(8*gbps*1e3),
    regardless of backward_us (one message can never overlap itself)."""
    return alpha_us + (bits / 8.0) / (gbps * 1e3)


def test_straggler_accounting_hand_computed():
    qw = make_compressor("topk", ratio=0.25)
    plan = _single_message_plan()
    bits = qw.payload_bits(64)  # one bucket, n=1
    sc = Scenario(name="straggle", n_workers=3,
                  straggler=StragglerSpec(prob=1.0, delay_us=5000.0, seed=3))
    cluster = SimCluster(sc, CompressionConfig(qw=qw))
    entry = cluster.step_accounting(0, plan, backward_us=200.0)

    model = _expected_exposed(bits, 50.0, 12.5)  # default link
    assert entry["straggler_hits"] == 3
    assert entry["world_size"] == 3
    for w in entry["workers"]:
        assert w["straggler_delay_us"] == 5000.0
        assert w["model_exposed_us"] == pytest.approx(model, abs=1e-3)
        assert w["exposed_us"] == pytest.approx(model + 5000.0, abs=1e-3)
    assert entry["exposed_comm_us"] == pytest.approx(model + 5000.0,
                                                     abs=1e-3)
    assert cluster.exposed_comm_total_us() == entry["exposed_comm_us"]

    # pure function of (seed, step): replaying the step replays the draws
    again = SimCluster(sc, CompressionConfig(qw=qw))
    assert (again.step_accounting(0, plan, backward_us=200.0)["workers"]
            == entry["workers"])


def test_zero_prob_straggler_draws_exact_zeros():
    s = StragglerSpec(prob=0.0, delay_us=1e9, seed=1)
    assert (s.draws(0, 8) == 0.0).all()
    s = StragglerSpec(prob=1.0, delay_us=0.0, seed=1)
    assert (s.draws(0, 8) == 0.0).all()


def test_hetero_link_accounting_hand_computed():
    qw = make_compressor("topk", ratio=0.25)
    plan = _single_message_plan()
    bits = qw.payload_bits(64)
    links = (LinkSpec(alpha_us=20.0, gbps=25.0),
             LinkSpec(alpha_us=400.0, gbps=1.25))
    sc = Scenario(name="hetero", n_workers=2, links=links)
    cluster = SimCluster(sc, CompressionConfig(qw=qw))
    entry = cluster.step_accounting(0, plan, backward_us=200.0)

    fast = _expected_exposed(bits, 20.0, 25.0)
    slow = _expected_exposed(bits, 400.0, 1.25)
    got = {w["worker"]: w for w in entry["workers"]}
    assert got[0]["model_exposed_us"] == pytest.approx(fast, abs=1e-3)
    assert got[1]["model_exposed_us"] == pytest.approx(slow, abs=1e-3)
    # synchronous allreduce: the step waits for the slowest worker
    assert entry["exposed_comm_us"] == pytest.approx(slow, abs=1e-3)


def test_link_cycling_covers_elastic_growth():
    links = (LinkSpec(10.0, 10.0), LinkSpec(20.0, 20.0))
    sc = Scenario(name="cyc", n_workers=5, links=links)
    assert sc.link(0) == links[0] and sc.link(1) == links[1]
    assert sc.link(4) == links[0]  # cycles beyond len(links)
    assert Scenario(name="plain").link(3) == LinkSpec()


def test_per_link_fusion_policy_fuses_on_high_alpha_links():
    """A high-latency link should carry fewer (more fused) messages than
    a zero-latency link under the same layerwise plan — the per-link
    FusionPolicy decision the accounting prices."""
    qw = make_compressor("topk", ratio=0.25)
    tree = grad_tree()
    plan = build_plan(tree, stacked_mask(tree), Granularity("layerwise"))
    sc = Scenario(name="fuse", n_workers=2,
                  links=(LinkSpec(alpha_us=0.0, gbps=12.5),
                         LinkSpec(alpha_us=5000.0, gbps=12.5)))
    cluster = SimCluster(sc, CompressionConfig(qw=qw))
    entry = cluster.step_accounting(0, plan)
    got = {w["worker"]: w for w in entry["workers"]}
    assert got[1]["n_messages"] <= got[0]["n_messages"]
    assert got[1]["n_messages"] < len(plan.buckets) or \
        got[0]["n_messages"] == len(plan.buckets)


# ==========================================================================
# non-IID shards: deterministic, skewed, well-formed
# ==========================================================================

def test_dirichlet_proportions_deterministic_and_stochastic():
    key = jax.random.key(42)
    p1 = dirichlet_proportions(key, 4, 10, alpha=0.1)
    p2 = dirichlet_proportions(key, 4, 10, alpha=0.1)
    assert p1.shape == (4, 10)
    assert bool((p1 == p2).all())  # pure function of the key
    assert np.allclose(np.asarray(p1).sum(1), 1.0, atol=1e-5)
    # hostile alpha => concentrated shards: every worker's modal class
    # holds far more than the uniform 1/10 share
    assert float(np.asarray(p1).max(axis=1).min()) > 0.3
    # workers differ (independent draws)
    assert not bool((p1[0] == p1[1]).all())


def test_noniid_classification_batch_deterministic_and_skewed():
    key = jax.random.key(7)
    props = dirichlet_proportions(key, 4, 10, alpha=0.05)
    b1 = noniid_classification_batch(jax.random.fold_in(key, 1), props, 32)
    b2 = noniid_classification_batch(jax.random.fold_in(key, 1), props, 32)
    assert_trees_bitwise(b1, b2, "noniid classification determinism")
    assert b1["images"].shape == (4, 32, 32, 32, 3)
    assert b1["labels"].shape == (4, 32)
    labels = np.asarray(b1["labels"])
    assert labels.min() >= 0 and labels.max() < 10
    # at alpha=0.05 each worker's modal class dominates its shard
    for w in range(4):
        _, counts = np.unique(labels[w], return_counts=True)
        assert counts.max() / 32 > 0.5


def test_noniid_lm_batch_deterministic():
    key = jax.random.key(9)
    trans = make_markov(vocab=32, seed=0)
    props = dirichlet_proportions(key, 4, 32, alpha=0.1)
    b1 = noniid_markov_lm_batch(jax.random.fold_in(key, 2), trans, props,
                                8, 16)
    b2 = noniid_markov_lm_batch(jax.random.fold_in(key, 2), trans, props,
                                8, 16)
    assert_trees_bitwise(b1, b2, "noniid lm determinism")
    assert b1["tokens"].shape == (4, 8, 16)
    assert bool((b1["targets"][:, :, :-1] == b1["tokens"][:, :, 1:]).all())


# ==========================================================================
# Scenario spec: hashable value object, validated
# ==========================================================================

def test_scenario_hashable_value_object():
    a = Scenario(name="x", n_workers=4,
                 links=(LinkSpec(10.0, 5.0),),
                 straggler=StragglerSpec(0.5, 100.0, 3),
                 rescales=(RescaleEvent(5, 2),), dirichlet_alpha=0.3)
    b = Scenario(name="x", n_workers=4,
                 links=(LinkSpec(10.0, 5.0),),
                 straggler=StragglerSpec(0.5, 100.0, 3),
                 rescales=(RescaleEvent(5, 2),), dirichlet_alpha=0.3)
    assert a == b and hash(a) == hash(b)
    assert not a.is_identity()
    assert "straggle" in a.describe() and "rescale" in a.describe()
    assert Scenario().is_identity()


@pytest.mark.parametrize("bad", [
    lambda: Scenario(n_workers=0),
    lambda: Scenario(dirichlet_alpha=0.0),
    lambda: Scenario(rescales=(RescaleEvent(10, 2), RescaleEvent(5, 4))),
    lambda: LinkSpec(alpha_us=-1.0),
    lambda: LinkSpec(gbps=0.0),
    lambda: StragglerSpec(prob=1.5),
    lambda: StragglerSpec(delay_us=-1.0),
    lambda: RescaleEvent(step=-1, world_size=2),
    lambda: RescaleEvent(step=0, world_size=0),
])
def test_scenario_validation(bad):
    with pytest.raises(ValueError):
        bad()
