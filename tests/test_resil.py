"""The resilience plane: wire integrity, fault injection, recovery.

Contracts held here (single-device; the detection gate across the codec
zoo and the genuinely multi-device ring-hop checks are in
tests/test_resil_detection.py, the resend contract across the zoo in
tests/test_resil_resend.py):

  1. FLETCHER-32: the vectorized in-graph checksum equals the byte-serial
     reference loop (sizes crossing every chunk boundary + a hypothesis
     property); the init=1 variant gives an all-zero buffer a NONZERO
     checksum (a dropped/zeroed message never verifies); any single-bit
     flip is detected (exhaustive small sweep).
  2. LAYOUT: codec.integrity reserves exactly one extra uint32 header
     word per fused message; the checksummed span starts past
     [n_buckets, fletcher32]; integrity never changes decoded numerics.
  3. HARDENED PARSE: parse_message_header accepts exactly the buffers
     _message_buffer emits and raises ValueError on every mutated
     header (truncation, zero/oversized bucket count, misplaced or
     decreasing or out-of-range offsets, ragged byte length).
  4. CHECKPOINTS: atomic (no partial file at the final path, tmp never
     matches latest_checkpoint), digest-verified (a flipped byte
     raises ValueError), bitwise round-trip.
  5. RECOVERY: faulted-with-resend training == clean training bitwise;
     a guarded non-finite step skips the update AND conserves the EF
     residual; repeated corruption flips the dense fallback; partial
     participation renormalizes the mean over survivors and freezes
     dead workers' EF rows; train_resilient resume is leaf-for-leaf
     bitwise (train N == train k, kill, resume).

The heavy tests carry the `fault` marker: tier-1 (`make verify`) only,
excluded from the `make verify-fast` inner loop.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from _sweep_helpers import (GRANS, KEY, assert_trees_bitwise, grad_tree,
                            wire_parts, worker_grads)
from repro.core import (CompressionConfig, Granularity,
                        aggregate_simulated_workers, make_compressor,
                        stacked_mask)
from repro.core.wire import (execute_schedule_wire, fletcher32,
                             parse_message_header, verify_message)
from repro.ckpt import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.resil import (FaultInjector, RecoveryConfig, RecoveryManager,
                         train_resilient)
from repro.sim import CorruptionSpec, Scenario, StragglerSpec, init_ef


# ==========================================================================
# 1. Fletcher-32 vs the byte-serial reference
# ==========================================================================

def _fletcher_ref(data: bytes) -> int:
    """The byte-serial reference loop (init=1 variant, LE 16-bit words,
    odd tail zero-padded)."""
    if len(data) % 2:
        data = data + b"\x00"
    s1, s2 = 1, 0
    for i in range(0, len(data), 2):
        w = data[i] | (data[i + 1] << 8)
        s1 = (s1 + w) % 65535
        s2 = (s2 + s1) % 65535
    return (s2 << 16) | s1


@pytest.mark.parametrize("size", [0, 1, 2, 3, 7, 100, 255, 4097])
def test_fletcher32_matches_reference(size):
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size, dtype=np.uint8)
    got = int(fletcher32(jnp.asarray(data)))
    assert got == _fletcher_ref(data.tobytes()), size


@pytest.mark.fault
@pytest.mark.parametrize("size", [65534, 65535, 65536, 65537, 200001])
def test_fletcher32_matches_reference_chunk_boundaries(size):
    """Sizes straddling the staged mod-65535 chunk reduction."""
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size, dtype=np.uint8)
    got = int(fletcher32(jnp.asarray(data)))
    assert got == _fletcher_ref(data.tobytes()), size


#: buffer lengths: empty, one byte (odd tail), either side of a 16-bit
#: word and of 256 bytes, and the largest length
FLETCHER_SIZES = [0, 1, 2, 3, 255, 256, 257, 511, 512]


@given(st.sampled_from(FLETCHER_SIZES).flatmap(
    lambda n: st.lists(st.integers(0, 255), min_size=n, max_size=n)))
@settings(max_examples=50)
def test_fletcher32_property(byte_list):
    data = np.asarray(byte_list, np.uint8)
    assert int(fletcher32(jnp.asarray(data))) == \
        _fletcher_ref(data.tobytes())


def test_fletcher32_zeros_nonzero():
    """init=1: the checksum of an all-zero buffer is NONZERO and length-
    dependent — a dropped (zeroed) message can never verify against a
    zeroed header word, and truncation-to-zeros shifts sum2."""
    for size in (2, 64, 100):
        c = int(fletcher32(jnp.zeros((size,), jnp.uint8)))
        assert c != 0, size
    assert int(fletcher32(jnp.zeros((2,), jnp.uint8))) != \
        int(fletcher32(jnp.zeros((64,), jnp.uint8)))


def test_fletcher32_single_bit_flip_always_detected():
    """A single flipped bit changes one 16-bit word by ±2^k, never ≡ 0
    mod 65535 — exhaustively over a small buffer, every (byte, bit)
    flip changes the checksum."""
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, 64, dtype=np.uint8)
    clean = int(fletcher32(jnp.asarray(data)))
    for pos in range(data.size):
        for bit in range(8):
            d = data.copy()
            d[pos] ^= np.uint8(1 << bit)
            assert int(fletcher32(jnp.asarray(d))) != clean, (pos, bit)


# ==========================================================================
# 2. layout: the integrity header word
# ==========================================================================

def test_integrity_header_reserves_one_word():
    for gran in GRANS:
        _, _, sched, lays = wire_parts("topk", {"ratio": 0.25}, gran)
        _, _, _, plain = wire_parts("topk", {"ratio": 0.25}, gran,
                                     integrity=False)
        for li, lp in zip(lays, plain):
            assert li.checksum and not lp.checksum
            assert li.header_nbytes == lp.header_nbytes + 4
            assert li.checksum_span_start == 8
        assert not any(getattr(lp, "checksum") for lp in plain)


def test_verify_message_requires_checksum_layout():
    _, codec, sched, lays = wire_parts("topk", {"ratio": 0.25}, GRANS[0],
                                        integrity=False)
    buf = jnp.zeros((lays[0].total_nbytes,), jnp.uint8)
    with pytest.raises(ValueError, match="checksum layout"):
        verify_message(buf, lays[0])


def test_integrity_decode_bit_identical():
    """The checksum word changes the header, never the numerics."""
    for gran in GRANS:
        t, codec, sched, _ = wire_parts("qsgd", {"levels": 16}, gran)
        _, plain_codec, _, _ = wire_parts("qsgd", {"levels": 16}, gran,
                                           integrity=False)
        out_i, bufs_i = execute_schedule_wire(sched, codec, None, t, KEY)
        out_p, bufs_p = execute_schedule_wire(sched, plain_codec, None, t,
                                              KEY)
        assert_trees_bitwise(out_i, out_p, gran.kind)
        for bi, bp in zip(bufs_i, bufs_p):
            assert bi.size == bp.size + 4, gran.kind


# ==========================================================================
# 3. hardened header parse
# ==========================================================================

def _valid_message():
    t, codec, sched, lays = wire_parts("topk", {"ratio": 0.25}, GRANS[0])
    _, bufs = execute_schedule_wire(sched, codec, None, t, KEY)
    return np.asarray(bufs[0]), lays[0]


def test_parse_message_header_accepts_real_buffers():
    b, lay = _valid_message()
    n_buckets, offsets = parse_message_header(b, checksum=True)
    assert n_buckets == len(lay.offsets)
    assert offsets == tuple(lay.offsets)
    assert offsets[0] == lay.header_nbytes


def test_parse_message_header_rejects_mutations():
    b, _ = _valid_message()
    words = b.view("<u4").copy()

    def parse(w):
        parse_message_header(w.view(np.uint8), checksum=True)

    with pytest.raises(ValueError, match="whole number"):
        parse_message_header(b[:-1], checksum=True)
    with pytest.raises(ValueError, match="at least"):
        parse_message_header(np.zeros((0,), np.uint8), checksum=True)
    w = words.copy()
    w[0] = 0                      # zero bucket count
    with pytest.raises(ValueError, match="n_buckets"):
        parse(w)
    w = words.copy()
    w[0] = 1 << 24                # bucket count beyond the buffer
    with pytest.raises(ValueError, match="n_buckets"):
        parse(w)
    w = words.copy()
    w[2] += 4                     # first offset off the header end
    with pytest.raises(ValueError, match="first bucket offset"):
        parse(w)
    if words[0] >= 2:
        w = words.copy()
        w[3] = w[2] - 4           # decreasing offsets
        with pytest.raises(ValueError, match="non-decreasing"):
            parse(w)
    w = words.copy()
    w[2 + int(words[0]) - 1] = b.size + 64   # last offset out of range
    with pytest.raises(ValueError):
        parse(w)


# ==========================================================================
# 4. checkpoints: atomic, digest-verified
# ==========================================================================

def _ck_tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), jnp.bfloat16),
            "n": jnp.int32(7)}


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    d = str(tmp_path)
    t = _ck_tree()
    path = save_checkpoint(d, 5, t)
    assert os.path.exists(path)
    # no staging residue, and a stray tmp file never wins latest
    assert not [f for f in os.listdir(d) if ".tmp" in f]
    open(os.path.join(d, "ckpt_00000099_s0.npz.tmp.npz"), "wb").close()
    assert latest_checkpoint(d) == path
    step, got = load_checkpoint(path, like=t)
    assert step == 5
    assert_trees_bitwise(got, t, "roundtrip")


def test_checkpoint_rejects_truncation(tmp_path):
    d = str(tmp_path)
    path = save_checkpoint(d, 1, _ck_tree())
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:len(raw) // 2])
    with pytest.raises(ValueError, match="corrupt or truncated"):
        load_checkpoint(path, like=_ck_tree())


def test_checkpoint_rejects_flipped_payload_byte(tmp_path):
    d = str(tmp_path)
    path = save_checkpoint(d, 1, _ck_tree())
    raw = bytearray(open(path, "rb").read())
    # flip a byte in the stored-array region (past the zip local header)
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ValueError, match="corrupt"):
        load_checkpoint(path, like=_ck_tree())


def test_checkpoint_rejects_missing_keys(tmp_path):
    d = str(tmp_path)
    path = save_checkpoint(d, 1, {"w": jnp.ones((2,))})
    with pytest.raises(ValueError, match="missing keys"):
        load_checkpoint(path, like={"w": jnp.ones((2,)),
                                    "extra": jnp.ones((3,))})


# ==========================================================================
# 5. CorruptionSpec / scenario plumbing
# ==========================================================================

def test_corruption_spec_validation():
    with pytest.raises(ValueError, match="prob"):
        CorruptionSpec(prob=1.5)
    with pytest.raises(ValueError, match="mode"):
        CorruptionSpec(mode="cosmic_ray")
    with pytest.raises(ValueError, match="n_bits"):
        CorruptionSpec(n_bits=0)
    duck = type("S", (), {"prob": 1.0, "mode": "bad", "n_bits": 1,
                          "seed": 0})()
    with pytest.raises(ValueError, match="mode"):
        FaultInjector(duck)
    assert CorruptionSpec().is_identity()
    assert not CorruptionSpec(prob=0.5).is_identity()


def test_scenario_corruption_identity_and_describe():
    assert Scenario(name="x").is_identity()
    s = Scenario(name="x", corruption=CorruptionSpec(prob=0.3,
                                                     mode="truncate"))
    assert not s.is_identity()
    assert "truncate" in s.describe()


def test_injector_passthrough_is_same_object():
    buf = jnp.arange(64, dtype=jnp.uint8)
    inj = FaultInjector(CorruptionSpec(prob=0.0))
    assert inj.corrupt(buf, KEY, tag=0) is buf
    hop_only = FaultInjector(CorruptionSpec(prob=1.0, mode="drop_hop"))
    assert hop_only.corrupt(buf, KEY, tag=0) is buf  # serialized path


def test_faults_require_wire():
    grads = worker_grads()
    sm = stacked_mask(grad_tree())
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                            granularity=GRANS[0], integrity=True)
    inj = FaultInjector(CorruptionSpec(prob=1.0))
    with pytest.raises(ValueError, match="wire"):
        aggregate_simulated_workers(grads, sm, cfg, KEY, wire=False,
                                    faults=inj)


# ==========================================================================
# 6. partial participation: survivor mean + EF freeze
# ==========================================================================

def test_partial_participation_hand_computed():
    """With the identity compressor, the aggregate under an alive mask
    is exactly the plain mean over survivors."""
    grads = worker_grads()
    sm = stacked_mask(grad_tree())
    cfg = CompressionConfig(qw=make_compressor("identity"),
                            granularity=GRANS[0])
    alive = np.array([True, False, True, True])
    out, _ = aggregate_simulated_workers(grads, sm, cfg, KEY,
                                         alive=alive)
    w = jnp.asarray(alive, jnp.float32)
    w = w / jnp.sum(w)
    want = jax.tree_util.tree_map(
        lambda g: jnp.tensordot(w, g, axes=1), grads)
    assert_trees_bitwise(out, want, "survivor-mean")


def test_partial_participation_freezes_dead_ef():
    grads = worker_grads()
    sm = stacked_mask(grad_tree())
    cfg = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                            granularity=GRANS[0], error_feedback=True)
    ef = jax.tree_util.tree_map(
        lambda x: jnp.ones_like(x), init_ef(grad_tree(), 4))
    alive = np.array([True, False, True, True])
    _, new_ef = aggregate_simulated_workers(grads, sm, cfg, KEY,
                                            ef_state=ef, alive=alive)
    for le, ln in zip(jax.tree_util.tree_leaves(ef),
                      jax.tree_util.tree_leaves(new_ef)):
        assert bool((ln[1] == le[1]).all())          # dead row frozen
        if le[0].size:                               # alive rows advanced
            assert not bool((ln[0] == le[0]).all()) or \
                not bool((ln[2] == le[2]).all())


# ==========================================================================
# 7. recovery manager + the resilient training loop
# ==========================================================================

class ToyRunner:
    """Tiny linear softmax classifier on the non-IID synthetic shard
    sampler — the campaign runner protocol at smoke scale."""
    categories = 4
    global_batch = 8
    _hw, _ch = 4, 1

    def init(self, key):
        d = self._hw * self._hw * self._ch
        return {"w": 0.1 * jax.random.normal(key, (d, self.categories)),
                "b": jnp.zeros((self.categories,))}

    def loss(self, params, batch, key):
        x = batch["images"].reshape(batch["images"].shape[0], -1)
        logits = x @ params["w"] + params["b"]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, batch["labels"][:, None].astype(jnp.int32), 1)[:, 0]
        return jnp.mean(lse - picked)

    def worker_batch(self, key, props, per):
        from repro.data import noniid_classification_batch
        return noniid_classification_batch(key, props, per,
                                           classes=self.categories,
                                           hw=self._hw,
                                           channels=self._ch)


def _comp(ef=True):
    return CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                             granularity=Granularity("layerwise"),
                             error_feedback=ef, integrity=True)


def test_recovery_manager_fallback_and_state_roundtrip():
    cfg = RecoveryConfig(dense_fallback_after=2)
    m = RecoveryManager(cfg)
    m.observe(detected=3, resends=3)
    assert not m.fallback_active and m.consecutive_failures == 1
    m.observe(detected=0)
    assert m.consecutive_failures == 0          # consecutive, not total
    m.observe(detected=1)
    m.observe(detected=2, skipped=1)
    assert m.fallback_active
    assert m.counters["resil/corrupt_detected"] == 6
    assert m.counters["resil/steps_skipped"] == 1
    m2 = RecoveryManager(cfg)
    m2.restore(m.state())
    assert m2.fallback_active and m2.counters == m.counters
    with pytest.raises(ValueError, match="dense_fallback_after"):
        RecoveryConfig(dense_fallback_after=0)
    with pytest.raises(ValueError, match="straggler"):
        RecoveryConfig(straggler_timeout_us=-1.0)


@pytest.mark.fault
def test_train_resilient_resume_bitwise(tmp_path):
    """train 6 == train 3 + kill + resume + train 3, leaf for leaf."""
    runner = ToyRunner()
    scen = Scenario(name="corrupt", n_workers=4,
                    corruption=CorruptionSpec(prob=0.5, seed=5))
    full = train_resilient(runner, scen, _comp(), steps=6, seed=1)
    d = str(tmp_path)
    train_resilient(runner, scen, _comp(), steps=3, seed=1,
                    ckpt_dir=d, ckpt_every=3)
    resumed = train_resilient(runner, scen, _comp(), steps=6, seed=1,
                              ckpt_dir=d, ckpt_every=3, resume=True)
    assert_trees_bitwise(resumed["params"], full["params"], "params")
    assert_trees_bitwise(resumed["ef"], full["ef"], "ef")
    assert resumed["losses"] == full["losses"][3:]
    assert resumed["counters"]["resil/corrupt_detected"] == \
        full["counters"]["resil/corrupt_detected"]


@pytest.mark.fault
def test_train_resilient_resend_matches_clean():
    """Recovery contract: a corruption-riddled run WITH resend is
    bitwise the corruption-free run — detection wired to action."""
    runner = ToyRunner()
    clean = train_resilient(runner, Scenario(name="clean", n_workers=4),
                            _comp(), steps=4, seed=2)
    faulted = train_resilient(
        runner,
        Scenario(name="bad", n_workers=4,
                 corruption=CorruptionSpec(prob=1.0, seed=9)),
        _comp(), steps=4, seed=2,
        recovery=RecoveryConfig(resend=True))
    assert faulted["counters"]["resil/corrupt_detected"] > 0
    assert faulted["counters"]["resil/resends"] == \
        faulted["counters"]["resil/corrupt_detected"]
    assert_trees_bitwise(faulted["params"], clean["params"], "params")
    assert_trees_bitwise(faulted["ef"], clean["ef"], "ef")
    assert faulted["losses"] == clean["losses"]


@pytest.mark.fault
def test_train_resilient_step_guard_conserves_ef():
    """A poisoned (non-finite) step is skipped and the EF residual rolls
    back: params stay finite and equal the pre-poison trajectory's
    values wherever the guard fired."""
    runner = ToyRunner()
    scen = Scenario(name="clean", n_workers=4)

    def poison(wg, key):
        # nan out every worker's gradient at exactly one step
        hit = jax.random.bernoulli(jax.random.fold_in(key, 0), 0.25)
        return jax.tree_util.tree_map(
            lambda g: jnp.where(hit, jnp.nan, g), wg)

    guarded = train_resilient(runner, scen, _comp(), steps=8, seed=3,
                              recovery=RecoveryConfig(step_guard=True),
                              grad_hook=poison)
    assert guarded["counters"]["resil/steps_skipped"] >= 1
    for leaf in jax.tree_util.tree_leaves(guarded["params"]):
        assert bool(jnp.isfinite(leaf).all())
    for leaf in jax.tree_util.tree_leaves(guarded["ef"]):
        assert bool(jnp.isfinite(leaf).all())
    unguarded = train_resilient(
        runner, scen, _comp(), steps=8, seed=3,
        recovery=RecoveryConfig(step_guard=False), grad_hook=poison)
    assert any(not bool(jnp.isfinite(leaf).all())
               for leaf in jax.tree_util.tree_leaves(unguarded["params"]))


@pytest.mark.fault
def test_train_resilient_dense_fallback():
    """Unrecoverable repeated corruption (no resend) flips the dense
    fallback after N consecutive corrupted steps; training then
    proceeds on the plain mean with finite losses."""
    runner = ToyRunner()
    scen = Scenario(name="bad", n_workers=4,
                    corruption=CorruptionSpec(prob=1.0, seed=4))
    res = train_resilient(
        runner, scen, _comp(), steps=6, seed=4,
        recovery=RecoveryConfig(resend=False, dense_fallback_after=2))
    assert res["fallback_active"]
    assert res["counters"]["resil/corrupt_detected"] > 0
    assert all(np.isfinite(res["losses"]))


@pytest.mark.fault
def test_train_resilient_partial_participation():
    runner = ToyRunner()
    scen = Scenario(name="strag", n_workers=4,
                    straggler=StragglerSpec(prob=0.5, delay_us=1e6,
                                            seed=13))
    res = train_resilient(
        runner, scen, _comp(), steps=6, seed=5,
        recovery=RecoveryConfig(straggler_timeout_us=10.0))
    assert all(np.isfinite(res["losses"]))


# ==========================================================================
# 8. engine step-guard + launcher resume
# ==========================================================================

@pytest.mark.fault
def test_engine_step_guard_clean_step_identical():
    """step_guard on a finite step: same params bitwise, skipped == 0."""
    from repro.configs.registry import get_smoke
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke("mamba2-1.3b")
    mesh = make_host_mesh(1, 1)
    comp = CompressionConfig(qw=make_compressor("topk", ratio=0.25),
                             granularity=Granularity("layerwise"))
    from repro.data import lm_batches
    batch = next(lm_batches(cfg.vocab, 4, 16, seed=0))
    with mesh:
        eng = Engine(cfg, mesh, comp=comp)
        params, opt_state = eng.init_state(0)
        plain = eng.build_train_step()
        p1, o1, m1 = plain(params, opt_state, batch, jnp.int32(0))
        params, opt_state = eng.init_state(0)
        guarded = eng.build_train_step(step_guard=True)
        p2, o2, m2 = guarded(params, opt_state, batch, jnp.int32(0))
    assert float(m2["skipped"]) == 0.0
    assert "skipped" not in m1
    assert_trees_bitwise(p1, p2, "step-guard-clean")
    assert float(m1["loss"]) == float(m2["loss"])


@pytest.mark.fault
@pytest.mark.slow
def test_train_launcher_resume_bitwise(tmp_path):
    """launch.train --resume replays to the checkpoint step and lands on
    the uninterrupted run's state bitwise (compared through the step-6
    checkpoints both runs write)."""
    from repro.launch.train import main

    base = ["--arch", "mamba2-1.3b", "--smoke", "--steps", "6",
            "--batch", "4", "--seq", "16", "--compressor", "topk",
            "--ratio", "0.25", "--step-guard", "--ckpt-every", "3"]
    a, c = str(tmp_path / "a"), str(tmp_path / "c")
    assert main(base + ["--ckpt-dir", a]) == 0
    os.makedirs(c)
    import shutil
    shutil.copy(os.path.join(a, "ckpt_00000003_s0.npz"), c)
    assert main(base + ["--ckpt-dir", c, "--resume"]) == 0
    with np.load(os.path.join(a, "ckpt_00000006_s0.npz"),
                 allow_pickle=False) as za, \
            np.load(os.path.join(c, "ckpt_00000006_s0.npz"),
                    allow_pickle=False) as zc:
        for k in za.files:
            if k == "__meta__":
                continue
            assert np.array_equal(np.asarray(za[k]), np.asarray(zc[k])), k
