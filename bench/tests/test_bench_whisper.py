"""Whisper-base in the program against the plain float32 reference
(bench/reference/whisper-base.py), at the program's --smoke size on the
CPU, with seeded random weights from bench/weights.py:

  * the parameter trees are the same, leaf for leaf, at smoke size and at
    the published size;
  * on one device, the loss and every leaf's gradient;
  * on four virtual devices (bench/tests/whisper_ring_check.py, a process
    of its own), the benchmark's timed path with QSGD-16 per layer over
    the streaming ring against the reference's four-worker readings, and
    the trace of that step: every wire message makes the n - 1 hops the
    ring's schedule states, each a `collective` span that carries the
    bytes of its message;
  * the control (the reference in bf16) and the fault that leaves the
    exchange out fail the limits that the sound run passes;
  * bench/ring_bytes.py counts the bytes the program's own message
    layouts hold.
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import whisper_smoke_cell as wcell  # noqa: E402
from bench import cells, compare, data, ring_bytes, weights  # noqa: E402
from bench.reference._common import contraction, train_readings  # noqa: E402

CFG, TRAFFIC, WORKERS = wcell.CONFIG, wcell.TRAFFIC, wcell.WORKERS
REF = cells.load_module(os.path.join(cells.BENCH, "reference",
                                     "whisper-base.py"))
SEED = 2 ** 31 + 4242
CELL = "whisper-base.qsgd16-layerwise-wire-ring.4chip"

#: set from smoke-size readings on the CPU at SEED: the program over the
#: ring reads 4.8e-7 (loss), 9.4e-9 (gradient) and 1.8e-8 (change); the
#: bf16 control 7.6e-4, 4.8e-4 and 1.1e-3; the exchange left out 0.13,
#: 1.07 and 1.02; half of each worker's rows 0.30, 0.33 and 0.28
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-4, "update_gap": 1e-4}


def _model(cfg_json):
    from repro.configs.registry import get_config, get_smoke
    from repro.models import DistConfig, Model
    get = get_smoke if cfg_json.get("smoke") else get_config
    return Model(get(cfg_json["arch"]), DistConfig())


def _shapes(model):
    return {jax.tree_util.keystr(p, simple=True, separator="/"):
            tuple(v.shape) for p, v in
            jax.tree_util.tree_flatten_with_path(model.param_shapes())[0]}


@pytest.mark.parametrize("size", ["smoke", "published"])
def test_param_specs_name_the_program_leaves(size):
    c = CFG if size == "smoke" else cells.load_cell(CELL).config
    want = {p: tuple(s[0]) for p, s in REF.param_specs(c).items()}
    assert _shapes(_model(c)) == want


def test_loss_and_gradients_match_reference_on_one_device():
    """float32 on both sides. Tolerances: the loss to 1e-5 relative and
    each leaf's gradient to 1e-4 in relative L2 norm; they differ only in
    the order of sums (flash attention's running softmax, LayerNorm's
    variance as E[x^2] - mean^2, gradient accumulation over blocks), which
    reads about 1e-6."""
    model = _model(CFG)
    specs = REF.param_specs(CFG)
    params = weights.make(specs, SEED, jnp.float32)
    batch = data.batch_ring(CFG, TRAFFIC, SEED)[0]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch, jax.random.key(0))))(params)
    rg, total, n = REF.grad_sum(weights.flatten(params), batch, CFG,
                                contraction("f32"))
    assert float(loss) == pytest.approx(total / n, rel=1e-5)
    for path, g in weights.flatten(grads).items():
        r = np.asarray(rg[path]) / n
        err = np.linalg.norm(np.asarray(g) - r) / np.linalg.norm(r)
        assert err < 1e-4, (path, err)


@pytest.fixture(scope="module")
def ring_run():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable,
                          os.path.join(HERE, "whisper_ring_check.py")],
                         capture_output=True, text=True, env=env,
                         timeout=400)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.timeout(420)
def test_ring_step_matches_four_worker_reference(ring_run):
    cell = ring_run["cell"]
    assert cell["devices"] == WORKERS and cell["failed"] == 0
    assert cell["attempted"] >= TRAFFIC["reference_steps"]
    chk = compare.checks(cell["numbers"], LIMITS)
    assert compare.passed(chk), chk


@pytest.mark.timeout(420)
def test_every_ring_hop_is_a_collective_span_with_its_bytes(ring_run):
    tr = ring_run["trace"]
    sizes = tr["message_bytes"]
    assert sorted(tr["hops"]) == sorted(str(m) for m in range(len(sizes)))
    for m, nb in enumerate(sizes):
        assert tr["hops"][str(m)] == [["collective", nb]] * (WORKERS - 1)
    assert tr["scopes"] == {"repro/encoder": True,
                            "repro/cross_attention": True}


def _readings(precision="f32", fault=None):
    hosts = data.batch_ring(CFG, TRAFFIC, SEED)
    return train_readings(REF, CFG, TRAFFIC, WORKERS, hosts, SEED,
                          int(TRAFFIC["first_step"]), precision=precision,
                          fault=fault)


@pytest.fixture(scope="module")
def reference():
    return _readings()


@pytest.mark.parametrize("what", ["control_bf16", "no_exchange"])
def test_control_and_missing_exchange_fail(reference, what):
    other = (_readings("bf16") if what == "control_bf16"
             else _readings(fault="no_exchange"))
    chk = compare.checks(compare.numbers(other, reference), LIMITS)
    assert not compare.passed(chk), chk
    if what == "control_bf16":      # the control fails every number
        assert all(c["value"] > c["limit"] for c in chk.values()), chk


def test_ring_bytes_equal_the_program_message_layouts():
    from repro.core import make_compressor
    from repro.core.plan import build_plan
    from repro.core.granularity import Granularity
    from repro.core.schedule import build_schedule
    from repro.core.wire import message_layouts, wire_codec
    model = _model(CFG)
    plan = build_plan(model.param_shapes(), model.stacked(),
                      Granularity("layerwise"))
    layouts = message_layouts(build_schedule(plan, 0.0),
                              wire_codec(make_compressor("qsgd",
                                                         levels=16)))
    codec = TRAFFIC["codec"]
    shapes = {p: tuple(s[0]) for p, s in REF.param_specs(CFG).items()}
    assert sorted(ring_bytes.message_bytes(codec, shapes).values()) == \
        sorted(int(l.total_nbytes) for l in layouts)
    assert ring_bytes.step_bytes(codec, shapes, WORKERS) == \
        (WORKERS - 1) * sum(int(l.total_nbytes) for l in layouts)


def test_whisper_flops_hand_count_and_published_step():
    f = cells.load_module(os.path.join(cells.BENCH, "configs",
                                       "whisper-base.flops.py"))
    # d 2, f 4, V 3, T 2 frames, S 2 tokens, one layer each side:
    # encoder 2 * (8*4 + 4*2*2 + 4*2*4) = 160; decoder 2 * (32 + 16 + 16
    # + 16 + 32) + 2 * 4*4 = 256; head 2 * 2*2*3 = 24
    c = {"d_model": 2, "encoder_ffn_dim": 4, "vocab_size": 3,
         "encoder_layers": 1, "decoder_layers": 1}
    assert f.forward_flops_per_segment(c, 2, 2) == 160 + 256 + 24
    assert f.flops_per_step(c, {"batch": 5, "frames": 2, "seq": 2}) == \
        3 * 440 * 5
    # 148.0 GFLOP a segment forward, 64 segments a step
    got = cells.load_cell(CELL).flops_per_step()
    assert math.isclose(got, 3 * 64 * 147.955253248e9, rel_tol=1e-12)
