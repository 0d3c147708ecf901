"""`correct` at a size a test run holds: the smoke mamba2 configuration
(float32) through the launcher, the window and the reference, on the CPU.

A sound run is correct. The control (the reference in the program's
place, one precision lower: bf16 weights and contractions for float32)
is not.
Nor is a run whose timed path is broken underneath: a step that returns
its state unchanged, or one that leaves out half of the batch. The
exchange between chips is left out in the reference put in the program's
place, over two workers, since the test process has one device.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import bench_smoke_cells as smoke  # noqa: E402
from bench import cells, compare, harness, run  # noqa: E402
from bench.reference._common import control_precision, train_readings  # noqa: E402,E501

#: set from smoke-size readings on the CPU over seeds 11-16 and SEED: the
#: program reads at most 7.2e-7 on every number, the control (bf16
#: weights and contractions) at least 1.6e-3 (loss), 1.3e-3 (gradient)
#: and 7.8e-4 (change), each fault at least 0.07, 0.36 and 0.24
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-4, "update_gap": 2e-4}
NAME = "mamba2-smoke.dense"
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def smoke_cell(tmp_path_factory):
    root = smoke.copy_bench(str(tmp_path_factory.mktemp("bench")))
    smoke.write(root, NAME, limits=LIMITS)
    cell = cells.load_cell(NAME, bench=root)
    harness.enable_compile_cache(persistent=False)
    from repro.launch import train as launcher
    job = launcher.prepare(launcher.parse_args(
        harness.launcher_argv(cell, SEED)))
    return cell, job


def _run(smoke_cell, break_step=None):
    cell, job = smoke_cell
    return run.run_cell(cell, SEED, 0.2, 0, require_tpu=False,
                        break_step=break_step, job=job)


def test_sound_run_is_correct(smoke_cell):
    out = _run(smoke_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _unchanged(step):
    def broken(params, opt, batch, i):
        _, _, m = step(jax.tree_util.tree_map(jnp.copy, params), opt,
                       batch, i)
        return params, opt, m
    return broken


def _half_batch(step):
    def broken(params, opt, batch, i):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt, half, i)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(smoke_cell, fault):
    out = _run(smoke_cell, break_step=fault)
    assert not out["correct"], out["checks"]


def _readings(cell, workers, precision="f32", fault=None):
    hosts = __import__("bench.data", fromlist=["batch_ring"]).batch_ring(
        cell.config, cell.traffic, SEED)
    return train_readings(cell.reference(), cell.config, cell.traffic,
                          workers, hosts, SEED,
                          int(cell.traffic["first_step"]),
                          precision=precision, fault=fault)


def test_control_is_not_correct(smoke_cell):
    cell, _ = smoke_cell
    ref = _readings(cell, 1)
    ctl = _readings(cell, 1, control_precision(cell.config["dtype"]))
    chk = compare.checks(compare.numbers(ctl, ref), LIMITS)
    assert not compare.passed(chk), chk


def test_exchange_left_out_is_not_correct(smoke_cell):
    cell, _ = smoke_cell
    ref = _readings(cell, 2)
    broken = _readings(cell, 2, fault="no_exchange")
    chk = compare.checks(compare.numbers(broken, ref), LIMITS)
    assert not compare.passed(chk), chk
