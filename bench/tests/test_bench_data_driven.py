"""The harness is driven by data: a new cell, configuration, traffic mix
and per-layer metric are files it finds by name, and adding them edits no
file that is there. BENCHMARK.json names only what has its files."""
import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import bench_smoke_cells as smoke  # noqa: E402
from bench import cells  # noqa: E402


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_found(tmp_path):
    root = smoke.copy_bench(str(tmp_path))
    before = _digest(root)
    smoke.write(root, "mamba2-smoke.dense", limits={"loss_gap": 1.0})
    with open(os.path.join(root, "metrics", "steps_traced.py"), "w") as f:
        f.write('UNIT = "steps"\n\n\ndef read(ctx):\n'
                '    return ctx["steps"] or None\n')
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())
    assert set(after) - set(before) == {
        "workloads/mamba2-smoke.dense.json",
        "configs/mamba2-smoke.json",
        "traffic/mamba2-smoke.dense.traffic.json",
        "metrics/steps_traced.py"}
    cell = cells.load_cell("mamba2-smoke.dense", bench=root)
    assert cell.config["name"] == "mamba2-smoke"
    assert cell.traffic["codec"] is None
    assert cell.reference().param_specs(cell.config)["embed"][0] == \
        (512, 128)
    readers = cells.metric_readers(root)
    assert readers["steps_traced"].read({"steps": 3}) == 3
    assert set(cells.metric_readers()) < set(readers)


def test_benchmark_json_names_only_what_has_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bj = json.load(f)
    for c in bj["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(
            cells.BENCH, "reference", c["name"] + ".py"))
        assert os.path.isfile(os.path.join(
            cells.BENCH, "configs", c["name"] + ".flops.py"))
    for w in bj["workloads"]:
        cell = cells.load_cell(w["name"])
        assert (cell.workload["config"], cell.workload["traffic"],
                cell.chips) == (w["config"], w["traffic"], w["chips"])
    readers = cells.metric_readers()
    for m in bj["per_layer"]:
        assert m["name"] in readers
        assert readers[m["name"]].UNIT == m["unit"]


def test_a_run_without_a_tpu_fails_before_any_work():
    from bench import run
    cell = cells.load_cell("mamba2-1.3b.dense")
    with pytest.raises(SystemExit):
        run.run_cell(cell, 1, 1.0, 0)


@pytest.mark.parametrize("name,codec_flags,extra", [
    ("mamba2-1.3b.dense", ["--compressor", "none"], []),
    ("mamba2-1.3b.qsgd16-layerwise-wire",
     ["--compressor", "qsgd", "--levels", "16", "--granularity",
      "layerwise"], ["--wire"])])
def test_launcher_takes_the_codec_the_reference_reads(name, codec_flags,
                                                      extra):
    from bench import harness
    argv = harness.launcher_argv(cells.load_cell(name), 2 ** 31 + 7)
    n = len(codec_flags) + len(extra)
    assert argv[-n:] == codec_flags + extra
    assert argv[argv.index("--seed") + 1] == "7"
