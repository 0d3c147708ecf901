"""The yardstick's arithmetic: the peaks table, the FLOP functions against
hand counts, and the wire-byte count against the program's own."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, wire_bytes  # noqa: E402


def test_peaks_v5e_from_the_published_table():
    p = cells.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 394e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com/tpu/docs/v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        cells.peaks("TPU v0 imaginary")


def _flops(config):
    return cells.load_module(os.path.join(
        cells.BENCH, "configs", config + ".flops.py"))


def test_mamba2_flops_hand_count():
    # d 4, d_inner 8, 2 heads of 4, state 2, 2 conv taps, chunk 2, vocab 10
    c = {"d_model": 4, "n_layer": 1, "vocab_size": 10, "d_state": 2,
         "d_conv": 2, "ngroups": 1, "expand": 2, "headdim": 4,
         "chunk_size": 2}
    # projections 2*4*(16+4+2) = 176, conv 2*2*(8+4) = 48,
    # SSD 2*2*2 + 2*2*2*4 + 4*2*4*2 = 8 + 32 + 64, out 2*8*4 = 64,
    # head 2*4*10 = 80
    per_token = 176 + 48 + 104 + 64 + 80
    f = _flops("mamba2-1.3b")
    assert f.forward_flops_per_token(c) == per_token
    assert f.flops_per_step(c, {"batch": 3, "seq": 5}) == 3 * per_token * 15


@pytest.mark.parametrize("name", ["mamba2-1.3b.dense",
                                  "mamba2-1.3b.qsgd16-layerwise-wire"])
def test_published_flops_of_the_cells(name):
    got = cells.load_cell(name).flops_per_step()
    assert math.isclose(got, 1.3827e14, rel_tol=1e-4), (name, got)


def test_wire_bytes_match_the_program_on_one_bucket():
    from repro.core import make_compressor
    from repro.core.wire import words_for
    from repro.kernels import ops
    comp = make_compressor("qsgd", levels=16)
    bits = wire_bytes.entry_bits(16)
    assert bits == comp.entry_bits
    n, d = 48, 524288               # mamba2-1.3b's w_bc bucket
    assert wire_bytes.words_for(bits * d) == words_for(bits * d)
    enc = ops.pack_bytes_moved(bits, fused=True)
    dec = ops.unpack_bytes_moved(bits, fused=True)
    want = n * d * (enc["read_bytes_per_elt"] + enc["write_bytes_per_elt"]
                    + dec["read_bytes_per_elt"] + dec["write_bytes_per_elt"])
    got = sum(wire_bytes.encode_bytes(d, bits)
              + wire_bytes.decode_bytes(d, bits) for _ in range(n))
    assert abs(got - want) <= n * 2 * 4   # word padding only


def test_unit_dims_layerwise():
    shapes = {"blocks/w": (3, 4, 5), "embed": (7, 2)}
    assert wire_bytes.unit_dims(shapes, "layerwise") == [20, 20, 20, 14]
    assert wire_bytes.unit_dims(shapes, "entire_model") == [74]


def test_change_is_measured_from_the_weights_the_run_started_from():
    import jax.numpy as jnp
    from bench import weights
    specs = {"blocks/w": ((2, 64, 32), "normal", 0.02),
             "blocks/a": ((2, 8), "a_log", 0.0),
             "g": ((32,), "ones", 0.0)}
    p0 = weights.make(specs, 2 ** 33 + 5, jnp.bfloat16)
    norms = weights.delta_norms(specs, jnp.bfloat16)
    kd = weights.seed_key_data(2 ** 33 + 5)
    assert all(float(v) == 0.0 for v in norms(p0, kd).values())
    p1 = dict(p0, g=p0["g"] + jnp.bfloat16(0.5))
    got = norms(p1, kd)
    assert float(got["g"]) == pytest.approx(math.sqrt(32 * 0.25), rel=1e-6)
    assert float(got["blocks/w"]) == float(got["blocks/a"]) == 0.0


def test_a_cell_without_limits_is_never_correct():
    from bench import compare
    assert not compare.passed(compare.checks({"grad_gap": 0.0}, {}))
    assert compare.passed(compare.checks({"grad_gap": 0.0, "x": 9.0},
                                         {"grad_gap": 0.1}))
