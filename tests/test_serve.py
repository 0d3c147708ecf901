"""Serve-path smoke tests (previously untested by tier-1): the serving
launcher CLI, the batched-serving example, and the packed-request wire
round-trip through a real decode step — so wire-format changes can never
break serving invisibly again."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow

from repro.launch.serve import main as serve_main, pack_request, \
    unpack_request  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_packed_request_roundtrips_through_serve_step():
    """One decode request, packed to a uint8 buffer and unpacked on the
    other side, produces bit-identical logits to the unpacked request."""
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_host_mesh
    from repro.models import ModelConfig
    from repro.models.config import InputShape
    cfg = ModelConfig(name="serve-wire", arch_type="dense", n_layers=2,
                      d_model=64, vocab=256, n_heads=4, n_kv_heads=2,
                      d_head=16, d_ff=128, dtype="float32")
    B, PROMPT, CACHE = 2, 4, 8
    eng = Engine(cfg, make_host_mesh(1, 1))
    params, _ = eng.init_state(seed=0)
    serve = eng.build_serve_step(InputShape("d", CACHE, B, "decode"))
    prefill = eng.build_prefill(InputShape("p", PROMPT, B, "prefill"),
                                cache_len=CACHE)
    prompts = jax.random.randint(jax.random.key(0), (B, PROMPT), 0,
                                 cfg.vocab)
    with eng.mesh:
        logits, cache = prefill(params, {"tokens": prompts})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        # the packed request IS the wire: uint8 in, request out
        buf = pack_request(tok, jnp.int32(PROMPT))
        assert buf.dtype == jnp.uint8 and buf.size == 4 * (2 + B)
        req = unpack_request(buf)
        assert bool((req["token"] == tok).all())
        assert int(req["pos"]) == PROMPT
        lg_packed, _ = serve(params, req, cache)
        # the serve step donates its cache — re-prefill (deterministic)
        # for the direct-request reference
        _, cache2 = prefill(params, {"tokens": prompts})
        lg_direct, _ = serve(params, {"token": tok,
                                      "pos": jnp.int32(PROMPT)}, cache2)
    assert bool((lg_packed == lg_direct).all())
    assert not bool(jnp.isnan(lg_packed).any())


def test_serve_cli_smoke(capsys):
    """launch/serve.py end to end on a 1-device mesh (smoke config):
    prefill + packed-request decode loop, sane output."""
    rc = serve_main(["--arch", "granite-20b", "--smoke", "--batch", "2",
                     "--prompt", "4", "--gen", "3"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "prefill(4 tok)" in out
    assert "sample continuation:" in out


def test_serve_batched_example_runs():
    """examples/serve_batched.py runs to completion (its own 8-device
    host mesh, seq-sharded KV cache) and reports the serve summary."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)  # the example sets its own device count
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples",
                                      "serve_batched.py")],
        capture_output=True, text=True, env=env, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "served 8 sequences" in proc.stdout
