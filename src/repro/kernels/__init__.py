"""Pallas TPU kernels for the compression hot path (+ fused RMSNorm).

How a kernel runs follows `jax.default_backend()` (`ops.interpret_mode`):
Mosaic compiles it on TPU; on CPU its body runs as ordinary jax ops.

The wire hot path is the fused single-launch compress+pack family in
`ops` — one kernel launch per UnitPlan bucket, payloads byte-identical
to the legacy three-pass (quantize -> bit-expand -> word-pack) pipeline:

- `ops.qsgd_pack_units` / `ops.qsgd_unpack_units` /
  `ops.qsgd_unpack_ef_units` — QSGD quantize+pack, unpack+dequantize,
  and decode+error-feedback (residual formed in the caller's regime;
  see the fp-contraction note in `kernels/qsgd.py`).
- `ops.terngrad_pack_units` / `ops.terngrad_unpack_units` /
  `ops.terngrad_unpack_ef_units` — 2-bit ternary.
- `ops.sign_pack_units` / `ops.sign_unpack_units` /
  `ops.sign_unpack_ef_units` — 1-bit sign.
- `ops.majority_words` — signSGD majority vote DIRECTLY on packed
  uint32 words (bit-sliced ripple-carry counting, never unpacking).
- `ops.fields_pack_units` / `ops.fields_unpack_units` (and the flat
  `ops.pack_fields` / `ops.unpack_fields`) — generic word-wise field
  packing for the natural / sparse-index codec legs.
- `ops.pack_bytes_moved` / `ops.unpack_bytes_moved` /
  `ops.count_pallas_calls` — the deterministic traffic + dispatch
  accounting BENCH_kernels.json gates on.

Every fused op has a pure-jnp twin running the identical tile
arithmetic (`kernels/ref.py`), the reference the tests compare the
kernels against bit for bit; in-kernel stochastic rounding draws come from
`kernels/prng.py` (bit-exact threefry reimplementation of the
`jax.random.uniform` draw the simulated compressors make).
"""
from repro.kernels.ops import (qsgd_compress, terngrad_compress,
                               blockwise_topk, rmsnorm)
