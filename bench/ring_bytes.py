"""Bytes one chip sends over the streaming ring in one train step.

A copy of the program's hop arithmetic (src/repro/core/plan.py buckets,
src/repro/core/schedule.py build_schedule at fusion 0,
src/repro/core/wire.py message_layouts, QSGDCodec.nbytes and
execute_schedule_stream), so that a change to the program cannot move the
yardstick.

The layer-wise units of the gradient are grouped by size into buckets,
one wire message per bucket. A message holds a header of one word for
the bucket count and one offset word per bucket, then each unit's payload:
for QSGD a 4-byte norm and its entries packed into 32-bit words at b =
max(2, ceil(log2(2 s + 1))) bits each. The ring moves every message
n - 1 hops, and in each hop every worker sends the whole message buffer
to its neighbour.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Tuple

from bench.wire_bytes import entry_bits, unit_dims, words_for


def unit_nbytes(d: int, bits: int) -> int:
    """One QSGD unit's payload: its norm and its packed words."""
    return 4 + 4 * words_for(bits * d)


def message_bytes(codec: dict, shapes: Dict[str, Tuple[int, ...]]
                  ) -> Dict[int, int]:
    """{unit size: bytes of that bucket's wire message}."""
    if codec["name"] != "qsgd":
        raise ValueError(f"no byte count for codec {codec['name']!r}")
    bits = entry_bits(int(codec["levels"]))
    counts = Counter(unit_dims(shapes, codec["granularity"]))
    return {d: 4 * 2 + n * unit_nbytes(d, bits) for d, n in counts.items()}


def step_bytes(codec: dict, shapes: Dict[str, Tuple[int, ...]],
               workers: int) -> int:
    """Bytes one chip sends over the ring in one step."""
    return (workers - 1) * sum(message_bytes(codec, shapes).values())
