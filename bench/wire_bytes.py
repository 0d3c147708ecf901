"""Bytes the fused wire kernels must move in one train step.

A copy of the program's byte arithmetic (src/repro/kernels/ops.py
pack_bytes_moved / unpack_bytes_moved, src/repro/core/wire.py QSGDCodec
nbytes), so that a change to the program cannot move the yardstick.

Per unit of d elements, QSGD with s levels packs each element into
b = max(2, ceil(log2(2 s + 1))) bits. The encode kernel reads the f32
gradient and 12 bytes of key and statistic per 512-lane row, and writes
the packed words; the decode kernel reads the words and writes f32.
Every worker encodes its own units once and decodes every worker's
payload of them (its own included), in the ring and in the all-gather.
The per-unit norm is an XLA reduction outside the kernels and is not
counted here.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

#: lane columns of one kernel row, and the key/statistic bytes per row
BLOCK_C = 512
ROW_COLS_BYTES = 12

#: leaves under these keys are stacks of layers: one unit per layer
STACKED = ("blocks", "layers", "encoder_blocks", "decoder_blocks")


def words_for(bits: int) -> int:
    return (bits + 31) // 32


def entry_bits(levels: int) -> int:
    return max(2, math.ceil(math.log2(2 * levels + 1)))


def unit_dims(shapes: Dict[str, Tuple[int, ...]], granularity: str
              ) -> List[int]:
    """Unit sizes of the program's partition of the gradient."""
    sizes = []
    for path, shape in shapes.items():
        n = math.prod(shape)
        if granularity == "layerwise" and path.split("/")[0] in STACKED:
            sizes += [n // shape[0]] * shape[0]
        else:
            sizes.append(n)
    if granularity == "entire_model":
        return [sum(sizes)]
    return sizes


def encode_bytes(d: int, bits: int) -> float:
    return d * (4.0 + ROW_COLS_BYTES / BLOCK_C) + 4 * words_for(bits * d)


def decode_bytes(d: int, bits: int) -> float:
    return 4 * words_for(bits * d) + 4.0 * d


def step_bytes(codec: dict, shapes: Dict[str, Tuple[int, ...]],
               workers: int) -> float:
    """HBM bytes of one step's wire kernels on one chip."""
    if codec["name"] != "qsgd":
        raise ValueError(f"no byte count for codec {codec['name']!r}")
    bits = entry_bits(int(codec["levels"]))
    return sum(encode_bytes(d, bits) + workers * decode_bytes(d, bits)
               for d in unit_dims(shapes, codec["granularity"]))
