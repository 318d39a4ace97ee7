"""Operation and byte counts, computed from shapes, and the shares of a
chip's peak built from them.  The peaks are `peaks.json`, keyed by the
`device_kind` JAX reports."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The table's row for `device_kind`; a kind missing from it is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def train_flops(n_params: int, tokens: float) -> float:
    """Model operations of forward and backward: 6 N per token (2 N
    forward, 4 N backward).  Recomputation (remat) is not counted, nor
    the state-space scan's own products, which 6 N leaves out."""
    return 6.0 * n_params * tokens


def matmul_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def matmul_bytes(m: int, k: int, n: int, in_bytes: int, out_bytes: int
                 ) -> float:
    """Each operand read once and the result written once."""
    return (m * k + k * n) * in_bytes + m * n * out_bytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Per cent of the least time the chip could take (the larger of the
    compute and the memory bound) over the measured time."""
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
