"""The least work of a request, from the request alone.

Never from the plan or the kernel's tiling, so that replicated reducers,
re-gathered tiles and a second write of blocks count against a roofline
share, and a later planner or kernel is measured against the same work:

- operations: each distinct unordered pair's dot product once, 2 d FLOPs;
- bytes: each table row the request touches, read once, plus the answer,
  written once as float32;
- least time: the larger of operations over peak FLOP/s and bytes over
  peak bytes/s of the cell's chips, with the bound that wins.
"""

from __future__ import annotations

import json
import os

__all__ = ["least_work", "least_time", "peaks"]

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
_OUT_BYTES = 4                    # answers are float32


def _overlap(lo0: int, n0: int, lo1: int, n1: int) -> int:
    return max(0, min(lo0 + n0, lo1 + n1) - max(lo0, lo1))


def least_work(req) -> dict:
    """``{"pairs", "flops", "bytes"}`` of one request."""
    na, nb = int(req.a.shape[0]), int(req.b.shape[0])
    d, itemsize = int(req.a.shape[1]), int(req.a.dtype.itemsize)
    if req.same_table:
        # one table: a pair met twice in the answer is one dot product,
        # and a row in both ranges is read once
        o = _overlap(req.origin[0], na, req.origin[1], nb)
        pairs = na * nb - o - o * (o - 1) // 2
        rows = na + nb - o
    else:
        pairs, rows = na * nb, na + nb
    return {"pairs": pairs, "flops": 2 * d * pairs,
            "bytes": rows * d * itemsize + na * nb * _OUT_BYTES}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict,
               chips: int) -> tuple[float, str]:
    """Seconds the cell's chips need at least, and the bound that wins."""
    t_ops = flops / (chips * peak["flops_per_s"])
    t_mem = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
