"""The plain reference, the comparison that decides ``correct``, and the
lower-precision control.

Reference: float64 NumPy on the host, ``a @ b.T`` over the rows the
request sent, with the self-pairs of one table (its global diagonal) set
to 0.  It knows nothing of the planner, the kernel or the assembly, so a
pair the plan leaves uncovered, a wrong source-map entry, a wrong tile
or a lost exchange between chips all show as a gap.

Compared number: the largest gap over every entry of an answer, over the
fp32 dot-product bound of its operands, ``gamma_d * max|a_i| * max|b_j|``
with ``gamma_d = d u / (1 - d u)`` and ``u = 2**-24`` (Higham, "Accuracy
and Stability of Numerical Algorithms", Sec. 3.1).  The worst answer of
the window is compared with the limit the configuration states.

Control: the same product with the operands rounded to bfloat16, the
precision below the configuration's float32, computed on the device in
the program's place.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["reference", "fp32_bound", "worst_gap_ratio", "control"]

_ROWS = 2048                      # reference rows per block
_THREADS = 8                      # requests compared at once


def _zero_self_pairs(out: np.ndarray, origin, r0: int = 0) -> None:
    """Cells whose global row equals their global column answer 0."""
    gi = origin[0] + r0 + np.arange(out.shape[0])
    j = gi - origin[1]
    ok = (j >= 0) & (j < out.shape[1])
    out[np.flatnonzero(ok), j[ok]] = 0.0


def reference(req, r0: int = 0, r1: int | None = None) -> np.ndarray:
    """Rows ``[r0, r1)`` of the exact answer, in float64."""
    a = req.a[r0:r1].astype(np.float64)
    out = a @ req.b.astype(np.float64).T
    if req.same_table:
        _zero_self_pairs(out, req.origin, r0)
    return out


def fp32_bound(a: np.ndarray, b: np.ndarray) -> float:
    d = a.shape[1]
    u = 2.0 ** -24
    gamma = d * u / (1 - d * u)
    na = np.einsum("ij,ij->i", a, a, dtype=np.float64).max()
    nb = np.einsum("ij,ij->i", b, b, dtype=np.float64).max()
    return float(gamma * math.sqrt(na * nb))


def worst_gap_ratio(answers) -> float:
    """Largest |answer - reference| over the fp32 bound, over every entry
    of every ``(request, answer)``; inf for none, for an answer of the
    wrong shape or with a non-finite entry.  The reference of a request
    is computed once, a block of rows at a time, for all its answers."""
    by_key: dict = {}
    for req, got in answers:
        by_key.setdefault(req.key, (req, []))[1].append(got)
    if not by_key:
        return math.inf
    # NumPy releases the interpreter lock: one request per thread
    with ThreadPoolExecutor(_THREADS) as pool:
        return max(pool.map(lambda kv: _worst_of(*kv), by_key.values()))


def _worst_of(req, gots) -> float:
    if any(g.shape != (req.a.shape[0], req.b.shape[0]) for g in gots):
        return math.inf
    gap = 0.0
    for r0 in range(0, req.a.shape[0], _ROWS):
        ref = reference(req, r0, r0 + _ROWS)
        for g in gots:
            d = float(np.abs(g[r0:r0 + _ROWS].astype(np.float64)
                             - ref).max())
            if not math.isfinite(d):
                return math.inf
            gap = max(gap, d)
    return gap / fp32_bound(req.a, req.b)


def control(req) -> np.ndarray:
    """The reference in bfloat16 operands with float32 accumulation, on
    the device: what serving the product at the precision below the
    configuration's would answer."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(req.a, jnp.bfloat16)
    b = jnp.asarray(req.b, jnp.bfloat16)
    out = np.array(jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32))
    if req.same_table:
        _zero_self_pairs(out, req.origin)
    return out
