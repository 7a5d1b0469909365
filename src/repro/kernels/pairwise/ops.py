"""Jitted wrapper for the pairwise kernel with metric post-processing.

``pairwise(use_kernel=False)`` dispatches to the jnp reference, keeping the
public API identical so the engine can flip ``use_kernel`` freely.  The
kernel compiles for the TPU by default; CPU callers pass ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pairwise import pairwise_gram
from .ref import pairwise_ref

__all__ = ["pairwise", "pairwise_kernel"]


def _finish(g, metric: str):
    if metric == "dot":
        return g
    n2 = jnp.diagonal(g)
    if metric == "l2":
        return n2[:, None] + n2[None, :] - 2.0 * g
    if metric == "cosine":
        nrm = jnp.sqrt(jnp.clip(n2, 1e-18))
        return g / (nrm[:, None] * nrm[None, :])
    raise ValueError(metric)


@functools.partial(jax.jit, static_argnames=("metric", "interpret", "bm",
                                             "bn", "bk"))
def pairwise_kernel(x, *, metric: str = "dot", interpret: bool = False,
                    bm: int = 128, bn: int = 128, bk: int = 512):
    """All-pairs similarity of rows of x via the Pallas kernel."""
    g = pairwise_gram(x, x, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return _finish(g, metric)


def pairwise(x, *, metric: str = "dot", use_kernel: bool = False, **kw):
    if use_kernel:
        return pairwise_kernel(x, metric=metric, **kw)
    return pairwise_ref(x, metric=metric)
