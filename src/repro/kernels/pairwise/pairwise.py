"""Pallas TPU kernel: blocked all-pairs Gram matrix (the reducer hot spot).

Within a reducer the A2A problem computes a similarity for every pair of its
inputs — a Gram matrix ``X @ X^T`` over the reducer's (L, d) block.  On TPU
this is MXU work; we tile (bm, bn, bk) so each step keeps two input tiles and
one accumulator tile in VMEM and issues 128x128-aligned matmuls.

The kernel computes C[i, j] = sum_k X[i, k] * Y[j, k] with fp32 accumulation;
metric post-processing (L2 / cosine) happens in ops.py from the same Gram
values (norms are the diagonal, so no extra memory pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pairwise_gram", "min_tile_sublanes"]

# minimum TPU tile second-to-last ("sublane") extent by dtype width; the
# last ("lane") dimension is always 128 (see the Pallas guide's tiling
# constraints table)
_MIN_SUBLANES = {4: 8, 2: 16, 1: 32}
_LANE = 128


def min_tile_sublanes(dtype) -> int:
    """Minimum sublane tile extent for ``dtype`` (8 f32 / 16 bf16 / 32 i8)."""
    return _MIN_SUBLANES.get(jnp.dtype(dtype).itemsize, 8)


def _clamp_block(b: int, n: int, dtype, *, lane: bool = False) -> int:
    """Shrink block size ``b`` toward extent ``n`` without breaking TPU tile
    alignment: the clamped block is rounded *up* to the dtype's minimum tile
    multiple (sublane, or 128 for the lane axis), so sub-tile bucket widths
    never produce unaligned BlockSpecs."""
    mult = _LANE if lane else min_tile_sublanes(dtype)
    return min(b, -(-max(n, 1) // mult) * mult)


def _gram_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int, m: int, n: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands need fp32 products (the default rounds them to bf16);
    # bf16 operands multiply exactly and do not accept the fp32 mode
    fp32 = x_ref.dtype == jnp.float32
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),     # X @ Y^T
        precision=jax.lax.Precision.HIGHEST if fp32 else None,
        preferred_element_type=jnp.float32,
    )

    # masked tail-tile: the row/col grids are ceil(M/bm) x ceil(N/bn), so the
    # last tiles can hang past the array — whatever the OOB lanes accumulated
    # is zeroed at flush instead of padding M/N up front.  (program_id is
    # read outside the `when` body: interpret mode can't substitute it
    # inside a cond branch.)
    bm, bn = acc_ref.shape
    row = pl.program_id(0) * bm + jax.lax.broadcasted_iota(
        jnp.int32, (bm, bn), 0)
    col = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (bm, bn), 1)
    valid = (row < m) & (col < n)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = jnp.where(valid, acc_ref[...], 0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def pairwise_gram(
    x: jax.Array,                 # (M, K)
    y: jax.Array,                 # (N, K)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:                   # (M, N) = x @ y^T
    M, K = x.shape
    N, Ky = y.shape
    assert K == Ky, (x.shape, y.shape)
    bm = _clamp_block(bm, M, x.dtype)
    bn = _clamp_block(bn, N, y.dtype)
    bk = _clamp_block(bk, K, x.dtype, lane=True)

    def pad(a, mult1):
        # only K is materially padded (it feeds the accumulation, so OOB
        # garbage there would corrupt results); M/N tails are handled by the
        # kernel's masked flush — narrow bucket blocks stay narrow instead
        # of rounding up to a full tile row/column.
        p1 = -a.shape[1] % mult1
        if p1:
            a = jnp.pad(a, ((0, 0), (0, p1)))
        return a

    xp = pad(x, bk)
    yp = pad(y, bk)
    Kp = xp.shape[1]
    n_k = Kp // bk
    grid = (-(-M // bm), -(-N // bn), n_k)

    return pl.pallas_call(
        functools.partial(_gram_kernel, n_k=n_k, m=M, n=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, yp)
