"""Fused gather+Gram Pallas TPU megakernel: the shuffle streams into the MXU.

Both shipped executors pay the map->reduce shuffle twice: ``_gather_reduce``
materializes the gathered ``(R, L, d)`` block in HBM (``jnp.take`` + mask),
then the ``pairwise_gram`` kernel reads it back to compute each reducer's
all-pairs block.  For the A2A workload that doubles HBM traffic on the very
quantity — communication cost — the mapping schema was optimized to
minimize.  This kernel consumes the plan's index matrix directly:

  * each reducer's slot ids (the mask folded in as -1) arrive in SMEM as
    one ``(1, Lp)`` block per grid step — SMEM holds 1 MiB, far less than
    a real plan, so the plan is streamed rather than prefetched whole;
  * input-table rows are DMA'd straight from the replicated ``(m, d)``
    table (left in ``ANY``/HBM) into two VMEM tiles — the gather *is* the
    DMA, and the padded ``(R, L, d)`` tensor is never written to HBM;
    masked slots issue no copy;
  * each reducer's ``(L, L)`` Gram block is accumulated tile-by-tile on the
    MXU with fp32 products and accumulation, and invalid pairs are zeroed
    as the block leaves the kernel (matching ``block_similarity``).

The one-row DMA needs 32-bit rows padded to whole 128-lane tiles: the
wrapper widens packed tables (bf16) to f32 and zero-pads the feature axis.

Grid layout: ``(R, n_tx, n_ty)`` row/column tiles of at most ``bl`` slots.
The row tile is gathered once per row of tiles (at ``j == 0``) and reused;
the column tile is re-gathered per step — the flash-attention tradeoff:
``n_t·L·d`` extra reads instead of an ``L·d`` HBM round trip, a win
whenever the slot count fits a few tiles (every capacity bucket of the
skew-aware plans; see ``fused_traffic_model``).  The square kernel is the
rectangular one with X == Y.

``fused_gather_gram_streamed`` is the jnp twin with the same tile dataflow
(per-bucket tiles only, never the dense ``(R, L, d)`` buffer) — it is what
the fused executor runs on non-TPU backends and what the dry-run lowers;
``fused_gather_gram_ref`` is the naive materializing oracle for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pairwise import min_tile_sublanes

__all__ = [
    "fused_gather_gram",
    "fused_gather_gram_ref",
    "fused_gather_gram_streamed",
    "fused_gather_gram_rect",
    "fused_gather_gram_rect_ref",
    "fused_gather_gram_rect_streamed",
    "fused_traffic_model",
]


def _round_up(n: int, mult: int) -> int:
    return -(-max(n, 1) // mult) * mult


_LANE = 128
# fp32 products on the MXU: the default precision rounds f32 rows to bf16
_FP32 = jax.lax.Precision.HIGHEST


def _dma_table(x: jax.Array) -> jax.Array:
    """The table as the one-row DMA needs it.  Packed dtypes (bf16) keep
    two rows per 32-bit sublane word in HBM, so a single row is not a
    legal DMA slice: they are widened to f32, which holds every bf16 value
    exactly.  The feature axis is zero-padded to whole 128-lane tiles;
    zero columns leave every Gram entry unchanged."""
    if jnp.dtype(x.dtype).itemsize < 4:
        x = x.astype(jnp.float32)
    pad = _round_up(x.shape[1], _LANE) - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _slot_ids(idx: jax.Array, mask: jax.Array, Lp: int) -> jax.Array:
    """Fold the mask into the index rows: masked and padding slots -> -1.
    Shaped (R, 1, Lp) so that one reducer's row is a legal block."""
    ids = jnp.where(mask.astype(bool), idx.astype(jnp.int32), -1)
    ids = jnp.pad(ids, ((0, 0), (0, Lp - ids.shape[1])), constant_values=-1)
    return ids[:, None, :]


def _ids_spec(Lp: int) -> pl.BlockSpec:
    """Reducer ``r``'s slot ids, one (1, Lp) row in SMEM per grid step: SMEM
    holds 1 MiB, so the plan is streamed row by row, never prefetched
    whole."""
    return pl.BlockSpec((None, 1, Lp), lambda r, i, j: (r, 0, 0),
                        memory_space=pltpu.SMEM)


def _gather(src_ref, ids_ref, tile: int, bl: int, dst_ref, sem) -> None:
    """DMA rows ``ids[tile*bl : (tile+1)*bl]`` of the HBM table into the
    (bl, d) VMEM tile — all copies in flight on one semaphore, then
    drained.  A negative id (masked slot) issues no copy: its row keeps
    stale VMEM contents, which only reach output entries the wrapper
    masks (a Gram entry depends on its own two rows only)."""
    base = tile * bl

    def copy(t):
        row = ids_ref[0, base + t]
        return row, pltpu.make_async_copy(
            src_ref.at[pl.ds(jnp.maximum(row, 0), 1), :],
            dst_ref.at[pl.ds(t, 1), :], sem)

    def start(t, c):
        row, cp = copy(t)
        pl.when(row >= 0)(cp.start)
        return c

    def wait(t, c):
        row, cp = copy(t)
        pl.when(row >= 0)(cp.wait)
        return c

    jax.lax.fori_loop(0, bl, start, 0)
    jax.lax.fori_loop(0, bl, wait, 0)


def _fused_kernel(xids_ref, yids_ref, x_ref, y_ref, o_ref, xi_ref, yj_ref,
                  sem_ref, *, blx: int, bly: int):
    """One (reducer, x-tile, y-tile) grid step.

    xids/yids — reducer r's (1, Lp) int32 slot ids in SMEM (-1 = masked);
    x_ref/y_ref — the full X / Y tables, ANY/HBM (rows DMA'd on demand);
    xi/yj — (blx, d) / (bly, d) VMEM gather tiles; o_ref — the (1, blx,
    bly) output tile.  The row tile gathers X rows through ``xids``, the
    column tile Y rows through ``yids``, and the MXU emits their cross
    block."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    # the x tile survives the whole y sweep; re-gather only the y tile
    @pl.when(j == 0)
    def _():
        _gather(x_ref, xids_ref, i, blx, xi_ref, sem_ref)
    _gather(y_ref, yids_ref, j, bly, yj_ref, sem_ref)

    o_ref[0, :, :] = jax.lax.dot_general(
        xi_ref[...], yj_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),        # Xi @ Yj^T
        precision=_FP32, preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bl", "interpret", "out_dtype"))
def fused_gather_gram_rect(
    x: jax.Array,                  # (mx, d) replicated X table
    y: jax.Array,                  # (my, d) replicated Y table
    xidx: jax.Array,               # (R, Lx) int32 X-side plan rows
    xmask: jax.Array,              # (R, Lx) bool/int32
    yidx: jax.Array,               # (R, Ly) int32 Y-side plan rows
    ymask: jax.Array,              # (R, Ly) bool/int32
    *,
    bl: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:                    # (R, Lx, Ly) masked per-reducer cross Gram
    """Fused gather+Gram: the shuffle streams into the MXU.  Row and column
    gathers run through independent index maps over two (possibly
    distinct) tables; each side pads to its own tile width, so |X| != |Y|
    plans never pad to a square."""
    R, Lx = xidx.shape
    Ly = yidx.shape[1]
    assert x.shape[1] == y.shape[1], (x.shape, y.shape)
    if R == 0:
        return jnp.zeros((0, Lx, Ly), out_dtype)
    x = _dma_table(x)
    y = _dma_table(y)
    d = x.shape[1]
    blx = min(bl, _round_up(Lx, min_tile_sublanes(x.dtype)))
    bly = min(bl, _round_up(Ly, min_tile_sublanes(y.dtype)))
    Lxp = _round_up(Lx, blx)
    Lyp = _round_up(Ly, bly)

    xids, yids = _slot_ids(xidx, xmask, Lxp), _slot_ids(yidx, ymask, Lyp)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, blx=blx, bly=bly),
        grid=(R, Lxp // blx, Lyp // bly),
        in_specs=[_ids_spec(Lxp), _ids_spec(Lyp),
                  pl.BlockSpec(memory_space=pl.ANY),    # X table in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],   # Y table in HBM
        out_specs=pl.BlockSpec((1, blx, bly), lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Lxp, Lyp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((blx, d), x.dtype),     # xi gather tile
            pltpu.VMEM((bly, d), y.dtype),     # yj gather tile
            pltpu.SemaphoreType.DMA(()),       # row copies of one tile
        ],
        interpret=interpret,
    )(xids, yids, x, y)
    valid = xmask.astype(bool)[:, :, None] & ymask.astype(bool)[:, None, :]
    return jnp.where(valid, out[:, :Lx, :Ly], 0).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("bl", "interpret", "out_dtype"))
def fused_gather_gram(
    x: jax.Array,                  # (m, d) replicated input table
    idx: jax.Array,                # (R, L) int32 plan rows
    mask: jax.Array,               # (R, L) bool/int32 slot validity
    *,
    bl: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:                    # (R, L, L) masked per-reducer Gram
    """The square (X == Y) case of :func:`fused_gather_gram_rect`."""
    return fused_gather_gram_rect(x, x, idx, mask, idx, mask, bl=bl,
                                  interpret=interpret, out_dtype=out_dtype)


def fused_gather_gram_rect_ref(x, y, xidx, xmask, yidx, ymask):
    """Materializing rectangular oracle: gather both sides -> mask ->
    batched cross Gram (fp32)."""
    gx = jnp.take(x, xidx, axis=0) * xmask.astype(x.dtype)[..., None]
    gy = jnp.take(y, yidx, axis=0) * ymask.astype(y.dtype)[..., None]
    return jax.lax.dot_general(
        gx, gy, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def fused_gather_gram_rect_streamed(x, y, xidx, xmask, yidx, ymask, *,
                                    bl: int = 128):
    """jnp twin of the kernel's tile dataflow.

    Gathers (R, bl, d) tiles per side only; the y tile is re-gathered per
    (i, j) step exactly like the kernel, so lowered HLO traffic mirrors
    the DMA schedule.  Non-TPU fused-executor path and dry-run target."""
    R, Lx = xidx.shape
    Ly = yidx.shape[1]
    xmaskf = xmask.astype(x.dtype)[..., None]
    ymaskf = ymask.astype(y.dtype)[..., None]
    dims = (((2,), (2,)), ((0,), (0,)))      # batched Xi @ Yj^T

    def tile(tab, idx, maskf, t, width):
        g = jnp.take(tab,
                     jax.lax.dynamic_slice_in_dim(idx, t * bl, width, 1),
                     axis=0)
        return g * jax.lax.dynamic_slice_in_dim(maskf, t * bl, width, 1)

    if Lx <= bl and Ly <= bl:
        gx = jnp.take(x, xidx, axis=0) * xmaskf
        gy = jnp.take(y, yidx, axis=0) * ymaskf
        return jax.lax.dot_general(gx, gy, dims, precision=_FP32,
                                   preferred_element_type=jnp.float32)

    def widths_of(L):
        n_t = L // bl
        return [bl] * n_t + ([L - n_t * bl] if L % bl else [])

    xw = widths_of(Lx)
    yw = widths_of(Ly)
    rows = []
    for i, wi in enumerate(xw):
        gi = tile(x, xidx, xmaskf, i, wi)
        rows.append(jnp.concatenate(
            [jax.lax.dot_general(gi, tile(y, yidx, ymaskf, j, wj), dims,
                                 precision=_FP32,
                                 preferred_element_type=jnp.float32)
             for j, wj in enumerate(yw)], axis=2))
    return jnp.concatenate(rows, axis=1)


def fused_gather_gram_ref(x, idx, mask):
    """Materializing oracle: gather -> mask -> batched Gram (fp32)."""
    return fused_gather_gram_rect_ref(x, x, idx, mask, idx, mask)


def fused_gather_gram_streamed(x, idx, mask, *, bl: int = 128):
    """jnp twin of the square kernel (non-TPU fused executor): the X == Y
    case of :func:`fused_gather_gram_rect_streamed`.  A multi-tile width
    never materializes its full (R, L, d) gather, and a bucketed plan
    never materializes the dense one."""
    return fused_gather_gram_rect_streamed(x, x, idx, mask, idx, mask,
                                           bl=bl)


def fused_traffic_model(buckets, d: int, itemsize: int,
                        bl: int = 128) -> dict:
    """Analytic HBM bytes of the kernel dataflow vs the unfused pipeline.

    Per reducer of bucket width Lb with n = ceil(Lb/bl) row tiles:

      fused    — xi gathered once per tile row (Lb rows), xj re-gathered per
                 (i, j) tile (n·Lb rows), plus the (Lb, Lb) fp32 block write.
      unfused  — the gather writes (Lb, d) then the Gram kernel reads it as
                 both operands (3·Lb·d round trip counted once each way ->
                 4·Lb·d with the gather's own table read), plus the block.

    Returns totals plus ``saved_bytes`` (the materialized-gather round trip
    the fused kernel removes, net of its tile re-reads).
    """
    fused = unfused = blocks = 0
    for b in buckets:
        Rb, Lb = int(b.idx.shape[0]), int(b.idx.shape[1])
        n = -(-Lb // bl)
        fused += Rb * (1 + n) * Lb * d * itemsize
        unfused += Rb * 4 * Lb * d * itemsize
        blocks += Rb * Lb * Lb * 4
    return {
        "fused_bytes": fused + blocks,
        "unfused_bytes": unfused + blocks,
        "saved_bytes": unfused - fused,
        "block_bytes": blocks,
    }
