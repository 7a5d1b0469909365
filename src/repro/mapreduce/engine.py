"""Mapping schema -> static gather plan -> sharded reducer execution.

The MapReduce shuffle of the paper is adapted to TPU/JAX as follows
(DESIGN.md "hardware adaptation"):

  * reducers become *reducer slots*, a leading array dimension sharded across
    the device mesh;
  * the map->reduce shuffle becomes ``jnp.take`` from the input array with a
    static index matrix computed from the schema — XLA lowers this to
    all-gather/collective traffic whose volume is the schema's communication
    cost (this is what the roofline benchmark measures);
  * the reduce function is vmapped over slots, so every device processes its
    slots in parallel (the MXU does the per-reducer all-pairs work through
    the Pallas ``pairwise`` kernel).

Executors share the plan format and are registered by name in
``repro.mapreduce.executors`` (the Executor protocol + registry; DESIGN.md
"executor registry").  This module is the shared substrate: the plan
builder, the bounded jit cache, and the dense/bucketed implementations the
executor classes wrap:

``run_reducers``           — the dense path: one gather padded to the global
                             max slot count.  Simple, one XLA program, but a
                             single heavy reducer forces every other reducer
                             to pad to its width — quadratic waste for
                             reducer functions like the all-pairs Gram block.
``run_reducers_bucketed``  — the skew-aware path (DESIGN.md "bucketed shuffle
                             execution"): reducers are grouped into capacity
                             buckets (powers-of-two over per-reducer slot
                             counts, ``repro.core.planner.compute_buckets``),
                             one vmapped gather+reduce per bucket, each
                             padded only to its own bucket width, outputs
                             reassembled in original reducer order.
"""

from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from contextlib import contextmanager
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import (compute_buckets, compute_rect_buckets,
                                rect_width_pairs)
from repro.core.schema import MappingSchema
from repro.obs import EVENTS as _OBS_EVENTS
from repro.obs import REGISTRY as _OBS_REGISTRY
from repro.obs import span as _obs_span

__all__ = [
    "ReducerBucket",
    "ReducerPlan",
    "SparsePlan",
    "build_plan",
    "build_sparse_plan",
    "block_subplan",
    "build_x2y_plan",
    "build_x2y_plan_arrays",
    "run_reducers",
    "run_reducers_bucketed",
    "run_reducers_x2y",
    "run_reducers_x2y_bucketed",
    "lower_reducers",
    "lower_reducers_bucketed",
    "jit_cache_stats",
    "configure_jit_cache",
    "block_cache_stats",
    "configure_block_cache",
    "compile_count",
    "fused_stats",
    "reset_fused_stats",
]


@dataclasses.dataclass(frozen=True)
class ReducerBucket:
    """One capacity bucket of the plan: reducers padded to a shared width.

    rows  (Rb,) int64 — original plan-row ids in bucket order; -1 marks a
          padding row added so the bucket divides the device count.
    idx   (Rb, width) int32 / mask (Rb, width) bool — same layout as the
          dense plan, but only ``width`` slots wide.

    Rectangular (X2Y) buckets additionally carry the Y side: ``yidx`` /
    ``ymask`` are (Rb, ywidth) gather rows into the *Y table* (``idx``
    then indexes the X table); ``yidx is None`` marks the square all-pairs
    case, where ``idx`` serves both block axes.
    """

    width: int
    rows: np.ndarray
    idx: np.ndarray
    mask: np.ndarray
    ywidth: int = 0
    yidx: Optional[np.ndarray] = None
    ymask: Optional[np.ndarray] = None

    @property
    def R(self) -> int:
        return int(self.idx.shape[0])

    @property
    def is_rect(self) -> bool:
        return self.yidx is not None

    @property
    def num_real(self) -> int:
        return int(np.sum(self.rows >= 0))

    @property
    def padded_elements(self) -> int:
        """Gather slots this bucket materializes (both sides for rect)."""
        if self.is_rect:
            return self.R * (self.width + self.ywidth)
        return self.R * self.width


@dataclasses.dataclass(frozen=True)
class ReducerPlan:
    """Static arrays derived from a MappingSchema.

    idx   (R, L) int32 — input ids per reducer slot; padded entries point at
          input 0 and are masked out.
    mask  (R, L) bool  — slot validity.
    buckets — capacity buckets over the same reducers (skew-aware executor);
          every real reducer row appears in exactly one bucket.

    The plan also carries the schema's provenance so downstream telemetry
    (benchmarks, serving dashboards) can report which registry strategy
    produced the traffic and how far it sits from the paper's
    replication-rate lower bound.
    """

    idx: np.ndarray
    mask: np.ndarray
    num_reducers: int          # before padding
    comm_cost: float           # schema communication cost (weighted bytes)
    max_inputs: int
    algorithm: str = "unknown"             # winning strategy (provenance)
    lower_bound: Optional[float] = None    # paper's comm lower bound
    buckets: tuple[ReducerBucket, ...] = ()
    # rectangular (X2Y) extension: per-reducer Y-side gather rows.  When
    # ``yidx is None`` the plan is the square all-pairs degenerate case
    # (X == Y) and ``idx``/``mask`` drive both block axes; otherwise
    # ``idx`` indexes the X table and ``yidx`` the Y table, and reducer
    # outputs are (Lx, Ly) cross blocks assembled into an (num_x, num_y)
    # matrix.
    yidx: Optional[np.ndarray] = None      # (R, Ly) int32 Y-table rows
    ymask: Optional[np.ndarray] = None     # (R, Ly) bool Y-slot validity
    max_y_inputs: int = 0
    num_x: int = 0                         # X-table size (rect plans)
    num_y: int = 0                         # Y-table size (rect plans)

    @property
    def R(self) -> int:
        return int(self.idx.shape[0])

    @property
    def L(self) -> int:
        return int(self.idx.shape[1])

    @property
    def is_rect(self) -> bool:
        """True for rectangular (X2Y) plans carrying a Y side."""
        return self.yidx is not None

    @property
    def Ly(self) -> int:
        """Dense Y-side slot count (== L for square plans)."""
        return int(self.yidx.shape[1]) if self.is_rect else self.L

    @property
    def optimality_gap(self) -> Optional[float]:
        """comm_cost / lower_bound (>= 1.0), or None without a bound."""
        if self.lower_bound is None or self.lower_bound <= 0.0:
            return None
        return self.comm_cost / self.lower_bound

    # ---------------------------------------------------------- telemetry
    @property
    def dense_padded_elements(self) -> int:
        """Gather slots the dense executor materializes (R x L; both sides
        for rectangular plans)."""
        if self.is_rect:
            return self.R * (self.L + self.Ly)
        return self.R * self.L

    @property
    def bucketed_padded_elements(self) -> int:
        """Gather slots the bucketed executor materializes."""
        if not self.buckets:
            return self.dense_padded_elements
        return sum(b.padded_elements for b in self.buckets)

    @property
    def padding_savings(self) -> float:
        """dense / bucketed padded elements (>= 1.0 up to row padding)."""
        return self.dense_padded_elements / max(self.bucketed_padded_elements,
                                                1)

    def bucket_widths(self) -> list[int]:
        return [b.width for b in self.buckets]


def y_side(p) -> tuple[np.ndarray, np.ndarray]:
    """The Y-side gather rows ``(yidx, ymask)`` of a plan or bucket.  A
    square (self-join) plan's Y side is its X side, ``(idx, mask)`` — the
    same arrays, not copies — so every Gram program serves the self-join
    as the X2Y job with X = Y."""
    return (p.idx, p.mask) if p.yidx is None else (p.yidx, p.ymask)


def _build_buckets(expanded: list[list[int]], *, pad_slots_to: int,
                   pad_reducers_to: int,
                   max_buckets: int) -> tuple[ReducerBucket, ...]:
    """Capacity buckets over expanded reducers (original row order kept
    within each bucket; rows padded to a multiple of ``pad_reducers_to``)."""
    counts = [len(ids) for ids in expanded]
    out = []
    for width, rows in compute_buckets(counts, pad_slots_to=pad_slots_to,
                                       max_buckets=max_buckets):
        Rb = -(-max(len(rows), 1) // pad_reducers_to) * pad_reducers_to
        idx = np.zeros((Rb, width), dtype=np.int32)
        mask = np.zeros((Rb, width), dtype=bool)
        rows_padded = np.full(Rb, -1, dtype=np.int64)
        rows_padded[: len(rows)] = rows
        for i, r in enumerate(rows):
            ids = expanded[r]
            idx[i, : len(ids)] = ids
            mask[i, : len(ids)] = True
        out.append(ReducerBucket(width=width, rows=rows_padded, idx=idx,
                                 mask=mask))
    return tuple(out)


def build_plan(schema: MappingSchema, *, pad_reducers_to: int = 1,
               pad_slots_to: int = 1, max_buckets: int = 8) -> ReducerPlan:
    """Flatten a schema into (idx, mask) plus capacity buckets.

    ``pad_reducers_to`` rounds reducer counts up to a multiple (device
    count) — applied to the dense plan and to every bucket independently;
    ``pad_slots_to`` rounds slot counts (kernel tile alignment);
    ``max_buckets`` bounds the number of capacity buckets (dispatch
    overhead of the bucketed executor)."""
    expanded = schema.expand()
    R0 = len(expanded)
    L0 = max((len(ids) for ids in expanded), default=1)
    L = -(-L0 // pad_slots_to) * pad_slots_to
    R = -(-max(R0, 1) // pad_reducers_to) * pad_reducers_to
    idx = np.zeros((R, L), dtype=np.int32)
    mask = np.zeros((R, L), dtype=bool)
    for r, ids in enumerate(expanded):
        idx[r, : len(ids)] = ids
        mask[r, : len(ids)] = True
    buckets = _build_buckets(expanded, pad_slots_to=pad_slots_to,
                             pad_reducers_to=pad_reducers_to,
                             max_buckets=max_buckets)
    return ReducerPlan(idx=idx, mask=mask, num_reducers=R0,
                       comm_cost=schema.communication_cost(), max_inputs=L0,
                       algorithm=schema.algorithm,
                       lower_bound=schema.lower_bound,
                       buckets=buckets)


# ---------------------------------------------------------------------------
# rectangular (X2Y) plans: per-reducer X-side and Y-side index lists
# ---------------------------------------------------------------------------
def _build_rect_buckets(xs: list[list[int]], ys: list[list[int]], *,
                        pad_slots_to: int, pad_reducers_to: int,
                        max_buckets: int) -> tuple[ReducerBucket, ...]:
    """Rectangular capacity buckets: reducers grouped by (wx, wy) width
    pairs (``compute_rect_buckets``), each side padded to its own
    power-of-two width; rows padded to a multiple of ``pad_reducers_to``."""
    out = []
    for wx, wy, rows in compute_rect_buckets(
            [len(a) for a in xs], [len(a) for a in ys],
            pad_slots_to=pad_slots_to, max_buckets=max_buckets):
        Rb = -(-max(len(rows), 1) // pad_reducers_to) * pad_reducers_to
        idx = np.zeros((Rb, wx), dtype=np.int32)
        mask = np.zeros((Rb, wx), dtype=bool)
        yidx = np.zeros((Rb, wy), dtype=np.int32)
        ymask = np.zeros((Rb, wy), dtype=bool)
        rows_padded = np.full(Rb, -1, dtype=np.int64)
        rows_padded[: len(rows)] = rows
        for i, r in enumerate(rows):
            a, b = xs[r], ys[r]
            idx[i, : len(a)] = a
            mask[i, : len(a)] = True
            yidx[i, : len(b)] = b
            ymask[i, : len(b)] = True
        out.append(ReducerBucket(width=wx, rows=rows_padded, idx=idx,
                                 mask=mask, ywidth=wy, yidx=yidx,
                                 ymask=ymask))
    return tuple(out)


def _rect_bucket_attrs(plan: ReducerPlan, pad_slots_to: int) -> dict:
    """What a ``lower`` span records of a rect plan it built:
    ``width_pairs``, the distinct (wx, wy) pairs before the bucket merge,
    and ``padded_entries``, the block entries its buckets write
    (Σ R·wx·wy, padding rows included)."""
    n = plan.num_reducers
    return {"width_pairs": rect_width_pairs(
                plan.mask[:n].sum(axis=1), plan.ymask[:n].sum(axis=1),
                pad_slots_to=pad_slots_to),
            "padded_entries": sum(b.R * b.width * b.ywidth
                                  for b in plan.buckets)}


def build_x2y_plan_arrays(
    xs: list[list[int]],               # per-reducer X-table row ids
    ys: list[list[int]],               # per-reducer Y-table row ids
    *,
    num_x: int,
    num_y: int,
    comm_cost: float = 0.0,
    algorithm: str = "x2y",
    lower_bound: Optional[float] = None,
    pad_reducers_to: int = 1,
    pad_slots_to: int = 1,
    max_buckets: int = 8,
) -> ReducerPlan:
    """Rectangular plan from explicit per-reducer X/Y id lists.

    The low-level builder ``build_x2y_plan`` and the streaming X2Y planner
    share: reducer ``r`` gathers ``xs[r]`` from the X table and ``ys[r]``
    from the Y table and emits the (|xs[r]|, |ys[r]|) cross block."""
    assert len(xs) == len(ys), (len(xs), len(ys))
    R0 = len(xs)
    Lx0 = max((len(a) for a in xs), default=1)
    Ly0 = max((len(a) for a in ys), default=1)
    Lx = -(-Lx0 // pad_slots_to) * pad_slots_to
    Ly = -(-Ly0 // pad_slots_to) * pad_slots_to
    R = -(-max(R0, 1) // pad_reducers_to) * pad_reducers_to
    idx = np.zeros((R, Lx), dtype=np.int32)
    mask = np.zeros((R, Lx), dtype=bool)
    yidx = np.zeros((R, Ly), dtype=np.int32)
    ymask = np.zeros((R, Ly), dtype=bool)
    for r in range(R0):
        a, b = xs[r], ys[r]
        idx[r, : len(a)] = a
        mask[r, : len(a)] = True
        yidx[r, : len(b)] = b
        ymask[r, : len(b)] = True
    buckets = _build_rect_buckets(xs, ys, pad_slots_to=pad_slots_to,
                                  pad_reducers_to=pad_reducers_to,
                                  max_buckets=max_buckets)
    return ReducerPlan(
        idx=idx, mask=mask, num_reducers=R0, comm_cost=float(comm_cost),
        max_inputs=Lx0, algorithm=algorithm, lower_bound=lower_bound,
        buckets=buckets, yidx=yidx, ymask=ymask, max_y_inputs=Ly0,
        num_x=int(num_x), num_y=int(num_y))


# ---------------------------------------------------------------------------
# sparse plans: CSR gather maps for block-addressed serving (no O(m^2) host)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SparsePlan:
    """CSR view of a schema for block-addressed execution.

    ``build_plan`` expands reducer -> original input ids, which at m = 10^6
    with thousands of inputs per reducer is ~10^9 host entries before a
    single gather runs.  The sparse plan stays at the schema's own
    granularity — three CSR maps totaling O(m + assignments):

      bin_indptr / bin_inputs    — bin -> original input ids (disjoint);
      bin_of                     — input -> bin (inverse of the above);
      red_indptr / red_bins      — reducer -> bin ids;
      binred_indptr / bin_reds   — bin -> reducer ids (inverse shuffle).

    ``block_subplan`` materializes only the reducers a requested
    ``[i0:i1) x [j0:j1)`` output block needs, as a rectangular
    :class:`ReducerPlan` in block-local coordinates, so every registry
    executor serves blocks through its existing ``run_x2y`` path.  Built
    sub-plans are LRU-cached on the instance (``_block_cache``) because
    the fused/sharded executors cache their inverse-shuffle srcmaps on the
    plan object.
    """

    num_inputs: int
    q: float
    bin_indptr: np.ndarray
    bin_inputs: np.ndarray
    bin_of: np.ndarray
    red_indptr: np.ndarray
    red_bins: np.ndarray
    binred_indptr: np.ndarray
    bin_reds: np.ndarray
    comm_cost: float = 0.0
    lower_bound: Optional[float] = None
    algorithm: str = "unknown"

    @property
    def num_bins(self) -> int:
        return int(len(self.bin_indptr) - 1)

    @property
    def num_reducers(self) -> int:
        return int(len(self.red_indptr) - 1)

    @property
    def host_entries(self) -> int:
        """Total host-side index entries — o(m^2) by construction."""
        return int(self.bin_inputs.size + self.bin_of.size
                   + 2 * self.red_bins.size)

    @property
    def optimality_gap(self) -> Optional[float]:
        if self.lower_bound is None or self.lower_bound <= 0.0:
            return None
        return self.comm_cost / self.lower_bound


def build_sparse_plan(schema: MappingSchema) -> SparsePlan:
    """CSR maps from a disjoint-bins schema, no per-input Python loops.

    Raises on overlapping-bin schemas (hybrid / big-input paths): those are
    small-m constructions that the dense ``build_plan`` already serves.
    """
    if schema.meta.get("bins_overlap", False):
        raise ValueError(
            "sparse plans require disjoint bins; use build_plan for the "
            "overlapping hybrid/big-input schemas")
    m = schema.m
    nb = len(schema.bins)
    bin_counts = np.asarray([len(b) for b in schema.bins], dtype=np.int64)
    bin_inputs = (np.concatenate(
        [np.asarray(b, dtype=np.int64) for b in schema.bins])
        if nb else np.zeros(0, dtype=np.int64))
    bin_indptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(bin_counts, out=bin_indptr[1:])
    bin_of = np.full(m, -1, dtype=np.int64)
    bin_of[bin_inputs] = np.repeat(
        np.arange(nb, dtype=np.int64), bin_counts)

    nr = len(schema.reducers)
    red_counts = np.asarray([len(r) for r in schema.reducers],
                            dtype=np.int64)
    red_bins = (np.concatenate(
        [np.asarray(r, dtype=np.int64) for r in schema.reducers])
        if nr else np.zeros(0, dtype=np.int64))
    red_indptr = np.zeros(nr + 1, dtype=np.int64)
    np.cumsum(red_counts, out=red_indptr[1:])

    # invert to bin -> reducers (the inverse-shuffle direction)
    red_of = np.repeat(np.arange(nr, dtype=np.int64), red_counts)
    order = np.lexsort((red_of, red_bins))
    binred_indptr = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(np.bincount(red_bins, minlength=nb), out=binred_indptr[1:])
    return SparsePlan(
        num_inputs=m, q=float(schema.q), bin_indptr=bin_indptr,
        bin_inputs=bin_inputs, bin_of=bin_of, red_indptr=red_indptr,
        red_bins=red_bins, binred_indptr=binred_indptr,
        bin_reds=red_of[order], comm_cost=schema.communication_cost(),
        lower_bound=schema.lower_bound, algorithm=schema.algorithm)


def _gather_csr(indptr: np.ndarray, data: np.ndarray,
                keys: np.ndarray) -> np.ndarray:
    """Concatenate ``data[indptr[k]:indptr[k+1]]`` over ``keys``."""
    if keys.size == 0:
        return np.zeros(0, dtype=data.dtype)
    return np.concatenate(
        [data[indptr[k]:indptr[k + 1]] for k in keys])


def block_subplan(sparse: SparsePlan, i0: int, i1: int, j0: int, j1: int,
                  *, pad_reducers_to: int = 1, pad_slots_to: int = 1,
                  max_buckets: int = 8,
                  cache_size: Optional[int] = None) -> Optional[ReducerPlan]:
    """Rectangular sub-plan serving output block ``[i0:i1) x [j0:j1)``.

    Selects exactly the reducers hosting at least one row bin *and* one
    column bin — for any required pair (i, j) in the block, the reducer
    the schema covers it with hosts ``bin_of[i]`` (a row bin) and
    ``bin_of[j]`` (a column bin), so it is selected and the block inherits
    the schema's full coverage.  Each selected reducer is restricted to
    the block-local X / Y ids it actually hosts; the result is an ordinary
    rectangular plan any executor runs via ``run_x2y``.  Returns ``None``
    for a block no reducer touches (empty ranges).  LRU-cached on the
    sparse plan so repeated requests reuse executor-side srcmaps;
    ``cache_size=None`` (default) takes the shared cap set by
    ``REPRO_BLOCK_CACHE_SIZE`` / :func:`configure_block_cache`, and
    hit/miss/evict counters feed :func:`block_cache_stats`.
    """
    if cache_size is None:
        cache_size = _BLOCK_CACHE_MAX
    if not (0 <= i0 <= i1 <= sparse.num_inputs
            and 0 <= j0 <= j1 <= sparse.num_inputs):
        raise IndexError(
            f"block [{i0}:{i1}) x [{j0}:{j1}) outside "
            f"m={sparse.num_inputs}")
    key = (i0, i1, j0, j1, pad_reducers_to, pad_slots_to, max_buckets)
    cache = sparse.__dict__.get("_block_cache")
    if cache is None:
        cache = OrderedDict()
        object.__setattr__(sparse, "_block_cache", cache)
    hit = key in cache
    with _obs_span("lower", cached=hit) as s:
        if hit:
            cache.move_to_end(key)
            _BLOCK_CACHE_STATS["hits"] += 1
            _OBS_REGISTRY.counter("cache.hits", cache="block").inc()
            return cache[key]
        _BLOCK_CACHE_STATS["misses"] += 1
        _OBS_REGISTRY.counter("cache.misses", cache="block").inc()
        plan = _build_block_subplan(sparse, i0, i1, j0, j1, pad_reducers_to,
                                    pad_slots_to, max_buckets)
        if s is not None and plan is not None:
            s.attrs.update(_rect_bucket_attrs(plan, pad_slots_to))
        cache[key] = plan
        while len(cache) > cache_size:
            evicted, _ = cache.popitem(last=False)
            _BLOCK_CACHE_STATS["evictions"] += 1
            _OBS_REGISTRY.counter("cache.evictions", cache="block").inc()
            _OBS_EVENTS.emit("cache_eviction", cache="block",
                             key=str(evicted))
    return plan


def _build_block_subplan(sparse: SparsePlan, i0: int, i1: int, j0: int,
                         j1: int, pad_reducers_to: int, pad_slots_to: int,
                         max_buckets: int) -> Optional[ReducerPlan]:
    """The sub-plan :func:`block_subplan` caches: the reducers hosting a
    row bin and a column bin of the block, in block-local ids."""
    row_bins = np.unique(sparse.bin_of[i0:i1])
    col_bins = np.unique(sparse.bin_of[j0:j1])
    row_bins = row_bins[row_bins >= 0]
    col_bins = col_bins[col_bins >= 0]
    row_reds = np.unique(
        _gather_csr(sparse.binred_indptr, sparse.bin_reds, row_bins))
    col_reds = np.unique(
        _gather_csr(sparse.binred_indptr, sparse.bin_reds, col_bins))
    cand = np.intersect1d(row_reds, col_reds, assume_unique=True)

    xs: list[np.ndarray] = []
    ys: list[np.ndarray] = []
    for r in cand:
        bins_r = sparse.red_bins[
            sparse.red_indptr[r]:sparse.red_indptr[r + 1]]
        inputs_r = _gather_csr(sparse.bin_indptr, sparse.bin_inputs, bins_r)
        xr = inputs_r[(inputs_r >= i0) & (inputs_r < i1)] - i0
        yr = inputs_r[(inputs_r >= j0) & (inputs_r < j1)] - j0
        if xr.size and yr.size:
            xs.append(xr)
            ys.append(yr)
    if not xs:
        return None
    return build_x2y_plan_arrays(
        xs, ys, num_x=i1 - i0, num_y=j1 - j0,
        comm_cost=float(sum(len(a) + len(b) for a, b in zip(xs, ys))),
        algorithm=f"block+{sparse.algorithm}",
        pad_reducers_to=pad_reducers_to, pad_slots_to=pad_slots_to,
        max_buckets=max_buckets)


def build_x2y_plan(schema: MappingSchema, num_x: int, *,
                   pad_reducers_to: int = 1, pad_slots_to: int = 1,
                   max_buckets: int = 8) -> ReducerPlan:
    """Flatten an X2Y schema (``plan_x2y`` convention: global ids
    ``0..num_x-1`` are X, ``num_x..`` are Y) into a rectangular plan:
    each reducer's expanded ids are split at the X/Y boundary, Y ids are
    re-based to Y-table-local rows, and capacity buckets group reducers by
    (wx, wy) power-of-two width pairs."""
    expanded = schema.expand()
    xs = [[i for i in ids if i < num_x] for ids in expanded]
    ys = [[i - num_x for i in ids if i >= num_x] for ids in expanded]
    return build_x2y_plan_arrays(
        xs, ys, num_x=num_x, num_y=len(schema.weights) - num_x,
        comm_cost=schema.communication_cost(), algorithm=schema.algorithm,
        lower_bound=schema.lower_bound, pad_reducers_to=pad_reducers_to,
        pad_slots_to=pad_slots_to, max_buckets=max_buckets)


def _shardings(mesh, shard_axes):
    axes = shard_axes if shard_axes is not None else mesh.axis_names
    P = jax.sharding.PartitionSpec
    red = jax.sharding.NamedSharding(mesh, P(axes))
    rep = jax.sharding.NamedSharding(mesh, P())
    return red, rep


def _gather_reduce(x, idx, mask, reducer_fn):
    gathered = jnp.take(x, idx, axis=0)          # (R, L, d) — the shuffle
    gathered = jnp.where(mask[..., None], gathered, 0)
    return jax.vmap(reducer_fn)(gathered, mask)


# One jitted executable per (reducer_fn, mesh, shard_axes): repeated calls —
# a serving loop, the benchmark's timed iterations, every bucket of a
# bucketed run — reuse the XLA compile cache instead of re-tracing through
# a fresh jax.jit wrapper each time.  Callers enable reuse by passing the
# *same* reducer_fn object (see allpairs._block_fn).
#
# The cache is a bounded LRU: a long-running PairwiseService loop that keeps
# constructing *fresh* reducer closures (defeating the reuse contract) evicts
# its oldest entries instead of growing without limit.  The cap is
# configurable via the ``REPRO_JIT_CACHE_SIZE`` environment variable (read
# at import and by ``configure_jit_cache()``); ``jit_cache_stats`` feeds the
# serving telemetry, including per-key hit counts.
def _env_cache_size(default: int = 64,
                    var: str = "REPRO_JIT_CACHE_SIZE") -> int:
    """``var`` as a cap >= 1; malformed or non-positive values fall back
    to the default (a cap of 0 would evict every insert immediately —
    unbounded retracing, the exact cost the cache exists to prevent)."""
    raw = os.environ.get(var, "")
    try:
        size = int(raw)
    except ValueError:
        return default
    return size if size >= 1 else default


_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_MAX = _env_cache_size()
_JIT_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0,
                    "shape_hits": 0, "shape_misses": 0}
_JIT_CACHE_HITS: dict = {}                    # key -> hit count (live entries)
_JIT_SHAPES: dict = {}                # key -> arg-shape signatures seen


def configure_jit_cache(max_size: Optional[int] = None) -> int:
    """Set the jit-cache LRU cap; with no argument, re-read
    ``REPRO_JIT_CACHE_SIZE`` from the environment (default 64).  Evicts
    oldest entries immediately if the cache exceeds the new cap.  Returns
    the active cap."""
    global _JIT_CACHE_MAX
    if max_size is None:
        max_size = _env_cache_size()
    assert max_size >= 1, max_size
    _JIT_CACHE_MAX = max_size
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _evict_oldest()
    return _JIT_CACHE_MAX


def _evict_oldest():
    key, _ = _JIT_CACHE.popitem(last=False)
    _JIT_CACHE_HITS.pop(key, None)
    _JIT_SHAPES.pop(key, None)
    _JIT_CACHE_STATS["evictions"] += 1
    _OBS_REGISTRY.counter("cache.evictions", cache="jit").inc()
    _OBS_EVENTS.emit("cache_eviction", cache="jit", key=_key_label(key))


def _record_shapes(key, args) -> None:
    """Shape-level compile telemetry.  ``jax.jit`` caches compilations per
    argument shape, so a jit-cache *key* hit can still pay a compile when
    the call carries a shape the entry has not seen.  Tracking signatures
    per key makes that visible: a new signature is a ``shape_miss`` (a
    retrace/compile happened), a repeat is a ``shape_hit`` — the counter
    the streaming warm-path tests pin (a warmed first edit must add zero
    shape_misses).  Recorded at the call sites, not by wrapping the jitted
    fn, so ``.lower()`` on cache entries keeps working."""
    sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
    seen = _JIT_SHAPES.setdefault(key, set())
    if sig in seen:
        _JIT_CACHE_STATS["shape_hits"] += 1
        _OBS_REGISTRY.counter("cache.shape_hits", cache="jit").inc()
    else:
        seen.add(sig)
        _JIT_CACHE_STATS["shape_misses"] += 1
        _OBS_REGISTRY.counter("cache.shape_misses", cache="jit").inc()


def _cache_get(key, factory):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        _JIT_CACHE_STATS["misses"] += 1
        _OBS_REGISTRY.counter("cache.misses", cache="jit").inc()
        fn = factory()
        _JIT_CACHE[key] = fn
        _JIT_CACHE_HITS[key] = 0
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _evict_oldest()
    else:
        _JIT_CACHE_STATS["hits"] += 1
        _OBS_REGISTRY.counter("cache.hits", cache="jit").inc()
        _JIT_CACHE_HITS[key] = _JIT_CACHE_HITS.get(key, 0) + 1
        _JIT_CACHE.move_to_end(key)
    return fn


# Backend compiles, counted from ``jax.monitoring``: every XLA compile (or
# persistent-cache read) of a program ``jax.jit`` builds for a new function or
# argument shape.  A jit-cache miss above only builds the ``jax.jit`` wrapper;
# the compile happens on the wrapper's first call with each shape.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILES = {"count": 0}


def _on_compile_event(event: str, secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _COMPILES["count"] += 1
        _OBS_REGISTRY.counter("jit.compiles").inc()
        _OBS_REGISTRY.counter("jit.compile_seconds").inc(secs)


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


def compile_count() -> int:
    """Backend compiles in this process so far (whatever the obs switch)."""
    return _COMPILES["count"]


@contextmanager
def upload_span(host):
    """An ``upload`` span around one site's host-to-device puts of the
    arrays in ``host``; ``bytes`` is the total of its NumPy arrays, what
    the puts copy (arrays already on the device count nothing)."""
    with _obs_span("upload") as s:
        if s is not None:
            s.attrs["bytes"] = sum(a.nbytes for a in jax.tree.leaves(host)
                                   if isinstance(a, np.ndarray))
        yield


def launch(fn, *args):
    """Call a jitted program under a ``launch`` span: the enqueue, plus any
    trace and compile (``compiles``); the device runs on after it."""
    c0 = _COMPILES["count"]
    with _obs_span("launch") as s:
        out = fn(*args)
        if s is not None:
            s.attrs["compiles"] = _COMPILES["count"] - c0
    return out


def _key_label(key) -> str:
    """Short human-readable label for a jit-cache key (telemetry only)."""
    if isinstance(key, tuple):
        return "|".join(_key_label(k) for k in key)
    name = getattr(key, "__name__", None)
    if isinstance(name, str):
        return name
    if key is None or isinstance(key, (str, int, bool, float)):
        return str(key)
    return type(key).__name__


def jit_cache_stats() -> dict:
    """Engine jit-cache counters (size / hits / misses / evictions), plus
    per-key hit counts for the live entries (labels are best-effort
    summaries of the cache key; colliding labels sum their hits)."""
    per_key: dict = {}
    for key, hits in _JIT_CACHE_HITS.items():
        label = _key_label(key)
        per_key[label] = per_key.get(label, 0) + hits
    return {**_JIT_CACHE_STATS, "size": len(_JIT_CACHE),
            "max_size": _JIT_CACHE_MAX, "per_key": per_key}


# The block sub-plan LRU (``block_subplan``) lives per SparsePlan instance
# but all instances share one configurable cap and one set of counters,
# mirroring the jit cache above: ``REPRO_BLOCK_CACHE_SIZE`` /
# ``configure_block_cache()`` set the cap, ``block_cache_stats()`` feeds
# the serving telemetry.  The cap is applied at insert time, so lowering
# it trims each plan's cache on that plan's next block request.
_BLOCK_CACHE_MAX = _env_cache_size(var="REPRO_BLOCK_CACHE_SIZE")
_BLOCK_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def configure_block_cache(max_size: Optional[int] = None) -> int:
    """Set the block sub-plan LRU cap; with no argument, re-read
    ``REPRO_BLOCK_CACHE_SIZE`` from the environment (default 64).
    Returns the active cap."""
    global _BLOCK_CACHE_MAX
    if max_size is None:
        max_size = _env_cache_size(var="REPRO_BLOCK_CACHE_SIZE")
    assert max_size >= 1, max_size
    _BLOCK_CACHE_MAX = max_size
    return _BLOCK_CACHE_MAX


def block_cache_stats() -> dict:
    """Block sub-plan cache counters (shared across all SparsePlans)."""
    return {**_BLOCK_CACHE_STATS, "max_size": _BLOCK_CACHE_MAX}


def _get_jitted(reducer_fn, mesh, shard_axes):
    def factory():
        run = partial(_gather_reduce, reducer_fn=reducer_fn)
        if mesh is None:
            return jax.jit(run)
        red_sharding, rep = _shardings(mesh, shard_axes)
        return jax.jit(run,
                       in_shardings=(rep, red_sharding, red_sharding),
                       out_shardings=red_sharding)
    return _cache_get((reducer_fn, mesh, shard_axes), factory)


def run_reducers(
    inputs: jax.Array,                     # (m, d) one row per input
    plan: ReducerPlan,
    reducer_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    shard_axes: Optional[tuple[str, ...]] = None,
    donate: bool = False,
):
    """Execute ``reducer_fn(block (L, d), mask (L,)) -> pytree`` per reducer.

    With a mesh, reducer slots are sharded over ``shard_axes`` (all mesh axes
    by default) and the input table is left replicated — the gather *is* the
    map->reduce shuffle.  Without a mesh, runs locally (CPU tests).
    """
    idx = jnp.asarray(plan.idx)
    mask = jnp.asarray(plan.mask)
    shard_axes = tuple(shard_axes) if shard_axes is not None else None
    fn = _get_jitted(reducer_fn, mesh, shard_axes)
    _record_shapes((reducer_fn, mesh, shard_axes), (inputs, idx, mask))
    return fn(inputs, idx, mask)


# ---------------------------------------------------------------------------
# bucketed (skew-aware) executor
# ---------------------------------------------------------------------------
def _dense_out_shapes(plan: ReducerPlan, reducer_fn, inputs):
    """Per-reducer output ShapeDtypes at the dense width L."""
    blk = jax.ShapeDtypeStruct((plan.L,) + inputs.shape[1:], inputs.dtype)
    msk = jax.ShapeDtypeStruct((plan.L,), jnp.bool_)
    return jax.eval_shape(reducer_fn, blk, msk)


def _pad_leaf_to(leaf, target_shape):
    """Zero-pad trailing extents of ``leaf`` (past its leading batch axis)
    up to ``target_shape`` — the slot-sized axes grow from bucket width to
    the dense width; equal axes are untouched."""
    pads = [(0, 0)]
    for have, want in zip(leaf.shape[1:], target_shape):
        assert have <= want, (leaf.shape, target_shape)
        pads.append((0, want - have))
    if any(p != (0, 0) for p in pads):
        leaf = jnp.pad(leaf, pads)
    return leaf


def run_reducers_bucketed(
    inputs: jax.Array,                     # (m, d) one row per input
    plan: ReducerPlan,
    reducer_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    shard_axes: Optional[tuple[str, ...]] = None,
    combine: str = "dense",
):
    """Skew-aware execution: one vmapped gather+reduce per capacity bucket.

    Each bucket pads only to its own width, so a single heavy reducer no
    longer inflates every light reducer to the global max slot count — on a
    Zipf-sized schema this cuts the gathered elements (and the quadratic
    reducer FLOPs of block reducers) by the plan's ``padding_savings``.

    combine='dense'    — return one pytree shaped exactly like the dense
        ``run_reducers`` output: bucket outputs are zero-padded along their
        slot-sized axes to the dense width and scattered back into original
        reducer order.  Rows past ``plan.num_reducers`` (mesh padding) are
        zeros, so ``reducer_fn`` must zero its masked-out output entries for
        the two executors to agree there (all shipped reducer functions do).
    combine='buckets'  — return ``[(bucket, out_pytree), ...]`` unpadded;
        downstream consumers (e.g. the per-bucket pair-matrix assembler)
        keep the memory win end-to-end.

    ``reducer_fn`` must be shape-polymorphic over the slot count L — it is
    traced once per bucket width.
    """
    assert combine in ("dense", "buckets"), combine
    buckets = plan.buckets
    if not buckets:
        # plans built before bucketing / empty schemas: dense semantics
        out = run_reducers(inputs, plan, reducer_fn, mesh=mesh,
                           shard_axes=shard_axes)
        return out if combine == "dense" else []

    shard_axes = tuple(shard_axes) if shard_axes is not None else None
    fn = _get_jitted(reducer_fn, mesh, shard_axes)

    per_bucket = []
    for b in buckets:
        idx, mask = jnp.asarray(b.idx), jnp.asarray(b.mask)
        _record_shapes((reducer_fn, mesh, shard_axes), (inputs, idx, mask))
        per_bucket.append((b, fn(inputs, idx, mask)))
    if combine == "buckets":
        return per_bucket

    dense_shapes = _dense_out_shapes(plan, reducer_fn, inputs)
    leaves_t, treedef = jax.tree.flatten(dense_shapes)
    acc = [jnp.zeros((plan.R,) + t.shape, t.dtype) for t in leaves_t]
    for b, out in per_bucket:
        valid = b.rows >= 0                      # static numpy mask
        rows = jnp.asarray(b.rows[valid])
        for i, leaf in enumerate(jax.tree.flatten(out)[0]):
            padded = _pad_leaf_to(leaf, leaves_t[i].shape)
            acc[i] = acc[i].at[rows].set(padded[np.flatnonzero(valid)])
    return jax.tree.unflatten(treedef, acc)


# ---------------------------------------------------------------------------
# rectangular (X2Y) runners
# ---------------------------------------------------------------------------
def _gather_reduce_x2y(xt, yt, xidx, xmask, yidx, ymask, reducer_fn):
    gx = jnp.take(xt, xidx, axis=0)              # (R, Lx, d) — X-side shuffle
    gx = jnp.where(xmask[..., None], gx, 0)
    gy = jnp.take(yt, yidx, axis=0)              # (R, Ly, d) — Y-side shuffle
    gy = jnp.where(ymask[..., None], gy, 0)
    return jax.vmap(reducer_fn)(gx, xmask, gy, ymask)


def _get_jitted_x2y(reducer_fn, mesh, shard_axes):
    def factory():
        run = partial(_gather_reduce_x2y, reducer_fn=reducer_fn)
        if mesh is None:
            return jax.jit(run)
        red_sharding, rep = _shardings(mesh, shard_axes)
        return jax.jit(run,
                       in_shardings=(rep, rep, red_sharding, red_sharding,
                                     red_sharding, red_sharding),
                       out_shardings=red_sharding)
    return _cache_get(("x2y", reducer_fn, mesh, shard_axes), factory)


def _as_tables(tables):
    """(x_table, y_table) from a pair or a single shared table (X == Y:
    one array, put on the device once)."""
    if isinstance(tables, (tuple, list)):
        return jnp.asarray(tables[0]), jnp.asarray(tables[1])
    t = jnp.asarray(tables)
    return t, t


def run_reducers_x2y(
    tables,                                # (x (mx, dx), y (my, dy)) pair
    plan: ReducerPlan,
    reducer_fn: Callable,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    shard_axes: Optional[tuple[str, ...]] = None,
):
    """Dense rectangular execution: ``reducer_fn(xblock (Lx, dx),
    xmask (Lx,), yblock (Ly, dy), ymask (Ly,)) -> pytree`` per reducer.

    The two gathers are the bipartite shuffle — X rows and Y rows ship to
    their reducer slots independently.  ``tables`` may be one array (shared
    table) or an (x, y) pair."""
    assert plan.is_rect, "run_reducers_x2y needs a rectangular plan"
    xt, yt = _as_tables(tables)
    shard_axes = tuple(shard_axes) if shard_axes is not None else None
    fn = _get_jitted_x2y(reducer_fn, mesh, shard_axes)
    args = (xt, yt, jnp.asarray(plan.idx), jnp.asarray(plan.mask),
            jnp.asarray(plan.yidx), jnp.asarray(plan.ymask))
    _record_shapes(("x2y", reducer_fn, mesh, shard_axes), args)
    return fn(*args)


def _dense_out_shapes_x2y(plan: ReducerPlan, reducer_fn, xt, yt):
    xb = jax.ShapeDtypeStruct((plan.L,) + xt.shape[1:], xt.dtype)
    xm = jax.ShapeDtypeStruct((plan.L,), jnp.bool_)
    yb = jax.ShapeDtypeStruct((plan.Ly,) + yt.shape[1:], yt.dtype)
    ym = jax.ShapeDtypeStruct((plan.Ly,), jnp.bool_)
    return jax.eval_shape(reducer_fn, xb, xm, yb, ym)


def run_reducers_x2y_bucketed(
    tables,
    plan: ReducerPlan,
    reducer_fn: Callable,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    shard_axes: Optional[tuple[str, ...]] = None,
    combine: str = "dense",
):
    """Skew-aware rectangular execution: one vmapped double-gather+reduce
    per (wx, wy) capacity bucket.  Semantics mirror
    :func:`run_reducers_bucketed`: ``combine='dense'`` scatters bucket
    outputs (padded on both slot axes to the dense (Lx, Ly)) back into
    original reducer order; ``combine='buckets'`` returns
    ``[(bucket, out_pytree), ...]`` unpadded."""
    assert combine in ("dense", "buckets"), combine
    assert plan.is_rect, "run_reducers_x2y_bucketed needs a rect plan"
    buckets = plan.buckets
    if not buckets:
        out = run_reducers_x2y(tables, plan, reducer_fn, mesh=mesh,
                               shard_axes=shard_axes)
        return out if combine == "dense" else []

    xt, yt = _as_tables(tables)
    shard_axes = tuple(shard_axes) if shard_axes is not None else None
    fn = _get_jitted_x2y(reducer_fn, mesh, shard_axes)

    per_bucket = []
    for b in buckets:
        args = (xt, yt, jnp.asarray(b.idx), jnp.asarray(b.mask),
                jnp.asarray(b.yidx), jnp.asarray(b.ymask))
        _record_shapes(("x2y", reducer_fn, mesh, shard_axes), args)
        per_bucket.append((b, fn(*args)))
    if combine == "buckets":
        return per_bucket

    dense_shapes = _dense_out_shapes_x2y(plan, reducer_fn, xt, yt)
    leaves_t, treedef = jax.tree.flatten(dense_shapes)
    acc = [jnp.zeros((plan.R,) + t.shape, t.dtype) for t in leaves_t]
    for b, out in per_bucket:
        valid = b.rows >= 0                      # static numpy mask
        rows = jnp.asarray(b.rows[valid])
        for i, leaf in enumerate(jax.tree.flatten(out)[0]):
            padded = _pad_leaf_to(leaf, leaves_t[i].shape)
            acc[i] = acc[i].at[rows].set(padded[np.flatnonzero(valid)])
    return jax.tree.unflatten(treedef, acc)


# ---------------------------------------------------------------------------
# fused dispatch counters, aggregated over the executor registry
# ---------------------------------------------------------------------------
# The executors live in ``repro.mapreduce.executors`` as registry
# objects with instance-scoped ``stats()``/``reset()``.  ``fused_stats()``
# below is the documented *aggregate* view: every ``FusedExecutor``
# instance publishes its increments into the obs registry's
# ``executor.<key>{executor=fused}`` series (one series per executor name,
# shared by all instances), and this shim sums them.  ``FUSED_STATS`` is
# retained as a legacy name only — it is no longer wired to any instance
# (the old shared-dict default made ``service.reset_stats()`` silently
# zero other callers' telemetry).
FUSED_STATS = {"calls": 0, "kernel": 0, "streamed": 0, "fallbacks": 0}

_FUSED_KEYS = ("calls", "kernel", "streamed", "fallbacks")


def fused_stats() -> dict:
    """Aggregate fused dispatch counters across every ``FusedExecutor``
    instance (the default registry instance and all ``make_executor``
    copies), read from the observability registry."""
    return {k: int(_OBS_REGISTRY.counter_total(f"executor.{k}",
                                               executor="fused"))
            for k in _FUSED_KEYS}


def reset_fused_stats() -> None:
    """Zero the aggregate fused counters (all instances' published
    series)."""
    for k in _FUSED_KEYS:
        _OBS_REGISTRY.reset_counters(f"executor.{k}", executor="fused")
    for k in FUSED_STATS:
        FUSED_STATS[k] = 0


def lower_reducers(
    input_shape: tuple[int, int],
    plan: ReducerPlan,
    reducer_fn: Callable,
    mesh: jax.sharding.Mesh,
    dtype=jnp.float32,
    shard_axes: Optional[tuple[str, ...]] = None,
):
    """Lower (no execution) for dry-run / roofline analysis.

    ``mesh=None`` lowers the unsharded single-program form (used by the
    benchmark's HLO buffer checks)."""
    idx = jax.ShapeDtypeStruct(plan.idx.shape, jnp.int32)
    mask = jax.ShapeDtypeStruct(plan.mask.shape, jnp.bool_)
    x = jax.ShapeDtypeStruct(input_shape, dtype)

    _run = partial(_gather_reduce, reducer_fn=reducer_fn)
    if mesh is None:
        return jax.jit(_run).lower(x, idx, mask)
    red_sharding, rep = _shardings(mesh, shard_axes)
    fn = jax.jit(
        _run,
        in_shardings=(rep, red_sharding, red_sharding),
        out_shardings=red_sharding,
    )
    return fn.lower(x, idx, mask)


def lower_reducers_bucketed(
    input_shape: tuple[int, int],
    plan: ReducerPlan,
    reducer_fn: Callable,
    mesh: jax.sharding.Mesh,
    dtype=jnp.float32,
    shard_axes: Optional[tuple[str, ...]] = None,
) -> list:
    """Lower every bucket program (no execution) for dry-run / roofline.

    Returns ``[(bucket, lowered), ...]``; per-device roofline terms add up
    across buckets (the programs run back-to-back on the same mesh).
    ``mesh=None`` lowers the unsharded single-program form of each bucket
    (the streaming dry-run's delta-vs-replan byte comparison)."""
    x = jax.ShapeDtypeStruct(input_shape, dtype)
    _run = partial(_gather_reduce, reducer_fn=reducer_fn)
    if mesh is None:
        fn = jax.jit(_run)
    else:
        red_sharding, rep = _shardings(mesh, shard_axes)
        fn = jax.jit(_run, in_shardings=(rep, red_sharding, red_sharding),
                     out_shardings=red_sharding)
    out = []
    for b in plan.buckets:
        idx = jax.ShapeDtypeStruct(b.idx.shape, jnp.int32)
        mask = jax.ShapeDtypeStruct(b.mask.shape, jnp.bool_)
        out.append((b, fn.lower(x, idx, mask)))
    return out
