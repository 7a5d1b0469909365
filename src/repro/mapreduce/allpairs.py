"""A2A applications: all-pairs similarity (common friends, drug interaction).

Every input is a feature row (multi-hot friend vector, patient-history
embedding, ...).  The planner guarantees each pair of rows meets at >= 1
reducer; reducers compute the dense pairwise block with the MXU-friendly
``pairwise`` kernel; results are scattered back into the (m, m) matrix.

``some_pairs_similarity`` is the sparse variant (Ullman & Ullman's
some-pairs problem): only an explicit pair set must meet, the planner
ships only pair-incident inputs, and the result is masked to the
requested pairs.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span as _obs_span

from repro.core import (plan_a2a, plan_a2a_hierarchical, plan_some_pairs,
                        plan_x2y)
from repro.core.schema import MappingSchema

from .engine import (ReducerPlan, SparsePlan, _rect_bucket_attrs,
                     build_plan, build_sparse_plan, build_x2y_plan, y_side)
from .executors import _derived, _fill_source_map, get_executor

__all__ = [
    "pairwise_similarity",
    "pairwise_similarity_block",
    "some_pairs_similarity",
    "x2y_similarity",
    "assemble_pair_matrix",
    "assemble_pair_matrix_bucketed",
    "assemble_x2y_matrix_bucketed",
    "block_similarity",
    "block_similarity_x2y",
]


def _gram(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T with fp32 products: the TPU's default matmul precision
    rounds f32 operands to bf16."""
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def block_similarity(block: jax.Array, mask: jax.Array, *,
                     metric: str = "dot", use_kernel: bool = False,
                     interpret: bool = False):
    """(L, d), (L,) -> (L, L) similarity of the valid rows; invalid -> 0."""
    if use_kernel:
        from repro.kernels.pairwise.ops import pairwise_kernel
        sims = pairwise_kernel(block, metric=metric, interpret=interpret)
    else:
        if metric == "dot":
            sims = _gram(block, block)
        elif metric == "l2":
            n2 = jnp.sum(block * block, axis=-1)
            sims = n2[:, None] + n2[None, :] - 2.0 * _gram(block, block)
        elif metric == "cosine":
            nrm = jnp.sqrt(jnp.sum(block * block, axis=-1) + 1e-9)
            sims = _gram(block, block) / (nrm[:, None] * nrm[None, :])
        else:
            raise ValueError(metric)
    valid = mask[:, None] & mask[None, :]
    return jnp.where(valid, sims, 0.0)


@functools.lru_cache(maxsize=None)
def _block_fn(metric: str, use_kernel: bool, interpret: bool = False):
    """Memoized reducer: the same (metric, use_kernel, interpret) must map
    to the *same* function object so the engine's jit cache is hit across
    calls instead of re-tracing every request.  The ``fused_metric`` tag is what
    lets the fused executor recognize this reducer as a Gram block and
    compute it without materializing the gather (non-tagged reducers fall
    back to the bucketed path)."""
    def fn(block, mask):
        return block_similarity(block, mask, metric=metric,
                                use_kernel=use_kernel, interpret=interpret)
    fn.__name__ = f"block_similarity_{metric}"
    fn.fused_metric = metric
    return fn


def block_similarity_x2y(xblock: jax.Array, xmask: jax.Array,
                         yblock: jax.Array, ymask: jax.Array, *,
                         metric: str = "dot"):
    """(Lx, d), (Lx,), (Ly, d), (Ly,) -> (Lx, Ly) cross similarity of the
    valid rows; invalid pairs -> 0.  The rectangular analogue of
    :func:`block_similarity` (which is the degenerate X == Y case)."""
    if metric == "dot":
        sims = _gram(xblock, yblock)
    elif metric == "l2":
        n2x = jnp.sum(xblock * xblock, axis=-1)
        n2y = jnp.sum(yblock * yblock, axis=-1)
        sims = n2x[:, None] + n2y[None, :] - 2.0 * _gram(xblock, yblock)
    elif metric == "cosine":
        nx = jnp.sqrt(jnp.sum(xblock * xblock, axis=-1) + 1e-9)
        ny = jnp.sqrt(jnp.sum(yblock * yblock, axis=-1) + 1e-9)
        sims = _gram(xblock, yblock) / (nx[:, None] * ny[None, :])
    else:
        raise ValueError(metric)
    valid = xmask[:, None] & ymask[None, :]
    return jnp.where(valid, sims, 0.0)


@functools.lru_cache(maxsize=None)
def _block_fn_x2y(metric: str):
    """Memoized two-sided reducer (same reuse contract as ``_block_fn``).
    The ``fused_metric`` tag lets the fused/sharded executors run the
    rectangular gather+Gram path instead of materializing the gathers."""
    def fn(xblock, xmask, yblock, ymask):
        return block_similarity_x2y(xblock, xmask, yblock, ymask,
                                    metric=metric)
    fn.__name__ = f"block_similarity_x2y_{metric}"
    fn.fused_metric = metric
    return fn


def _plan_for(schema, *, pad_reducers_to: int, pad_slots_to: int):
    """``build_plan`` memoized on the schema object, under a ``lower``
    span (``cached``: the schema held the plan).

    Plans are pure functions of (schema, padding); caching them on the
    schema keeps the per-request host work O(1) for repeated profiles —
    the same static-plan reuse contract as ``repro.core.PLAN_CACHE``."""
    key = (pad_reducers_to, pad_slots_to)
    cache = schema.__dict__.setdefault("_reducer_plan_cache", {})
    plan = cache.get(key)
    with _obs_span("lower", cached=plan is not None):
        if plan is None:
            plan = cache[key] = build_plan(
                schema, pad_reducers_to=pad_reducers_to,
                pad_slots_to=pad_slots_to)
    return plan


def _x2y_plan_for(schema, num_x: int, *, pad_reducers_to: int,
                  pad_slots_to: int):
    """``build_x2y_plan`` memoized on the schema object (same contract as
    ``_plan_for``); a build records its bucket merge on the span
    (``width_pairs``, ``padded_entries``: ``engine._rect_bucket_attrs``)."""
    key = ("x2y", num_x, pad_reducers_to, pad_slots_to)
    cache = schema.__dict__.setdefault("_reducer_plan_cache", {})
    plan = cache.get(key)
    with _obs_span("lower", cached=plan is not None) as s:
        if plan is None:
            plan = cache[key] = build_x2y_plan(
                schema, num_x, pad_reducers_to=pad_reducers_to,
                pad_slots_to=pad_slots_to)
            if s is not None:
                s.attrs.update(_rect_bucket_attrs(plan, pad_slots_to))
    return plan


def _pair_source_map_rect(plan: ReducerPlan, mx: int, my: int,
                          zero_diag: bool = False) -> np.ndarray:
    """Inverse-shuffle map of the fused assembly: (mx, my) int32 positions
    into the concatenation ``[0.0, blocks_0.ravel(), ...]`` of per-bucket
    Gram stacks (bucket order = ``plan.buckets``).  Rows come from each
    bucket's X-side ids, columns from its Y side (a self-join's Y side is
    its X side, ``engine.y_side``).

    A cell covered by several reducers keeps one (deterministic) source —
    duplicate block values agree exactly, so assembly is a gather instead
    of the bucketed path's max-combine scatter.  Uncovered cells, and with
    ``zero_diag`` the diagonal (a self-join's self-pairs), point at slot 0
    (-> 0.0).  Cached on the plan by (shape, ``zero_diag``): like the
    index matrix, a static artifact reused across waves; looked up and
    built under a ``maps`` span."""
    def build():
        srcmap = np.zeros((mx, my), np.int32)
        _fill_source_map(srcmap, ((b.idx, b.mask, *y_side(b))
                                  for b in plan.buckets))
        if zero_diag:
            np.fill_diagonal(srcmap, 0)
        return srcmap
    return _derived(plan, "_pair_srcmap_cache", (mx, my, zero_diag),
                    "srcmap", build)


@jax.jit
def _scatter_blocks_x2y(out: jax.Array, blocks: jax.Array, xidx: jax.Array,
                        xmask: jax.Array, yidx: jax.Array,
                        ymask: jax.Array) -> jax.Array:
    """max-scatter (R, Lx, Ly) cross blocks into the running (mx, my)
    matrix (initialized to -inf); duplicates agree, so max is
    deterministic.  The streaming patch path relies on the max-combine
    (clean cells keep their value after -inf invalidation)."""
    Ly = yidx.shape[1]
    Lx = xidx.shape[1]
    rows = jnp.repeat(xidx[:, :, None], Ly, axis=2)    # (R, Lx, Ly)
    cols = jnp.repeat(yidx[:, None, :], Lx, axis=1)
    valid = xmask[:, :, None] & ymask[:, None, :]
    flat_vals = jnp.where(valid, blocks, -jnp.inf).reshape(-1)
    return out.at[rows.reshape(-1), cols.reshape(-1)].max(flat_vals)


@jax.jit
def _finish_x2y_matrix(out: jax.Array) -> jax.Array:
    """Uncovered / invalidated cells -> 0 (no diagonal to zero: an (x, y)
    pair is never a self-pair)."""
    return jnp.where(jnp.isneginf(out), 0.0, out)


def assemble_x2y_matrix_bucketed(per_bucket, shape: tuple[int, int]):
    """Scatter per-bucket (Rb, Lx, Ly[, c]) cross blocks into the global
    (mx, my[, c]) output.

    ``per_bucket`` is ``run_reducers_x2y_bucketed(..., combine='buckets')``
    output.  Invalid slots drop into a scratch row (duplicate covered
    cells agree exactly, so plain ``set`` is deterministic), which also
    handles payload-carrying blocks — the skew-join's (Lx, Ly, dx+dy)
    concat outputs assemble through the same path as similarity
    matrices."""
    mx, my = shape
    if not per_bucket:
        return jnp.zeros((mx, my), dtype=jnp.float32)
    out = None
    for b, blocks in per_bucket:
        trailing = blocks.shape[3:]
        if out is None:
            out = jnp.zeros((mx + 1, max(my, 1)) + trailing, blocks.dtype)
        xidx = jnp.asarray(b.idx)
        yidx = jnp.asarray(b.yidx)
        valid = jnp.asarray(b.mask)[:, :, None] \
            & jnp.asarray(b.ymask)[:, None, :]
        rows = jnp.where(valid, xidx[:, :, None], mx)   # invalid -> scratch
        cols = jnp.where(valid, yidx[:, None, :], 0)
        out = out.at[rows.reshape(-1), cols.reshape(-1)].set(
            blocks.reshape((-1,) + trailing))
    return out[:mx, :my]


def _run_and_assemble(x, plan, fn, m, mesh, executor,
                      use_kernel: bool = False, interpret: bool = False):
    """Single dispatch point: ``executor`` is a registry name ("dense",
    "bucketed", "fused", "sharded", "streaming") or an
    :class:`Executor` instance (the serving tier passes its own so
    telemetry stays instance-scoped)."""
    return get_executor(executor).run_pairs(
        x, plan, fn, m, mesh=mesh, use_kernel=use_kernel,
        interpret=interpret)


def pairwise_similarity(
    x: jax.Array,                       # (m, d)
    *,
    q: float,
    weights=None,                       # per-input sizes; default: uniform
    schema: Optional[MappingSchema] = None,
    metric: str = "dot",
    mesh=None,
    use_kernel: bool = False,
    pad_slots_to: int = 1,
    executor: str = "bucketed",
    interpret: bool = False,
):
    """All-pairs similarity executed through a mapping schema.

    ``executor='bucketed'`` (default) runs the skew-aware capacity-bucket
    executor — each reducer pads only to its bucket width, and per-bucket
    blocks are scattered straight into the (m, m) matrix so the padding
    saving survives end-to-end.  ``executor='dense'`` is the one-program
    global-max-padded path (differential-test oracle).

    ``executor='fused'`` streams the shuffle straight into the Gram
    computation (DESIGN.md "fused shuffle execution"): all capacity buckets
    plus the pair-matrix assembly run in one program, and the gathered
    block is never materialized in HBM.  On TPU (or with
    ``use_kernel=True``) the fused gather+Gram Pallas kernel does the work;
    set ``interpret=True`` to run that kernel on CPU.  Non-Gram reducers
    and bucketless plans silently fall back to the bucketed executor.

    ``executor='sharded'`` LPT-balances the reducers across the local
    device mesh and runs the fused pipeline per shard under ``shard_map``
    (DESIGN.md "sharded execution") with one cross-shard assembly gather.

    ``executor`` may also be an :class:`repro.mapreduce.executors.Executor`
    instance (instance-scoped telemetry); dispatch goes through the
    executor registry either way.  Returns (sims (m, m) with zero
    diagonal, plan, schema)."""
    m = x.shape[0]
    with _obs_span("plan", workload="pairs", m=m):
        if schema is None:
            w = (np.full(m, 1.0) if weights is None
                 else np.asarray(weights, float))
            schema = plan_a2a(w, q)
        plan = _plan_for(
            schema,
            pad_reducers_to=(mesh.devices.size if mesh is not None else 1),
            pad_slots_to=pad_slots_to,
        )
    fn = _block_fn(metric, use_kernel, interpret)
    with _obs_span("execute", workload="pairs",
                   reducers=plan.num_reducers):
        sims = _run_and_assemble(x, plan, fn, m, mesh, executor,
                                 use_kernel=use_kernel, interpret=interpret)
    return sims, plan, schema


def _sparse_plan_for(schema) -> SparsePlan:
    """Memoized CSR plan for a schema (same caching contract as
    ``_plan_for``: one sparse plan per schema object, shared across block
    requests so executor-side srcmaps and the sub-plan LRU persist)."""
    cached = schema.__dict__.get("_sparse_plan")
    if cached is None:
        cached = build_sparse_plan(schema)
        schema.__dict__["_sparse_plan"] = cached
    return cached


def pairwise_similarity_block(
    x: jax.Array,                       # (m, d)
    i0: int, i1: int, j0: int, j1: int,
    *,
    q: Optional[float] = None,
    weights=None,                       # per-input sizes; default: uniform
    schema: Optional[MappingSchema] = None,
    metric: str = "dot",
    mesh=None,
    pad_slots_to: int = 1,
    executor: str = "bucketed",
    interpret: bool = False,
):
    """One ``[i0:i1) x [j0:j1)`` sub-block of the all-pairs similarity
    matrix, without materializing (m, m) anywhere.

    The schema is planned hierarchically (``plan_a2a_hierarchical`` — the
    flat planner at small m, two-level super-input packing at large m) and
    lowered once to a CSR :class:`~repro.mapreduce.engine.SparsePlan`
    cached on the schema; each block request then routes through the
    executor's ``run_block`` — the registry default selects only the
    reducers covering the block and serves them via ``run_x2y``, so
    per-block work scales with the block, not with m.  Global-diagonal
    cells inside the block are zeroed, matching ``pairwise_similarity``.

    Returns (block (i1-i0, j1-j0), sparse plan, schema)."""
    m = x.shape[0]
    if schema is None:
        if q is None:
            raise ValueError("pass q or a pre-planned schema")
        w = np.full(m, 1.0) if weights is None else np.asarray(weights, float)
        schema = plan_a2a_hierarchical(w, q)
    sparse = _sparse_plan_for(schema)
    fn = _block_fn_x2y(metric)
    block = get_executor(executor).run_block(
        x, sparse, fn, int(i0), int(i1), int(j0), int(j1), mesh=mesh,
        interpret=interpret, pad_slots_to=pad_slots_to)
    return block, sparse, schema


def some_pairs_similarity(
    x: jax.Array,                       # (m, d)
    pairs: Sequence[tuple[int, int]],   # required pairs (i, j)
    *,
    q: float,
    weights=None,                       # per-input sizes; default: uniform
    schema: Optional[MappingSchema] = None,
    metric: str = "dot",
    mesh=None,
    use_kernel: bool = False,
    pad_slots_to: int = 1,
    executor: str = "bucketed",
    interpret: bool = False,
):
    """Similarity for an explicit pair set through a some-pairs schema.

    Unlike :func:`pairwise_similarity`, only inputs incident to a required
    pair are shipped to reducers (the planner's sparse strategies leave the
    rest unplaced), and the returned matrix is masked to the required pairs
    (symmetric).  ``executor='fused'`` serves the some-pairs (X2Y) workload
    on the same fused gather+Gram path as A2A.  Returns
    (sims (m, m), plan, schema).
    """
    m = x.shape[0]
    with _obs_span("plan", workload="some_pairs", m=m):
        if schema is None:
            w = (np.full(m, 1.0) if weights is None
                 else np.asarray(weights, float))
            schema = plan_some_pairs(w, q, pairs)
        plan = _plan_for(
            schema,
            pad_reducers_to=(mesh.devices.size if mesh is not None else 1),
            pad_slots_to=pad_slots_to,
        )
    fn = _block_fn(metric, use_kernel, interpret)
    with _obs_span("execute", workload="some_pairs",
                   reducers=plan.num_reducers):
        sims = _run_and_assemble(x, plan, fn, m, mesh, executor,
                                 use_kernel=use_kernel, interpret=interpret)
    want = np.zeros((m, m), dtype=bool)
    p = np.asarray(list(pairs), dtype=np.int64).reshape(-1, 2)
    if p.size:
        want[p[:, 0], p[:, 1]] = True
        want[p[:, 1], p[:, 0]] = True
    sims = jnp.where(jnp.asarray(want), sims, 0.0)
    return sims, plan, schema


def x2y_similarity(
    x: jax.Array,                       # (mx, d) X-side feature rows
    y: jax.Array,                       # (my, d) Y-side feature rows
    *,
    q: float,
    wx=None,                            # X-side input sizes; default uniform
    wy=None,                            # Y-side input sizes; default uniform
    schema: Optional[MappingSchema] = None,
    metric: str = "dot",
    mesh=None,
    use_kernel: bool = False,
    pad_slots_to: int = 1,
    executor: str = "bucketed",
    interpret: bool = False,
):
    """Cross similarity of every X row against every Y row through an X2Y
    mapping schema (paper Section 10).

    The planner packs X into bins of size b and Y into bins of q - b; each
    reducer meets one X bin with one Y bin, so every cross pair is covered.
    Execution is rectangular end-to-end: reducers emit (Lx, Ly) cross
    blocks (never a padded square), ``executor='fused'`` runs the
    rectangular gather+Gram kernel with independent row/column gather maps,
    ``executor='sharded'`` LPT-balances the rectangular sub-plans over the
    mesh, and ``executor='streaming'`` serves the (mx, my) matrix as
    patchable state.  Returns (sims (mx, my), plan, schema)."""
    mx, my = x.shape[0], y.shape[0]
    with _obs_span("plan", workload="x2y", mx=mx, my=my):
        if schema is None:
            wx_ = np.full(mx, 1.0) if wx is None else np.asarray(wx, float)
            wy_ = np.full(my, 1.0) if wy is None else np.asarray(wy, float)
            schema = plan_x2y(wx_, wy_, q)
        plan = _x2y_plan_for(
            schema, mx,
            pad_reducers_to=(mesh.devices.size if mesh is not None else 1),
            pad_slots_to=pad_slots_to,
        )
    fn = _block_fn_x2y(metric)
    with _obs_span("execute", workload="x2y", reducers=plan.num_reducers):
        sims = get_executor(executor).run_x2y(
            (x, y), plan, fn, (mx, my), mesh=mesh, use_kernel=use_kernel,
            interpret=interpret)
    return sims, plan, schema


@jax.jit
def _scatter_blocks(out: jax.Array, blocks: jax.Array, idx: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """max-scatter (R, L, L) reducer blocks into the running (m, m) matrix
    (initialized to -inf).  A pair may meet at several reducers; values
    agree, so `max` combine is deterministic.  One program per block
    shape: the streaming delta path and its load-time warm-up call this
    once per sub-plan shape, and op-by-op dispatch would compile every
    primitive of it separately."""
    L = idx.shape[1]
    rows = jnp.repeat(idx[:, :, None], L, axis=2)     # (R, L, L) row ids
    cols = jnp.repeat(idx[:, None, :], L, axis=1)     # (R, L, L) col ids
    valid = (mask[:, :, None] & mask[:, None, :])
    flat_vals = jnp.where(valid, blocks, -jnp.inf).reshape(-1)
    return out.at[rows.reshape(-1), cols.reshape(-1)].max(flat_vals)


@functools.partial(jax.jit, static_argnums=1)
def _finish_pair_matrix(out: jax.Array, m: int) -> jax.Array:
    out = jnp.where(jnp.isneginf(out), 0.0, out)
    return out * (1.0 - jnp.eye(m, dtype=out.dtype))


def assemble_pair_matrix(blocks: jax.Array, plan: ReducerPlan, m: int):
    """Scatter per-reducer (L, L) blocks into the global (m, m) matrix.

    Diagonal is zeroed (no self-pairs in A2A)."""
    out = jnp.full((m, m), -jnp.inf, dtype=blocks.dtype)
    out = _scatter_blocks(out, blocks, jnp.asarray(plan.idx),
                          jnp.asarray(plan.mask))
    return _finish_pair_matrix(out, m)


def assemble_pair_matrix_bucketed(per_bucket, m: int):
    """Scatter per-bucket (Rb, Lb, Lb) blocks into the global (m, m) matrix.

    ``per_bucket`` is ``run_reducers_bucketed(..., combine='buckets')``
    output.  Each bucket scatters at its own width — no block is ever
    padded to the dense L, so the bucketed executor's memory saving holds
    through assembly.  Padding rows (all-masked) contribute nothing."""
    if not per_bucket:
        return jnp.zeros((m, m), dtype=jnp.float32)
    dtype = per_bucket[0][1].dtype
    out = jnp.full((m, m), -jnp.inf, dtype=dtype)
    for b, blocks in per_bucket:
        out = _scatter_blocks(out, blocks, jnp.asarray(b.idx),
                              jnp.asarray(b.mask))
    return _finish_pair_matrix(out, m)
