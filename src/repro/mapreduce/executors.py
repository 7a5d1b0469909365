"""Executor protocol + registry: one pluggable execution subsystem.

The planner decides *where* inputs go; an :class:`Executor` decides *how*
the resulting :class:`~repro.mapreduce.engine.ReducerPlan` runs on the
hardware.  Every executor is a class exposing

  ``run(inputs, plan, reducer_fn, ...)``   — execute the plan;
  ``run_pairs(x, plan, reducer_fn, m, ...)`` — execute + assemble the
        (m, m) pair matrix (the all-pairs / some-pairs applications);
  ``lower(input_shape, plan, ...)``        — AOT-lower for dry-run /
        roofline analysis;
  ``stats()`` / ``reset()``                — instance-scoped dispatch
        telemetry (no module globals to pollute across callers);

registered by name ("dense", "bucketed", "fused", "sharded", "coded",
"streaming")
so applications dispatch through ``get_executor(name)`` instead of
per-module ``if executor == ...`` ladders.  ``make_executor(name)`` returns
a *fresh* instance with its own counters — what ``serve.PairwiseService``
holds so concurrent services never share telemetry.

The registry executors:

``dense``     — one gather padded to the global max slot count
                (differential-test oracle).
``bucketed``  — skew-aware: one vmapped gather+reduce per capacity bucket
                (DESIGN.md "bucketed shuffle execution").
``fused``     — gather+Gram megakernel: the shuffle streams straight into
                the MXU, all buckets in one program (DESIGN.md "fused
                shuffle execution"); non-Gram reducers fall back to
                bucketed.
``sharded``   — shard-balanced multi-device execution (DESIGN.md "sharded
                execution"): ``repro.core.planner.partition_plan`` LPT-
                balances reducers over the mesh's reducer axis, each shard
                runs the fused/bucketed tile pipeline under ``shard_map``,
                and one cross-shard gather assembles the (m, m) matrix.
``coded``     — coded shuffle execution (DESIGN.md "coded shuffle
                execution"; Afrati et al., arXiv:1206.4377): each
                reducer's sub-plan is replicated on ``r`` LPT-chosen
                shards, the output matrix is row-sliced, replica holders
                serve their slice's cells locally, and only the residual
                entries cross shards in one batched all-to-all — assembly
                bytes fall roughly as ``(1 - r/S)`` at the price of
                ``r×`` input shipping.
``streaming`` — delta execution of maintained plans (DESIGN.md "streaming
                maintenance"; ``repro.stream``, registered lazily): only
                the reducers an edit dirtied are recomputed, and the
                cached (m, m) matrix is patched instead of rebuilt.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core.planner import PlanPartition, partition_plan
from repro.obs import EVENTS as _EVENTS
from repro.obs import LEDGER as _LEDGER
from repro.obs import REGISTRY as _REGISTRY_OBS
from repro.obs import _config as _obs_config

from . import engine as _engine
from .engine import (
    ReducerBucket,
    ReducerPlan,
    _as_tables,
    _cache_get,
    _shardings,
    run_reducers,
    run_reducers_bucketed,
    run_reducers_x2y,
    run_reducers_x2y_bucketed,
)

__all__ = [
    "Executor",
    "DenseExecutor",
    "BucketedExecutor",
    "FusedExecutor",
    "ShardedExecutor",
    "CodedExecutor",
    "coded_assembly_model",
    "choose_replication",
    "register_executor",
    "get_executor",
    "make_executor",
    "list_executors",
]


# ---------------------------------------------------------------------------
# protocol + registry
# ---------------------------------------------------------------------------
class Executor:
    """Base executor: run / run_pairs / lower / stats / reset.

    Subclasses set ``name`` and implement the four methods; ``_stats`` is a
    plain dict owned by the instance (pass one in to share counters across
    instances).  Every ``_count`` additionally publishes into the process
    observability registry as ``executor.<key>{executor=<name>}`` — ONE
    labeled series per executor name shared by all its instances, which is
    the aggregate view ``engine.fused_stats()`` reads.  Dispatches also
    reconcile into the comm ledger (``repro.obs.LEDGER``): measured gather
    slots and assembly bytes vs the plan's predicted cost and lower bound
    (DESIGN.md 1j)."""

    name: str = "?"

    def __init__(self, stats: Optional[dict] = None):
        self._stats = stats if stats is not None else self._fresh_stats()

    def _fresh_stats(self) -> dict:
        return {"calls": 0}

    # -- protocol ----------------------------------------------------------
    def run(self, inputs, plan: ReducerPlan, reducer_fn: Callable, *,
            mesh=None, shard_axes=None, **kwargs):
        raise NotImplementedError

    def run_pairs(self, x, plan: ReducerPlan, reducer_fn: Callable, m: int,
                  *, mesh=None, use_kernel: bool = False,
                  interpret: bool = False):
        """Execute the plan and assemble the (m, m) pair matrix."""
        raise NotImplementedError

    def run_x2y(self, tables, plan: ReducerPlan, reducer_fn: Callable,
                shape: tuple[int, int], *, mesh=None,
                use_kernel: bool = False, interpret: bool = False):
        """Execute a rectangular (X2Y) plan and assemble the (mx, my[, c])
        cross output.

        ``tables`` is an (x_table, y_table) pair (or one shared array);
        ``reducer_fn(xblock, xmask, yblock, ymask)`` emits (Lx, Ly[, c])
        cross blocks; ``shape = (mx, my)`` sizes the assembled output.
        The square ``run_pairs`` is the degenerate X == Y case of this
        method."""
        raise NotImplementedError

    def run_block(self, x, sparse, reducer_fn: Callable,
                  i0: int, i1: int, j0: int, j1: int, *, mesh=None,
                  use_kernel: bool = False, interpret: bool = False,
                  pad_reducers_to: int = 1, pad_slots_to: int = 1,
                  max_buckets: int = 8):
        """Serve the ``[i0:i1) x [j0:j1)`` sub-block of the (m, m) pair
        matrix without materializing the whole matrix.

        ``sparse`` is an :class:`~repro.mapreduce.engine.SparsePlan`;
        ``reducer_fn`` is a two-sided (X2Y) reducer.  The default routes
        the block's reducers — selected by
        :func:`~repro.mapreduce.engine.block_subplan` — through this
        executor's own ``run_x2y`` (fused/sharded executors therefore
        reuse their inverse-shuffle srcmap machinery restricted to the
        block), then zeroes global-diagonal cells to match the dense pair
        matrix's convention.  Works for every registry executor; override
        only to specialize the routing."""
        bx, by = i1 - i0, j1 - j0
        sub = _engine.block_subplan(
            sparse, i0, i1, j0, j1, pad_reducers_to=pad_reducers_to,
            pad_slots_to=pad_slots_to, max_buckets=max_buckets)
        if sub is None or bx == 0 or by == 0:
            out = jnp.zeros((max(bx, 0), max(by, 0)), jnp.float32)
        else:
            out = self.run_x2y((x[i0:i1], x[j0:j1]), sub, reducer_fn,
                               (bx, by), mesh=mesh, use_kernel=use_kernel,
                               interpret=interpret)
        lo, hi = max(i0, j0), min(i1, j1)
        if lo < hi:  # the block crosses the global diagonal: zero it
            d = jnp.arange(lo, hi)
            out = out.at[d - i0, d - j0].set(0.0)
        self._count("block_calls")
        return out

    def lower(self, input_shape, plan: ReducerPlan, *, reducer_fn=None,
              metric=None, mesh=None, dtype=jnp.float32, shard_axes=None,
              **kwargs):
        raise NotImplementedError

    def stats(self) -> dict:
        """Snapshot of this instance's dispatch counters."""
        return dict(self._stats)

    def reset(self) -> None:
        """Zero this instance's counters (in place: shared dicts stay
        shared)."""
        for k in self._stats:
            self._stats[k] = 0 if not isinstance(self._stats[k], float) \
                else 0.0

    def _count(self, key: str, by: int = 1) -> None:
        self._stats[key] = self._stats.get(key, 0) + by
        _REGISTRY_OBS.counter(f"executor.{key}", executor=self.name).inc(by)

    def _count_fallback(self, reason: str) -> None:
        """A non-fusable dispatch fell back to the bucketed path: count it
        and emit the (previously silent) lifecycle event."""
        self._count("fallbacks")
        _EVENTS.emit("executor_fallback", executor=self.name, reason=reason)

    def _reconcile(self, plan, workload: str, table, *,
                   measured_slots: int, replication: float = 1.0,
                   assembled_bytes: int = 0, local_bytes: int = 0,
                   residual_bytes: int = 0, meta: Optional[dict] = None
                   ) -> None:
        """Record this execution's comm reconciliation (no-op when obs is
        disabled).  ``table`` supplies the input row size (d, itemsize)."""
        if not _obs_config.ENABLED:
            return
        d, itemsize = _row_bytes(table)
        _LEDGER.record(
            executor=self.name, workload=workload,
            predicted_rows=float(plan.comm_cost),
            lb_rows=plan.lower_bound,
            plan_slots=_plan_valid_slots(plan),
            measured_slots=int(measured_slots), d=d, itemsize=itemsize,
            replication=replication, assembled_bytes=assembled_bytes,
            local_bytes=local_bytes, residual_bytes=residual_bytes,
            meta=meta)


def _row_bytes(table) -> tuple[int, int]:
    """(d, itemsize) of one input row — the ledger's byte scale.  Works on
    numpy/jax arrays; anything shapeless falls back to (0, 4)."""
    shape = getattr(table, "shape", None)
    if not shape or len(shape) < 2:
        return 0, 4
    itemsize = getattr(getattr(table, "dtype", None), "itemsize", 4)
    return int(shape[-1]), int(itemsize)


def _plan_valid_slots(plan) -> int:
    """Valid gather slots the plan books (X + Y sides for rect plans) —
    the ledger's ``plan_slots`` denominator.  Cached on the plan."""
    n = plan.__dict__.get("_obs_plan_slots")
    if n is None:
        n = int(np.asarray(plan.mask).sum())
        if plan.ymask is not None:
            n += int(np.asarray(plan.ymask).sum())
        object.__setattr__(plan, "_obs_plan_slots", n)
    return n


def _bucket_valid_slots(plan) -> int:
    """Valid gather slots the bucketed/fused program materializes (sum of
    per-bucket masks; padding rows are all-False, so this equals the dense
    mask sum — the 1.0-ratio invariant tests pin).  Cached on the plan."""
    n = plan.__dict__.get("_obs_bucket_slots")
    if n is None:
        if plan.buckets:
            n = 0
            for b in plan.buckets:
                n += int(np.asarray(b.mask).sum())
                if b.ymask is not None:
                    n += int(np.asarray(b.ymask).sum())
        else:
            n = _plan_valid_slots(plan)
        object.__setattr__(plan, "_obs_bucket_slots", n)
    return n


def _group_valid_slots(plan, cache_key, groups, count_y: bool) -> int:
    """Valid gather slots in stacked shard groups (the sharded/coded
    executors' measured side).  5-tuple groups carry (xi, xm, yi, ym,
    rows); ``count_y=False`` for the square coded path, where xm and ym
    are the same gather and copies must be counted once.  Cached on the
    plan per (shards, replication, rect) key."""
    cache = plan.__dict__.get("_obs_group_slots")
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_obs_group_slots", cache)
    n = cache.get(cache_key)
    if n is None:
        n = 0
        for grp in groups:
            if len(grp) >= 5:
                n += int(np.asarray(grp[1]).sum())
                if count_y:
                    n += int(np.asarray(grp[3]).sum())
            else:                       # (idx, mask, rows) square stack
                n += int(np.asarray(grp[1]).sum())
        cache[cache_key] = n
    return n


def _group_gram_entries(plan, cache_key, groups) -> int:
    """Gram entries the stacked shard groups produce — what the sharded
    all-gather assembly ships.  Cached on the plan (same cache as the slot
    sums, disjoint keys)."""
    cache = plan.__dict__.get("_obs_group_slots")
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_obs_group_slots", cache)
    n = cache.get(cache_key)
    if n is None:
        n = 0
        for grp in groups:
            if len(grp) >= 5:            # rect: (xi, xm, yi, ym, rows)
                xi, yi = grp[0], grp[2]
                n += int(np.prod(xi.shape[:2])) * xi.shape[2] * yi.shape[2]
            else:                        # square: (idx, mask, rows)
                i = grp[0]
                n += int(np.prod(i.shape[:2])) * i.shape[2] ** 2
        cache[cache_key] = n
    return n


_REGISTRY: dict[str, Executor] = {}
_CLASSES: dict[str, type] = {}


def register_executor(executor: Executor) -> Executor:
    """Register ``executor`` as the default instance for its ``name``
    (latest registration wins — extension point for custom executors)."""
    _REGISTRY[executor.name] = executor
    _CLASSES[executor.name] = type(executor)
    return executor


def get_executor(name) -> Executor:
    """Default registry instance by name; Executor instances pass through
    (so application entry points accept either).  Unknown names raise
    ``ValueError`` — the registry is the single dispatch point."""
    if isinstance(name, Executor):
        return name
    ex = _REGISTRY.get(name)
    if ex is None and name == "streaming":
        # the streaming subsystem registers its executor on import; loaded
        # lazily so the engine never pays for it unless it is used
        import repro.stream  # noqa: F401
        ex = _REGISTRY.get(name)
    if ex is None:
        raise ValueError(
            f"unknown executor {name!r} (registered: {list_executors()})")
    return ex


def make_executor(name: str, **kwargs) -> Executor:
    """Fresh instance (own stats) of the executor registered under
    ``name``."""
    get_executor(name)                       # raise on unknown names
    return _CLASSES[name](**kwargs)


def list_executors() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# dense + bucketed: wrappers over the engine substrate
# ---------------------------------------------------------------------------
class DenseExecutor(Executor):
    """One gather padded to the global max slot count (the oracle path)."""

    name = "dense"

    def run(self, inputs, plan, reducer_fn, *, mesh=None, shard_axes=None,
            **kwargs):
        self._count("calls")
        return run_reducers(inputs, plan, reducer_fn, mesh=mesh,
                            shard_axes=shard_axes, **kwargs)

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        from .allpairs import assemble_pair_matrix
        self._count("calls")
        self._reconcile(plan, "pairs", x,
                        measured_slots=_plan_valid_slots(plan))
        blocks = run_reducers(x, plan, reducer_fn, mesh=mesh)  # (R, L, L)
        return assemble_pair_matrix(blocks, plan, m)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False):
        from .allpairs import assemble_x2y_matrix_bucketed
        self._count("calls")
        self._reconcile(plan, "x2y", _as_tables(tables)[0],
                        measured_slots=_plan_valid_slots(plan))
        blocks = run_reducers_x2y(tables, plan, reducer_fn, mesh=mesh)
        # the plan's dense idx/mask/yidx/ymask rows are bucket-shaped, so
        # the whole plan assembles as a single "bucket"
        return assemble_x2y_matrix_bucketed([(plan, blocks)], shape)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None, **kwargs):
        from .engine import lower_reducers
        return lower_reducers(input_shape, plan, reducer_fn, mesh,
                              dtype=dtype, shard_axes=shard_axes)


class BucketedExecutor(Executor):
    """Skew-aware: one vmapped gather+reduce per capacity bucket."""

    name = "bucketed"

    def run(self, inputs, plan, reducer_fn, *, mesh=None, shard_axes=None,
            combine: str = "dense", **kwargs):
        self._count("calls")
        return run_reducers_bucketed(inputs, plan, reducer_fn, mesh=mesh,
                                     shard_axes=shard_axes, combine=combine,
                                     **kwargs)

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        from .allpairs import assemble_pair_matrix_bucketed
        self._count("calls")
        self._reconcile(plan, "pairs", x,
                        measured_slots=_bucket_valid_slots(plan))
        per_bucket = run_reducers_bucketed(x, plan, reducer_fn, mesh=mesh,
                                           combine="buckets")
        return assemble_pair_matrix_bucketed(per_bucket, m)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False):
        from .allpairs import assemble_x2y_matrix_bucketed
        self._count("calls")
        self._reconcile(plan, "x2y", _as_tables(tables)[0],
                        measured_slots=_bucket_valid_slots(plan))
        per_bucket = run_reducers_x2y_bucketed(tables, plan, reducer_fn,
                                               mesh=mesh, combine="buckets")
        return assemble_x2y_matrix_bucketed(per_bucket, shape)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None, **kwargs):
        """Protocol deviation (documented): the bucketed path is one XLA
        program PER capacity bucket, so this returns
        ``[(bucket, Lowered), ...]`` — not a single ``Lowered`` like the
        other executors.  Roofline consumers sum the per-bucket terms
        (``dryrun_engine.analyze_bucketed`` via ``combine_hlo_stats``)."""
        from .engine import lower_reducers_bucketed
        return lower_reducers_bucketed(input_shape, plan, reducer_fn, mesh,
                                       dtype=dtype, shard_axes=shard_axes)


# ---------------------------------------------------------------------------
# fused (gather+Gram megakernel) executor
# ---------------------------------------------------------------------------
def _finish_fused_blocks(g, mask, metric: str):
    """Metric post-processing of a masked per-reducer Gram stack.

    Mirrors ``allpairs.block_similarity`` exactly: norms are the Gram
    diagonal (masked rows were zeroed at gather time, so their norms are 0),
    invalid pairs -> 0.
    """
    if metric != "dot":
        n2 = jnp.diagonal(g, axis1=1, axis2=2)            # (Rb, Lb)
        if metric == "l2":
            g = n2[:, :, None] + n2[:, None, :] - 2.0 * g
        elif metric == "cosine":
            nrm = jnp.sqrt(n2 + 1e-9)
            g = g / (nrm[:, :, None] * nrm[:, None, :])
        else:
            raise ValueError(metric)
    valid = mask[:, :, None] & mask[:, None, :]
    return jnp.where(valid, g, 0.0)


def _finish_rect_blocks(g, xidx, xmask, yidx, ymask, n2x, n2y, metric: str):
    """Metric post-processing of a masked rectangular cross-Gram stack.

    Mirrors ``allpairs.block_similarity_x2y`` exactly.  Cross blocks carry
    no Gram diagonal, so per-row squared norms are gathered from the
    table-level vectors ``n2x``/``n2y`` (masked slots -> 0, matching the
    zero-masked gathers of the reference path); invalid pairs -> 0.
    """
    if metric != "dot":
        gx = jnp.where(xmask, jnp.take(n2x, xidx, axis=0), 0.0)  # (Rb, Lx)
        gy = jnp.where(ymask, jnp.take(n2y, yidx, axis=0), 0.0)  # (Rb, Ly)
        if metric == "l2":
            g = gx[:, :, None] + gy[:, None, :] - 2.0 * g
        elif metric == "cosine":
            g = g / (jnp.sqrt(gx + 1e-9)[:, :, None]
                     * jnp.sqrt(gy + 1e-9)[:, None, :])
        else:
            raise ValueError(metric)
    valid = xmask[:, :, None] & ymask[:, None, :]
    return jnp.where(valid, g, 0.0)


def _scatter_rows(bucket: ReducerBucket, R: int) -> np.ndarray:
    """Bucket rows for drop-style scatter: padding rows (-1) -> row R."""
    return np.where(bucket.rows >= 0, bucket.rows, R).astype(np.int32)


def _make_fused_jitted(metric, combine, mesh, shard_axes, use_kernel,
                       interpret, bl, postprocess):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram,
        fused_gather_gram_streamed,
    )

    def run(x, buckets, pp_arg, R, L):
        per_bucket = []
        for idx, msk, rows in buckets:
            if use_kernel:
                g = fused_gather_gram(x, idx, msk, bl=bl,
                                      interpret=interpret)
            else:
                g = fused_gather_gram_streamed(x, idx, msk, bl=bl)
            mb = msk.astype(bool)
            per_bucket.append(((idx, mb, rows),
                               _finish_fused_blocks(g, mb, metric)))
        if postprocess is not None:
            return postprocess(per_bucket, pp_arg)
        if combine == "buckets":
            return [g for _, g in per_bucket]
        # dense combine: scatter bucket blocks (padded to the dense width)
        # into original reducer order; padding rows land in the extra row R
        acc = jnp.zeros((R + 1, L, L), jnp.float32)
        for (idx, msk, rows), g in per_bucket:
            Lb = g.shape[1]
            gp = jnp.pad(g, ((0, 0), (0, L - Lb), (0, L - Lb)))
            acc = acc.at[rows].set(gp)
        return acc[:R]

    if mesh is None:
        return jax.jit(run, static_argnums=(3, 4))
    red_sharding, rep = _shardings(mesh, shard_axes)
    return jax.jit(run, in_shardings=(rep, red_sharding, rep),
                   static_argnums=(3, 4))


def _make_fused_rect_jitted(metric, mesh, shard_axes, use_kernel,
                            interpret, bl):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram_rect,
        fused_gather_gram_rect_streamed,
    )

    def run(xt, yt, buckets, srcmap):
        n2x = jnp.sum(xt.astype(jnp.float32) ** 2, axis=-1)   # (mx,)
        n2y = jnp.sum(yt.astype(jnp.float32) ** 2, axis=-1)   # (my,)
        vals = [jnp.zeros((1,), jnp.float32)]
        for xidx, xmsk, yidx, ymsk in buckets:
            if use_kernel:
                g = fused_gather_gram_rect(xt, yt, xidx, xmsk, yidx, ymsk,
                                           bl=bl, interpret=interpret)
            else:
                g = fused_gather_gram_rect_streamed(xt, yt, xidx, xmsk,
                                                    yidx, ymsk, bl=bl)
            g = _finish_rect_blocks(g, xidx, xmsk.astype(bool),
                                    yidx, ymsk.astype(bool), n2x, n2y,
                                    metric)
            vals.append(g.reshape(-1))
        # rectangular inverse shuffle: ONE assembly gather through the
        # host-precomputed source map (slot 0 -> 0.0 for uncovered cells)
        return jnp.take(jnp.concatenate(vals), srcmap, axis=0)

    if mesh is None:
        return jax.jit(run)
    red_sharding, rep = _shardings(mesh, shard_axes)
    return jax.jit(run, in_shardings=(rep, rep, red_sharding, rep))


class FusedExecutor(Executor):
    """Fused shuffle execution: the gathered block stays out of HBM.

    Per capacity bucket, the plan's ``idx``/``mask`` rows drive the fused
    gather+Gram Pallas kernel (``use_kernel=True``; scalar-prefetched rows,
    table rows DMA'd HBM->VMEM, fp32 MXU accumulation — gathered rows live
    only in VMEM scratch) or its jnp twin with the same tile dataflow
    (``use_kernel=False``, the non-TPU default) — the twin still gathers
    ``(Rb, bl, d)`` tiles as XLA intermediates, but a multi-tile bucket
    never materializes its full ``(Rb, Lb, d)`` block and no bucket ever
    materializes the dense ``(R, L, d)`` one.  *All* buckets execute
    inside ONE jitted program, so a request pays a single dispatch instead
    of one per bucket.

    Only Gram-block reducers are fusable: ``reducer_fn`` must carry a
    ``fused_metric`` attribute (see ``allpairs._block_fn``).  Any other
    reducer — and bucketless plans — falls back to the bucketed executor
    with identical outputs; fallbacks are counted in this instance's
    ``stats()``.
    """

    name = "fused"

    def _fresh_stats(self) -> dict:
        return {"calls": 0, "kernel": 0, "streamed": 0, "fallbacks": 0}

    def run(self, inputs, plan, reducer_fn, *, mesh=None, shard_axes=None,
            combine: str = "dense", postprocess: Optional[Callable] = None,
            postprocess_arg=None, use_kernel: Optional[bool] = None,
            interpret: bool = False, bl: int = 128):
        """``combine`` follows the bucketed executor ('dense' / 'buckets');
        ``postprocess(per_bucket, postprocess_arg)`` — a *stable* function
        object, traced into the same program — lets applications fuse their
        assembly step too (allpairs passes its inverse-shuffle gather map).
        ``use_kernel=None`` auto-selects: Pallas on TPU, streamed jnp
        elsewhere."""
        assert combine in ("dense", "buckets"), combine
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or not plan.buckets:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "no_buckets")
            out = run_reducers_bucketed(
                inputs, plan, reducer_fn, mesh=mesh, shard_axes=shard_axes,
                combine="buckets" if postprocess is not None else combine)
            if postprocess is not None:
                # honor the postprocess contract on the fallback path (eager)
                per_bucket = [((jnp.asarray(b.idx), jnp.asarray(b.mask),
                                jnp.asarray(_scatter_rows(b, plan.R))),
                               blocks)
                              for b, blocks in out]
                return postprocess(per_bucket, postprocess_arg)
            return out

        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self._count("kernel" if use_kernel else "streamed")
        shard_axes = tuple(shard_axes) if shard_axes is not None else None
        fn = _cache_get(
            ("fused", metric, combine, postprocess, mesh, shard_axes,
             bool(use_kernel), bool(interpret), bl),
            lambda: _make_fused_jitted(metric, combine, mesh, shard_axes,
                                       use_kernel, interpret, bl,
                                       postprocess))
        buckets = tuple(
            (jnp.asarray(b.idx), jnp.asarray(b.mask),
             jnp.asarray(_scatter_rows(b, plan.R)))
            for b in plan.buckets)
        return fn(inputs, buckets, postprocess_arg, plan.R, plan.L)

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        from .allpairs import _assemble_from_srcmap, _pair_source_map
        # reconcile here, not in run(): the delegation below must not
        # double-record the request
        self._reconcile(plan, "pairs", x,
                        measured_slots=_bucket_valid_slots(plan))
        srcmap = jnp.asarray(_pair_source_map(plan, m))
        return self.run(
            x, plan, reducer_fn, mesh=mesh,
            postprocess=_assemble_from_srcmap, postprocess_arg=srcmap,
            use_kernel=(True if use_kernel else None), interpret=interpret)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False, bl: int = 128):
        """Rectangular fused path: per rect bucket, independent X/Y gather
        maps drive the rectangular gather+Gram kernel (streamed jnp twin
        off-TPU), and ONE inverse-shuffle gather assembles the (mx, my)
        matrix.  Non-Gram reducers fall back to the rect-bucketed path
        (identical outputs; counted)."""
        from .allpairs import (
            _pair_source_map_rect,
            assemble_x2y_matrix_bucketed,
        )
        self._count("calls")
        self._reconcile(plan, "x2y", _as_tables(tables)[0],
                        measured_slots=_bucket_valid_slots(plan))
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or not plan.buckets:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "no_buckets")
            per_bucket = run_reducers_x2y_bucketed(
                tables, plan, reducer_fn, mesh=mesh, combine="buckets")
            return assemble_x2y_matrix_bucketed(per_bucket, shape)
        uk = True if use_kernel else jax.default_backend() == "tpu"
        self._count("kernel" if uk else "streamed")
        srcmap = jnp.asarray(_pair_source_map_rect(plan, *shape))
        fn = _cache_get(
            ("fused-x2y", metric, mesh, None, bool(uk), bool(interpret),
             bl),
            lambda: _make_fused_rect_jitted(metric, mesh, None, uk,
                                            interpret, bl))
        buckets = tuple(
            (jnp.asarray(b.idx), jnp.asarray(b.mask),
             jnp.asarray(b.yidx), jnp.asarray(b.ymask))
            for b in plan.buckets)
        xt, yt = _as_tables(tables)
        return fn(xt, yt, buckets, srcmap)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None,
              combine: str = "buckets", use_kernel: bool = False,
              bl: int = 128, **kwargs):
        """Lower the single all-bucket program (no execution).  Defaults to
        the streamed (jnp) lowering so the dry-run works on any backend; on
        this path the program is directly comparable with the bucketed
        lowering — same math, one program, no materialized gather for
        multi-tile widths.  Returns one ``Lowered``."""
        if metric is None:
            metric = getattr(reducer_fn, "fused_metric", None)
        assert metric is not None, "fused lowering needs a Gram metric"
        shard_axes = tuple(shard_axes) if shard_axes is not None else None
        fn = _make_fused_jitted(metric, combine, mesh, shard_axes,
                                use_kernel, False, bl, None)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        buckets = tuple(
            (jax.ShapeDtypeStruct(b.idx.shape, jnp.int32),
             jax.ShapeDtypeStruct(b.mask.shape, jnp.bool_),
             jax.ShapeDtypeStruct((b.R,), jnp.int32))
            for b in plan.buckets)
        return fn.lower(x, buckets, None, plan.R, plan.L)


# ---------------------------------------------------------------------------
# sharded (LPT-balanced multi-device) executor
# ---------------------------------------------------------------------------
def _shard_mesh(mesh, shard_axes):
    """(mesh, axes, num_shards): the mesh + axis names the sharded executor
    partitions over.  ``mesh=None`` builds a 1-D mesh over all local
    devices (the CPU test path under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    if mesh is None:
        mesh = make_mesh((len(jax.devices()),), ("shard",))
        axes = ("shard",)
    else:
        axes = tuple(shard_axes) if shard_axes else tuple(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    num_shards = int(np.prod([sizes[a] for a in axes]))
    return mesh, axes, num_shards


def _put(a, mesh, spec) -> jax.Array:
    """Place a host array straight into its mesh sharding.  ``jnp.asarray``
    would stage the whole array on the default device first: the first
    device would hold every shard's maps at once."""
    return jax.device_put(a, jax.sharding.NamedSharding(mesh, spec))


def _stacked_groups(plan: ReducerPlan, part: PlanPartition,
                    rows_by_shard=None):
    """Stack the partition into uniform per-width device arrays.

    For every execution width ``w`` appearing in the partition, build
    ``idx (S, Rw, w)`` / ``mask (S, Rw, w)`` / ``rows (S, Rw)`` where
    ``Rw = max_s |shard s's width-w reducers|`` — each shard's rows padded
    (masked, rows -> plan.R) to the common count so ``shard_map`` can split
    the leading axis across the mesh.  LPT balances total work, so the
    cross-shard padding this stacking adds is small exactly when the
    balance factor is small.  Returns ``[(idx, mask, rows), ...]`` with
    widths ascending (numpy; the executor converts once per plan).

    ``rows_by_shard`` overrides the per-shard row sets (default: the
    partition's primary ``shard_rows``) — the coded executor passes
    ``part.replica_rows`` so every shard's stack holds all of its
    replicas, not just its primary assignment.
    """
    S = part.num_shards
    R0 = plan.num_reducers
    widths = part.widths
    if rows_by_shard is None:
        rows_by_shard = part.shard_rows
    # per-global-row source arrays at the row's execution width
    if plan.buckets:
        src_idx = {}
        src_mask = {}
        for b in plan.buckets:
            rows = np.asarray(b.rows)
            for i, g in enumerate(rows):
                if 0 <= g < R0:
                    src_idx[int(g)] = np.asarray(b.idx)[i]
                    src_mask[int(g)] = np.asarray(b.mask)[i]
    else:
        src_idx = {r: np.asarray(plan.idx)[r] for r in range(R0)}
        src_mask = {r: np.asarray(plan.mask)[r] for r in range(R0)}

    groups = []
    for w in sorted(set(int(x) for x in widths)) if R0 else []:
        per_shard = [rows[widths[rows] == w] for rows in rows_by_shard]
        Rw = max((len(p) for p in per_shard), default=0)
        if Rw == 0:
            continue
        idx = np.zeros((S, Rw, w), np.int32)
        mask = np.zeros((S, Rw, w), bool)
        rows_out = np.full((S, Rw), plan.R, np.int32)   # padding -> row R
        for s, p in enumerate(per_shard):
            for k, g in enumerate(p):
                idx[s, k, :] = src_idx[int(g)][:w]
                mask[s, k, :] = src_mask[int(g)][:w]
                rows_out[s, k] = int(g)
        groups.append((idx, mask, rows_out))
    return groups


_SRCMAP_CHUNK = 1 << 24          # block cells expanded at once on the host
# Per-shard bodies call the Pallas kernels, whose outputs carry no
# varying-mesh-axis type: shard_map's vma check cannot trace them
_CHECK_VMA = False


def _fill_source_map(srcmap: np.ndarray, blocks) -> None:
    """Point ``srcmap[row, col]`` at every valid cell's position in the
    concatenation ``[0.0, blocks_0.ravel(), blocks_1.ravel(), ...]``.

    ``blocks`` yields ``(xidx (R, Lx), xmask, yidx (R, Ly), ymask)`` per
    stack of reducer blocks (a square plan passes its idx/mask twice).
    Reducers are expanded a chunk at a time, so host memory stays bounded
    however many cells the plan has; a cell covered twice keeps its last
    writer, exactly as one whole-stack assignment would."""
    base = 1
    for xidx, xmask, yidx, ymask in blocks:
        R, Lx = xidx.shape
        cells = Lx * yidx.shape[1]
        if base + R * cells - 1 > np.iinfo(np.int32).max:
            raise ValueError("plan has more block cells than an int32 "
                             "source map can address")
        step = max(1, _SRCMAP_CHUNK // max(cells, 1))
        for r0 in range(0, R, step):
            sl = slice(r0, r0 + step)
            valid = xmask[sl, :, None] & ymask[sl, None, :]
            rows = np.broadcast_to(xidx[sl, :, None], valid.shape)[valid]
            cols = np.broadcast_to(yidx[sl, None, :], valid.shape)[valid]
            srcmap[rows, cols] = base + r0 * cells + np.flatnonzero(valid)
        base += R * cells


def _sharded_srcmap(groups, m: int) -> np.ndarray:
    """Inverse-shuffle map for the cross-shard assembly gather: (m, m)
    int32 positions into ``[0.0, group_0.ravel(), group_1.ravel(), ...]``
    of the stacked per-width Gram outputs (each ``(S, Rw, w, w)``).
    Uncovered cells and the diagonal point at slot 0 (-> 0.0)."""
    srcmap = np.zeros((m, m), np.int32)
    flat = [(idx.reshape(-1, idx.shape[2]), mask.reshape(-1, idx.shape[2]))
            for idx, mask, _rows in groups]
    _fill_source_map(srcmap, ((i, k, i, k) for i, k in flat))
    np.fill_diagonal(srcmap, 0)
    return srcmap


def _stacked_rect_groups(plan: ReducerPlan, part: PlanPartition,
                         rows_by_shard=None):
    """Rectangular analogue of :func:`_stacked_groups`: groups keyed by the
    (wx, wy) execution-width *pair*, each stacked into
    ``xidx/xmask (S, Rw, wx)``, ``yidx/ymask (S, Rw, wy)``, ``rows (S, Rw)``
    device arrays (padding rows masked, rows -> plan.R).  ``rows_by_shard``
    overrides the per-shard row sets as in :func:`_stacked_groups`."""
    S = part.num_shards
    R0 = plan.num_reducers
    widths = part.widths
    ywidths = part.ywidths
    if rows_by_shard is None:
        rows_by_shard = part.shard_rows
    src = {}
    if plan.buckets:
        for b in plan.buckets:
            rows = np.asarray(b.rows)
            for i, g in enumerate(rows):
                if 0 <= g < R0:
                    src[int(g)] = (np.asarray(b.idx)[i],
                                   np.asarray(b.mask)[i],
                                   np.asarray(b.yidx)[i],
                                   np.asarray(b.ymask)[i])
    else:
        for r in range(R0):
            src[r] = (np.asarray(plan.idx)[r], np.asarray(plan.mask)[r],
                      np.asarray(plan.yidx)[r], np.asarray(plan.ymask)[r])

    keys = sorted({(int(widths[r]), int(ywidths[r]))
                   for r in range(R0)}) if R0 else []
    groups = []
    for wx, wy in keys:
        per_shard = [rows[(widths[rows] == wx) & (ywidths[rows] == wy)]
                     for rows in rows_by_shard]
        Rw = max((len(p) for p in per_shard), default=0)
        if Rw == 0:
            continue
        xidx = np.zeros((S, Rw, wx), np.int32)
        xmask = np.zeros((S, Rw, wx), bool)
        yidx = np.zeros((S, Rw, wy), np.int32)
        ymask = np.zeros((S, Rw, wy), bool)
        rows_out = np.full((S, Rw), plan.R, np.int32)   # padding -> row R
        for s, p in enumerate(per_shard):
            for k, g in enumerate(p):
                xi, xm, yi, ym = src[int(g)]
                xidx[s, k, :] = xi[:wx]
                xmask[s, k, :] = xm[:wx]
                yidx[s, k, :] = yi[:wy]
                ymask[s, k, :] = ym[:wy]
                rows_out[s, k] = int(g)
        groups.append((xidx, xmask, yidx, ymask, rows_out))
    return groups


def _sharded_rect_srcmap(groups, shape: tuple[int, int]) -> np.ndarray:
    """Rectangular cross-shard assembly map: (mx, my) int32 positions into
    ``[0.0, group_0.ravel(), ...]`` of the stacked per-(wx, wy) cross-Gram
    outputs (each ``(S, Rw, wx, wy)``).  No diagonal to zero — an (x, y)
    pair is never a self-pair; uncovered cells point at slot 0."""
    srcmap = np.zeros(shape, np.int32)
    _fill_source_map(srcmap, (
        tuple(a.reshape(-1, a.shape[2]) for a in (xi, xm, yi, ym))
        for xi, xm, yi, ym, _rows in groups))
    return srcmap


def _make_sharded_jitted(metric, combine, mesh, axes, use_kernel,
                         interpret, bl):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram,
        fused_gather_gram_streamed,
    )

    P = jax.sharding.PartitionSpec

    def per_shard_fn(x, idx, msk):
        # local shapes: x (m, d) replicated, idx/msk (1, Rw, w)
        if use_kernel:
            g = fused_gather_gram(x, idx[0], msk[0], bl=bl,
                                  interpret=interpret)
        else:
            g = fused_gather_gram_streamed(x, idx[0], msk[0], bl=bl)
        mb = msk[0].astype(bool)
        return _finish_fused_blocks(g, mb, metric)[None]   # (1, Rw, w, w)

    def run(x, groups, srcmap, R, L):
        outs = []
        for idx, msk, rows in groups:
            g = jax.shard_map(per_shard_fn, mesh=mesh,
                              in_specs=(P(), P(axes), P(axes)),
                              out_specs=P(axes),
                              check_vma=_CHECK_VMA)(x, idx, msk)
            outs.append((rows, g))
        if combine == "pairs":
            # ONE cross-shard assembly gather: concatenate the sharded
            # Gram stacks and gather the replicated (m, m) matrix through
            # the host-precomputed source map (XLA inserts the all-gather
            # here — the only cross-shard communication in the program)
            vals = [jnp.zeros((1,), jnp.float32)]
            vals += [g.reshape(-1) for _, g in outs]
            return jnp.take(jnp.concatenate(vals), srcmap, axis=0)
        # dense combine: scatter shard blocks (padded to the dense width)
        # back into original reducer order; padding rows drop into row R
        acc = jnp.zeros((R + 1, L, L), jnp.float32)
        for rows, g in outs:
            w = g.shape[-1]
            gp = jnp.pad(g, ((0, 0), (0, 0), (0, L - w), (0, L - w)))
            acc = acc.at[rows.reshape(-1)].set(gp.reshape(-1, L, L))
        return acc[:R]

    return jax.jit(run, static_argnums=(3, 4))


def _make_sharded_rect_jitted(metric, mesh, axes, use_kernel, interpret,
                              bl):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram_rect,
        fused_gather_gram_rect_streamed,
    )

    P = jax.sharding.PartitionSpec

    def per_shard_fn(xt, yt, n2x, n2y, xidx, xmsk, yidx, ymsk):
        # local shapes: xt/yt/n2x/n2y replicated, idx/msk (1, Rw, w)
        if use_kernel:
            g = fused_gather_gram_rect(xt, yt, xidx[0], xmsk[0], yidx[0],
                                       ymsk[0], bl=bl, interpret=interpret)
        else:
            g = fused_gather_gram_rect_streamed(xt, yt, xidx[0], xmsk[0],
                                                yidx[0], ymsk[0], bl=bl)
        return _finish_rect_blocks(g, xidx[0], xmsk[0].astype(bool),
                                   yidx[0], ymsk[0].astype(bool),
                                   n2x, n2y, metric)[None]  # (1, Rw, wx, wy)

    def run(xt, yt, groups, srcmap):
        n2x = jnp.sum(xt.astype(jnp.float32) ** 2, axis=-1)
        n2y = jnp.sum(yt.astype(jnp.float32) ** 2, axis=-1)
        vals = [jnp.zeros((1,), jnp.float32)]
        for xidx, xmsk, yidx, ymsk, _rows in groups:
            g = jax.shard_map(per_shard_fn, mesh=mesh,
                              in_specs=(P(), P(), P(), P(), P(axes),
                                        P(axes), P(axes), P(axes)),
                              out_specs=P(axes), check_vma=_CHECK_VMA)(
                xt, yt, n2x, n2y, xidx, xmsk, yidx, ymsk)
            vals.append(g.reshape(-1))
        # ONE cross-shard assembly gather of the (mx, my) matrix — the
        # only cross-shard communication in the program
        return jnp.take(jnp.concatenate(vals), srcmap, axis=0)

    return jax.jit(run)


class ShardedExecutor(Executor):
    """Shard-balanced multi-device execution of a reducer plan.

    ``repro.core.planner.partition_plan`` LPT-balances the plan's reducers
    (weighted by per-reducer gather+FLOP work at their capacity-bucket
    width) into one compact sub-plan per shard of the mesh's reducer axis.
    The sub-plans are stacked into uniform per-width arrays and executed
    under ``shard_map``: every device runs the fused gather+Gram tile
    pipeline (streamed jnp twin off-TPU) over exactly its LPT-assigned
    reducers — instead of XLA's blind even row-split of a skew-ordered
    plan — and the only cross-shard communication is the single assembly
    gather of the (m, m) pair matrix at the end (``run_pairs``) or the
    dense scatter (``run``).

    ``mesh=None`` builds a 1-D mesh over all local devices — on CPU, run
    tests/benches under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    to get an 8-shard mesh.  Like the fused executor, only Gram-block
    reducers (``fused_metric`` tag) take the sharded path; anything else
    falls back to the bucketed executor (counted in ``stats()``).
    """

    name = "sharded"

    def _fresh_stats(self) -> dict:
        return {"calls": 0, "sharded": 0, "fallbacks": 0, "num_shards": 0,
                "balance_factor": 0.0}

    # -- partition plumbing (host-side static artifacts, cached on plan) --
    def partition(self, plan: ReducerPlan,
                  num_shards: int) -> PlanPartition:
        """The plan's LPT partition for ``num_shards`` (cached on the plan
        like the index matrix: a static artifact reused across waves)."""
        cache = plan.__dict__.get("_shard_partition_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_shard_partition_cache", cache)
        part = cache.get(num_shards)
        if part is None:
            part = partition_plan(plan, num_shards)
            cache[num_shards] = part
        return part

    def _groups_for(self, plan, part):
        cache = plan.__dict__.get("_shard_groups_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_shard_groups_cache", cache)
        groups = cache.get(part.num_shards)
        if groups is None:
            groups = _stacked_groups(plan, part)
            cache[part.num_shards] = groups
        return groups

    def _srcmap_for(self, plan, groups, num_shards: int, m: int):
        cache = plan.__dict__.get("_shard_srcmap_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_shard_srcmap_cache", cache)
        srcmap = cache.get((num_shards, m))
        if srcmap is None:
            srcmap = _sharded_srcmap(groups, m)
            cache[(num_shards, m)] = srcmap
        return srcmap

    def _rect_groups_for(self, plan, part):
        cache = plan.__dict__.get("_shard_rect_groups_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_shard_rect_groups_cache", cache)
        groups = cache.get(part.num_shards)
        if groups is None:
            groups = _stacked_rect_groups(plan, part)
            cache[part.num_shards] = groups
        return groups

    def _rect_srcmap_for(self, plan, groups, num_shards: int, shape):
        cache = plan.__dict__.get("_shard_rect_srcmap_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_shard_rect_srcmap_cache", cache)
        srcmap = cache.get((num_shards, shape))
        if srcmap is None:
            srcmap = _sharded_rect_srcmap(groups, shape)
            cache[(num_shards, shape)] = srcmap
        return srcmap

    def _note(self, part: PlanPartition) -> None:
        self._stats["num_shards"] = part.num_shards
        self._stats["balance_factor"] = float(part.balance_factor)
        _REGISTRY_OBS.gauge("executor.num_shards",
                            executor=self.name).set(part.num_shards)
        _REGISTRY_OBS.gauge("executor.balance_factor",
                            executor=self.name).set(part.balance_factor)

    def _dispatch(self, x, plan, metric, combine, srcmap_m, mesh,
                  shard_axes, use_kernel, interpret, bl,
                  workload: str = "reduce"):
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition(plan, S)
        groups = self._groups_for(plan, part)
        self._count("sharded")
        self._note(part)
        if _obs_config.ENABLED:
            assembled = 0
            meta = {"num_shards": S, "combine": combine}
            if combine == "pairs":
                _d, isz = _row_bytes(x)
                per_shard = int(_group_gram_entries(
                    plan, ("gram", S), groups) * isz * (S - 1) / S)
                assembled = S * per_shard
                meta["assembly_bytes_per_shard"] = per_shard
            self._reconcile(
                plan, workload, x,
                measured_slots=_group_valid_slots(
                    plan, ("sharded", S), groups, count_y=False),
                assembled_bytes=assembled, meta=meta)
        P = jax.sharding.PartitionSpec
        if combine == "pairs":
            srcmap = _put(self._srcmap_for(plan, groups, S, srcmap_m), mesh,
                          P())
        else:
            srcmap = jnp.zeros((1,), jnp.int32)      # unused placeholder
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        fn = _cache_get(
            ("sharded", metric, combine, mesh, axes, bool(use_kernel),
             bool(interpret), bl),
            lambda: _make_sharded_jitted(metric, combine, mesh, axes,
                                         use_kernel, interpret, bl))
        jgroups = tuple(tuple(_put(a, mesh, P(axes)) for a in g)
                        for g in groups)
        return fn(x, jgroups, srcmap, plan.R, plan.L)

    # -- protocol ----------------------------------------------------------
    def run(self, inputs, plan, reducer_fn, *, mesh=None, shard_axes=None,
            combine: str = "dense", use_kernel: Optional[bool] = None,
            interpret: bool = False, bl: int = 128, **kwargs):
        """Dense-combine semantics match ``run_reducers`` for Gram-block
        reducers; non-Gram reducers fall back to the bucketed executor
        (identical outputs — sharding is a pure execution-plan change)."""
        assert combine == "dense", combine
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "empty_plan")
            return run_reducers_bucketed(inputs, plan, reducer_fn,
                                         mesh=mesh, combine=combine)
        return self._dispatch(inputs, plan, metric, "dense", None, mesh,
                              shard_axes, use_kernel, interpret, bl)

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        from .allpairs import assemble_pair_matrix_bucketed
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "empty_plan")
            self._reconcile(plan, "pairs", x,
                            measured_slots=_bucket_valid_slots(plan))
            per_bucket = run_reducers_bucketed(x, plan, reducer_fn,
                                               mesh=mesh, combine="buckets")
            return assemble_pair_matrix_bucketed(per_bucket, m)
        return self._dispatch(x, plan, metric, "pairs", m, mesh, None,
                              (True if use_kernel else None), interpret,
                              128, workload="pairs")

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False, bl: int = 128):
        """LPT-balance the rectangular plan over the mesh (per-reducer work
        = wx + wy + flop·wx·wy), run the rectangular gather+Gram tile
        pipeline per shard under ``shard_map``, and assemble the (mx, my)
        matrix with ONE cross-shard gather.  Non-Gram reducers fall back
        to the rect-bucketed path (counted)."""
        from .allpairs import assemble_x2y_matrix_bucketed
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "empty_plan")
            self._reconcile(plan, "x2y", _as_tables(tables)[0],
                            measured_slots=_bucket_valid_slots(plan))
            per_bucket = run_reducers_x2y_bucketed(
                tables, plan, reducer_fn, mesh=mesh, combine="buckets")
            return assemble_x2y_matrix_bucketed(per_bucket, shape)
        mesh, axes, S = _shard_mesh(mesh, None)
        part = self.partition(plan, S)
        groups = self._rect_groups_for(plan, part)
        self._count("sharded")
        self._note(part)
        if _obs_config.ENABLED:
            xt0 = _as_tables(tables)[0]
            _d, isz = _row_bytes(xt0)
            per_shard = int(_group_gram_entries(
                plan, ("gram_rect", S), groups) * isz * (S - 1) / S)
            self._reconcile(
                plan, "x2y", xt0,
                measured_slots=_group_valid_slots(
                    plan, ("sharded_rect", S), groups, count_y=True),
                assembled_bytes=S * per_shard,
                meta={"num_shards": S,
                      "assembly_bytes_per_shard": per_shard})
        P = jax.sharding.PartitionSpec
        srcmap = _put(self._rect_srcmap_for(plan, groups, S, tuple(shape)),
                      mesh, P())
        uk = True if use_kernel else jax.default_backend() == "tpu"
        fn = _cache_get(
            ("sharded-x2y", metric, mesh, axes, bool(uk), bool(interpret),
             bl),
            lambda: _make_sharded_rect_jitted(metric, mesh, axes, uk,
                                              interpret, bl))
        jgroups = tuple(tuple(_put(a, mesh, P(axes)) for a in grp)
                        for grp in groups)
        xt, yt = _as_tables(tables)
        return fn(xt, yt, jgroups, srcmap)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None,
              combine: str = "pairs", m: Optional[int] = None,
              use_kernel: bool = False, bl: int = 128, **kwargs):
        """Lower the sharded program (no execution) for dry-run/roofline.

        ``combine='pairs'`` (default) lowers the full pipeline including
        the cross-shard assembly gather of the ``(m, m)`` matrix
        (``m`` defaults to ``input_shape[0]``); ``combine='dense'`` lowers
        the dense-combine scatter form.  Returns one ``Lowered``.
        """
        if metric is None:
            metric = getattr(reducer_fn, "fused_metric", None)
        assert metric is not None, "sharded lowering needs a Gram metric"
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition(plan, S)
        groups = self._groups_for(plan, part)
        if combine == "pairs":
            mm = m if m is not None else input_shape[0]
            srcmap = jax.ShapeDtypeStruct((mm, mm), jnp.int32)
        else:
            srcmap = jax.ShapeDtypeStruct((1,), jnp.int32)
        fn = _make_sharded_jitted(metric, combine, mesh, axes,
                                  use_kernel, False, bl)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        sgroups = tuple(
            (jax.ShapeDtypeStruct(i.shape, jnp.int32),
             jax.ShapeDtypeStruct(k.shape, jnp.bool_),
             jax.ShapeDtypeStruct(r.shape, jnp.int32))
            for i, k, r in groups)
        return fn.lower(x, sgroups, srcmap, plan.R, plan.L)


# ---------------------------------------------------------------------------
# coded (replicated shuffle) executor
# ---------------------------------------------------------------------------
def _coded_maps(groups, shape: tuple[int, int], row_block: int,
                zero_diag: bool):
    """Host-side maps for the coded combining stage.

    ``groups`` are replica-stacked rect groups
    ``[(xidx (S,Rw,wx), xmask, yidx (S,Rw,wy), ymask, rows (S,Rw)), ...]``
    where ``rows`` holds each shard's full replica set (padding slots have
    all-false masks and are skipped).  The output ``(mx, my)`` matrix is
    row-sliced: shard ``s`` owns rows ``[s*row_block, (s+1)*row_block)``.

    Per output cell the serving Gram entry is resolved to either a
    position in the owning shard's *local* value vector (a replica is
    held: zero traffic) or a slot in the residual exchange: for every
    (block, destination) pair with no local replica, the block rows whose
    output rows fall in the destination's slice — never the whole block —
    are stride-split across ALL replica holders (least-filled lane
    first), so each holder ships ~1/r of the residual and the exchange
    lanes shrink as replication grows.  The residual is batched into
    per-destination lanes and moved by ONE tiled all-to-all sized by the
    maximum lane.

    Returns ``(sendmap (S, S, E) int32`` into the shard-local value
    vector, ``srcmap (S, row_block, my) int32`` into
    ``[vals_local (Lv), recv (S*E)]``, and a stats dict).  Slot 0 of the
    value vector is 0.0 (uncovered cells, padding lanes, the diagonal).
    """
    mx, my = shape
    S = groups[0][0].shape[0] if groups else 1
    bases = []
    Lv = 1
    for xidx, _xm, yidx, _ym, _rows in groups:
        bases.append(Lv)
        Lv += xidx.shape[1] * xidx.shape[2] * yidx.shape[2]

    # holders: global row -> [(shard, group, slot), ...] (replica set)
    holders: dict[int, list] = {}
    for gi, (_xi, xmask, _yi, ymask, rows) in enumerate(groups):
        live = xmask.any(axis=2) & ymask.any(axis=2)      # (S, Rw)
        for s, k in np.argwhere(live):
            holders.setdefault(int(rows[s, k]), []).append(
                (int(s), gi, int(k)))

    send: list[list[list]] = [[[] for _ in range(S)] for _ in range(S)]
    cnt = np.zeros((S, S), dtype=np.int64)
    recv_fill: list[list] = [[] for _ in range(S)]
    srcmap = np.zeros((S, row_block, my), dtype=np.int64)
    local_entries = 0
    for _b, hl in holders.items():
        s0, gi, k0 = hl[0]
        xidx, xmask, yidx, ymask, _rows = groups[gi]
        wx, wy = xidx.shape[2], yidx.shape[2]
        pv = np.flatnonzero(xmask[s0, k0])
        qv = np.flatnonzero(ymask[s0, k0])
        if not pv.size or not qv.size:
            continue
        gx = xidx[s0, k0][pv].astype(np.int64)
        gy = yidx[s0, k0][qv].astype(np.int64)
        ds = gx // row_block
        hpos = {s: bases[g] + k * wx * wy for s, g, k in hl}
        for s in np.unique(ds):
            s = int(s)
            sel = ds == s
            p_s, gx_s = pv[sel], gx[sel]
            if s in hpos:                      # local replica: no traffic
                pos = hpos[s] + (p_s[:, None] * wy + qv[None, :])
                srcmap[s][np.ix_(gx_s - s * row_block, gy)] = pos
                local_entries += pos.size
            else:                              # residual: split over holders
                hs = sorted(hpos, key=lambda tt: cnt[tt, s])
                for j, t in enumerate(hs):
                    p_j, gx_j = p_s[j::len(hs)], gx_s[j::len(hs)]
                    if not p_j.size:
                        continue
                    pos = hpos[t] + (p_j[:, None] * wy + qv[None, :])
                    send[t][s].append(pos.ravel())
                    recv_fill[s].append((t, int(cnt[t, s]), gx_j, gy))
                    cnt[t, s] += pos.size
    E = max(1, int(cnt.max(initial=0)))
    sendmap = np.zeros((S, S, E), dtype=np.int64)
    for t in range(S):
        for s in range(S):
            if send[t][s]:
                v = np.concatenate(send[t][s])
                sendmap[t, s, :len(v)] = v
    for s in range(S):
        for t, e0, gx_s, gy in recv_fill[s]:
            e = e0 + np.arange(len(gx_s) * len(gy), dtype=np.int64)
            srcmap[s][np.ix_(gx_s - s * row_block, gy)] = (
                Lv + t * E + e.reshape(len(gx_s), len(gy)))
    if zero_diag:
        for s in range(S):
            d = np.arange(s * row_block, min((s + 1) * row_block, mx))
            srcmap[s, d - s * row_block, d] = 0
    stats = {
        "local_entries": int(local_entries),
        "residual_entries": int(cnt.sum()),
        "lane_max": E,
        "lane_fill": float(cnt.sum() / max(S * S * E, 1)),
        "vals_len": int(Lv),
    }
    return (sendmap.astype(np.int32), srcmap.astype(np.int32), stats)


def _make_coded_jitted(metric, mesh, axes, use_kernel, interpret, bl):
    from repro.compat import all_to_all
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram_rect,
        fused_gather_gram_rect_streamed,
    )

    P = jax.sharding.PartitionSpec

    def per_shard_fn(xt, yt, n2x, n2y, groups, sendmap, srcmap):
        # local shapes: tables/norms replicated, stacks (1, Rw, w),
        # sendmap (1, S, E), srcmap (1, row_block, my)
        vals = [jnp.zeros((1,), jnp.float32)]
        for xidx, xmsk, yidx, ymsk in groups:
            if use_kernel:
                g = fused_gather_gram_rect(xt, yt, xidx[0], xmsk[0],
                                           yidx[0], ymsk[0], bl=bl,
                                           interpret=interpret)
            else:
                g = fused_gather_gram_rect_streamed(xt, yt, xidx[0],
                                                    xmsk[0], yidx[0],
                                                    ymsk[0], bl=bl)
            g = _finish_rect_blocks(g, xidx[0], xmsk[0].astype(bool),
                                    yidx[0], ymsk[0].astype(bool),
                                    n2x, n2y, metric)
            vals.append(g.reshape(-1))
        vloc = jnp.concatenate(vals)
        # coded combining: replicas serve locally through srcmap; ONLY the
        # residual lanes cross shards, in one batched tiled all-to-all —
        # there is no all-gather of the Gram stacks in this program
        send = jnp.take(vloc, sendmap[0], axis=0)          # (S, E)
        recv = all_to_all(send, axes)                      # (S, E)
        full = jnp.concatenate([vloc, recv.reshape(-1)])
        return jnp.take(full, srcmap[0], axis=0)[None]     # (1, rb, my)

    def run(xt, yt, groups, sendmap, srcmap):
        n2x = jnp.sum(xt.astype(jnp.float32) ** 2, axis=-1)
        n2y = jnp.sum(yt.astype(jnp.float32) ** 2, axis=-1)
        out = jax.shard_map(per_shard_fn, mesh=mesh,
                            in_specs=(P(), P(), P(), P(), P(axes), P(axes),
                                      P(axes)),
                            out_specs=P(axes), check_vma=_CHECK_VMA)(
            xt, yt, n2x, n2y, groups, sendmap, srcmap)
        return out.reshape(-1, out.shape[-1])   # (S*rb, my); caller trims

    return jax.jit(run)


class CodedExecutor(ShardedExecutor):
    """Coded shuffle execution: trade replication for cross-shard traffic.

    The sharded executor pays ONE cross-shard all-gather to assemble the
    replicated (m, m) matrix — every shard receives every Gram stack.  The
    coded executor (the coded-MapReduce tradeoff of Afrati et al.,
    arXiv:1206.4377) spends replication to cut that traffic:
    ``partition_plan(..., replication=r)`` materializes each reducer's
    sub-plan on r LPT-chosen shards, the output matrix is row-sliced
    across shards, and assembly becomes a coded combining stage — a shard
    holding a replica serves its slice's cells from local Gram entries
    (zero traffic), and only the residual entries (block rows owned by a
    slice with no replica) are exchanged, batched into per-destination
    lanes and moved by ONE tiled all-to-all.  Per shard the residual is
    ~``2G/S * (1 - r/S)`` entries (G = total Gram entries) vs ~``G`` for
    the uncoded all-gather, so measured assembly bytes collapse and keep
    falling as r grows; ``choose_replication`` picks the knee of the
    replication-vs-communication frontier.

    Same fallback rules as the sharded executor (Gram-block reducers
    only); ``replication`` is clamped to the mesh's shard count.
    """

    name = "coded"

    def __init__(self, stats: Optional[dict] = None, replication: int = 2):
        super().__init__(stats=stats)
        self.replication = int(replication)

    def _fresh_stats(self) -> dict:
        return {"calls": 0, "coded": 0, "fallbacks": 0, "num_shards": 0,
                "balance_factor": 0.0, "replication": 0,
                "local_entries": 0, "residual_entries": 0,
                "local_fraction": 0.0}

    # -- replication-aware partition plumbing (cached on the plan) --------
    def partition_coded(self, plan: ReducerPlan, num_shards: int,
                        replication: Optional[int] = None) -> PlanPartition:
        r = min(self.replication if replication is None else int(replication),
                num_shards)
        cache = plan.__dict__.get("_coded_partition_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_coded_partition_cache", cache)
        part = cache.get((num_shards, r))
        if part is None:
            part = partition_plan(plan, num_shards, replication=r)
            cache[(num_shards, r)] = part
        return part

    def _coded_groups_for(self, plan, part, rect: bool):
        cache = plan.__dict__.get("_coded_groups_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_coded_groups_cache", cache)
        key = (part.num_shards, part.replication, rect)
        groups = cache.get(key)
        if groups is None:
            if rect:
                groups = _stacked_rect_groups(
                    plan, part, rows_by_shard=part.replica_rows)
            else:
                groups = [(i, k, i, k, r) for i, k, r in _stacked_groups(
                    plan, part, rows_by_shard=part.replica_rows)]
            cache[key] = groups
        return groups

    def _coded_maps_for(self, plan, groups, part, shape, zero_diag: bool):
        cache = plan.__dict__.get("_coded_maps_cache")
        if cache is None:
            cache = {}
            object.__setattr__(plan, "_coded_maps_cache", cache)
        key = (part.num_shards, part.replication, tuple(shape), zero_diag)
        maps = cache.get(key)
        if maps is None:
            rb = -(-shape[0] // part.num_shards)
            maps = _coded_maps(groups, tuple(shape), rb, zero_diag)
            cache[key] = maps
        return maps

    def _note_coded(self, part: PlanPartition, mstats: dict) -> None:
        self._note(part)
        self._stats["replication"] = int(part.replication)
        self._stats["local_entries"] = mstats["local_entries"]
        self._stats["residual_entries"] = mstats["residual_entries"]
        tot = mstats["local_entries"] + mstats["residual_entries"]
        self._stats["local_fraction"] = (
            mstats["local_entries"] / tot if tot else 1.0)
        _REGISTRY_OBS.gauge("executor.replication",
                            executor=self.name).set(part.replication)
        _REGISTRY_OBS.gauge("executor.local_fraction",
                            executor=self.name).set(
                                self._stats["local_fraction"])

    def _coded_dispatch(self, xt, yt, plan, metric, shape, zero_diag,
                        mesh, shard_axes, use_kernel, interpret, bl,
                        rect: bool, workload: str = "pairs"):
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition_coded(plan, S)
        groups = self._coded_groups_for(plan, part, rect)
        sendmap, srcmap, mstats = self._coded_maps_for(
            plan, groups, part, shape, zero_diag)
        self._count("coded")
        self._note_coded(part, mstats)
        if _obs_config.ENABLED:
            # identical ring accounting to ``coded_assembly_model``:
            # residual lanes x itemsize x (S-1)/S per shard
            _d, isz = _row_bytes(xt)
            frac = (S - 1) / S if S > 1 else 0.0
            per_shard = int(sendmap.shape[1] * sendmap.shape[2]
                            * isz * frac)
            self._reconcile(
                plan, workload, xt,
                measured_slots=_group_valid_slots(
                    plan, ("coded", S, part.replication, rect), groups,
                    count_y=rect),
                replication=float(part.replication),
                assembled_bytes=S * per_shard,
                local_bytes=int(mstats["local_entries"]) * isz,
                residual_bytes=int(mstats["residual_entries"]) * isz,
                meta={"num_shards": S,
                      "replication": int(part.replication),
                      "assembly_bytes_per_shard": per_shard,
                      "lane_max": mstats["lane_max"]})
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        fn = _cache_get(
            ("coded", metric, mesh, axes, bool(use_kernel),
             bool(interpret), bl),
            lambda: _make_coded_jitted(metric, mesh, axes, use_kernel,
                                       interpret, bl))
        P = jax.sharding.PartitionSpec
        jgroups = tuple(tuple(_put(a, mesh, P(axes)) for a in g[:4])
                        for g in groups)
        out = fn(xt, yt, jgroups, _put(sendmap, mesh, P(axes)),
                 _put(srcmap, mesh, P(axes)))
        return out[:shape[0]]

    # -- protocol ----------------------------------------------------------
    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        from .allpairs import assemble_pair_matrix_bucketed
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "empty_plan")
            self._reconcile(plan, "pairs", x,
                            measured_slots=_bucket_valid_slots(plan))
            per_bucket = run_reducers_bucketed(x, plan, reducer_fn,
                                               mesh=mesh, combine="buckets")
            return assemble_pair_matrix_bucketed(per_bucket, m)
        x = jnp.asarray(x)
        return self._coded_dispatch(
            x, x, plan, metric, (m, m), True, mesh, None,
            (True if use_kernel else None), interpret, 128, rect=False,
            workload="pairs")

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False, bl: int = 128):
        from .allpairs import assemble_x2y_matrix_bucketed
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "empty_plan")
            self._reconcile(plan, "x2y", _as_tables(tables)[0],
                            measured_slots=_bucket_valid_slots(plan))
            per_bucket = run_reducers_x2y_bucketed(
                tables, plan, reducer_fn, mesh=mesh, combine="buckets")
            return assemble_x2y_matrix_bucketed(per_bucket, shape)
        uk = True if use_kernel else None
        xt, yt = _as_tables(tables)
        return self._coded_dispatch(
            xt, yt, plan, metric, tuple(shape), False, mesh, None, uk,
            interpret, bl, rect=True, workload="x2y")

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None,
              m: Optional[int] = None, replication: Optional[int] = None,
              use_kernel: bool = False, bl: int = 128, **kwargs):
        """Lower the coded all-pairs program (no execution) for dry-run /
        roofline: per-shard rect tile pipeline + the residual all-to-all.
        ``replication`` overrides the instance rate (clamped to the
        mesh's shard count); the send/recv lane sizes baked into the
        lowered shapes are the real host-computed ones, so HLO collective
        bytes measure the actual coded exchange."""
        if metric is None:
            metric = getattr(reducer_fn, "fused_metric", None)
        assert metric is not None, "coded lowering needs a Gram metric"
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition_coded(plan, S, replication)
        groups = self._coded_groups_for(plan, part, rect=False)
        mm = m if m is not None else input_shape[0]
        sendmap, srcmap, _ = self._coded_maps_for(
            plan, groups, part, (mm, mm), True)
        fn = _make_coded_jitted(metric, mesh, axes, use_kernel, False, bl)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        sgroups = tuple(
            (jax.ShapeDtypeStruct(xi.shape, jnp.int32),
             jax.ShapeDtypeStruct(xm.shape, jnp.bool_),
             jax.ShapeDtypeStruct(yi.shape, jnp.int32),
             jax.ShapeDtypeStruct(ym.shape, jnp.bool_))
            for xi, xm, yi, ym, _rows in groups)
        return fn.lower(x, x, sgroups,
                        jax.ShapeDtypeStruct(sendmap.shape, jnp.int32),
                        jax.ShapeDtypeStruct(srcmap.shape, jnp.int32))


def coded_assembly_model(plan, num_shards: int, replication: int, m: int,
                         *, itemsize: int = 4) -> dict:
    """Analytic bytes of the coded combining stage at replication ``r`` —
    host-only (builds the real send/recv maps, lowers nothing).

    ``assembly_bytes_per_shard`` uses the same ring accounting as the
    roofline HLO parser (result bytes x (S-1)/S for the tiled
    all-to-all), so model and measured numbers are directly comparable;
    ``uncoded_assembly_bytes_per_shard`` is the sharded executor's
    all-gather of the full primary Gram stacks under the same accounting.
    """
    S = int(num_shards)
    r = min(int(replication), S)
    part = partition_plan(plan, S, replication=r)
    sq = _stacked_groups(plan, part, rows_by_shard=part.replica_rows)
    groups = [(i, k, i, k, rows) for i, k, rows in sq]
    rb = -(-int(m) // S)
    sendmap, _srcmap, st = _coded_maps(groups, (int(m), int(m)), rb, True)
    frac = (S - 1) / S if S > 1 else 0.0
    primary = _stacked_groups(plan, part)
    gram_entries = sum(int(np.prod(i.shape[:2])) * i.shape[2] ** 2
                       for i, _k, _r in primary)
    return {
        "replication": r,
        "num_shards": S,
        "local_entries": st["local_entries"],
        "residual_entries": st["residual_entries"],
        "local_fraction": (
            st["local_entries"]
            / max(st["local_entries"] + st["residual_entries"], 1)),
        "lane_max": st["lane_max"],
        "lane_fill": st["lane_fill"],
        "assembly_bytes_per_shard": int(sendmap.shape[1] * sendmap.shape[2]
                                        * itemsize * frac),
        "uncoded_assembly_bytes_per_shard": int(gram_entries * itemsize
                                                * frac),
        "replica_slots": [int(x) for x in part.replica_slots],
    }


def choose_replication(plan, num_shards: int, m: int, d: int, *,
                       itemsize: int = 4,
                       candidates=None) -> tuple[int, list[dict]]:
    """Auto-``r``: sweep the replication-vs-communication frontier and
    pick the knee for ``num_shards`` shards.

    Total cluster communication at replication r =
    ``r x shipped input bytes`` (every replica shard receives its
    sub-plan's input rows: the paper's map->reduce cost scales linearly
    with r) ``+ S x assembly bytes per shard`` (falls with r as replicas
    serve locally).  The knee is the argmin of that total — past it,
    extra replicas ship more input rows than they save in assembly.
    Returns ``(best_r, frontier)`` with one model row per candidate,
    each including the total and both terms.
    """
    S = int(num_shards)
    if candidates is None:
        candidates = []
        r = 1
        while r <= S:
            candidates.append(r)
            r *= 2
    shipped_bytes = float(plan.comm_cost) * d * itemsize
    frontier = []
    for r in sorted(set(min(int(c), S) for c in candidates)):
        rec = coded_assembly_model(plan, S, r, m, itemsize=itemsize)
        rec["shipped_bytes"] = r * shipped_bytes
        rec["total_comm_bytes"] = (rec["shipped_bytes"]
                                   + S * rec["assembly_bytes_per_shard"])
        frontier.append(rec)
    best = min(frontier, key=lambda rec: rec["total_comm_bytes"])
    return best["replication"], frontier


# ---------------------------------------------------------------------------
# default registry instances
# ---------------------------------------------------------------------------
# Every default instance owns its counters (no shared module-level dicts:
# a service resetting its own executor can never zero another caller's
# telemetry).  ``engine.fused_stats()`` stays live as the documented
# aggregate view — ``_count`` publishes each increment into the obs
# registry's per-executor-name series, which that shim reads.
register_executor(DenseExecutor())
register_executor(BucketedExecutor())
register_executor(FusedExecutor())
register_executor(ShardedExecutor())
register_executor(CodedExecutor())
