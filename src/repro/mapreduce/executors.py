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
                serve their slice's cells locally, and only the cells
                with no local holder cross shards, each once, in one
                batched all-to-all — assembly bytes fall as r grows, at
                the price of ``r×`` input shipping.
``streaming`` — delta execution of maintained plans (DESIGN.md "streaming
                maintenance"; ``repro.stream``, registered lazily): only
                the reducers an edit dirtied are recomputed, and the
                cached (m, m) matrix is patched instead of rebuilt.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core.planner import PlanPartition, partition_plan
from repro.obs import EVENTS as _EVENTS
from repro.obs import LEDGER as _LEDGER
from repro.obs import REGISTRY as _REGISTRY_OBS
from repro.obs import TRACER as _TRACER
from repro.obs import _config as _obs_config
from repro.obs import span as _obs_span

from . import engine as _engine
from .engine import (
    ReducerPlan,
    _as_tables,
    _cache_get,
    _shardings,
    launch,
    run_reducers,
    run_reducers_bucketed,
    run_reducers_x2y,
    run_reducers_x2y_bucketed,
    upload_span,
    y_side,
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
        only to specialize the routing.  The sub-plan is taken under a
        ``plan`` span, the rest under ``execute``, as ``pairwise_similarity``
        does."""
        bx, by = i1 - i0, j1 - j0
        with _obs_span("plan", workload="block"):
            sub = _engine.block_subplan(
                sparse, i0, i1, j0, j1, pad_reducers_to=pad_reducers_to,
                pad_slots_to=pad_slots_to, max_buckets=max_buckets)
        with _obs_span("execute", workload="block",
                       reducers=0 if sub is None else sub.num_reducers):
            if sub is None or bx == 0 or by == 0:
                out = jnp.zeros((max(bx, 0), max(by, 0)), jnp.float32)
            else:
                tables = launch(lambda: (x[i0:i1], x[j0:j1]))
                out = self.run_x2y(tables, sub, reducer_fn, (bx, by),
                                   mesh=mesh, use_kernel=use_kernel,
                                   interpret=interpret)
            lo, hi = max(i0, j0), min(i1, j1)
            if lo < hi:  # the block crosses the global diagonal: zero it
                def zero_diagonal(o):
                    d = jnp.arange(lo, hi)
                    return o.at[d - i0, d - j0].set(0.0)
                out = launch(zero_diagonal, out)
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

    def _bucketed_answer(self, tables, plan, reducer_fn, shape, workload,
                         mesh, reason: str):
        """Serve an answer the Gram programs cannot (a reducer without a
        ``fused_metric`` tag, or nothing to run) on the bucketed path:
        identical outputs, counted as a fallback."""
        from .allpairs import (assemble_pair_matrix_bucketed,
                               assemble_x2y_matrix_bucketed)
        self._count_fallback(reason)
        self._reconcile(plan, workload, _as_tables(tables)[0],
                        measured_slots=_bucket_valid_slots(plan))
        if workload == "x2y":
            return assemble_x2y_matrix_bucketed(run_reducers_x2y_bucketed(
                tables, plan, reducer_fn, mesh=mesh, combine="buckets"),
                shape)
        return assemble_pair_matrix_bucketed(run_reducers_bucketed(
            tables, plan, reducer_fn, mesh=mesh, combine="buckets"),
            shape[0])

    def _reconcile(self, plan, workload: str, table, *,
                   measured_slots: int, replication: float = 1.0,
                   assembled_bytes: int = 0, local_bytes: int = 0,
                   residual_bytes: int = 0, meta: Optional[dict] = None,
                   stacks=None) -> None:
        """Record this execution's comm reconciliation (no-op when obs is
        disabled).  ``table`` supplies the input row size (d, itemsize);
        ``stacks`` are the (X-side, Y-side) gather rows of the reducer
        blocks the programs write (default: the plan's buckets, else its
        dense rows).  Their float32 bytes go to the ledger, whose
        ``ledger.block_bytes`` counter sums them, and to the innermost
        open span as ``block_bytes``."""
        if not _obs_config.ENABLED:
            return
        d, itemsize = _row_bytes(table)
        if stacks is None:
            stacks = _plan_stacks(plan, dense=not plan.buckets)
        block_bytes = 4 * _block_entries(stacks)
        _LEDGER.record(
            executor=self.name, workload=workload,
            predicted_rows=float(plan.comm_cost),
            lb_rows=plan.lower_bound,
            plan_slots=_plan_valid_slots(plan),
            measured_slots=int(measured_slots), d=d, itemsize=itemsize,
            replication=replication, assembled_bytes=assembled_bytes,
            local_bytes=local_bytes, residual_bytes=residual_bytes,
            block_bytes=block_bytes, meta=meta)
        s = _TRACER.current()
        if s is not None:
            s.attrs["block_bytes"] = s.attrs.get("block_bytes", 0) \
                + block_bytes


def _plan_stacks(plan, dense: bool) -> list:
    """(X-side, Y-side) gather rows of each stack of reducer blocks: the
    plan's capacity buckets, or its dense rows (``dense``)."""
    return [(p.idx, y_side(p)[0])
            for p in ([plan] if dense else plan.buckets)]


def _group_stacks(groups) -> list:
    """(X-side, Y-side) gather rows of stacked shard groups
    (xi, xm, yi, ym, rows)."""
    return [(g[0], g[2]) for g in groups]


def _block_entries(stacks) -> int:
    """Cells of the reducer blocks written: per stack, its reducers
    (padding rows and every shard included) times the X width times the
    Y width."""
    return sum(int(np.prod(xi.shape[:-1])) * int(xi.shape[-1])
               * int(yi.shape[-1]) for xi, yi in stacks)


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


def _group_valid_slots(plan, cache_key, groups) -> int:
    """Valid gather slots in stacked shard groups (xi, xm, yi, ym, rows),
    the sharded/coded executors' measured side.  A self-join's Y side is
    the same gather as its X side and is counted once.  Cached on the plan
    per (executor, shards[, replication]) key."""
    cache = plan.__dict__.get("_obs_group_slots")
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_obs_group_slots", cache)
    n = cache.get(cache_key)
    if n is None:
        n = sum(int(g[1].sum()) + (int(g[3].sum()) if plan.is_rect else 0)
                for g in groups)
        cache[cache_key] = n
    return n


def _derived(plan, attr: str, key, what: str, build: Callable,
             attrs: Optional[Callable] = None):
    """A host artefact derived from ``plan``, built once and kept on the
    plan (dict ``attr``, under ``key``): a static artifact reused across
    waves, like the index matrix.  Looked up and built under a ``maps``
    span (``what``, ``cached``, and whatever ``attrs(value)`` adds)."""
    cache = plan.__dict__.get(attr)
    if cache is None:
        cache = {}
        object.__setattr__(plan, attr, cache)
    value = cache.get(key)
    with _obs_span("maps", what=what, cached=value is not None) as s:
        if value is None:
            value = cache[key] = build()
        if s is not None and attrs is not None:
            s.attrs.update(attrs(value))
    return value


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
                        measured_slots=_plan_valid_slots(plan),
                        stacks=_plan_stacks(plan, dense=True))
        blocks = run_reducers(x, plan, reducer_fn, mesh=mesh)  # (R, L, L)
        return assemble_pair_matrix(blocks, plan, m)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False):
        from .allpairs import assemble_x2y_matrix_bucketed
        self._count("calls")
        self._reconcile(plan, "x2y", _as_tables(tables)[0],
                        measured_slots=_plan_valid_slots(plan),
                        stacks=_plan_stacks(plan, dense=True))
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
def _kernel_for(use_kernel) -> bool:
    """The Gram executors' one kernel rule: the Pallas kernel on TPU or
    where the caller asks for it (``use_kernel=True``), else its streamed
    jnp twin."""
    return bool(use_kernel) or jax.default_backend() == "tpu"


def _specs(tree):
    """ShapeDtypeStructs of a tree of host arrays, for AOT lowering."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jax.dtypes.canonicalize_dtype(a.dtype)), tree)


def _scatter_dense(stacks, R: int, shape) -> jax.Array:
    """Gram blocks back in plan-row order, zero-padded to the dense
    ``shape`` (the ``combine='dense'`` layout of ``run_reducers``).
    ``stacks`` pairs host row ids with their block stacks; padding rows
    (-1, or ``R`` and above) land in a scratch row."""
    acc = jnp.zeros((R + 1, *shape), jnp.float32)
    for rows, g in stacks:
        rows = np.asarray(rows).reshape(-1)
        g = g.reshape(-1, *g.shape[-2:])
        acc = acc.at[np.where((rows >= 0) & (rows < R), rows, R)].set(
            jnp.pad(g, ((0, 0), (0, shape[0] - g.shape[1]),
                        (0, shape[1] - g.shape[2]))))
    return acc[:R]


def _finish_rect_blocks(g, xidx, xmask, yidx, ymask, n2x, n2y, metric: str):
    """Metric post-processing of a masked cross-Gram stack.

    Mirrors ``allpairs.block_similarity_x2y`` exactly.  Per-row squared
    norms are gathered from the table-level vectors ``n2x``/``n2y``
    (masked slots -> 0, matching the zero-masked gathers of the reference
    path); invalid pairs -> 0.
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


def _make_fused_rect_jitted(metric, mesh, shard_axes, use_kernel,
                            interpret, bl):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram_rect,
        fused_gather_gram_rect_streamed,
    )

    def run(xt, yt, buckets, srcmap):
        # a self-join passes no Y side (None): its table and ids are X's
        yt = xt if yt is None else yt
        n2x = jnp.sum(xt.astype(jnp.float32) ** 2, axis=-1)   # (mx,)
        n2y = jnp.sum(yt.astype(jnp.float32) ** 2, axis=-1)   # (my,)
        blocks = []
        for xidx, xmsk, yidx, ymsk in buckets:
            if yidx is None:
                yidx, ymsk = xidx, xmsk
            if use_kernel:
                g = fused_gather_gram_rect(xt, yt, xidx, xmsk, yidx, ymsk,
                                           bl=bl, interpret=interpret)
            else:
                g = fused_gather_gram_rect_streamed(xt, yt, xidx, xmsk,
                                                    yidx, ymsk, bl=bl)
            blocks.append(_finish_rect_blocks(
                g, xidx, xmsk.astype(bool), yidx, ymsk.astype(bool), n2x,
                n2y, metric))
        if srcmap is None:                     # the blocks themselves
            return blocks
        # inverse shuffle: ONE assembly gather through the host-precomputed
        # source map (slot 0 -> 0.0 for uncovered cells)
        vals = [jnp.zeros((1,), jnp.float32)]
        vals += [g.reshape(-1) for g in blocks]
        return jnp.take(jnp.concatenate(vals), srcmap, axis=0)

    if mesh is None:
        return jax.jit(run)
    red_sharding, rep = _shardings(mesh, shard_axes)
    return jax.jit(run, in_shardings=(rep, rep, red_sharding, rep))


class FusedExecutor(Executor):
    """Fused shuffle execution: the gathered block stays out of HBM.

    Per capacity bucket, the plan's X and Y gather rows drive the fused
    gather+Gram Pallas kernel (TPU or ``use_kernel=True``; scalar-
    prefetched rows, table rows DMA'd HBM->VMEM, fp32 MXU accumulation —
    gathered rows live only in VMEM scratch) or its jnp twin with the same
    tile dataflow (elsewhere) — the twin still gathers ``(Rb, bl, d)``
    tiles as XLA intermediates, but a multi-tile bucket never materializes
    its full ``(Rb, Lb, d)`` block and no bucket ever materializes the
    dense ``(R, L, d)`` one.  *All* buckets execute inside ONE jitted
    program, so a request pays a single dispatch instead of one per
    bucket.  The program is the X2Y one: a self-join passes its table and
    each bucket's ids once, with no Y side, the program reads them for
    both sides, and its source map zeroes the diagonal.

    Only Gram-block reducers are fusable: ``reducer_fn`` must carry a
    ``fused_metric`` attribute (see ``allpairs._block_fn``).  Any other
    reducer — and bucketless plans — falls back to the bucketed executor
    with identical outputs; fallbacks are counted in this instance's
    ``stats()``.
    """

    name = "fused"

    def _fresh_stats(self) -> dict:
        return {"calls": 0, "kernel": 0, "streamed": 0, "fallbacks": 0}

    def _launch(self, xt, yt, plan, metric, srcmap, mesh, shard_axes,
                use_kernel, interpret, bl):
        """Every bucket through the one program, assembled through
        ``srcmap`` (``None``: the blocks).  A self-join passes its table
        and each bucket's ids once, with no Y side (``None``): the program
        reads X's for both, so XLA sees one array where X = Y."""
        uk = _kernel_for(use_kernel)
        self._count("kernel" if uk else "streamed")
        fn = _cache_get(
            ("fused-x2y", metric, mesh, shard_axes, uk, bool(interpret),
             bl),
            lambda: _make_fused_rect_jitted(metric, mesh, shard_axes, uk,
                                            interpret, bl))
        # the source map goes first: its transfer, the largest, overlaps
        # the rest of the host work
        host = (srcmap, tuple((b.idx, b.mask, b.yidx, b.ymask)
                              for b in plan.buckets))
        with upload_span(host):
            dev_srcmap, buckets = jax.tree.map(jnp.asarray, host)
        return launch(fn, xt, None if yt is xt else yt, buckets, dev_srcmap)

    def run(self, inputs, plan, reducer_fn, *, mesh=None, shard_axes=None,
            combine: str = "dense", use_kernel: Optional[bool] = None,
            interpret: bool = False, bl: int = 128):
        """Per-reducer Gram blocks from the same program, without the
        assembly gather.  ``combine`` follows the bucketed executor:
        'dense' puts them in plan-row order at the dense widths, 'buckets'
        returns ``[(bucket, blocks), ...]``."""
        assert combine in ("dense", "buckets"), combine
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or not plan.buckets:
            self._count_fallback(
                "non_gram_reducer" if metric is None else "no_buckets")
            return run_reducers_bucketed(inputs, plan, reducer_fn, mesh=mesh,
                                         shard_axes=shard_axes,
                                         combine=combine)
        shard_axes = tuple(shard_axes) if shard_axes is not None else None
        blocks = self._launch(*_as_tables(inputs), plan, metric, None, mesh,
                              shard_axes, use_kernel, interpret, bl)
        if combine == "buckets":
            return list(zip(plan.buckets, blocks))
        return _scatter_dense(
            [(b.rows, g) for b, g in zip(plan.buckets, blocks)], plan.R,
            (plan.L, plan.Ly))

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        """The self-join: :meth:`run_x2y` with X = Y, diagonal zeroed."""
        return self._assembled(x, plan, reducer_fn, (m, m), "pairs", mesh,
                               use_kernel, interpret, 128)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False, bl: int = 128):
        """Per bucket, independent X/Y gather maps drive the rectangular
        gather+Gram kernel (streamed jnp twin off-TPU), and ONE
        inverse-shuffle gather assembles the (mx, my) matrix.  Non-Gram
        reducers fall back to the rect-bucketed path (identical outputs;
        counted)."""
        return self._assembled(tables, plan, reducer_fn, tuple(shape), "x2y",
                               mesh, use_kernel, interpret, bl)

    def _assembled(self, tables, plan, reducer_fn, shape, workload, mesh,
                   use_kernel, interpret, bl):
        from .allpairs import _pair_source_map_rect
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or not plan.buckets:
            return self._bucketed_answer(
                tables, plan, reducer_fn, shape, workload, mesh,
                "non_gram_reducer" if metric is None else "no_buckets")
        xt, yt = _as_tables(tables)
        self._reconcile(plan, workload, xt,
                        measured_slots=_bucket_valid_slots(plan))
        srcmap = _pair_source_map_rect(plan, *shape,
                                       zero_diag=workload == "pairs")
        return self._launch(xt, yt, plan, metric, srcmap, mesh, None,
                            use_kernel, interpret, bl)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None,
              use_kernel: bool = False, bl: int = 128, **kwargs):
        """Lower the one all-bucket program (no execution) on a self-join
        table of ``input_shape``, up to the per-bucket blocks.  Defaults to
        the streamed (jnp) lowering so the dry-run works on any backend; on
        this path the program is directly comparable with the bucketed
        lowering — same math, one program, no materialized gather for
        multi-tile widths.  Returns one ``Lowered``."""
        if metric is None:
            metric = getattr(reducer_fn, "fused_metric", None)
        assert metric is not None, "fused lowering needs a Gram metric"
        shard_axes = tuple(shard_axes) if shard_axes is not None else None
        fn = _make_fused_rect_jitted(metric, mesh, shard_axes, use_kernel,
                                     False, bl)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        buckets = _specs(tuple((b.idx, b.mask, b.yidx, b.ymask)
                               for b in plan.buckets))
        return fn.lower(x, None, buckets, None)


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


_SRCMAP_CHUNK = 1 << 24          # block cells expanded at once on the host
# Per-shard bodies call the Pallas kernels, whose outputs carry no
# varying-mesh-axis type: shard_map's vma check cannot trace them
_CHECK_VMA = False


def _fill_source_map(srcmap: np.ndarray, blocks) -> None:
    """Point ``srcmap[row, col]`` at every valid cell's position in the
    concatenation ``[0.0, blocks_0.ravel(), blocks_1.ravel(), ...]``.

    ``blocks`` yields ``(xidx (R, Lx), xmask, yidx (R, Ly), ymask)`` per
    stack of reducer blocks (a self-join's Y side is its X side).
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


def _stacked_rect_groups(plan: ReducerPlan, part: PlanPartition,
                         rows_by_shard=None):
    """Stack the partition into uniform device arrays, one group per
    (wx, wy) execution-width pair: ``xidx/xmask (S, Rw, wx)``,
    ``yidx/ymask (S, Rw, wy)``, ``rows (S, Rw)``, where ``Rw = max_s
    |shard s's reducers of that pair|`` — each shard's rows padded
    (masked, rows -> plan.R) to the common count so ``shard_map`` can
    split the leading axis across the mesh.  LPT balances total work, so
    the cross-shard padding this stacking adds is small exactly when the
    balance factor is small.  A self-join's Y side is its X side
    (``y_side``): the same arrays.  Groups come in ascending width order
    (numpy; the executor converts once per plan).

    ``rows_by_shard`` overrides the per-shard row sets (default: the
    partition's primary ``shard_rows``) — the coded executor passes
    ``part.replica_rows`` so every shard's stack holds all of its
    replicas, not just its primary assignment."""
    S = part.num_shards
    R0 = plan.num_reducers
    widths = part.widths
    ywidths = widths if part.ywidths is None else part.ywidths
    if rows_by_shard is None:
        rows_by_shard = part.shard_rows
    src = {}
    for p in plan.buckets or (plan,):
        yidx, ymask = y_side(p)
        rows = p.rows if plan.buckets else np.arange(R0)
        for i, g in enumerate(rows):
            if 0 <= g < R0:
                src[int(g)] = (p.idx[i], p.mask[i], yidx[i], ymask[i])

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
        # a self-join's Y side is its X side's arrays (written twice below)
        yidx, ymask = ((np.zeros((S, Rw, wy), np.int32),
                        np.zeros((S, Rw, wy), bool)) if plan.is_rect
                       else (xidx, xmask))
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


def _group_args(plan, groups) -> tuple:
    """Stacked groups as the sharded program takes them: a self-join's Y
    side (its X side's arrays) is left out (``None``), so the program
    reads one array for both."""
    return tuple(g[:4] if plan.is_rect else (*g[:2], None, None)
                 for g in groups)


def _sharded_rect_srcmap(groups, shape: tuple[int, int],
                         zero_diag: bool = False) -> np.ndarray:
    """Cross-shard assembly map: ``shape`` int32 positions into
    ``[0.0, group_0.ravel(), ...]`` of the stacked per-(wx, wy) Gram
    outputs (each ``(S, Rw, wx, wy)``).  Uncovered cells, and with
    ``zero_diag`` a self-join's diagonal, point at slot 0 (-> 0.0)."""
    srcmap = np.zeros(shape, np.int32)
    _fill_source_map(srcmap, (tuple(a.reshape(-1, a.shape[2])
                                    for a in g[:4]) for g in groups))
    if zero_diag:
        np.fill_diagonal(srcmap, 0)
    return srcmap


def _make_sharded_rect_jitted(metric, mesh, axes, use_kernel, interpret,
                              bl):
    from repro.kernels.pairwise.fused_gather_gram import (
        fused_gather_gram_rect,
        fused_gather_gram_rect_streamed,
    )

    P = jax.sharding.PartitionSpec

    def per_shard_fn(xt, yt, n2x, n2y, xidx, xmsk, yidx, ymsk):
        # local shapes: xt/yt/n2x/n2y replicated, idx/msk (1, Rw, w); a
        # self-join passes no Y-side ids (None): they are X's
        if yidx is None:
            yidx, ymsk = xidx, xmsk
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
        yt = xt if yt is None else yt          # a self-join: one table
        n2x = jnp.sum(xt.astype(jnp.float32) ** 2, axis=-1)
        n2y = jnp.sum(yt.astype(jnp.float32) ** 2, axis=-1)
        outs = [jax.shard_map(per_shard_fn, mesh=mesh,
                              in_specs=(P(), P(), P(), P(), P(axes),
                                        P(axes), P(axes), P(axes)),
                              out_specs=P(axes), check_vma=_CHECK_VMA)(
                    xt, yt, n2x, n2y, *g) for g in groups]
        if srcmap is None:                     # the group stacks themselves
            return outs
        # ONE cross-shard assembly gather of the (mx, my) matrix — the
        # only cross-shard communication in the program (XLA inserts the
        # all-gather here)
        vals = [jnp.zeros((1,), jnp.float32)]
        vals += [g.reshape(-1) for g in outs]
        return jnp.take(jnp.concatenate(vals), srcmap, axis=0)

    return jax.jit(run)


class ShardedExecutor(Executor):
    """Shard-balanced multi-device execution of a reducer plan.

    ``repro.core.planner.partition_plan`` LPT-balances the plan's reducers
    (weighted by per-reducer gather+FLOP work at their capacity-bucket
    width) into one compact sub-plan per shard of the mesh's reducer axis.
    The sub-plans are stacked into uniform per-(wx, wy) arrays and executed
    under ``shard_map``: every device runs the fused gather+Gram tile
    pipeline (streamed jnp twin off-TPU) over exactly its LPT-assigned
    reducers — instead of XLA's blind even row-split of a skew-ordered
    plan — and the only cross-shard communication is the single assembly
    gather of the answer at the end (``run_pairs``, ``run_x2y``) or the
    dense scatter (``run``).  One program serves both: a self-join is the
    X2Y job with X = Y, its diagonal zeroed.

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
        return _derived(plan, "_shard_partition_cache", num_shards,
                        "partition", lambda: partition_plan(plan, num_shards))

    def _groups_for(self, plan, part):
        return _derived(plan, "_shard_groups_cache", part.num_shards,
                        "groups", lambda: _stacked_rect_groups(plan, part))

    def _srcmap_for(self, plan, groups, num_shards: int, shape,
                    zero_diag: bool):
        return _derived(plan, "_shard_srcmap_cache",
                        (num_shards, shape, zero_diag), "srcmap",
                        lambda: _sharded_rect_srcmap(groups, shape,
                                                     zero_diag))

    def _note(self, part: PlanPartition) -> None:
        self._stats["num_shards"] = part.num_shards
        self._stats["balance_factor"] = float(part.balance_factor)
        _REGISTRY_OBS.gauge("executor.num_shards",
                            executor=self.name).set(part.num_shards)
        _REGISTRY_OBS.gauge("executor.balance_factor",
                            executor=self.name).set(part.balance_factor)

    def _dispatch(self, xt, yt, plan, metric, shape, zero_diag, mesh,
                  shard_axes, use_kernel, interpret, bl, workload):
        """The plan's shard groups through the one sharded program:
        assembled into ``shape`` (``zero_diag``: a self-join's diagonal
        zeroed), or with ``shape=None`` scattered into the dense
        per-reducer layout."""
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition(plan, S)
        groups = self._groups_for(plan, part)
        self._count("sharded")
        self._note(part)
        if _obs_config.ENABLED:
            stacks = _group_stacks(groups)
            meta = {"num_shards": S}
            assembled = 0
            if shape is not None:
                _d, isz = _row_bytes(xt)
                per_shard = int(_block_entries(stacks) * isz * (S - 1) / S)
                assembled = S * per_shard
                meta["assembly_bytes_per_shard"] = per_shard
            self._reconcile(
                plan, workload, xt,
                measured_slots=_group_valid_slots(plan, ("sharded", S),
                                                  groups),
                assembled_bytes=assembled, meta=meta, stacks=stacks)
        host_srcmap = (None if shape is None else
                       self._srcmap_for(plan, groups, S, shape, zero_diag))
        uk = _kernel_for(use_kernel)
        fn = _cache_get(
            ("sharded-x2y", metric, mesh, axes, uk, bool(interpret), bl),
            lambda: _make_sharded_rect_jitted(metric, mesh, axes, uk,
                                              interpret, bl))
        P = jax.sharding.PartitionSpec
        args = _group_args(plan, groups)
        with upload_span((args, host_srcmap)):
            jgroups = jax.tree.map(lambda a: _put(a, mesh, P(axes)), args)
            srcmap = (None if shape is None
                      else _put(host_srcmap, mesh, P()))
        out = launch(fn, xt, None if yt is xt else yt, jgroups, srcmap)
        if shape is not None:
            return out
        return _scatter_dense([(g[4], o) for g, o in zip(groups, out)],
                              plan.R, (plan.L, plan.Ly))

    # the coded executor answers through its combining stage instead
    _answer = _dispatch

    def _assembled(self, tables, plan, reducer_fn, shape, workload, mesh,
                   use_kernel, interpret, bl):
        self._count("calls")
        metric = getattr(reducer_fn, "fused_metric", None)
        if metric is None or plan.num_reducers == 0:
            return self._bucketed_answer(
                tables, plan, reducer_fn, shape, workload, mesh,
                "non_gram_reducer" if metric is None else "empty_plan")
        xt, yt = _as_tables(tables)
        return self._answer(xt, yt, plan, metric, shape, workload == "pairs",
                            mesh, None, use_kernel, interpret, bl, workload)

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
        return self._dispatch(*_as_tables(inputs), plan, metric, None, False,
                              mesh, shard_axes, use_kernel, interpret, bl,
                              "reduce")

    def run_pairs(self, x, plan, reducer_fn, m, *, mesh=None,
                  use_kernel=False, interpret=False):
        return self._assembled(x, plan, reducer_fn, (m, m), "pairs", mesh,
                               use_kernel, interpret, 128)

    def run_x2y(self, tables, plan, reducer_fn, shape, *, mesh=None,
                use_kernel=False, interpret=False, bl: int = 128):
        """LPT-balance the rectangular plan over the mesh (per-reducer work
        = wx + wy + flop·wx·wy), run the rectangular gather+Gram tile
        pipeline per shard under ``shard_map``, and assemble the (mx, my)
        matrix with ONE cross-shard gather.  Non-Gram reducers fall back
        to the rect-bucketed path (counted)."""
        return self._assembled(tables, plan, reducer_fn, tuple(shape), "x2y",
                               mesh, use_kernel, interpret, bl)

    def lower(self, input_shape, plan, *, reducer_fn=None, metric=None,
              mesh=None, dtype=jnp.float32, shard_axes=None,
              combine: str = "pairs", m: Optional[int] = None,
              use_kernel: bool = False, bl: int = 128, **kwargs):
        """Lower the sharded program (no execution) for dry-run/roofline,
        on a self-join table of ``input_shape``.

        ``combine='pairs'`` (default) lowers the full pipeline including
        the cross-shard assembly gather of the ``(m, m)`` matrix
        (``m`` defaults to ``input_shape[0]``); ``combine='dense'`` lowers
        the group stacks alone.  Returns one ``Lowered``.
        """
        if metric is None:
            metric = getattr(reducer_fn, "fused_metric", None)
        assert metric is not None, "sharded lowering needs a Gram metric"
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        groups = self._groups_for(plan, self.partition(plan, S))
        mm = m if m is not None else input_shape[0]
        srcmap = (jax.ShapeDtypeStruct((mm, mm), jnp.int32)
                  if combine == "pairs" else None)
        fn = _make_sharded_rect_jitted(metric, mesh, axes, use_kernel, False,
                                       bl)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        return fn.lower(x, None, _specs(_group_args(plan, groups)), srcmap)


# ---------------------------------------------------------------------------
# coded (replicated shuffle) executor
# ---------------------------------------------------------------------------
# served-cell markers in ``_coded_maps``'s winner grid (>= 0: a block row)
_SERVED_LOCAL, _SERVED_ZERO = -2, -3
# block entries ``_coded_maps`` works on at a time (bounds its host memory)
_CODED_CHUNK = 1 << 22


class _SlotClass(NamedTuple):
    """Live slots of one group with equal valid counts ``(nx, ny)``: a
    dense ``(K, nx, ny)`` grid of block entries, whatever the masks.  The
    entry ``(p, q)`` of a slot sits at ``base + p * wy + q`` in its
    shard's value vector."""
    shard: np.ndarray       # (K,) shard holding the slot
    block: np.ndarray       # (K,) reducer row
    base: np.ndarray        # (K,) offset of the slot's Gram block
    px: np.ndarray          # (K, nx) valid x positions
    py: np.ndarray          # (K, ny) valid y positions
    gx: np.ndarray          # (K, nx) their global rows
    gy: np.ndarray          # (K, ny) their global columns
    wy: int


def _slot_classes(groups, bases) -> list:
    """Every live slot of the replica-stacked ``groups`` as
    :class:`_SlotClass` es, in group, class, shard, slot order."""
    classes = []
    for gi, (xidx, xmask, yidx, ymask, rows) in enumerate(groups):
        wx, wy = xidx.shape[2], yidx.shape[2]
        nx, ny = xmask.sum(axis=2), ymask.sum(axis=2)
        s_all, k_all = np.nonzero((nx > 0) & (ny > 0))
        if not len(s_all):
            continue
        key = nx[s_all, k_all] * (wy + 1) + ny[s_all, k_all]
        order = np.argsort(key, kind="stable")
        _u, first = np.unique(key[order], return_index=True)
        for sel in np.split(order, first[1:]):
            s, k = s_all[sel], k_all[sel]
            px = np.argsort(~xmask[s, k], axis=1, kind="stable")
            py = np.argsort(~ymask[s, k], axis=1, kind="stable")
            px, py = px[:, :nx[s[0], k[0]]], py[:, :ny[s[0], k[0]]]
            classes.append(_SlotClass(
                s, rows[s, k].astype(np.int64),
                bases[gi] + k.astype(np.int64) * (wx * wy), px, py,
                np.take_along_axis(xidx[s, k], px, 1).astype(np.int64),
                np.take_along_axis(yidx[s, k], py, 1).astype(np.int64), wy))
    return classes


def _coded_maps(groups, shape: tuple[int, int], row_block: int,
                zero_diag: bool):
    """Host-side maps for the coded combining stage.

    ``groups`` are replica-stacked rect groups
    ``[(xidx (S,Rw,wx), xmask, yidx (S,Rw,wy), ymask, rows (S,Rw)), ...]``
    where ``rows`` holds each shard's full replica set (padding slots have
    all-false masks and are skipped).  The output ``(mx, my)`` matrix is
    row-sliced: shard ``s`` owns rows ``[s*row_block, (s+1)*row_block)``.

    Each covered answer cell has ONE serving source, decided before any
    entry is placed in a send lane.  Several blocks cover one cell where
    the schema repeats coverage (the self-join's within-bin cells sit in
    every reducer that holds the bin); their Gram entries are identical.

    1. Local holder first: a cell covered by some block held on the
       cell's owning shard is served from that shard's *local* value
       vector (zero traffic).
    2. Otherwise one residual block serves it: each block, for each
       destination slice without a replica of it, claims only its cells
       there that nothing serves yet, and those are stride-split across
       ALL the block's holders (least-filled lane first), so each holder
       ships ~1/r of them and the lanes stay balanced as r grows.

    The residual is therefore exactly the covered cells without a local
    holder, each shipped once, batched into per-destination lanes and
    moved by ONE tiled all-to-all sized by the maximum lane.  The cells
    are worked in bulk over :func:`_slot_classes`, ``_CODED_CHUNK`` block
    entries at a time.

    Returns ``(sendmap (S, S, E) int32`` into the shard-local value
    vector, ``srcmap (S, row_block, my) int32`` into
    ``[vals_local (Lv), recv (S*E)]``, and a stats dict:
    ``local_entries`` / ``residual_entries`` (cells served each way),
    ``skipped_entries`` (valid block entries that serve no cell:
    duplicates and, with ``zero_diag``, the diagonal), ``lane_max`` (E),
    ``lane_fill``, ``vals_len`` (Lv)).  Slot 0 of the value vector is
    0.0 (uncovered cells, padding lanes, the diagonal).
    """
    mx, my = shape
    S = groups[0][0].shape[0] if groups else 1
    bases = []
    Lv = 1
    for xidx, _xm, yidx, _ym, _rows in groups:
        bases.append(Lv)
        Lv += xidx.shape[1] * xidx.shape[2] * yidx.shape[2]
    assert Lv < 2 ** 31, "coded value vector outgrows int32 positions"
    classes = _slot_classes(groups, bases)

    # holders of each block: a shard bitmask and each holder's slot base;
    # the lowest holder's slot stands for the block in the residual pass
    nb = 1 + max((int(c.block.max()) for c in classes), default=-1)
    hbits = np.zeros(nb, np.int64)
    hbase = np.zeros((nb, S), np.int64)
    for c in classes:
        np.bitwise_or.at(hbits, c.block, np.int64(1) << c.shard)
        hbase[c.block, c.shard] = c.base
    lowest = hbits & -hbits
    reps = [np.flatnonzero((np.int64(1) << c.shard) == lowest[c.block])
            for c in classes]

    def chunks(c, slots):
        step = max(1, _CODED_CHUNK // (c.px.shape[1] * c.py.shape[1]))
        for i in range(0, len(slots), step):
            yield slots[i:i + step]

    def cells(c, ks, kk, pp):            # flat (mx, my) cells of rows
        return c.gx[ks[kk], pp][:, None] * my + c.gy[ks[kk]]

    winner = np.full(S * row_block * my, -1, np.int32)   # -1: unserved
    src = np.zeros(S * row_block * my, np.int32)         # srcmap, flat
    for c in classes:                                    # 1. local
        for ks in chunks(c, np.arange(len(c.shard))):
            kk, pp = np.nonzero(c.gx[ks] // row_block
                                == c.shard[ks][:, None])
            at = cells(c, ks, kk, pp)
            src[at] = (c.base[ks[kk]] + c.px[ks[kk], pp] * c.wy)[:, None] \
                + c.py[ks[kk]]
            winner[at] = _SERVED_LOCAL
    diag = np.arange(min(mx, my)) * (my + 1)
    if zero_diag:
        winner[diag] = _SERVED_ZERO
    local_entries = int(np.count_nonzero(winner == _SERVED_LOCAL))

    # 2. residual: block by block, rows whose slice holds no replica claim
    # their unserved cells (in one chunk, one claimant of a cell wins).
    # Rows go in (block, destination) order, so each such pair's claimed
    # entries come out contiguous; pair = block ordinal * S + destination
    w_cell, w_rel = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
    row_pair, row_n = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
    rep_b = [np.zeros(0, np.int64)]
    valid = nrep = 0
    for c, rep in zip(classes, reps):
        valid += len(rep) * c.px.shape[1] * c.py.shape[1]
        for ks in chunks(c, rep):
            ds = c.gx[ks] // row_block
            kk, pp = np.nonzero(
                ((hbits[c.block[ks]][:, None] >> ds) & 1) == 0)
            o = np.argsort(kk * S + ds[kk, pp], kind="stable")
            kk, pp = kk[o], pp[o]
            at = cells(c, ks, kk, pp)
            ri, qi = np.nonzero(winner[at] == -1)
            at, kr = at[ri, qi], ks[kk[ri]]
            winner[at] = c.block[kr]
            won = winner[at] == c.block[kr]
            ri, qi, kr = ri[won], qi[won], kr[won]
            w_cell.append(at[won])
            w_rel.append(c.px[kr, pp[ri]] * c.wy + c.py[kr, qi])
            row_pair.append((nrep + kk) * S + ds[kk, pp])
            row_n.append(np.bincount(ri, minlength=len(kk)))
            rep_b.append(c.block[ks])
            nrep += len(ks)
    w_cell, w_rel, rep_b = map(np.concatenate, (w_cell, w_rel, rep_b))
    row_n = np.concatenate(row_n)
    row_pair = np.concatenate(row_pair)[row_n > 0]
    heads = np.flatnonzero(np.diff(row_pair, prepend=-1))
    count = (np.add.reduceat(row_n[row_n > 0], heads) if len(heads)
             else np.zeros(0, np.int64))
    starts = np.cumsum(count) - count          # first entry of each pair
    blk, dest = rep_b[row_pair[heads] // S], row_pair[heads] % S

    # lanes: each pair's cells stride-split over the block's holders,
    # least-filled lane first: piece j of pair i (its ranks j, j + h, ...)
    # goes to holder t at lane offset e0
    cnt = [[0] * S for _ in range(S)]
    holders: dict[int, list] = {}
    h_max = max((bin(int(v)).count("1") for v in np.unique(hbits)),
                default=1)
    t = np.zeros((len(blk), h_max), np.int64)
    e0 = np.zeros((len(blk), h_max), np.int64)
    nh = np.ones(len(blk), np.int64)
    for i, (bits, s, n) in enumerate(zip(hbits[blk].tolist(),
                                         dest.tolist(), count.tolist())):
        hs = holders.get(bits)
        if hs is None:
            hs = holders[bits] = [h for h in range(S) if bits >> h & 1]
        if len(hs) > 1:
            hs = sorted(hs, key=lambda h: cnt[h][s])
            nh[i] = len(hs)
        for j, h in enumerate(hs[:n]):
            t[i, j], e0[i, j] = h, cnt[h][s]
            cnt[h][s] += (n - j + len(hs) - 1) // len(hs)
    E = max(1, max(max(row) for row in cnt))
    assert Lv + S * E < 2 ** 31, "coded maps outgrow int32 positions"
    # per piece: the holder's slot base, first send slot and srcmap value
    base = hbase[blk[:, None], t].ravel()
    send_at = ((t * S + dest[:, None]) * E + e0).ravel()
    src_at = (Lv + t * E + e0).ravel()
    sendmap = np.zeros(S * S * E, np.int32)
    owner = np.repeat(np.arange(len(blk)), count)
    for a in range(0, len(w_cell), _CODED_CHUNK):
        e = slice(a, a + _CODED_CHUNK)
        i = owner[e]
        rank = np.arange(a, a + len(i)) - starts[i]
        k = i * h_max + rank % nh[i]
        rank //= nh[i]
        sendmap[send_at[k] + rank] = base[k] + w_rel[e]
        src[w_cell[e]] = src_at[k] + rank
    if zero_diag:
        src[diag] = 0
    stats = {
        "local_entries": local_entries,
        "residual_entries": len(w_cell),
        "skipped_entries": int(valid - local_entries - len(w_cell)),
        "lane_max": E,
        "lane_fill": float(len(w_cell) / (S * S * E)),
        "vals_len": int(Lv),
    }
    return (sendmap.reshape(S, S, E), src.reshape(S, row_block, my),
            stats)


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
    (zero traffic), and only the residual is exchanged, batched into
    per-destination lanes and moved by ONE tiled all-to-all.  Each answer
    cell is served from ONE source, a local holder first
    (``_coded_maps``): the residual is exactly the covered cells without
    a holder on their owning shard, each shipped once however many blocks
    cover it (the self-join's within-bin cells sit in every reducer
    holding the bin), against every Gram stack for the uncoded
    all-gather — so measured assembly bytes collapse and keep falling as
    r grows; ``choose_replication`` picks the knee of the
    replication-vs-communication frontier.  Stats count
    ``local_entries`` / ``residual_entries`` (cells served each way) and
    ``skipped_entries`` (block entries no cell is served from: duplicates
    and the diagonal; gauge ``executor.coded_skipped_entries``).

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
                "skipped_entries": 0, "local_fraction": 0.0}

    # -- replication-aware partition plumbing (cached on the plan) --------
    def partition_coded(self, plan: ReducerPlan, num_shards: int,
                        replication: Optional[int] = None) -> PlanPartition:
        r = min(self.replication if replication is None else int(replication),
                num_shards)
        return _derived(
            plan, "_coded_partition_cache", (num_shards, r), "partition",
            lambda: partition_plan(plan, num_shards, replication=r))

    def _coded_groups_for(self, plan, part):
        return _derived(plan, "_coded_groups_cache",
                        (part.num_shards, part.replication), "groups",
                        lambda: _stacked_rect_groups(
                            plan, part, rows_by_shard=part.replica_rows))

    def _coded_maps_for(self, plan, groups, part, shape, zero_diag: bool):
        rb = -(-shape[0] // part.num_shards)
        return _derived(
            plan, "_coded_maps_cache",
            (part.num_shards, part.replication, tuple(shape), zero_diag),
            "coded_maps",
            lambda: _coded_maps(groups, tuple(shape), rb, zero_diag),
            lambda maps: {"skipped_entries": maps[2]["skipped_entries"]})

    def _note_coded(self, part: PlanPartition, mstats: dict) -> None:
        self._note(part)
        self._stats["replication"] = int(part.replication)
        self._stats["local_entries"] = mstats["local_entries"]
        self._stats["residual_entries"] = mstats["residual_entries"]
        self._stats["skipped_entries"] = mstats["skipped_entries"]
        tot = mstats["local_entries"] + mstats["residual_entries"]
        self._stats["local_fraction"] = (
            mstats["local_entries"] / tot if tot else 1.0)
        _REGISTRY_OBS.gauge("executor.replication",
                            executor=self.name).set(part.replication)
        _REGISTRY_OBS.gauge("executor.local_fraction",
                            executor=self.name).set(
                                self._stats["local_fraction"])
        _REGISTRY_OBS.gauge("executor.coded_skipped_entries",
                            executor=self.name).set(
                                mstats["skipped_entries"])

    def _answer(self, xt, yt, plan, metric, shape, zero_diag, mesh,
                shard_axes, use_kernel, interpret, bl, workload):
        """The answer through the coded combining stage: the replica-stacked
        Gram blocks serve their slices' cells locally, the residual crosses
        shards in one all-to-all."""
        mesh, axes, S = _shard_mesh(mesh, shard_axes)
        part = self.partition_coded(plan, S)
        groups = self._coded_groups_for(plan, part)
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
                    plan, ("coded", S, part.replication), groups),
                replication=float(part.replication),
                assembled_bytes=S * per_shard,
                local_bytes=int(mstats["local_entries"]) * isz,
                residual_bytes=int(mstats["residual_entries"]) * isz,
                meta={"num_shards": S,
                      "replication": int(part.replication),
                      "assembly_bytes_per_shard": per_shard,
                      "lane_max": mstats["lane_max"]},
                stacks=_group_stacks(groups))
        uk = _kernel_for(use_kernel)
        fn = _cache_get(
            ("coded", metric, mesh, axes, uk, bool(interpret), bl),
            lambda: _make_coded_jitted(metric, mesh, axes, uk, interpret,
                                       bl))
        P = jax.sharding.PartitionSpec
        host = (tuple(g[:4] for g in groups), sendmap, srcmap)
        with upload_span(host):
            jgroups = tuple(tuple(_put(a, mesh, P(axes)) for a in g)
                            for g in host[0])
            jsend = _put(sendmap, mesh, P(axes))
            jsrc = _put(srcmap, mesh, P(axes))
        out = launch(fn, xt, yt, jgroups, jsend, jsrc)
        return out[:shape[0]]

    # -- protocol ----------------------------------------------------------
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
        groups = self._coded_groups_for(plan, part)
        mm = m if m is not None else input_shape[0]
        sendmap, srcmap, _ = self._coded_maps_for(
            plan, groups, part, (mm, mm), True)
        fn = _make_coded_jitted(metric, mesh, axes, use_kernel, False, bl)
        x = jax.ShapeDtypeStruct(input_shape, dtype)
        return fn.lower(x, x, *_specs((tuple(g[:4] for g in groups),
                                       sendmap, srcmap)))


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
    groups = _stacked_rect_groups(plan, part, rows_by_shard=part.replica_rows)
    rb = -(-int(m) // S)
    sendmap, _srcmap, st = _coded_maps(groups, (int(m), int(m)), rb, True)
    frac = (S - 1) / S if S > 1 else 0.0
    gram_entries = _block_entries(_group_stacks(
        _stacked_rect_groups(plan, part)))
    return {
        "replication": r,
        "num_shards": S,
        "local_entries": st["local_entries"],
        "residual_entries": st["residual_entries"],
        "skipped_entries": st["skipped_entries"],
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
