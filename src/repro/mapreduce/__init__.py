"""JAX execution engine for mapping schemas.

The planner (``repro.core``) decides *where* inputs go; this package
executes the plan on a device mesh: the map->reduce shuffle becomes a static
gather whose communication volume is exactly the schema's communication
cost, and the reduce phase becomes a vmapped/shard_mapped reducer function.
The hardware adaptation (reducer slots, static gather plans, wave batching)
is documented in DESIGN.md.

Public API
----------
``build_plan(schema, ...)``
    Flatten a :class:`repro.core.MappingSchema` into a :class:`ReducerPlan`
    — static (R, L) index/mask arrays padded for the mesh and kernel tiles.
    The plan carries the schema's provenance (``algorithm``,
    ``lower_bound``, ``optimality_gap``) for downstream telemetry.
``run_reducers(inputs, plan, reducer_fn, mesh=...)``
    Execute a reducer function over every slot; the gather *is* the
    shuffle.  Dense path: every reducer padded to the global max slot
    count.
``run_reducers_bucketed(inputs, plan, reducer_fn, mesh=...)``
    Skew-aware path: one vmapped gather+reduce per capacity bucket, each
    padded only to its own power-of-two width (DESIGN.md "bucketed
    shuffle execution").  ``combine='dense'`` reproduces the dense output
    layout; ``combine='buckets'`` keeps per-bucket outputs unpadded.
``get_executor(name)`` / ``make_executor(name)`` / ``register_executor``
    The executor registry (``repro.mapreduce.executors``): executors are
    classes exposing ``run`` / ``run_pairs`` / ``run_x2y`` / ``lower`` /
    ``stats`` and registered by name ("dense", "bucketed", "fused",
    "sharded", "coded", "streaming") — the single dispatch point for every
    application entry below.  The Gram executors ("fused", "sharded",
    "coded") run one pipeline: a self-join is the X2Y job with X = Y (the
    plan's ids on both sides) and its diagonal zeroed, so one
    gather+Gram program and one source-map assembly serve the self-join,
    X2Y joins and tiles.
``pairwise_similarity(x, q=...)``
    A2A application: all-pairs similarity through a planned schema.
``some_pairs_similarity(x, pairs, q=...)``
    Sparse variant (Ullman & Ullman's some-pairs problem): only the
    required pairs must meet, only pair-incident inputs are shipped.
``assemble_pair_matrix(blocks, plan, m)``
    Scatter per-reducer blocks back into the global (m, m) matrix.
``skew_join(...)``
    X2Y application: skewed join via the Section-10 bipartite schema.
"""

from .engine import (
    ReducerBucket,
    ReducerPlan,
    SparsePlan,
    block_cache_stats,
    block_subplan,
    build_plan,
    build_sparse_plan,
    build_x2y_plan,
    compile_count,
    configure_block_cache,
    configure_jit_cache,
    fused_stats,
    jit_cache_stats,
    run_reducers,
    run_reducers_bucketed,
    run_reducers_x2y,
    run_reducers_x2y_bucketed,
)
from .executors import (
    Executor,
    get_executor,
    list_executors,
    make_executor,
    register_executor,
)
from .allpairs import (
    assemble_pair_matrix,
    assemble_pair_matrix_bucketed,
    assemble_x2y_matrix_bucketed,
    pairwise_similarity,
    pairwise_similarity_block,
    some_pairs_similarity,
    x2y_similarity,
)
from .skewjoin import join, skew_join

__all__ = [
    "ReducerBucket", "ReducerPlan", "SparsePlan", "build_plan",
    "build_sparse_plan", "block_subplan", "build_x2y_plan",
    "run_reducers", "run_reducers_bucketed", "run_reducers_x2y",
    "run_reducers_x2y_bucketed",
    "Executor", "get_executor", "make_executor", "register_executor",
    "list_executors",
    "fused_stats", "jit_cache_stats", "configure_jit_cache", "compile_count",
    "block_cache_stats", "configure_block_cache",
    "pairwise_similarity", "pairwise_similarity_block",
    "some_pairs_similarity", "x2y_similarity",
    "assemble_pair_matrix", "assemble_pair_matrix_bucketed",
    "assemble_x2y_matrix_bucketed",
    "skew_join", "join",
]
