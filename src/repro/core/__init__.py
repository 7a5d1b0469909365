"""Core: mapping schemas for different-sized inputs in MapReduce.

Reproduces Afrati, Dolev, Korach, Sharma, Ullman — "Assignment Problems of
Different-Sized Inputs in MapReduce" (2015): A2A and X2Y mapping-schema
planners with capacity-q reducers, bin-packing approximations, the optimal
unit-size constructions (q=2, q=3, AU method + extensions), the hybrid and
big-input paths, plus the paper's lower/upper bounds for validation.

Public planner API
------------------
``plan_a2a(weights, q, method='auto')``
    All-pairs mapping schema.  ``method='auto'`` runs the strategy-registry
    portfolio: every applicable strategy is costed with an exact closed-form
    estimate and only the argmin winner is materialized.  Results are
    memoized in ``PLAN_CACHE`` by the (sorted-weights, q, method) profile;
    a repeat of the same weight vector returns the same schema object.
``plan_x2y(wx, wy, q)``
    Bipartite (X-to-Y) mapping schema, Section 10.
``plan_some_pairs(weights, q, pairs)``
    Cover an explicit required-pair subset (Ullman & Ullman, "Some Pairs
    Problems"): dense instances fall back to the A2A portfolio, sparse ones
    pay only for the bin pairs that contain required pairs.
``plan_unit(n, k)``
    Unit-size scheduler: n identical items, integer capacity k.
``plan_a2a_materialized(weights, q)``
    The seed build-every-candidate portfolio, kept as the benchmark
    baseline and correctness oracle for the estimate-based planner.
``estimate_a2a(weights, q)``
    (strategy label, exact communication cost) without building a schema.
``naive_pairs(weights, q)``
    One reducer per pair — the worst-case baseline.

Every returned :class:`MappingSchema` carries ``lower_bound`` (the paper's
replication-rate communication lower bound for its instance) and reports
``optimality_gap()`` = measured cost / lower bound.

Extension points: ``strategies.register_unit_strategy`` and
``strategies.register_a2a_strategy`` add constructions that all planners
pick up automatically; ``PLAN_CACHE`` (a :class:`strategies.PlanCache`)
can be cleared or resized.

Supporting modules: ``unit_schemas`` (Sections 5-7 constructions),
``binpack`` (O(n log n) FFD/BFD), ``bounds`` (Theorems 8/9/11/25 + Table 1),
``exact`` (brute-force optima for tiny instances), ``primes``.
"""

from .binpack import bfd, ffd, pack, pack_prefix, prefix_bins
from .bounds import (
    a2a_algk_comm_upper_bound,
    a2a_binpack_comm_lower_bound,
    a2a_comm_lower_bound,
    a2a_k2_comm_upper_bound,
    a2a_reducers_lower_bound,
    a2a_unit_comm_lower_bound,
    a2a_unit_reducers_lower_bound,
    big_input_comm_upper_bound,
    some_pairs_comm_lower_bound,
    x2y_comm_lower_bound,
    x2y_comm_upper_bound,
    x2y_reducers_lower_bound,
)
from .hierarchy import (
    choose_grouping_factor,
    plan_a2a_hierarchical,
    sampled_pair_coverage,
)
from .planner import (
    PlanPartition,
    bucket_summary,
    compute_buckets,
    compute_rect_buckets,
    estimate_a2a,
    estimate_x2y,
    naive_pairs,
    partition_plan,
    plan_a2a,
    plan_a2a_materialized,
    plan_some_pairs,
    plan_unit,
    plan_x2y,
    reducer_work,
)
from .primes import is_prime, next_prime, prev_prime
from .schema import InfeasibleError, MappingSchema
from .strategies import (
    A2A_REGISTRY,
    PLAN_CACHE,
    PlanCache,
    UNIT_REGISTRY,
    register_a2a_strategy,
    register_unit_strategy,
)
from . import unit_schemas

__all__ = [
    "MappingSchema", "InfeasibleError",
    "plan_a2a", "plan_a2a_materialized", "plan_x2y", "plan_unit",
    "plan_some_pairs", "estimate_a2a", "estimate_x2y", "naive_pairs",
    "compute_buckets", "compute_rect_buckets", "bucket_summary",
    "PlanPartition", "partition_plan", "reducer_work",
    "PLAN_CACHE", "PlanCache",
    "UNIT_REGISTRY", "A2A_REGISTRY",
    "register_unit_strategy", "register_a2a_strategy",
    "ffd", "bfd", "pack", "pack_prefix", "prefix_bins",
    "plan_a2a_hierarchical", "choose_grouping_factor",
    "sampled_pair_coverage",
    "is_prime", "prev_prime", "next_prime",
    "unit_schemas",
    "a2a_comm_lower_bound", "a2a_reducers_lower_bound",
    "a2a_binpack_comm_lower_bound", "a2a_unit_comm_lower_bound",
    "a2a_unit_reducers_lower_bound", "a2a_k2_comm_upper_bound",
    "a2a_algk_comm_upper_bound", "big_input_comm_upper_bound",
    "x2y_comm_lower_bound", "x2y_comm_upper_bound",
    "x2y_reducers_lower_bound", "some_pairs_comm_lower_bound",
]
