"""Strategy-registry planner: estimator exactness, cache, some-pairs.

The contract that lets ``plan_a2a(method='auto')`` skip materialization is
that every registered strategy's ``estimate`` equals the communication cost
of the schema its ``build`` produces.  These tests enforce that invariant
per strategy and end-to-end (estimate-based auto == materialize-everything
portfolio), plus the PlanCache semantics and ``plan_some_pairs`` validity.
"""

import numpy as np
import pytest

from repro.core import (
    InfeasibleError,
    PLAN_CACHE,
    estimate_a2a,
    naive_pairs,
    plan_a2a,
    plan_a2a_materialized,
    plan_some_pairs,
    plan_unit,
    some_pairs_comm_lower_bound,
)
from repro.core.schema import MappingSchema
from repro.core.strategies import (
    A2AProfile,
    PlanCache,
    a2a_portfolio,
    schema_host_bytes,
    unit_estimates,
)


@pytest.fixture(autouse=True)
def _fresh_cache():
    PLAN_CACHE.clear()
    yield
    PLAN_CACHE.clear()


def unit_schema(reducers, bw, k) -> MappingSchema:
    return MappingSchema(np.asarray(bw, float), float(k) * 10.0,
                         [[i] for i in range(len(bw))], reducers,
                         algorithm="unit")


# ------------------------------------------------- estimator == built cost
class TestUnitEstimates:
    @pytest.mark.parametrize("n,k", [
        (5, 2), (23, 2), (64, 2),              # alg_even k=2
        (10, 4), (40, 6), (100, 10),           # alg_even larger k
        (7, 3), (16, 3), (31, 3), (23, 5),     # alg_odd
        (25, 5), (49, 7), (20, 5),             # au_square (+ filtered)
        (30, 6), (11, 4), (29, 7),             # au_projective / alg3
        (27, 3), (16, 2), (125, 5),            # alg4
        (3, 8), (2, 2),                        # single
    ])
    def test_estimate_matches_built_cost(self, n, k):
        rng = np.random.default_rng(n * 100 + k)
        bw = rng.uniform(0.1, 1.0, n)
        cands = unit_estimates(bw, k)
        assert cands, f"no unit strategy for n={n}, k={k}"
        for strat, est in cands:
            reds = strat.build(n, k)
            s = unit_schema(reds, bw, k)
            s.validate("a2a")
            assert np.isclose(est, s.communication_cost(), rtol=1e-9), (
                f"{strat.name}: estimate {est} != built "
                f"{s.communication_cost()} at n={n}, k={k}")

    def test_every_registered_strategy_exercised(self):
        seen = set()
        for n, k in [(23, 2), (31, 3), (25, 5), (30, 6), (11, 4),
                     (27, 3), (3, 8), (127, 12)]:
            bw = np.ones(n)
            for strat, _ in unit_estimates(bw, k):
                seen.add(strat.name)
        assert {"single", "alg_even", "alg_odd", "au_square",
                "au_projective", "alg3", "alg4"} <= seen

    def test_plan_unit_api_unchanged(self):
        reds, name = plan_unit(25, 5)
        assert name == "au_square"
        s = unit_schema(reds, np.ones(25), 5)
        s.validate("a2a")


class TestA2AEstimates:
    def test_strategy_estimates_exact(self):
        rng = np.random.default_rng(7)
        for _ in range(25):
            m = int(rng.integers(3, 50))
            w = rng.uniform(0.01, 0.5, m)
            if w.sum() <= 1.0:
                continue
            prof = A2AProfile(w, 1.0)
            for strat, est in a2a_portfolio(prof):
                s = strat.build(prof)
                assert np.isclose(est, s.communication_cost(), rtol=1e-9), (
                    f"{strat.name}: {est} != {s.communication_cost()}")

    def test_auto_matches_materialized_portfolio(self):
        rng = np.random.default_rng(11)
        for _ in range(20):
            m = int(rng.integers(2, 60))
            w = rng.uniform(0.01, 0.5, m)
            fast = plan_a2a(w, 1.0)
            fast.validate("a2a")
            slow = plan_a2a_materialized(w, 1.0)
            assert fast.communication_cost() <= \
                slow.communication_cost() + 1e-9

    def test_estimate_a2a_no_materialization_matches_plan(self):
        rng = np.random.default_rng(13)
        w = rng.uniform(0.02, 0.4, 40)
        name, est = estimate_a2a(w, 1.0)
        s = plan_a2a(w, 1.0)
        assert np.isclose(est, s.communication_cost(), rtol=1e-9)
        assert name in s.algorithm

    def test_big_input_estimate(self):
        w = np.array([0.6] + [0.05] * 20)
        name, est = estimate_a2a(w, 1.0)
        s = plan_a2a(w, 1.0)
        assert name.startswith("big-input")
        assert np.isclose(est, s.communication_cost(), rtol=1e-9)


# ------------------------------------------------------------- lower bounds
class TestLowerBoundWiring:
    def test_every_plan_carries_lower_bound(self):
        rng = np.random.default_rng(3)
        w = rng.uniform(0.02, 0.4, 30)
        for schema in (plan_a2a(w, 1.0),
                       plan_a2a(w, 1.0, method="binpack-k2"),
                       plan_a2a([0.6] + [0.05] * 10, 1.0),
                       plan_a2a([0.1, 0.2], 1.0),
                       naive_pairs(w, 1.0)):
            assert schema.lower_bound is not None
            gap = schema.optimality_gap()
            assert gap is not None and gap >= 0.999, schema.algorithm

    def test_gap_none_without_bound(self):
        s = MappingSchema(np.ones(2), 2.0, [[0], [1]], [[0, 1]])
        assert s.optimality_gap() is None


# ------------------------------------------------------------------- cache
class TestPlanCache:
    def test_permutation_hits_cache(self):
        rng = np.random.default_rng(5)
        w = rng.uniform(0.02, 0.4, 25)
        s1 = plan_a2a(w, 1.0)
        misses = PLAN_CACHE.misses
        perm = rng.permutation(len(w))
        s2 = plan_a2a(w[perm], 1.0)
        assert PLAN_CACHE.misses == misses     # pure hit
        assert PLAN_CACHE.hits >= 1
        s2.validate("a2a")
        assert np.isclose(s1.communication_cost(), s2.communication_cost())

    def test_remap_preserves_input_identity(self):
        w = np.array([0.3, 0.1, 0.25, 0.2])
        plan_a2a(w, 1.0)                       # prime the cache
        perm = np.array([2, 0, 3, 1])
        s = plan_a2a(w[perm], 1.0)
        # input i of the permuted call must carry weight w[perm][i]
        np.testing.assert_allclose(s.weights, w[perm])
        s.validate("a2a")

    def test_lru_eviction(self):
        cache = PlanCache(maxsize=2)
        cache.put(("a",), 1)
        cache.put(("b",), 2)
        cache.put(("c",), 3)
        assert len(cache) == 2
        assert cache.get(("a",)) is None
        assert cache.get(("c",)) == 3

    def test_use_cache_false_bypasses(self):
        w = np.full(10, 0.3)
        plan_a2a(w, 1.0, use_cache=False)
        assert len(PLAN_CACHE) == 0

    def test_registering_strategy_invalidates_cache(self):
        from repro.core import A2A_REGISTRY, register_a2a_strategy
        w = np.full(10, 0.3)
        plan_a2a(w, 1.0)
        assert len(PLAN_CACHE) > 0
        register_a2a_strategy(lambda prof: [])     # no-op strategy factory
        try:
            assert len(PLAN_CACHE) == 0            # stale plans dropped
        finally:
            A2A_REGISTRY.pop()


# ------------------------------------------------- remapped-schema memo
class TestSchemaMemo:
    """A repeat of one literal weight vector gets the same schema object
    back, so whatever is memoized on it is found again."""

    def _w(self, seed=5, m=25):
        return np.random.default_rng(seed).uniform(0.02, 0.4, m)

    def test_same_weights_same_schema(self):
        w = self._w()
        s1 = plan_a2a(w, 1.0)
        hits = PLAN_CACHE.hits
        s2 = plan_a2a(w.copy(), 1.0)       # equal values, another array
        assert s2 is s1
        assert PLAN_CACHE.hits == hits + 1  # the plan cache counts as before
        assert plan_a2a(w, 1.0, method="binpack-k2") is not s1
        assert plan_a2a(w, 2.0) is not s1

    def test_permutation_gets_new_schema_from_plan_hit(self):
        w = self._w()
        s1 = plan_a2a(w, 1.0)
        misses = PLAN_CACHE.misses
        perm = np.random.default_rng(6).permutation(len(w))
        s2 = plan_a2a(w[perm], 1.0)
        assert s2 is not s1
        assert PLAN_CACHE.misses == misses   # canonical entry hit
        s2.validate("a2a")
        np.testing.assert_allclose(s2.weights, w[perm])
        assert plan_a2a(w[perm], 1.0) is s2
        assert plan_a2a(w, 1.0) is s1

    def test_use_cache_false_stays_out(self):
        w = self._w()
        a = plan_a2a(w, 1.0, use_cache=False)
        b = plan_a2a(w, 1.0, use_cache=False)
        assert a is not b
        assert PLAN_CACHE.stats()["schemas"] == 0
        kept = plan_a2a(w, 1.0)
        assert plan_a2a(w, 1.0, use_cache=False) is not kept
        assert PLAN_CACHE.stats()["schemas"] == 1

    @pytest.mark.parametrize("drop", ["clear", "invalidate", "register"])
    def test_dropped_with_its_canonical_entry(self, drop):
        from repro.core import A2A_REGISTRY, register_a2a_strategy
        w = self._w()
        s1 = plan_a2a(w, 1.0)
        other = plan_a2a(self._w(seed=9), 1.0)
        if drop == "clear":
            PLAN_CACHE.clear()
        elif drop == "invalidate":
            order = np.argsort(-w, kind="stable")
            assert PLAN_CACHE.invalidate(PlanCache.key(w[order], 1.0, "auto"))
            # only the invalidated profile's schema went
            assert plan_a2a(self._w(seed=9), 1.0) is other
        else:
            register_a2a_strategy(lambda prof: [])
            A2A_REGISTRY.pop()
        s2 = plan_a2a(w, 1.0)
        assert s2 is not s1
        s2.validate("a2a")

    def test_evicts_at_its_bound(self):
        from repro.core.strategies import SCHEMA_MEMO_SIZE
        profiles = [self._w(seed=100 + i) for i in range(SCHEMA_MEMO_SIZE + 1)]
        first = [plan_a2a(w, 1.0) for w in profiles]
        assert PLAN_CACHE.stats()["schemas"] == SCHEMA_MEMO_SIZE
        assert len(PLAN_CACHE) == SCHEMA_MEMO_SIZE + 1  # canonical: all kept
        assert plan_a2a(profiles[-1], 1.0) is first[-1]
        assert plan_a2a(profiles[0], 1.0) is not first[0]   # evicted, oldest

    def _with_maps(self, schema, pad=1):
        """Lower ``schema`` and build its source map, as the fused path
        does; return the host bytes now memoized on it."""
        from repro.mapreduce.allpairs import _pair_source_map_rect, _plan_for
        plan = _plan_for(schema, pad_reducers_to=pad, pad_slots_to=pad)
        _pair_source_map_rect(plan, schema.m, schema.m, zero_diag=True)
        return schema_host_bytes(schema)

    def _perms(self, n):
        """``n`` orders of one weight vector: distinct memo keys whose
        plans and maps hold the same bytes."""
        w = self._w(m=40)
        return [w[np.random.default_rng(300 + i).permutation(len(w))]
                for i in range(n)]

    def test_bytes_bound_evicts_oldest(self, monkeypatch):
        import repro.core.strategies as strategies
        ws = self._perms(4)
        nb = self._with_maps(plan_a2a(ws[0], 1.0))
        PLAN_CACHE.clear()
        assert nb >= 40 * 40 * 4                  # the (m, m) source map
        bound = 2 * nb + nb // 2                  # room for two entries
        monkeypatch.setattr(strategies, "SCHEMA_MEMO_BYTES", bound)
        schemas = []
        for i, w in enumerate(ws):
            schemas.append(plan_a2a(w, 1.0))
            assert PLAN_CACHE.schema_bytes() <= bound
            if i < 3:
                assert self._with_maps(schemas[-1]) == nb
        # the fourth insert found 3 * nb kept and evicted the oldest
        assert PLAN_CACHE.stats()["schemas"] == 3
        assert PLAN_CACHE.schema_bytes() == 2 * nb
        assert plan_a2a(ws[3], 1.0) is schemas[3]
        assert plan_a2a(ws[1], 1.0) is schemas[1]
        assert plan_a2a(ws[0], 1.0) is not schemas[0]

    def test_bytes_bound_keeps_the_last_used(self, monkeypatch):
        """A schema holding more than the bound is kept while it is the
        one in use, and goes when another is looked up."""
        import repro.core.strategies as strategies
        monkeypatch.setattr(strategies, "SCHEMA_MEMO_BYTES", 1)
        ws = self._perms(2)
        s0 = plan_a2a(ws[0], 1.0)
        self._with_maps(s0)
        assert plan_a2a(ws[0], 1.0) is s0         # alone: kept
        s1 = plan_a2a(ws[1], 1.0)                 # s0's maps exceed the bound
        assert PLAN_CACHE.stats()["schemas"] == 1
        assert PLAN_CACHE.schema_bytes() == 0
        self._with_maps(s1)
        assert plan_a2a(ws[1], 1.0) is s1
        assert plan_a2a(ws[0], 1.0) is not s0

    def test_bytes_bound_counts_growth_on_hits(self, monkeypatch):
        """Maps built on a kept schema after its insert (another padding)
        count at the next lookup, a hit included."""
        import repro.core.strategies as strategies
        ws = self._perms(2)
        s0, s1 = plan_a2a(ws[0], 1.0), plan_a2a(ws[1], 1.0)
        nb = self._with_maps(s0)
        assert self._with_maps(s1) == nb
        monkeypatch.setattr(strategies, "SCHEMA_MEMO_BYTES", 2 * nb + nb // 2)
        assert plan_a2a(ws[0], 1.0) is s0         # 2 * nb kept: within
        assert plan_a2a(ws[1], 1.0) is s1
        assert self._with_maps(s0, pad=8) > nb    # s0 grows on its own
        assert plan_a2a(ws[1], 1.0) is s1         # a hit: s0 goes
        assert PLAN_CACHE.stats()["schemas"] == 1
        assert PLAN_CACHE.schema_bytes() == nb

    def test_canonical_eviction_drops_its_schemas(self):
        cache = PlanCache(maxsize=1)
        cache.put(("a",), 1)
        cache.put_schema(("la",), ("a",), "schema-a")
        cache.put_schema(("lz",), ("z",), "never kept")   # no entry "z"
        assert cache.get_schema(("la",)) == "schema-a"
        assert cache.get_schema(("lz",)) is None
        cache.put(("b",), 2)                              # evicts "a"
        assert cache.get_schema(("la",)) is None
        assert cache.stats()["schemas"] == 0

    def test_kept_schema_does_not_alias_the_caller(self):
        w = self._w()
        s = plan_a2a(w, 1.0)
        w_before = w.copy()
        w[:] = 0.1                            # the caller reuses its buffer
        np.testing.assert_array_equal(s.weights, w_before)
        assert not s.weights.flags.writeable

    def test_some_pairs_leaves_the_shared_schema_alone(self):
        w = self._w()
        pairs = [(i, j) for i in range(len(w)) for j in range(i + 1, len(w))]
        s = plan_a2a(w, 1.0)
        algorithm, meta = s.algorithm, dict(s.meta)
        sp = plan_some_pairs(w, 1.0, pairs, method="a2a")
        assert sp.algorithm.startswith("some-pairs:a2a:")
        assert plan_a2a(w, 1.0) is s
        assert s.algorithm == algorithm and s.meta == meta


# ------------------------------------------------------ X2Y schema memo
class TestX2YSchemaMemo:
    """``plan_x2y`` keeps the plan-reuse contract of ``plan_a2a``: a
    repeat of one literal ``(wx, wy, q, num_splits)`` gets the same schema
    object back, through the same ``PLAN_CACHE`` store and memo."""

    def _w(self, seed=5, mx=30, my=22):
        rng = np.random.default_rng(seed)
        return rng.uniform(0.02, 0.4, mx), rng.uniform(0.02, 0.4, my)

    @staticmethod
    def _valid(schema, wx, wy):
        mx = len(wx)
        schema.validate("x2y", x_ids=range(mx),
                        y_ids=range(mx, mx + len(wy)))
        np.testing.assert_array_equal(schema.weights,
                                      np.concatenate([wx, wy]))

    def test_same_weights_same_schema(self):
        from repro.core import plan_x2y
        wx, wy = self._w()
        s1 = plan_x2y(wx, wy, 1.0)
        hits = PLAN_CACHE.hits
        assert plan_x2y(wx.copy(), list(wy), 1.0) is s1
        assert PLAN_CACHE.hits == hits + 1    # the store counts as a2a's
        assert plan_x2y(wx, wy, 1.0, num_splits=4) is not s1
        assert plan_x2y(wx, wy, 1.5) is not s1
        assert plan_x2y(wy, wx, 1.0) is not s1    # the sides swapped
        assert PLAN_CACHE.stats()["schemas"] == 4
        self._valid(s1, wx, wy)

    @pytest.mark.parametrize("change", ["permute_x", "permute_y", "value"])
    def test_other_weights_get_a_fresh_exact_schema(self, change):
        from repro.core import plan_x2y
        wx, wy = self._w()
        s1 = plan_x2y(wx, wy, 1.0)
        misses = PLAN_CACHE.misses
        perm = np.random.default_rng(6).permutation
        if change == "permute_x":
            wx2, wy2 = wx[perm(len(wx))], wy
        elif change == "permute_y":
            wx2, wy2 = wx, wy[perm(len(wy))]
        else:
            wx2, wy2 = wx, wy.copy()
            wy2[3] = 0.3 if wy2[3] != 0.3 else 0.2
        s2 = plan_x2y(wx2, wy2, 1.0)
        assert s2 is not s1
        # a permutation is a canonical hit; a new value plans anew
        assert PLAN_CACHE.misses == misses + (change == "value")
        self._valid(s2, wx2, wy2)
        assert s2.communication_cost() == pytest.approx(
            s2.meta["estimated_cost"])
        assert plan_x2y(wx2, wy2, 1.0) is s2
        assert plan_x2y(wx, wy, 1.0) is s1
        self._valid(s1, wx, wy)

    def test_matches_the_uncached_plan(self):
        """Planning in canonical order and remapping gives the schema a
        direct plan of the caller's order gives: same bins, reducers and
        split."""
        from repro.core import plan_x2y
        from repro.core.binpack import pack
        wx, wy = self._w(seed=11, mx=40, my=35)
        s = plan_x2y(wx, wy, 1.0)
        b = s.meta["b"]
        mx = len(wx)
        want = [list(bn) for bn in pack(wx, b, "best")] + \
            [[mx + i for i in bn] for bn in pack(wy, 1.0 - b, "best")]
        assert s.bins == want
        nx = s.meta["x_bins"]
        assert s.reducers == [[i, nx + j] for i in range(nx)
                              for j in range(len(want) - nx)]

    def test_kept_schema_does_not_alias_the_caller(self):
        from repro.core import plan_x2y
        wx, wy = self._w()
        s = plan_x2y(wx, wy, 1.0)
        before = np.concatenate([wx, wy])
        wx[:] = 0.1
        wy[:] = 0.1
        np.testing.assert_array_equal(s.weights, before)
        assert not s.weights.flags.writeable

    @staticmethod
    def _with_maps(schema, mx, my):
        """Lower ``schema`` and build its rect source map, as the fused
        x2y path does; return the plan and the map."""
        from repro.mapreduce.allpairs import (
            _pair_source_map_rect,
            _x2y_plan_for,
        )
        plan = _x2y_plan_for(schema, mx, pad_reducers_to=1, pad_slots_to=1)
        return plan, _pair_source_map_rect(plan, mx, my)

    def test_memo_bytes_count_the_rect_plan_and_map(self):
        from repro.core import plan_x2y
        wx, wy = self._w()
        s = plan_x2y(wx, wy, 1.0)
        plan, srcmap = self._with_maps(s, len(wx), len(wy))
        arrays = [plan.idx, plan.mask, plan.yidx, plan.ymask, srcmap]
        for b in plan.buckets:
            arrays += [b.rows, b.idx, b.mask, b.yidx, b.ymask]
        want = sum(a.nbytes for a in arrays)
        assert srcmap.shape == (len(wx), len(wy))
        assert schema_host_bytes(s) == want
        assert PLAN_CACHE.schema_bytes() == want

    def test_bytes_bound_keeps_the_entry_in_use(self, monkeypatch):
        """Under a byte bound the x2y and a2a schemas share one memo: the
        one looked up last stays whatever it holds, the older goes."""
        import repro.core.strategies as strategies
        from repro.core import plan_x2y
        wx, wy = self._w()
        s0 = plan_x2y(wx, wy, 1.0)
        self._with_maps(s0, len(wx), len(wy))
        monkeypatch.setattr(strategies, "SCHEMA_MEMO_BYTES", 1)
        assert plan_x2y(wx, wy, 1.0) is s0        # alone: kept
        a = plan_a2a(wx, 1.0)                     # s0's maps pass the bound
        assert PLAN_CACHE.stats()["schemas"] == 1
        assert plan_a2a(wx, 1.0) is a
        s1 = plan_x2y(wx, wy, 1.0)
        assert s1 is not s0
        self._valid(s1, wx, wy)
        self._with_maps(s1, len(wx), len(wy))
        assert plan_x2y(wx, wy, 1.0) is s1        # in use: kept
        assert PLAN_CACHE.stats()["schemas"] == 1

    def test_evicts_at_the_count_bound(self):
        from repro.core import plan_x2y
        from repro.core.strategies import SCHEMA_MEMO_SIZE
        profiles = [self._w(seed=200 + i)
                    for i in range(SCHEMA_MEMO_SIZE + 1)]
        first = [plan_x2y(wx, wy, 1.0) for wx, wy in profiles]
        assert PLAN_CACHE.stats()["schemas"] == SCHEMA_MEMO_SIZE
        assert plan_x2y(*profiles[-1], 1.0) is first[-1]
        assert plan_x2y(*profiles[0], 1.0) is not first[0]


# -------------------------------------------------------------- some pairs
class TestPlanSomePairs:
    def _random_instance(self, seed, m=30, density=0.2):
        rng = np.random.default_rng(seed)
        w = rng.uniform(0.02, 0.3, m)
        all_pairs = [(i, j) for i in range(m) for j in range(i + 1, m)]
        take = max(1, int(density * len(all_pairs)))
        idx = rng.choice(len(all_pairs), size=take, replace=False)
        return w, [all_pairs[i] for i in idx]

    @pytest.mark.parametrize("density", [0.02, 0.2, 0.8])
    def test_valid_and_bounded(self, density):
        w, pairs = self._random_instance(17, density=density)
        s = plan_some_pairs(w, 1.0, pairs)
        s.validate("some", required_pairs=pairs)
        assert s.lower_bound is not None
        assert s.communication_cost() >= \
            some_pairs_comm_lower_bound(w, 1.0, pairs) * 0.999

    def test_estimated_cost_exact(self):
        for density in (0.05, 0.3):
            w, pairs = self._random_instance(23, density=density)
            s = plan_some_pairs(w, 1.0, pairs)
            assert np.isclose(s.meta["estimated_cost"],
                              s.communication_cost(), rtol=1e-9), s.algorithm

    def test_sparse_cheaper_than_a2a(self):
        w, pairs = self._random_instance(29, m=40, density=0.05)
        sparse = plan_some_pairs(w, 1.0, pairs)
        dense = plan_a2a(w, 1.0)
        assert sparse.communication_cost() < dense.communication_cost()

    def test_duplicate_and_reversed_pairs_ignored(self):
        w = np.full(6, 0.2)
        s1 = plan_some_pairs(w, 1.0, [(0, 1), (1, 0), (0, 1), (2, 3)])
        assert s1.meta["required_pairs"] == 2
        s1.validate("some", required_pairs=[(0, 1), (2, 3)])

    def test_infeasible_pair_raises(self):
        with pytest.raises(InfeasibleError):
            plan_some_pairs([0.7, 0.6, 0.1], 1.0, [(0, 1)])

    def test_empty_pairs(self):
        s = plan_some_pairs([0.2, 0.3], 1.0, [])
        assert s.num_reducers == 0
        assert s.communication_cost() == 0.0

    def test_big_incident_input_falls_back(self):
        # one input > q/2 rules out the sparse-bin strategy but the pair
        # and a2a strategies still apply
        w = [0.6, 0.1, 0.1, 0.1]
        pairs = [(0, 1), (2, 3)]
        s = plan_some_pairs(w, 1.0, pairs)
        s.validate("some", required_pairs=pairs)


# ---------------------------------------------------------------------------
# Property tests (hypothesis-optional): rectangular and some-pairs planners
# ---------------------------------------------------------------------------
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.core import estimate_x2y, plan_x2y, x2y_comm_lower_bound  # noqa: E402


class TestX2YProperties:
    """Random rectangular profiles: the X2Y planner's schema covers
    exactly the cross pairs, respects capacity, and its recorded estimate
    equals the built schema's measured communication cost."""

    @staticmethod
    def _profile(seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(1, 12))
        n = int(rng.integers(1, 12))
        wx = rng.uniform(0.02, 0.45, m)
        wy = rng.uniform(0.02, 0.45, n)
        return wx, wy

    @settings(max_examples=40, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_random_rect_profile_valid_and_exact(self, seed):
        wx, wy = self._profile(seed)
        q = float(wx.max() + wy.max()) * np.random.default_rng(
            seed + 1).uniform(1.0, 3.0)
        schema = plan_x2y(wx, wy, q)
        m, n = len(wx), len(wy)
        schema.validate("x2y", x_ids=range(m), y_ids=range(m, m + n))
        # estimate == built cost (the contract that lets the b-sweep run
        # estimate-only and materialize just the winner)
        assert np.isclose(schema.meta["estimated_cost"],
                          schema.communication_cost(), rtol=1e-9)
        # ... and the sweep's own closed form agrees
        b, est = estimate_x2y(wx, wy, q)
        assert np.isclose(est, schema.communication_cost(), rtol=1e-9)
        assert schema.communication_cost() >= \
            x2y_comm_lower_bound(wx, wy, q) - 1e-9
        assert schema.lower_bound == pytest.approx(
            x2y_comm_lower_bound(wx, wy, q))

    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_covers_exactly_the_cross_pairs(self, seed):
        wx, wy = self._profile(seed)
        q = float(wx.max() + wy.max() + 0.1)
        m, n = len(wx), len(wy)
        schema = plan_x2y(wx, wy, q)
        met = set()
        for ids in schema.expand():
            xs = [i for i in ids if i < m]
            ys = [j for j in ids if j >= m]
            met.update((i, j) for i in xs for j in ys)
            # no same-side pair is ever *required* by X2Y; reducers are
            # one X bin against one Y bin so none can co-ship two bins of
            # the same side beyond what one bin holds
        want = {(i, j) for i in range(m) for j in range(m, m + n)}
        assert met == want


class TestSomePairsProperties:
    """Random required-pair subsets: the winning some-pairs strategy's
    schema covers exactly the required pairs and the estimate used for
    strategy selection equals the built cost."""

    @staticmethod
    def _instance(seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(2, 25))
        w = rng.uniform(0.02, 0.4, m)
        density = float(rng.uniform(0.05, 0.9))
        cand = [(i, j) for i in range(m) for j in range(i + 1, m)]
        take = rng.random(len(cand)) < density
        pairs = [p for p, t in zip(cand, take) if t]
        return w, pairs

    @settings(max_examples=40, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_random_pair_subset_valid_and_exact(self, seed):
        w, pairs = self._instance(seed)
        q = 1.0
        schema = plan_some_pairs(w, q, pairs)
        schema.validate("some", required_pairs=pairs)
        if not pairs:
            assert schema.communication_cost() == 0.0
            return
        assert np.isclose(schema.meta["estimated_cost"],
                          schema.communication_cost(), rtol=1e-9), \
            schema.algorithm
        assert schema.communication_cost() >= \
            some_pairs_comm_lower_bound(w, q, pairs) - 1e-9

    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_partial_cover_never_ships_pair_free_inputs_extra(self, seed):
        w, pairs = self._instance(seed)
        schema = plan_some_pairs(w, 1.0, pairs)
        if not schema.meta.get("partial_cover", False):
            return
        incident = {i for p in pairs for i in p}
        placed = {i for b in schema.bins for i in b}
        assert placed <= incident
