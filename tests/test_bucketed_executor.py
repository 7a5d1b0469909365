"""Differential tests: bucketed executor == dense executor.

The bucketed (skew-aware) path must be a pure execution-plan change: same
outputs (allclose), same plan provenance (comm cost, algorithm, lower
bound), strictly fewer-or-equal padded gather elements.  Degenerate
schemas — single reducer, all-equal sizes, one giant input — are the cases
where bucket construction is most likely to be off by one.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (bucket_summary, compute_buckets,
                        compute_rect_buckets, plan_a2a)
from repro.core.planner import naive_pairs
from repro.mapreduce import (
    build_plan,
    pairwise_similarity,
    run_reducers,
    run_reducers_bucketed,
    some_pairs_similarity,
)


def _weights(kind: str, m: int, seed: int, q: float = 1.0):
    rng = np.random.default_rng(seed)
    return {
        "uniform": lambda: rng.uniform(0.05, 0.33, m),
        "zipf": lambda: np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45 * q),
        "equal": lambda: np.full(m, 0.21 * q),
        "one-giant": lambda: np.concatenate(
            [[0.8 * q], rng.uniform(0.02, 0.1, m - 1)]),
        "single-reducer": lambda: np.full(m, q / (m + 1)),
    }[kind]()


def _block_gram(blk, msk):
    s = blk @ blk.T
    v = msk[:, None] & msk[None, :]
    return jnp.where(v, s, 0.0)


# ------------------------------------------------------------ compute_buckets
class TestComputeBuckets:
    def test_partition_and_widths(self):
        counts = [1, 2, 3, 5, 9, 17, 33, 64, 64, 2]
        buckets = compute_buckets(counts)
        seen = np.concatenate([ids for _, ids in buckets])
        assert sorted(seen.tolist()) == list(range(len(counts)))
        for width, ids in buckets:
            for r in ids:
                assert counts[r] <= width          # never under-padded
        widths = [w for w, _ in buckets]
        assert widths == sorted(widths)
        assert max(widths) <= 64                   # clamped to dense width

    def test_max_buckets_merges_upward(self):
        counts = [1, 2, 4, 8, 16, 32, 64, 128, 256]
        buckets = compute_buckets(counts, max_buckets=3)
        assert len(buckets) <= 3
        for width, ids in buckets:
            for r in ids:
                assert counts[r] <= width

    def test_pad_slots_to_alignment(self):
        buckets = compute_buckets([3, 10, 100], pad_slots_to=8)
        for width, _ in buckets:
            assert width % 8 == 0

    def test_empty(self):
        assert compute_buckets([]) == []

    @given(st.lists(st.integers(1, 300), min_size=1, max_size=100),
           st.integers(1, 8))
    @settings(max_examples=40, deadline=None)
    def test_property_partition(self, counts, max_buckets):
        buckets = compute_buckets(counts, max_buckets=max_buckets)
        seen = sorted(int(i) for _, ids in buckets for i in ids)
        assert seen == list(range(len(counts)))
        assert len(buckets) <= max_buckets
        for width, ids in buckets:
            assert all(counts[r] <= width for r in ids)


# ------------------------------------------------------- compute_rect_buckets
def _rect_counts(seed: int, n: int):
    """Per-reducer X and Y slot counts: mostly small, a skewed tail up to
    300 on either side."""
    rng = np.random.default_rng(seed)
    xc = np.minimum(rng.zipf(1.5, n), 300)
    yc = np.minimum(rng.zipf(1.5, n), 300)
    return xc.tolist(), yc.tolist()


class TestComputeRectBuckets:
    @pytest.mark.parametrize("pad_slots_to", [1, 8])
    @pytest.mark.parametrize("max_buckets", [1, 3, 8])
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_contract(self, seed, max_buckets, pad_slots_to):
        """Every reducer in exactly one bucket, never narrower than its
        counts on either side; widths ``pad_slots_to * 2^j`` clamped to the
        dense width; at most ``max_buckets``; ascending area; sorted ids;
        the same buckets on a second call."""
        xc, yc = _rect_counts(seed, 400 + 100 * seed)
        buckets = compute_rect_buckets(xc, yc, pad_slots_to=pad_slots_to,
                                       max_buckets=max_buckets)
        ids = np.concatenate([r for _, _, r in buckets])
        assert sorted(ids.tolist()) == list(range(len(xc)))
        assert len(buckets) <= max_buckets
        dense = [-(-max(c) // pad_slots_to) * pad_slots_to for c in (xc, yc)]
        for wx, wy, rows in buckets:
            assert list(rows) == sorted(rows)
            assert all(xc[r] <= wx and yc[r] <= wy for r in rows)
            for w, dw in ((wx, dense[0]), (wy, dense[1])):
                t = w // pad_slots_to
                assert w == dw or (w % pad_slots_to == 0 and t & (t - 1) == 0)
                assert w <= dw
        areas = [wx * wy for wx, wy, _ in buckets]
        assert areas == sorted(areas)
        again = compute_rect_buckets(xc, yc, pad_slots_to=pad_slots_to,
                                     max_buckets=max_buckets)
        assert [(a, b, r.tolist()) for a, b, r in again] == \
            [(a, b, r.tolist()) for a, b, r in buckets]

    @pytest.mark.parametrize("seed", [0, 1])
    def test_shapes_do_not_depend_on_reducer_order(self, seed):
        xc, yc = _rect_counts(seed, 500)
        perm = np.random.default_rng(seed).permutation(len(xc))
        a = compute_rect_buckets(xc, yc)
        b = compute_rect_buckets([xc[i] for i in perm], [yc[i] for i in perm])
        assert [(wx, wy, len(r)) for wx, wy, r in a] == \
            [(wx, wy, len(r)) for wx, wy, r in b]
        for (_, _, ra), (_, _, rb) in zip(a, b):
            assert sorted(perm[rb].tolist()) == ra.tolist()

    def test_merges_the_sparse_wide_pairs_not_the_crowded_small_one(self):
        """1000 reducers of 4 x 4 and one each of 4 x 128 and 128 x 4, cut
        to two buckets.  Joining the two wide ones into 128 x 128 adds
        2·(16384 - 512) = 31,744 entries; folding the 4 x 4 crowd into
        4 x 128 would add 1000·(512 - 16) = 496,000."""
        xc = [4] * 1000 + [4, 128]
        yc = [4] * 1000 + [128, 4]
        got = compute_rect_buckets(xc, yc, max_buckets=2)
        assert [(wx, wy, len(r)) for wx, wy, r in got] == \
            [(4, 4, 1000), (128, 128, 2)]

    def test_small_reducers_stay_out_of_wide_buckets(self):
        """4000 reducers of 2-8 slots a side, beside 10 of 128 x w and 10 of
        w x 128 with w in 2-128: over 8 width pairs.  Each small reducer
        needs at most 8 x 8 entries and each wide one at most 128 x 128, so
        a merge that keeps them apart writes at most 4000·64 + 20·16384 =
        583,680 entries.  Merging by area alone cascades the small ones
        into 128 x 128 (over 65 M entries)."""
        rng = np.random.default_rng(7)
        small = rng.integers(2, 9, size=(4000, 2))
        other = 2 ** rng.integers(1, 8, size=20)
        xc = small[:, 0].tolist() + [128] * 10 + other[10:].tolist()
        yc = small[:, 1].tolist() + other[:10].tolist() + [128] * 10
        buckets = compute_rect_buckets(xc, yc)
        assert len(buckets) <= 8
        for wx, wy, rows in buckets:
            if (rows < 4000).any():
                assert wx * wy < 32 * 128, (wx, wy)
        padded = sum(wx * wy * len(r) for wx, wy, r in buckets)
        assert padded <= 4000 * 64 + 20 * 128 * 128

    def test_empty(self):
        assert compute_rect_buckets([], []) == []


# ----------------------------------------------------------- executor parity
KINDS = ["uniform", "zipf", "equal", "one-giant", "single-reducer"]


class TestExecutorDifferential:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("m", [5, 29])
    def test_block_outputs_allclose(self, kind, m):
        q = 1.0
        w = _weights(kind, m, seed=m)
        schema = plan_a2a(w, q)
        plan = build_plan(schema)
        rng = np.random.default_rng(m)
        x = jnp.asarray(rng.normal(size=(m, 6)).astype(np.float32))
        dense = run_reducers(x, plan, _block_gram)
        buck = run_reducers_bucketed(x, plan, _block_gram)
        assert dense.shape == buck.shape
        np.testing.assert_allclose(np.asarray(dense), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kind", KINDS)
    def test_reduction_outputs_allclose(self, kind):
        """Reducers whose output drops the slot axis entirely."""
        m, q = 17, 1.0
        w = _weights(kind, m, seed=3)
        plan = build_plan(plan_a2a(w, q))
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(m, 4)).astype(np.float32))
        fn = lambda blk, msk: jnp.sum(blk * msk[:, None], axis=0)
        dense = run_reducers(x, plan, fn)
        buck = run_reducers_bucketed(x, plan, fn)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)

    def test_plan_provenance_shared(self):
        """Bucketing changes execution layout only — cost, provenance and
        bounds are properties of the schema, identical on both paths."""
        w = _weights("zipf", 40, seed=9)
        schema = plan_a2a(w, 1.0)
        plan = build_plan(schema)
        assert plan.comm_cost == pytest.approx(schema.communication_cost())
        assert plan.algorithm == schema.algorithm
        assert plan.lower_bound == schema.lower_bound
        rows = np.concatenate([b.rows for b in plan.buckets])
        real = np.sort(rows[rows >= 0])
        assert real.tolist() == list(range(plan.num_reducers))
        valid_dense = int(plan.mask.sum())
        valid_buckets = int(sum(b.mask.sum() for b in plan.buckets))
        assert valid_dense == valid_buckets     # same shipped rows = comm cost
        assert plan.bucketed_padded_elements <= plan.dense_padded_elements

    def test_mesh_padded_rows(self):
        """pad_reducers_to pads every bucket to the device-count multiple."""
        w = _weights("zipf", 30, seed=11)
        plan = build_plan(plan_a2a(w, 1.0), pad_reducers_to=4)
        assert plan.R % 4 == 0
        for b in plan.buckets:
            assert b.R % 4 == 0
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(30, 5)).astype(np.float32))
        dense = run_reducers(x, plan, _block_gram)
        buck = run_reducers_bucketed(x, plan, _block_gram)
        n = plan.num_reducers
        np.testing.assert_allclose(np.asarray(dense[:n]),
                                   np.asarray(buck[:n]),
                                   rtol=1e-5, atol=1e-5)

    @given(st.lists(st.floats(0.02, 0.45), min_size=2, max_size=32),
           st.integers(0, 5))
    @settings(max_examples=25, deadline=None)
    def test_property_random_schemas(self, weights, seed):
        w = np.asarray(weights)
        schema = plan_a2a(w, 1.0)
        plan = build_plan(schema)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(len(w), 4)).astype(np.float32))
        dense = run_reducers(x, plan, _block_gram)
        buck = run_reducers_bucketed(x, plan, _block_gram)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------------- end-to-end (application)
class TestApplicationDifferential:
    @pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
    def test_pairwise_similarity_executors_agree(self, metric):
        m, q = 26, 1.0
        w = _weights("zipf", m, seed=7)
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(m, 8)).astype(np.float32))
        schema = plan_a2a(w, q)
        s_d, plan_d, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, metric=metric,
            executor="dense")
        s_b, plan_b, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, metric=metric,
            executor="bucketed")
        np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)
        assert plan_d.comm_cost == plan_b.comm_cost

    def test_some_pairs_executors_agree(self):
        m, q = 20, 1.0
        rng = np.random.default_rng(13)
        w = rng.uniform(0.02, 0.3, m)
        pairs = [(0, 1), (2, 9), (5, 17), (3, 4), (11, 12)]
        x = jnp.asarray(rng.normal(size=(m, 8)).astype(np.float32))
        s_d, _, sch = some_pairs_similarity(x, pairs, q=q, weights=w,
                                            executor="dense")
        s_b, _, _ = some_pairs_similarity(x, pairs, q=q, weights=w,
                                          schema=sch, executor="bucketed")
        np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    def test_naive_plan_buckets(self):
        """naive-pairs: every reducer has 2 slots -> exactly one bucket."""
        w = np.full(10, 0.3)
        plan = build_plan(naive_pairs(w, 1.0))
        assert plan.bucket_widths() == [2]
        assert plan.bucketed_padded_elements == plan.dense_padded_elements

    def test_summary_matches_plan(self):
        w = _weights("zipf", 35, seed=21)
        schema = plan_a2a(w, 1.0)
        plan = build_plan(schema)
        summ = bucket_summary(schema)
        assert summ["dense_padded_slots"] == plan.dense_padded_elements
        assert summ["num_reducers"] == plan.num_reducers
        # summary assumes no row padding; with pad_reducers_to=1 they agree
        assert summ["bucketed_padded_slots"] == plan.bucketed_padded_elements
        assert summ["padding_savings"] == pytest.approx(plan.padding_savings)
