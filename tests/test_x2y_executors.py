"""Rectangular (X2Y) execution: kernel, partition, streaming, skew join.

The X2Y differential suite behind the conformance matrix: the rectangular
fused gather+Gram kernel against its materializing oracle (multi-tile,
bf16, masked tails, non-power-of-two |X| != |Y|), ``partition_plan``
invariants on rectangular sub-plans, streaming edits on both the X and Y
sides with ``PlanDelta.verify_x2y`` coverage proofs and
streamed == cold-dense equality after every edit, and the
``skew_join(executor=...)`` regression on the paper's Example 3
heavy-hitter profile.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition_plan, plan_x2y
from repro.core.planner import reducer_work
from repro.kernels.pairwise.fused_gather_gram import (
    fused_gather_gram_rect,
    fused_gather_gram_rect_ref,
    fused_gather_gram_rect_streamed,
)
from repro.mapreduce import build_x2y_plan, skew_join
from repro.mapreduce.allpairs import (
    _block_fn_x2y,
    block_similarity_x2y,
    x2y_similarity,
)

TOL = dict(rtol=1e-5, atol=1e-5)


def _rect_case(R, Lx, Ly, mx, my, d, seed, dtype=np.float32,
               tail_masks=True):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(mx, d)).astype(dtype))
    y = jnp.asarray(rng.normal(size=(my, d)).astype(dtype))
    xidx = jnp.asarray(rng.integers(0, mx, size=(R, Lx)), jnp.int32)
    yidx = jnp.asarray(rng.integers(0, my, size=(R, Ly)), jnp.int32)
    if tail_masks:
        xmask = jnp.asarray(
            np.arange(Lx)[None, :] < rng.integers(1, Lx + 1, size=(R, 1)))
        ymask = jnp.asarray(
            np.arange(Ly)[None, :] < rng.integers(1, Ly + 1, size=(R, 1)))
    else:
        xmask = jnp.ones((R, Lx), bool)
        ymask = jnp.ones((R, Ly), bool)
    return x, y, xidx, xmask, yidx, ymask


class TestRectKernel:
    """Rect Pallas kernel (interpret mode) == streamed twin == oracle."""

    @pytest.mark.parametrize("R,Lx,Ly,bl", [
        (3, 8, 8, 8),              # single tile per side
        (5, 19, 11, 8),            # multi-tile, masked tails, |X| != |Y|
        (4, 9, 9, 8),              # square through the rect path
        (2, 7, 23, 8),             # non-pow2, Y side much wider
    ])
    def test_kernel_matches_reference(self, R, Lx, Ly, bl):
        x, y, xidx, xmask, yidx, ymask = _rect_case(
            R, Lx, Ly, mx=31, my=17, d=6, seed=R + Lx)
        ref = fused_gather_gram_rect_ref(x, y, xidx, xmask, yidx, ymask)
        got = fused_gather_gram_rect(x, y, xidx, xmask, yidx, ymask,
                                     bl=bl, interpret=True)
        streamed = fused_gather_gram_rect_streamed(x, y, xidx, xmask,
                                                   yidx, ymask, bl=bl)
        assert got.shape == (R, Lx, Ly)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
        np.testing.assert_allclose(np.asarray(streamed), np.asarray(ref),
                                   **TOL)

    def test_bf16_tables_accumulate_fp32(self):
        x, y, xidx, xmask, yidx, ymask = _rect_case(
            4, 12, 7, mx=20, my=15, d=8, seed=0)
        xb = x.astype(jnp.bfloat16)
        yb = y.astype(jnp.bfloat16)
        ref = fused_gather_gram_rect_ref(xb, yb, xidx, xmask, yidx, ymask)
        got = fused_gather_gram_rect(xb, yb, xidx, xmask, yidx, ymask,
                                     bl=8, interpret=True)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-2, atol=1e-2)

    def test_all_masked_rows_are_zero(self):
        x, y, xidx, _, yidx, ymask = _rect_case(
            3, 5, 4, mx=9, my=9, d=3, seed=2, tail_masks=False)
        xmask = jnp.zeros((3, 5), bool)
        got = fused_gather_gram_rect(x, y, xidx, xmask, yidx, ymask,
                                     bl=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got), 0.0)

    def test_zero_reducers(self):
        x, y, *_ = _rect_case(1, 4, 4, mx=5, my=5, d=3, seed=3)
        e = jnp.zeros((0, 4), jnp.int32)
        m = jnp.zeros((0, 4), bool)
        got = fused_gather_gram_rect(x, y, e, m, e, m, bl=8,
                                     interpret=True)
        assert got.shape == (0, 4, 4)

    @pytest.mark.parametrize("R,Lx,Ly,d,dtype", [
        (2100, 8, 4, 8, np.float32),       # whole-plan SMEM prefetch > 1 MiB
        (5, 19, 11, 64, np.float32),       # rows narrower than 128 lanes
        (4, 12, 20, 16, jnp.bfloat16),     # packed dtype, masked slots
    ], ids=["smem-overflow-R", "d64", "bf16-masked"])
    def test_chip_layout_repairs(self, R, Lx, Ly, d, dtype):
        """Plans the chip's compiler once refused.  Masked slots carry
        out-of-range ids: the kernel must issue no copy for them."""
        x, y, xidx, xmask, yidx, ymask = _rect_case(
            R, Lx, Ly, mx=40, my=30, d=d, seed=R + d)
        x, y = x.astype(dtype), y.astype(dtype)
        ref = fused_gather_gram_rect_ref(x, y, xidx, xmask, yidx, ymask)
        got = fused_gather_gram_rect(
            x, y, jnp.where(xmask, xidx, 40 + 7), xmask,
            jnp.where(ymask, yidx, 30 + 7), ymask, bl=8, interpret=True)
        assert got.shape == (R, Lx, Ly) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


class TestRectPartition:
    """``partition_plan`` on rectangular plans: coverage, both-side
    sub-plan fidelity, and rect-aware (wx + wy + flop*wx*wy) work."""

    def _plan(self, seed=0, q=8.0):
        rng = np.random.default_rng(seed)
        wx = rng.integers(1, 4, size=14).astype(float)
        wy = rng.integers(1, 3, size=10).astype(float)
        schema = plan_x2y(wx, wy, q)
        return build_x2y_plan(schema, 14)

    @pytest.mark.parametrize("num_shards", [2, 3, 8])
    def test_partition_preserves_rect_rows(self, num_shards):
        plan = self._plan()
        part = partition_plan(plan, num_shards)
        all_rows = np.sort(np.concatenate(list(part.shard_rows)))
        np.testing.assert_array_equal(all_rows,
                                      np.arange(plan.num_reducers))
        assert part.ywidths is not None
        for rows, sub in zip(part.shard_rows, part.shards):
            np.testing.assert_array_equal(sub.idx, plan.idx[rows])
            np.testing.assert_array_equal(sub.mask, plan.mask[rows])
            # the Y side travels with the sub-plan
            np.testing.assert_array_equal(sub.yidx, plan.yidx[rows])
            np.testing.assert_array_equal(sub.ymask, plan.ymask[rows])
            assert sub.num_x == plan.num_x and sub.num_y == plan.num_y

    def test_rect_reducer_work_counts_both_sides(self):
        plan = self._plan()
        work = reducer_work(plan, flop_weight=0.0)
        xs = plan.mask[: plan.num_reducers].sum(axis=1)
        # zero flop weight -> work is the two execution widths summed,
        # which upper-bounds the true slot counts
        assert np.all(work[: plan.num_reducers] >= xs)

    def test_shipped_slots_count_both_sides(self):
        plan = self._plan()
        part = partition_plan(plan, 4)
        total = plan.mask[: plan.num_reducers].sum() \
            + plan.ymask[: plan.num_reducers].sum()
        assert int(part.shipped_rows.sum()) == int(total)


class TestStreamingX2Y:
    """Insert/delete on both sides: every delta's coverage proof passes
    and the patched matrix equals a cold dense build after every edit."""

    def _cold_dense(self, inc, X, Y):
        ax, ay = inc.active_x_ids(), inc.active_y_ids()
        out = np.zeros((len(inc.wx), len(inc.wy)), np.float32)
        if len(ax) and len(ay):
            out[np.ix_(ax, ay)] = np.asarray(X)[ax] @ np.asarray(Y)[ay].T
        return out

    def test_edit_stream_matches_cold_dense(self):
        import repro.stream as st
        from repro.mapreduce import make_executor

        rng = np.random.default_rng(7)
        d, q = 4, 8.0
        inc = st.IncrementalX2YPlanner(q, wx=[2.0, 1.0, 3.0],
                                       wy=[1.0, 2.0])
        ex = make_executor("streaming")
        fn = _block_fn_x2y("dot")
        X = rng.normal(size=(3, d)).astype(np.float32)
        Y = rng.normal(size=(2, d)).astype(np.float32)

        sims = ex.run_x2y((jnp.asarray(X), jnp.asarray(Y)), inc.plan(),
                          fn, (3, 2))
        np.testing.assert_allclose(np.asarray(sims),
                                   self._cold_dense(inc, X, Y), **TOL)

        ops = [("ix", 1.5), ("iy", 2.5), ("dx", 1), ("iy", 0.5),
               ("ix", 2.0), ("dy", 0), ("ix", 1.0), ("iy", 1.5),
               ("dx", 0), ("ix", 3.0), ("dy", 2), ("iy", 2.0)]
        saw_delta = saw_both_sides = 0
        for kind, arg in ops:
            if kind == "ix":
                delta = inc.insert_x(arg)
                X = np.concatenate(
                    [X, rng.normal(size=(1, d)).astype(np.float32)])
            elif kind == "iy":
                delta = inc.insert_y(arg)
                Y = np.concatenate(
                    [Y, rng.normal(size=(1, d)).astype(np.float32)])
            elif kind == "dx":
                delta = inc.delete_x(arg)
            else:
                delta = inc.delete_y(arg)
            # re-run the coverage proof explicitly (check=True already ran
            # it on the dirty subset; this is the full-expansion variant)
            delta.verify_x2y(inc.x_expanded(), inc.y_expanded(),
                             inc.active_x_ids(), inc.active_y_ids())
            sims = ex.apply_delta_x2y(
                (jnp.asarray(X), jnp.asarray(Y)), delta, fn,
                (X.shape[0], Y.shape[0]), plan_provider=inc.plan)
            np.testing.assert_allclose(
                np.asarray(sims), self._cold_dense(inc, X, Y),
                err_msg=f"{kind}({arg}) kind={delta.kind}", **TOL)
            saw_delta += int(not delta.full_replan)
            saw_both_sides += int(delta.kind in ("insert_y", "delete_y"))
        # the stream actually exercised the patch path on both sides
        assert saw_delta > 0 and saw_both_sides > 0
        st_stats = ex.stats()
        assert st_stats["delta_updates"] > 0

    def test_insert_infeasible_rolls_back(self):
        import repro.stream as st
        inc = st.IncrementalX2YPlanner(4.0, wx=[2.0], wy=[1.0])
        from repro.core.schema import InfeasibleError
        with pytest.raises(InfeasibleError):
            inc.insert_x(100.0)
        assert len(inc.wx) == 1 and inc.num_active_x == 1

    def test_one_sided_bootstrap(self):
        """Start with only X inputs (no cross pairs), then grow Y."""
        import repro.stream as st
        inc = st.IncrementalX2YPlanner(6.0, wx=[2.0, 3.0])
        assert inc.num_reducers == 0 and inc.comm_cost == 0.0
        delta = inc.insert_y(2.0)          # first Y forces a real split
        # forced re-plans are patch deltas now: the fresh plan is adopted
        # as planning state, but only the new input's reducers recompute
        assert delta.meta.get("replan") and delta.meta.get("forced")
        assert not delta.full_replan
        assert len(delta.dirty_rows) >= 1
        assert inc.num_reducers >= 1
        plan = inc.plan()
        assert plan.is_rect
        # every live cross pair covered
        covered = {(i, j)
                   for xs, ys in zip(inc.x_expanded(), inc.y_expanded())
                   for i in xs for j in ys}
        want = {(int(i), int(j)) for i in inc.active_x_ids()
                for j in inc.active_y_ids()}
        assert want <= covered


class TestSkewJoinExecutors:
    """Example 3 heavy-hitter profile: join through every executor equals
    the dense join (the documented ``executor=`` contract is real)."""

    def _example3(self):
        # one heavy B-value: 200 X-tuples, 8 Y-tuples, sizes skewed
        rng = np.random.default_rng(42)
        mx, my = 40, 8                     # scaled-down Example 3 shape
        xv = rng.normal(size=(mx, 3)).astype(np.float32)
        yv = rng.normal(size=(my, 2)).astype(np.float32)
        wx = rng.uniform(0.01, 0.1, mx)
        wx[0] = 2.0                        # the heavy hitter
        wy = rng.uniform(0.01, 0.5, my)
        return xv, yv, wx, wy, 4.0

    @pytest.mark.parametrize("executor",
                             ["bucketed", "fused", "sharded", "streaming"])
    def test_join_matches_dense(self, executor):
        xv, yv, wx, wy, q = self._example3()
        ref, schema = skew_join(jnp.asarray(xv), jnp.asarray(yv), q=q,
                                wx=wx, wy=wy, executor="dense")
        out, _ = skew_join(jnp.asarray(xv), jnp.asarray(yv), q=q,
                           wx=wx, wy=wy, schema=schema, executor=executor)
        assert out.shape == ref.shape == (40, 8, 5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    def test_fused_counts_fallback_not_silence(self):
        """The join's reducer is not a Gram block: the fused executor must
        take (and count) its fallback rather than mis-fusing."""
        from repro.mapreduce import make_executor
        from repro.mapreduce.allpairs import _x2y_plan_for
        from repro.mapreduce.skewjoin import join_block
        xv, yv, wx, wy, q = self._example3()
        schema = plan_x2y(wx, wy, q)
        plan = _x2y_plan_for(schema, len(wx), pad_reducers_to=1,
                             pad_slots_to=1)
        ex = make_executor("fused")
        ex.run_x2y((jnp.asarray(xv), jnp.asarray(yv)), plan, join_block,
                   (len(wx), len(wy)))
        assert ex.stats()["fallbacks"] == 1


class TestX2YSimilarityExecutors:
    """x2y_similarity differential: all executors, all metrics."""

    @pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
    @pytest.mark.parametrize("executor",
                             ["bucketed", "fused", "sharded", "streaming"])
    def test_matches_dense(self, metric, executor):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(13, 5)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(9, 5)), jnp.float32)
        wx = rng.integers(1, 4, size=13).astype(float)
        wy = rng.integers(1, 3, size=9).astype(float)
        q = float(wx.max() + wy.max() + 1)
        ref, plan, schema = x2y_similarity(x, y, q=q, wx=wx, wy=wy,
                                           metric=metric, executor="dense")
        out, _, _ = x2y_similarity(x, y, q=q, schema=schema, metric=metric,
                                   executor=executor)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
        # the dense result itself equals the direct formula
        direct = block_similarity_x2y(x, jnp.ones(13, bool), y,
                                      jnp.ones(9, bool), metric=metric)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(direct),
                                   **TOL)

    def test_fused_kernel_interpret_path(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(11, 4)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
        ref, plan, schema = x2y_similarity(x, y, q=6.0, metric="cosine",
                                           executor="dense")
        out, _, _ = x2y_similarity(x, y, q=6.0, schema=schema,
                                   metric="cosine", executor="fused",
                                   use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    def test_square_degenerate_case_matches_allpairs(self):
        """X == Y through the rect path reproduces the square all-pairs
        result off the diagonal (the rect path has no self-pairs to
        zero)."""
        from repro.mapreduce.allpairs import pairwise_similarity
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(10, 4)), jnp.float32)
        sq, _, _ = pairwise_similarity(x, q=4.0, executor="bucketed")
        rect, _, _ = x2y_similarity(x, x, q=8.0, executor="bucketed")
        sq = np.asarray(sq)
        rect = np.asarray(rect)
        off = ~np.eye(10, dtype=bool)
        np.testing.assert_allclose(rect[off], sq[off], **TOL)


class TestX2YService:
    """``PairwiseService(executor="fused").x2y`` with the rect kernel in
    interpret mode, against a float64 NumPy reference, on Zipf sizes on
    both sides: the first request, a repeat of the same weights and a
    permuted profile."""

    @staticmethod
    def _fp32_bound(a, b):
        """|fl(a.b) - a.b| <= gamma_d |a| |b| for float32 dot products
        (Higham, Sec. 3.1), with u = 2^-24: the largest row norms bound
        every entry's error."""
        d = a.shape[1]
        u = 2.0 ** -24
        gamma = d * u / (1 - d * u)
        na = np.sqrt(np.einsum("ij,ij->i", a, a).max())
        nb = np.sqrt(np.einsum("ij,ij->i", b, b).max())
        return gamma * na * nb

    @pytest.mark.parametrize("side", ["x", "y"])
    def test_fused_kernel_matches_float64_reference(self, side):
        from repro.core import PLAN_CACHE
        from repro.serve import PairwiseService
        PLAN_CACHE.clear()
        rng = np.random.default_rng(17)
        mx, my, d, q = 192, 160, 128, 8.0

        def zipf(n):
            return np.clip(rng.zipf(1.6, n) / 32.0, 0.01, 0.45 * q)

        x = rng.standard_normal((mx, d), dtype=np.float32)
        y = rng.standard_normal((my, d), dtype=np.float32)
        wx, wy = zipf(mx), zipf(my)
        perm = rng.permutation(mx if side == "x" else my)
        pwx, pwy = (wx[perm], wy) if side == "x" else (wx, wy[perm])
        svc = PairwiseService(q=q, executor="fused", use_kernel=True,
                              interpret=True)
        ref = x.astype(np.float64) @ y.astype(np.float64).T
        bound = self._fp32_bound(x.astype(np.float64), y.astype(np.float64))
        plans = []
        for wxi, wyi in ((wx, wy), (wx.copy(), wy.copy()), (pwx, pwy)):
            sims, info = svc.x2y(x, y, wx=wxi, wy=wyi)
            got = np.asarray(sims, np.float64)
            assert got.shape == (mx, my)
            assert np.abs(got - ref).max() <= bound
            assert info["fused_path"] == "kernel"
            plans.append(plan_x2y(wxi, wyi, q))
        assert plans[1] is plans[0]            # the repeat: one schema
        assert plans[2] is not plans[0]        # the permutation: its own
        assert plans[0].num_reducers > 1


class TestMergedRectPlan:
    """Rect plans whose width pairs outnumber ``max_buckets``, so the
    bucket merge runs before any executor sees them."""

    @staticmethod
    def _cross_plan(seed):
        """Every X bin meets every Y bin (the Section 10 layout) over bins
        of 1-33 X rows and 1-20 Y rows, shuffled ids: 35 reducers in 35
        distinct width pairs, cut to 8 buckets."""
        from repro.mapreduce.engine import build_x2y_plan_arrays
        rng = np.random.default_rng(seed)
        xsz, ysz = [1, 2, 3, 5, 9, 17, 33], [1, 3, 6, 12, 20]
        xb = np.split(rng.permutation(sum(xsz)), np.cumsum(xsz)[:-1])
        yb = np.split(rng.permutation(sum(ysz)), np.cumsum(ysz)[:-1])
        xs = [a.tolist() for a in xb for _ in yb]
        ys = [b.tolist() for _ in xb for b in yb]
        return build_x2y_plan_arrays(xs, ys, num_x=sum(xsz), num_y=sum(ysz))

    @pytest.mark.parametrize("seed", [0, 1])
    def test_fused_matches_bucketed_and_float64(self, seed):
        from repro.core.planner import rect_width_pairs
        from repro.mapreduce import make_executor
        from repro.mapreduce.allpairs import assemble_x2y_matrix_bucketed
        from repro.mapreduce.engine import run_reducers_x2y_bucketed
        plan = self._cross_plan(seed)
        n = plan.num_reducers
        assert rect_width_pairs(plan.mask[:n].sum(1),
                                plan.ymask[:n].sum(1)) == 35
        assert len(plan.buckets) == 8
        mx, my = plan.num_x, plan.num_y
        rng = np.random.default_rng(seed + 10)
        xv = rng.standard_normal((mx, 16), dtype=np.float32)
        yv = rng.standard_normal((my, 16), dtype=np.float32)
        x, y = jnp.asarray(xv), jnp.asarray(yv)
        fn = _block_fn_x2y("dot")
        ref = xv.astype(np.float64) @ yv.astype(np.float64).T
        bucketed = np.asarray(assemble_x2y_matrix_bucketed(
            run_reducers_x2y_bucketed((x, y), plan, fn, combine="buckets"),
            (mx, my)))
        np.testing.assert_allclose(bucketed, ref, **TOL)
        for use_kernel in (False, True):
            got = make_executor("fused").run_x2y(
                (x, y), plan, fn, (mx, my), use_kernel=use_kernel,
                interpret=use_kernel)
            np.testing.assert_allclose(np.asarray(got), bucketed, **TOL)
            np.testing.assert_allclose(np.asarray(got), ref, **TOL)


    def test_block_subplan_span_records_the_merge(self):
        """A tile's sub-plan build records its bucket merge on the ``lower``
        span; a cache hit builds nothing and records nothing."""
        from repro import obs
        from repro.core import plan_a2a
        from repro.core.planner import rect_width_pairs
        from repro.mapreduce import block_subplan, build_sparse_plan
        w = np.random.default_rng(16).uniform(0.02, 0.3, 80)
        sparse = build_sparse_plan(plan_a2a(w, 1.0))
        prior = obs.configure()
        obs.configure(enabled=True)
        try:
            before = {s.span_id for s in obs.TRACER.spans()}
            plans = [block_subplan(sparse, 0, 40, 40, 80) for _ in range(2)]
            lower = [s for s in obs.TRACER.spans()
                     if s.name == "lower" and s.span_id not in before]
        finally:
            obs.configure(enabled=prior)
        plan = plans[0]
        assert plans[1] is plan
        n = plan.num_reducers
        assert [s.attrs["cached"] for s in lower] == [False, True]
        assert lower[0].attrs["width_pairs"] == rect_width_pairs(
            plan.mask[:n].sum(1), plan.ymask[:n].sum(1))
        assert lower[0].attrs["padded_entries"] == sum(
            b.R * b.width * b.ywidth for b in plan.buckets)
        assert "width_pairs" not in lower[1].attrs


class TestCellPlan:
    """The ``x2y.sift128-4k`` cell's own plan, from its configuration's
    size profiles (``bench/configs/sift128-x2y-4k.json``)."""

    def test_sift128_x2y_4k_buckets_pad_little(self):
        """133,125 reducers (375 X bins x 355 Y bins) in 49 width pairs, cut
        to 8 buckets.  Merging the two smallest-area pairs put 102,738 of
        them, 3.5 x 5.1 slots on average, in one 32 x 128 bucket: 717,037,568
        block entries.  The least-added-padding merge writes ~50.8 M."""
        import json
        import os
        import runpy

        from repro import obs
        from repro.mapreduce.allpairs import _x2y_plan_for
        bench = os.path.join(os.path.dirname(__file__), "..", "bench")
        sizes = runpy.run_path(os.path.join(bench, "traffic.py"))["sizes"]
        with open(os.path.join(bench, "configs", "sift128-x2y-4k.json")) as f:
            c = json.load(f)
        wx = sizes(c["sizes_x"], c["mx"], c["q"])
        wy = sizes(c["sizes_y"], c["my"], c["q"])
        schema = plan_x2y(wx, wy, c["q"])
        prior = obs.configure()
        obs.configure(enabled=True)
        try:
            before = {s.span_id for s in obs.TRACER.spans()}
            plan = _x2y_plan_for(schema, c["mx"], pad_reducers_to=1,
                                 pad_slots_to=1)
            lower = [s for s in obs.TRACER.spans()
                     if s.name == "lower" and s.span_id not in before]
        finally:
            obs.configure(enabled=prior)
        assert plan.num_reducers == 133_125 and len(plan.buckets) == 8
        padded = sum(b.R * b.width * b.ywidth for b in plan.buckets)
        assert padded <= 60_000_000
        assert len(lower) == 1 and lower[0].attrs["cached"] is False
        assert lower[0].attrs["width_pairs"] == 49
        assert lower[0].attrs["padded_entries"] == padded
