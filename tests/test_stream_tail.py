"""Stream-tail regressions: the drift-replan trigger, AOT delta-shape
warmup, background (double-buffered) re-plans, and local repacking.

Pins the BENCH_stream failure mode this work fixed: the Thm-8 bound is
~2x loose for binpack-k2, so a relative-only drift trigger measured
1.007x while the schema actually sat at gap 2.05x — ``drift_replans: 0``
forever.  The absolute ``max_gap`` ceiling (on the *achievable* gap) must
fire even with the relative trigger disabled.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import repro.stream as st
from repro.core import a2a_comm_lower_bound, plan_a2a
from repro.mapreduce import jit_cache_stats, make_executor
from repro.mapreduce import pairwise_similarity
from repro.mapreduce.allpairs import _block_fn_x2y

TOL = dict(rtol=1e-4, atol=1e-4)


def _zipf(rng, m, q):
    return np.clip(rng.zipf(1.6, m) / 32.0, 0.01, 0.45 * q)


def _service(m, q=1.0, d=8, seed=0, **load_kw):
    from repro.serve import PairwiseService
    rng = np.random.default_rng(seed)
    w = _zipf(rng, m, q)
    x = rng.normal(size=(m, d)).astype(np.float32)
    svc = PairwiseService(q, executor="streaming")
    sims, info = svc.load_table(x, w, **load_kw)
    return rng, svc, sims, info


def _cold_dense(svc):
    """Cold full re-plan on the dense executor: the oracle a streamed
    matrix must match on the active block."""
    planner = svc._planner
    act = planner.active_ids()
    wa = planner.active_weights()
    schema = plan_a2a(wa, svc.q, use_cache=False)
    sims, _, _ = pairwise_similarity(svc._table[act], q=svc.q, weights=wa,
                                     schema=schema, executor="dense")
    return np.asarray(sims), act


def _assert_conformant(planner):
    snap = planner.snapshot()
    snap.validate("a2a")
    assert abs(snap.communication_cost() - planner.comm_cost) < 1e-6


class TestAOTWarmup:
    def test_first_edit_compiles_nothing_new(self):
        # seed 0 picks binpack-k2 on this profile (partition schemas are
        # the warmable family; overlapping hybrid schemas are opaque to
        # delta_shapes and fall back to edit-time compilation)
        rng, svc, _, info0 = _service(64, seed=0, warmup=True)
        assert svc._planner.algorithm.startswith("binpack")
        assert info0["warmed_shapes"] > 0
        before = jit_cache_stats()
        _, info = svc.add_input(
            rng.normal(size=(1, 8)).astype(np.float32), 0.2)
        after = jit_cache_stats()
        # the cold tail: zero new programs AND zero new arg shapes on the
        # very first edit after load_table
        assert after["misses"] == before["misses"]
        assert after["shape_misses"] == before["shape_misses"]
        assert info["dirty_reducers"] >= 1

    def test_warmup_counts_into_executor_stats(self):
        _, svc, _, info0 = _service(64, seed=0, warmup=True)
        assert svc.executor_stats()["warmed_shapes"] == \
            info0["warmed_shapes"]

    def test_warmup_off_by_request(self):
        _, svc, _, info0 = _service(64, seed=0, warmup=False)
        assert info0["warmed_shapes"] == 0

    def test_x2y_first_edits_compile_nothing_new(self):
        rng = np.random.default_rng(0)
        d, q = 8, 4.0
        wx = np.clip(rng.zipf(1.6, 24) / 8.0, 0.05, 0.45 * q)
        wy = np.clip(rng.zipf(1.6, 16) / 8.0, 0.05, 0.45 * q)
        inc = st.IncrementalX2YPlanner(q, wx=wx, wy=wy)
        ex = make_executor("streaming")
        fn = _block_fn_x2y("dot")
        X = rng.normal(size=(24, d)).astype(np.float32)
        Y = rng.normal(size=(16, d)).astype(np.float32)
        ex.run_x2y((jnp.asarray(X), jnp.asarray(Y)), inc.plan(),
                   fn, (24, 16))
        warmed = ex.warm_delta_shapes_x2y(
            (jnp.asarray(X), jnp.asarray(Y)), inc.delta_shapes(), fn)
        assert warmed > 0
        before = jit_cache_stats()
        delta = inc.insert_x(0.7)
        X = np.concatenate([X, rng.normal(size=(1, d)).astype(np.float32)])
        ex.apply_delta_x2y((jnp.asarray(X), jnp.asarray(Y)), delta, fn,
                           (X.shape[0], Y.shape[0]), plan_provider=inc.plan)
        delta = inc.insert_y(0.5)
        Y = np.concatenate([Y, rng.normal(size=(1, d)).astype(np.float32)])
        ex.apply_delta_x2y((jnp.asarray(X), jnp.asarray(Y)), delta, fn,
                           (X.shape[0], Y.shape[0]), plan_provider=inc.plan)
        after = jit_cache_stats()
        assert after["misses"] == before["misses"]
        assert after["shape_misses"] == before["shape_misses"]


class TestMaxGapCeiling:
    def test_ceiling_fires_when_relative_trigger_is_dead(self):
        # the BENCH_stream regression: disable the relative trigger
        # entirely (replan_drift=1e9 — the old behaviour for a schema
        # whose theorem gap starts ~2x) and drift the profile with
        # deletions; the absolute ceiling on the achievable gap must
        # still fire
        rng, svc, _, _ = _service(
            128, seed=0, warmup=False, replan_drift=1e9, max_gap=1.05)
        planner = svc._planner
        assert planner.algorithm.startswith("binpack")
        # Thm 8 is loose for binpack-k2: the theorem gap sits far above
        # the achievable gap from the very first plan
        assert planner.optimality_gap > 1.5
        assert planner.achievable_gap < 1.3
        for _ in range(64):
            act = planner.active_ids()
            if len(act) <= 6:
                break
            svc.remove_input(int(rng.choice(act)))
            # the relative trigger alone would never have fired
            assert planner.gap_drift < 1e9
        assert planner.stats["drift_replans"] >= 1
        assert svc.stats["stream_replans"] >= 1
        _assert_conformant(planner)

    def test_lower_bound_recomputed_on_every_path(self):
        # repair, drift-replan and repack paths must all report bounds
        # for the *live* profile
        rng, svc, _, _ = _service(
            96, seed=0, warmup=False, max_gap=1.1, repack_gap=1.02)
        planner = svc._planner
        for _ in range(48):
            act = planner.active_ids()
            if rng.random() < 0.4 or len(act) <= 6:
                svc.add_input(rng.normal(size=(1, 8)).astype(np.float32),
                              float(_zipf(rng, 1, svc.q)[0]))
            else:
                svc.remove_input(int(rng.choice(act)))
            fresh = a2a_comm_lower_bound(planner.active_weights(), svc.q)
            assert planner.lower_bound == pytest.approx(fresh, rel=1e-12)
            assert planner.achievable_gap >= 1.0 - 1e-9
        # the churn exercised at least one non-repair path
        s = planner.stats
        assert s["drift_replans"] + s["repacks"] >= 1

    def test_x2y_ceiling_fires(self):
        rng = np.random.default_rng(1)
        q = 4.0
        wx = np.clip(rng.zipf(1.6, 32) / 8.0, 0.05, 0.45 * q)
        wy = np.clip(rng.zipf(1.6, 24) / 8.0, 0.05, 0.45 * q)
        inc = st.IncrementalX2YPlanner(q, wx=wx, wy=wy,
                                       replan_drift=1e9, max_gap=1.05)
        for _ in range(30):
            ax, ay = inc.active_x_ids(), inc.active_y_ids()
            if len(ax) > 4 and rng.random() < 0.6:
                delta = inc.delete_x(int(rng.choice(ax)))
            elif len(ay) > 4:
                delta = inc.delete_y(int(rng.choice(ay)))
            else:
                break
            delta.verify_x2y(inc.x_expanded(), inc.y_expanded(),
                             inc.active_x_ids(), inc.active_y_ids())
        assert inc.stats["drift_replans"] >= 1


class TestBackgroundReplan:
    def test_edits_during_inflight_replan_stay_correct(self):
        rng, svc, sims, _ = _service(
            64, seed=0, warmup=False, max_gap=1.02, background=True)
        planner = svc._planner
        pending = swaps = 0
        for _ in range(40):
            act = planner.active_ids()
            if rng.random() < 0.3 or len(act) < 6:
                sims, info = svc.add_input(
                    rng.normal(size=(1, 8)).astype(np.float32),
                    float(_zipf(rng, 1, svc.q)[0]))
            else:
                sims, info = svc.remove_input(int(rng.choice(act)))
            pending += int(info["replan_pending"])
            swaps += int(info["swap"])
            assert not info["full_replan"]
            ref, act = _cold_dense(svc)
            got = np.asarray(sims)[np.ix_(act, act)]
            np.testing.assert_allclose(got, ref, **TOL)
        # the replan genuinely ran off the edit path and landed
        assert pending >= 1
        assert swaps >= 1
        assert planner.stats["swaps"] == swaps == \
            svc.stats["stream_swaps"]
        # double-buffering: the executor's cold build was paid exactly
        # once, at load time — never on a replan
        assert svc.executor_stats()["full_builds"] == 1

    def test_swap_preserves_conformance_and_flush(self):
        rng, svc, _, _ = _service(
            64, seed=0, warmup=False, max_gap=1.02, background=True)
        planner = svc._planner
        for _ in range(40):
            act = planner.active_ids()
            if rng.random() < 0.3 or len(act) < 6:
                svc.add_input(rng.normal(size=(1, 8)).astype(np.float32),
                              float(_zipf(rng, 1, svc.q)[0]))
            else:
                svc.remove_input(int(rng.choice(act)))
            _assert_conformant(planner)
        svc.flush_replan()  # drain any still-in-flight plan
        _assert_conformant(planner)

    @staticmethod
    def _x2y_profile(seed=1, q=4.0):
        rng = np.random.default_rng(seed)
        return (rng, np.clip(rng.zipf(1.6, 32) / 8.0, 0.05, 0.45 * q),
                np.clip(rng.zipf(1.6, 24) / 8.0, 0.05, 0.45 * q))

    def test_x2y_background_replan_stays_off_the_plan_cache(self):
        """The daemon thread reads and writes no PLAN_CACHE state; the
        swap then drops the stream's superseded entry."""
        from repro.core.strategies import PLAN_CACHE
        PLAN_CACHE.clear()
        _, wx, wy = self._x2y_profile()
        inc = st.IncrementalX2YPlanner(4.0, wx=wx, wy=wy, background=True)
        before = PLAN_CACHE.stats()
        assert before["size"] == before["schemas"] == 1
        assert inc._start_background()
        inc._bg["thread"].join()
        assert inc._bg["error"] is None
        assert PLAN_CACHE.stats() == before
        assert inc.flush_replan()
        after = PLAN_CACHE.stats()
        assert (after["size"], after["schemas"]) == (0, 0)
        assert after["invalidations"] == before["invalidations"] + 1
        assert inc.stats["swaps"] == 1

    def test_x2y_sync_replans_keep_one_entry(self):
        """A synchronous re-plan drops the entry of the profile the stream
        left, so a churning stream holds one canonical entry."""
        from repro.core.strategies import PLAN_CACHE
        PLAN_CACHE.clear()
        rng, wx, wy = self._x2y_profile()
        inc = st.IncrementalX2YPlanner(4.0, wx=wx, wy=wy,
                                       replan_drift=1e9, max_gap=1.05)
        for _ in range(30):
            ax, ay = inc.active_x_ids(), inc.active_y_ids()
            if len(ax) > 4 and rng.random() < 0.6:
                inc.delete_x(int(rng.choice(ax)))
            elif len(ay) > 4:
                inc.delete_y(int(rng.choice(ay)))
            else:
                break
            assert PLAN_CACHE.stats()["size"] == 1
        assert inc.stats["drift_replans"] >= 1
        assert PLAN_CACHE.stats()["invalidations"] == \
            inc.stats["drift_replans"]


class TestRepack:
    def test_deletion_churn_triggers_repack(self):
        rng, svc, sims, _ = _service(
            128, seed=0, warmup=False, max_gap=3.0, repack_gap=1.0)
        planner = svc._planner
        repack_edits = 0
        for _ in range(64):
            act = planner.active_ids()
            if len(act) <= 6:
                break
            sims, info = svc.remove_input(int(rng.choice(act)))
            repack_edits += int(info["repack"])
        s = planner.stats
        assert s["repacks"] >= 1
        assert s["migrations"] >= 1
        assert repack_edits == s["repacks"] == svc.stats["stream_repacks"]
        # repacking is pure planning-state surgery: the served matrix is
        # untouched and still matches a cold re-plan
        ref, act = _cold_dense(svc)
        got = np.asarray(sims)[np.ix_(act, act)]
        np.testing.assert_allclose(got, ref, **TOL)
        _assert_conformant(planner)

    def test_repack_never_increases_cost(self):
        # churn with repacking disabled, then invoke the pass directly:
        # on a fixed profile, committed migrations + pruning can only
        # shave communication cost
        rng, svc, _, _ = _service(
            128, seed=0, warmup=False, replan_drift=1e9, max_gap=None)
        planner = svc._planner
        for _ in range(48):
            act = planner.active_ids()
            if len(act) <= 6:
                break
            svc.remove_input(int(rng.choice(act)))
        assert planner.kind == "binpack"
        cost_before = planner.comm_cost
        moved, pruned = planner._repack_pass()
        assert moved + pruned >= 1
        assert planner.comm_cost <= cost_before + 1e-9
        _assert_conformant(planner)
