"""Sharded executor + plan partitioning: differential and invariant tests.

The sharded executor must be a pure execution-plan change — identical
outputs to the bucketed and dense executors on random, Zipf-skewed, and
degenerate schemas — and ``partition_plan`` must preserve the plan's
coverage/capacity structure on every shard while keeping the LPT balance
tight.  The in-process tests run at whatever local device count the main
test process has (1 on plain CPU); the subprocess test forces an 8-device
CPU mesh via ``XLA_FLAGS`` to exercise real multi-shard ``shard_map``
execution, like ``make bench-sharded`` does.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition_plan, plan_a2a
from repro.core.planner import reducer_work
from repro.mapreduce import (
    build_plan,
    get_executor,
    list_executors,
    make_executor,
    pairwise_similarity,
    run_reducers,
    some_pairs_similarity,
)
from repro.mapreduce.allpairs import _block_fn
from repro.mapreduce.engine import ReducerBucket, ReducerPlan


def _weights(kind: str, m: int, seed: int, q: float = 1.0):
    rng = np.random.default_rng(seed)
    return {
        "uniform": lambda: rng.uniform(0.05, 0.33, m),
        "zipf": lambda: np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45 * q),
        "one-giant": lambda: np.concatenate(
            [[0.8 * q], rng.uniform(0.02, 0.1, m - 1)]),
    }[kind]()


def _rand(rng, shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


# ----------------------------------------------------------------- registry
class TestExecutorRegistry:
    def test_all_executors_registered(self):
        core = {"bucketed", "dense", "fused", "sharded", "coded"}
        assert core.issubset(set(list_executors()))
        # the streaming executor registers lazily on first resolution
        get_executor("streaming")
        assert set(list_executors()) == core | {"streaming"}

    def test_unknown_executor_raises(self):
        with pytest.raises(ValueError, match="unknown executor"):
            get_executor("warp-drive")
        x = jnp.ones((4, 3), jnp.float32)
        with pytest.raises(ValueError, match="unknown executor"):
            pairwise_similarity(x, q=1.0, weights=np.full(4, 0.2),
                                executor="warp-drive")

    def test_instances_pass_through(self):
        ex = get_executor("bucketed")
        assert get_executor(ex) is ex

    def test_make_executor_is_instance_scoped(self):
        """Fresh instances own their counters: exercising one never moves
        another's — the PairwiseService isolation contract."""
        a = make_executor("fused")
        b = make_executor("fused")
        default = get_executor("fused")
        base_b = b.stats()["calls"]
        base_d = default.stats()["calls"]
        w = np.full(6, 0.3)
        plan = build_plan(plan_a2a(w, 1.0))
        x = jnp.ones((6, 3), jnp.float32)
        a.run(x, plan, _block_fn("dot", False))
        assert a.stats()["calls"] == 1
        assert b.stats()["calls"] == base_b
        assert default.stats()["calls"] == base_d

    def test_reset_is_instance_scoped(self):
        a = make_executor("sharded")
        a._count("calls")
        a.reset()
        assert a.stats()["calls"] == 0


# ----------------------------------------------------------- partition_plan
class TestPartitionPlan:
    @pytest.mark.parametrize("kind", ["uniform", "zipf", "one-giant"])
    @pytest.mark.parametrize("num_shards", [1, 3, 8])
    def test_coverage_and_capacity_preserved(self, kind, num_shards):
        """Every real reducer lands in exactly one shard with its idx/mask
        rows verbatim — coverage and reducer capacity are untouched."""
        m = 31
        plan = build_plan(plan_a2a(_weights(kind, m, seed=m), 1.0))
        part = partition_plan(plan, num_shards)
        all_rows = np.concatenate([r for r in part.shard_rows]
                                  ) if plan.num_reducers else np.zeros(0)
        np.testing.assert_array_equal(np.sort(all_rows),
                                      np.arange(plan.num_reducers))
        for rows, sub in zip(part.shard_rows, part.shards):
            assert sub.num_reducers == len(rows)
            np.testing.assert_array_equal(sub.idx, plan.idx[rows])
            np.testing.assert_array_equal(sub.mask, plan.mask[rows])

    @pytest.mark.parametrize("num_shards", [2, 5])
    def test_comm_cost_and_shipped_rows_conserved(self, num_shards):
        """The schema's communication cost is a cluster quantity: the
        per-shard shares must sum back to the plan totals (>= the lower
        bound the schema already certifies)."""
        plan = build_plan(plan_a2a(_weights("zipf", 40, seed=7), 1.0))
        part = partition_plan(plan, num_shards)
        assert int(part.shipped_rows.sum()) == int(plan.mask.sum())
        assert float(part.comm_cost.sum()) == pytest.approx(plan.comm_cost)
        assert sum(s.comm_cost for s in part.shards) == \
            pytest.approx(plan.comm_cost)
        if plan.lower_bound:
            assert part.comm_cost.sum() >= plan.lower_bound - 1e-9

    @pytest.mark.parametrize("kind", ["uniform", "zipf", "one-giant"])
    @pytest.mark.parametrize("num_shards", [2, 4, 8])
    def test_greedy_balance_bound(self, kind, num_shards):
        """LPT guarantee: max load <= mean + max single-reducer work, i.e.
        balance_factor <= 1 + S * max_work / total_work."""
        m = 48
        plan = build_plan(plan_a2a(_weights(kind, m, seed=m), 1.0))
        part = partition_plan(plan, num_shards)
        work = reducer_work(plan)
        if work.sum() > 0:
            bound = 1.0 + num_shards * float(work.max()) / float(work.sum())
            assert 1.0 <= part.balance_factor <= bound + 1e-9

    def test_zipf_m512_balance_meets_acceptance_bar(self):
        """The acceptance-criteria partition: Zipf m=512, 8 shards,
        LPT balance factor <= 1.25 (pure host work — no execution)."""
        rng = np.random.default_rng(0)
        w = np.clip(rng.zipf(1.6, 512).astype(np.float64) / 32.0,
                    0.01, 0.45)
        plan = build_plan(plan_a2a(w, 1.0))
        part = partition_plan(plan, 8)
        assert part.balance_factor <= 1.25, part.report()

    def test_sub_plan_buckets_are_consistent(self):
        """Sub-plan buckets re-index rows locally and keep idx/mask rows
        aligned with the sub-plan's own row order."""
        plan = build_plan(plan_a2a(_weights("zipf", 37, seed=3), 1.0))
        part = partition_plan(plan, 3)
        for sub in part.shards:
            seen = []
            for b in sub.buckets:
                assert np.all(b.rows >= 0)        # compact: no padding rows
                seen.extend(int(r) for r in b.rows)
                for i, local_row in enumerate(b.rows):
                    # bucket row i is sub-plan row local_row, truncated to
                    # the bucket width
                    np.testing.assert_array_equal(
                        b.idx[i], sub.idx[local_row][: b.width])
                    np.testing.assert_array_equal(
                        b.mask[i], sub.mask[local_row][: b.width])
            assert sorted(seen) == list(range(sub.num_reducers))

    def test_more_shards_than_reducers(self):
        """num_shards > R: singleton shards plus empties; coverage holds."""
        plan = build_plan(plan_a2a(np.full(4, 0.3), 1.0))
        part = partition_plan(plan, 16)
        nonempty = [r for r in part.shard_rows if len(r)]
        assert len(nonempty) == min(plan.num_reducers, 16)
        assert sum(len(r) for r in part.shard_rows) == plan.num_reducers

    def test_empty_plan(self):
        plan = build_plan(plan_a2a([], 1.0))
        part = partition_plan(plan, 4)
        assert part.balance_factor == 1.0
        assert all(len(r) == 0 for r in part.shard_rows)

    def test_bucketless_plan_uses_dense_width(self):
        """Plans with no capacity buckets fall back to the dense width as
        the per-reducer work unit."""
        idx = np.arange(6, dtype=np.int32).reshape(2, 3)
        mask = np.ones((2, 3), bool)
        plan = ReducerPlan(idx=idx, mask=mask, num_reducers=2,
                           comm_cost=6.0, max_inputs=3)
        part = partition_plan(plan, 2)
        assert [len(r) for r in part.shard_rows] == [1, 1]
        np.testing.assert_array_equal(part.widths, [3, 3])


# ------------------------------------------------------------- differential
KINDS = ["uniform", "zipf", "one-giant"]


class TestShardedExecutorDifferential:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("m", [5, 29])
    def test_pairwise_sharded_matches_bucketed_and_dense(self, kind, m):
        w = _weights(kind, m, seed=m)
        rng = np.random.default_rng(m)
        x = _rand(rng, (m, 6))
        schema = plan_a2a(w, 1.0)
        s_d, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="dense")
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="bucketed")
        s_s, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="sharded")
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_d),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
    def test_metrics_agree(self, metric):
        m = 26
        w = _weights("zipf", m, seed=7)
        rng = np.random.default_rng(7)
        x = _rand(rng, (m, 8))
        schema = plan_a2a(w, 1.0)
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        metric=metric, executor="bucketed")
        s_s, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        metric=metric, executor="sharded")
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    def test_dense_combine_run_matches_run_reducers(self):
        m = 23
        w = _weights("zipf", m, seed=3)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(5)
        x = _rand(rng, (m, 8))
        fn = _block_fn("dot", False)
        dense = run_reducers(x, plan, fn)
        sharded = get_executor("sharded").run(x, plan, fn)
        assert sharded.shape == dense.shape
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)

    def test_some_pairs_sharded_agrees(self):
        m = 20
        rng = np.random.default_rng(13)
        w = rng.uniform(0.02, 0.3, m)
        pairs = [(0, 1), (2, 9), (5, 17), (3, 4), (11, 12)]
        x = _rand(rng, (m, 8))
        s_b, _, sch = some_pairs_similarity(x, pairs, q=1.0, weights=w,
                                            executor="bucketed")
        s_s, _, _ = some_pairs_similarity(x, pairs, q=1.0, weights=w,
                                          schema=sch, executor="sharded")
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    def test_single_input_degenerate(self):
        x = jnp.ones((1, 4), jnp.float32)
        s_s, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="sharded")
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="bucketed")
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_b))

    def test_all_masked_bucket(self):
        """Handmade plan whose only bucket is entirely padding rows."""
        idx = np.zeros((2, 3), np.int32)
        mask = np.zeros((2, 3), bool)
        plan = ReducerPlan(
            idx=idx, mask=mask, num_reducers=0, comm_cost=0.0, max_inputs=3,
            buckets=(ReducerBucket(width=3,
                                   rows=np.full(2, -1, np.int64),
                                   idx=idx, mask=mask),))
        x = jnp.ones((4, 5), jnp.float32)
        fn = _block_fn("dot", False)
        ex = make_executor("sharded")
        out = ex.run(x, plan, fn)
        assert ex.stats()["fallbacks"] == 1       # no real reducers
        assert float(jnp.abs(out).max()) == 0.0

    def test_non_gram_reducer_falls_back(self):
        m = 17
        w = _weights("zipf", m, seed=3)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(5)
        x = _rand(rng, (m, 4))

        def colsum(blk, msk):
            return jnp.sum(blk * msk[:, None], axis=0)

        ex = make_executor("sharded")
        from repro.mapreduce import run_reducers_bucketed
        sharded = ex.run(x, plan, colsum)
        buck = run_reducers_bucketed(x, plan, colsum)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)
        assert ex.stats()["fallbacks"] == 1
        assert ex.stats()["calls"] == 1

    def test_sharded_telemetry_recorded(self):
        m = 19
        w = _weights("uniform", m, seed=2)
        rng = np.random.default_rng(2)
        x = _rand(rng, (m, 4))
        ex = make_executor("sharded")
        schema = plan_a2a(w, 1.0)
        pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                            executor=ex)
        st = ex.stats()
        assert st["sharded"] == 1
        assert st["num_shards"] >= 1
        assert st["balance_factor"] >= 1.0


# ------------------------------------------------- forced 8-device CPU mesh
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import partition_plan, plan_a2a
    from repro.mapreduce import build_plan, get_executor, \\
        pairwise_similarity

    rng = np.random.default_rng(0)
    for kind in ("uniform", "zipf", "one-giant"):
        m = 48
        if kind == "uniform":
            w = rng.uniform(0.05, 0.33, m)
        elif kind == "zipf":
            w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
        else:
            w = np.concatenate([[0.8], rng.uniform(0.02, 0.1, m - 1)])
        x = jnp.asarray(rng.normal(size=(m, 6)).astype(np.float32))
        schema = plan_a2a(w, 1.0)
        s_d, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="dense")
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="bucketed")
        s_s, plan, _ = pairwise_similarity(x, q=1.0, weights=w,
                                           schema=schema,
                                           executor="sharded")
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_d),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_s), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)
        part = partition_plan(plan, 8)
        assert all(len(r) >= 0 for r in part.shard_rows)
    st = get_executor("sharded").stats()
    assert st["num_shards"] == 8, st
    print("SHARDED_OK", st["balance_factor"])
""")


def test_sharded_differential_on_8_device_mesh():
    """sharded == bucketed == dense under a real 8-shard shard_map mesh
    (subprocess: the main test process keeps its default device count)."""
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             # force-host-device script must not probe TPU hardware
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "HOME": os.environ.get("HOME", "/tmp")},
    )
    assert "SHARDED_OK" in res.stdout, res.stdout + res.stderr
