"""Differential tests: fused executor == bucketed == dense.

The fused gather+Gram path (kernel, streamed twin, and the one-program
executor with inverse-shuffle assembly) must be a pure execution-plan
change: identical outputs on random, Zipf-skewed, and degenerate schemas.
All Pallas paths run in ``interpret=True`` mode (CPU CI); the same
``pallas_call`` lowers to the TPU kernel (``tests/test_chip_compile.py``
compiles it for a described chip).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_a2a
from repro.kernels.pairwise.fused_gather_gram import (
    fused_gather_gram,
    fused_gather_gram_ref,
    fused_gather_gram_streamed,
)
from repro.kernels.pairwise.pairwise import (
    _clamp_block,
    min_tile_sublanes,
    pairwise_gram,
)
from repro.mapreduce import (
    build_plan,
    pairwise_similarity,
    run_reducers,
    get_executor,
    run_reducers_bucketed,
    some_pairs_similarity,
)
from repro.mapreduce import engine as engine_mod
from repro.mapreduce.allpairs import _block_fn
from repro.mapreduce.engine import ReducerBucket, ReducerPlan


def _weights(kind: str, m: int, seed: int, q: float = 1.0):
    rng = np.random.default_rng(seed)
    return {
        "uniform": lambda: rng.uniform(0.05, 0.33, m),
        "zipf": lambda: np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45 * q),
        "one-giant": lambda: np.concatenate(
            [[0.8 * q], rng.uniform(0.02, 0.1, m - 1)]),
        "single-reducer": lambda: np.full(m, q / (m + 1)),
    }[kind]()


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)


# ------------------------------------------------------------------- kernel
class TestFusedGatherGramKernel:
    @pytest.mark.parametrize("R,L,m,d,bl", [
        (3, 5, 17, 8, 8),          # single tile, ragged width
        (5, 16, 37, 16, 8),        # two row tiles
        (4, 24, 50, 12, 8),        # three row tiles
        (1, 1, 2, 4, 8),           # minimal
    ])
    def test_kernel_matches_ref(self, R, L, m, d, bl):
        rng = np.random.default_rng(R * 100 + L)
        x = _rand(rng, (m, d))
        idx = jnp.asarray(rng.integers(0, m, (R, L)).astype(np.int32))
        mask = jnp.asarray(rng.uniform(size=(R, L)) > 0.3)
        got = fused_gather_gram(x, idx, mask, bl=bl, interpret=True)
        ref = fused_gather_gram_ref(x, idx, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("L,bl", [(5, 8), (20, 8), (37, 16)])
    def test_streamed_matches_ref(self, L, bl):
        rng = np.random.default_rng(L)
        x = _rand(rng, (29, 8))
        idx = jnp.asarray(rng.integers(0, 29, (6, L)).astype(np.int32))
        mask = jnp.asarray(rng.uniform(size=(6, L)) > 0.4)
        got = fused_gather_gram_streamed(x, idx, mask, bl=bl)
        ref = fused_gather_gram_ref(x, idx, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_table(self):
        rng = np.random.default_rng(0)
        x = _rand(rng, (31, 16), jnp.bfloat16)
        idx = jnp.asarray(rng.integers(0, 31, (4, 18)).astype(np.int32))
        mask = jnp.asarray(rng.uniform(size=(4, 18)) > 0.3)
        got = fused_gather_gram(x, idx, mask, bl=16, interpret=True)
        ref = fused_gather_gram_ref(x, idx, mask)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_all_masked_rows_are_zero(self):
        rng = np.random.default_rng(1)
        x = _rand(rng, (11, 4))
        idx = jnp.asarray(rng.integers(0, 11, (3, 6)).astype(np.int32))
        mask = jnp.zeros((3, 6), bool)
        got = fused_gather_gram(x, idx, mask, bl=8, interpret=True)
        assert float(jnp.abs(got).max()) == 0.0

    @pytest.mark.parametrize("R,L,m,d,dtype", [
        (2100, 8, 64, 8, jnp.float32),     # whole-plan SMEM prefetch > 1 MiB
        (6, 20, 40, 64, jnp.float32),      # rows narrower than 128 lanes
        (5, 24, 40, 16, jnp.bfloat16),     # packed dtype, masked slots
    ], ids=["smem-overflow-R", "d64", "bf16-masked"])
    def test_chip_layout_repairs(self, R, L, m, d, dtype):
        """Plans the chip's compiler once refused.  Masked slots carry
        out-of-range ids: the kernel must issue no copy for them."""
        rng = np.random.default_rng(R + d)
        x = _rand(rng, (m, d), dtype)
        idx = rng.integers(0, m, (R, L)).astype(np.int32)
        mask = rng.uniform(size=(R, L)) > 0.3
        got = fused_gather_gram(x, jnp.asarray(np.where(mask, idx, m + 7)),
                                jnp.asarray(mask), bl=8, interpret=True)
        ref = fused_gather_gram_ref(x, jnp.asarray(idx), jnp.asarray(mask))
        assert got.shape == (R, L, L) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- executor
KINDS = ["uniform", "zipf", "one-giant", "single-reducer"]


class TestFusedExecutorDifferential:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("m", [5, 29])
    def test_dense_combine_matches_both_executors(self, kind, m):
        w = _weights(kind, m, seed=m)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(m)
        x = _rand(rng, (m, 6))
        fn = _block_fn("dot", False)
        dense = run_reducers(x, plan, fn)
        buck = run_reducers_bucketed(x, plan, fn)
        fused = get_executor("fused").run(x, plan, fn, use_kernel=False)
        assert fused.shape == dense.shape
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)

    def test_kernel_path_dense_combine(self):
        """The Pallas megakernel inside the fused program (interpret)."""
        m = 23
        w = _weights("zipf", m, seed=3)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(5)
        x = _rand(rng, (m, 8))
        fn = _block_fn("dot", False)
        dense = run_reducers(x, plan, fn)
        fused = get_executor("fused").run(x, plan, fn, use_kernel=True,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
    def test_pairwise_similarity_fused_agrees(self, metric):
        m, q = 26, 1.0
        w = _weights("zipf", m, seed=7)
        rng = np.random.default_rng(7)
        x = _rand(rng, (m, 8))
        schema = plan_a2a(w, q)
        s_b, plan_b, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, metric=metric,
            executor="bucketed")
        s_f, plan_f, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, metric=metric,
            executor="fused")
        np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)
        assert plan_f.comm_cost == plan_b.comm_cost

    def test_pairwise_similarity_fused_kernel_interpret(self):
        m, q = 19, 1.0
        w = _weights("uniform", m, seed=2)
        rng = np.random.default_rng(2)
        x = _rand(rng, (m, 8))
        schema = plan_a2a(w, q)
        s_d, _, _ = pairwise_similarity(x, q=q, weights=w, schema=schema,
                                        executor="dense")
        s_f, _, _ = pairwise_similarity(x, q=q, weights=w, schema=schema,
                                        executor="fused", use_kernel=True,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_d),
                                   rtol=1e-4, atol=1e-4)

    def test_single_input_degenerate(self):
        """m=1: no pairs, plan degenerates — fused must not crash."""
        x = jnp.ones((1, 4), jnp.float32)
        s_f, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="fused")
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="bucketed")
        np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_b))

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
        fused = get_executor("fused").run(x, plan, fn, use_kernel=False)
        buck = run_reducers_bucketed(x, plan, fn)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(buck))
        assert float(jnp.abs(fused).max()) == 0.0

    def test_non_gram_reducer_falls_back(self):
        m = 17
        w = _weights("zipf", m, seed=3)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(5)
        x = _rand(rng, (m, 4))

        def colsum(blk, msk):
            return jnp.sum(blk * msk[:, None], axis=0)

        before = engine_mod.fused_stats()
        fused = get_executor("fused").run(x, plan, colsum)
        after = engine_mod.fused_stats()
        buck = run_reducers_bucketed(x, plan, colsum)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)
        assert after["fallbacks"] == before["fallbacks"] + 1
        assert after["calls"] == before["calls"] + 1


class TestSomePairsFused:
    def test_x2y_some_pairs_fused_agrees(self):
        """The some-pairs (X2Y) workload on the same fused path."""
        m, q = 20, 1.0
        rng = np.random.default_rng(13)
        w = rng.uniform(0.02, 0.3, m)
        pairs = [(0, 1), (2, 9), (5, 17), (3, 4), (11, 12)]
        x = _rand(rng, (m, 8))
        s_b, _, sch = some_pairs_similarity(x, pairs, q=q, weights=w,
                                            executor="bucketed")
        s_f, _, _ = some_pairs_similarity(x, pairs, q=q, weights=w,
                                          schema=sch, executor="fused")
        np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)
        # required pairs must carry the true similarity
        ref = np.asarray(x) @ np.asarray(x).T
        for i, j in pairs:
            np.testing.assert_allclose(float(s_f[i, j]), ref[i, j],
                                       rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ jit cache LRU
class TestJitCacheLRU:
    def test_bounded_with_eviction(self, monkeypatch):
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_MAX", 4)
        w = np.full(6, 0.3)
        plan = build_plan(plan_a2a(w, 1.0))
        x = jnp.ones((6, 3), jnp.float32)
        before_evictions = engine_mod._JIT_CACHE_STATS["evictions"]
        for i in range(8):
            # fresh closure every iteration — the anti-pattern the bound
            # protects against
            fn = (lambda k: lambda blk, msk: jnp.sum(blk, axis=0) * k)(i)
            run_reducers(x, plan, fn)
        assert len(engine_mod._JIT_CACHE) <= 4
        assert engine_mod._JIT_CACHE_STATS["evictions"] > before_evictions

    def test_stats_shape_and_hits(self):
        stats = engine_mod.jit_cache_stats()
        for key in ("size", "max_size", "hits", "misses", "evictions",
                    "per_key"):
            assert key in stats
        w = np.full(5, 0.3)
        plan = build_plan(plan_a2a(w, 1.0))
        x = jnp.ones((5, 3), jnp.float32)
        fn = _block_fn("dot", False)
        run_reducers(x, plan, fn)
        h0 = engine_mod.jit_cache_stats()["hits"]
        run_reducers(x, plan, fn)
        assert engine_mod.jit_cache_stats()["hits"] == h0 + 1

    def test_per_key_hit_counts(self, monkeypatch):
        """Per-key counters: repeat lookups of one key accumulate under its
        label; fresh keys start at zero."""
        from collections import OrderedDict
        monkeypatch.setattr(engine_mod, "_JIT_CACHE", OrderedDict())
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_HITS", {})
        for _ in range(4):
            engine_mod._cache_get(("stable_key",), lambda: object())
        engine_mod._cache_get(("fresh_key",), lambda: object())
        per_key = engine_mod.jit_cache_stats()["per_key"]
        assert per_key["stable_key"] == 3
        assert per_key["fresh_key"] == 0

    def test_eviction_order_is_lru(self, monkeypatch):
        """A freshly-touched entry must survive eviction; the
        least-recently-used one goes first."""
        from collections import OrderedDict
        monkeypatch.setattr(engine_mod, "_JIT_CACHE", OrderedDict())
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_HITS", {})
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_MAX", 2)
        engine_mod._cache_get("A", lambda: "a")
        engine_mod._cache_get("B", lambda: "b")
        engine_mod._cache_get("A", lambda: "a")       # touch A: B is LRU now
        engine_mod._cache_get("C", lambda: "c")       # evicts B, not A
        assert "A" in engine_mod._JIT_CACHE
        assert "B" not in engine_mod._JIT_CACHE
        assert "C" in engine_mod._JIT_CACHE
        # evicted keys drop out of the per-key counters too
        assert "B" not in engine_mod.jit_cache_stats()["per_key"]

    def test_env_configurable_cap(self, monkeypatch):
        """REPRO_JIT_CACHE_SIZE drives the LRU cap via
        configure_jit_cache(); shrinking below the live size evicts
        immediately in LRU order."""
        from collections import OrderedDict
        monkeypatch.setattr(engine_mod, "_JIT_CACHE", OrderedDict())
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_HITS", {})
        monkeypatch.setattr(engine_mod, "_JIT_CACHE_MAX",
                            engine_mod._JIT_CACHE_MAX)
        monkeypatch.setenv("REPRO_JIT_CACHE_SIZE", "3")
        assert engine_mod.configure_jit_cache() == 3
        assert engine_mod.jit_cache_stats()["max_size"] == 3
        for k in "ABC":
            engine_mod._cache_get(k, lambda: k)
        monkeypatch.setenv("REPRO_JIT_CACHE_SIZE", "1")
        assert engine_mod.configure_jit_cache() == 1
        assert list(engine_mod._JIT_CACHE) == ["C"]   # oldest evicted first
        monkeypatch.delenv("REPRO_JIT_CACHE_SIZE")
        assert engine_mod.configure_jit_cache() == 64  # default restored
        # malformed / non-positive values fall back to the default instead
        # of crashing the import or setting a cap-0 evict-everything cache
        for bad in ("abc", "0", "-3", ""):
            monkeypatch.setenv("REPRO_JIT_CACHE_SIZE", bad)
            assert engine_mod.configure_jit_cache() == 64, bad


# ------------------------------------------------- pairwise_gram block clamp
class TestPairwiseGramClamp:
    @pytest.mark.parametrize("dtype,sub", [
        (jnp.float32, 8), (jnp.bfloat16, 16), (jnp.int8, 32)])
    def test_min_tile_sublanes(self, dtype, sub):
        assert min_tile_sublanes(dtype) == sub

    def test_clamped_blocks_are_tile_aligned(self):
        # sub-tile extents round UP to the dtype tile, not to raw max(8, M)
        assert _clamp_block(128, 10, jnp.bfloat16) == 16
        assert _clamp_block(128, 10, jnp.float32) == 16
        assert _clamp_block(128, 3, jnp.float32) == 8
        assert _clamp_block(128, 200, jnp.float32) == 128
        assert _clamp_block(512, 9, jnp.float32, lane=True) == 128

    @pytest.mark.parametrize("M,dtype", [(10, jnp.bfloat16), (3, jnp.float32),
                                         (1, jnp.bfloat16)])
    def test_sub_tile_widths_still_correct(self, M, dtype):
        rng = np.random.default_rng(M)
        x = _rand(rng, (M, 20), dtype)
        got = pairwise_gram(x, x, interpret=True)
        ref = np.asarray(x, np.float32) @ np.asarray(x, np.float32).T
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=tol, atol=tol)
