"""One Gram pipeline: the fused and sharded executors serve the self-join
as the X2Y job with X = Y (the plan's ids on both sides, the diagonal
zeroed), through the same program, source map and uploads as an X2Y
request.  Differential against the dense executor on every metric,
including uncovered cells and degenerate plans."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_a2a
from repro.mapreduce import build_plan, get_executor, make_executor
from repro.mapreduce import engine as engine_mod
from repro.mapreduce import executors
from repro.mapreduce.allpairs import _block_fn, x2y_similarity
from repro.mapreduce.engine import ReducerBucket, ReducerPlan, y_side
from repro.obs import TRACER


def _one_reducer_plan(ids, width: int) -> ReducerPlan:
    """A handmade plan of one reducer holding ``ids`` in a bucket of
    ``width`` slots (the rest masked); every other pair is uncovered."""
    idx = np.zeros((1, width), np.int32)
    mask = np.zeros((1, width), bool)
    idx[0, :len(ids)] = ids
    mask[0, :len(ids)] = True
    return ReducerPlan(
        idx=idx, mask=mask, num_reducers=1, comm_cost=float(len(ids)),
        max_inputs=width,
        buckets=(ReducerBucket(width=width, rows=np.zeros(1, np.int64),
                               idx=idx, mask=mask),))


def _plan(kind: str, m: int) -> ReducerPlan:
    if kind == "zipf":
        rng = np.random.default_rng(m)
        w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
        return build_plan(plan_a2a(w, 1.0))
    if kind == "one-reducer":
        return _one_reducer_plan([4, 0, 9, 2], 8)
    return _one_reducer_plan([], 4)                    # all slots masked


@pytest.mark.parametrize("kind", ["zipf", "one-reducer", "all-masked"])
@pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
@pytest.mark.parametrize("name", ["fused", "sharded"])
def test_selfjoin_through_the_rect_route_equals_dense(name, metric, kind):
    """The self-join's answer equals the dense executor's: same entries,
    a zero diagonal, uncovered cells 0."""
    m = 23
    plan = _plan(kind, m)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(m, 6))
                    .astype(np.float32))
    fn = _block_fn(metric, False)
    ex = make_executor(name)
    got = np.asarray(ex.run_pairs(x, plan, fn, m))
    want = np.asarray(get_executor("dense").run_pairs(x, plan, fn, m))
    assert ex.stats()["fallbacks"] == 0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert not np.diagonal(got).any()
    if kind != "zipf":                  # cells no reducer covers read 0
        covered = np.zeros((m, m), bool)
        ids = plan.idx[plan.mask]
        covered[np.ix_(ids, ids)] = True
        assert not got[~covered].any()
        assert kind == "one-reducer" or not got.any()


def test_fused_selfjoin_puts_each_bucket_once(monkeypatch):
    """A fused self-join passes its table and each bucket's ids and mask
    once, with no Y side (the program reads X's for both); its one
    ``upload`` span's ``bytes`` are Σ(idx + mask) + the (m, m) int32
    source map."""
    plan = _plan("zipf", 40)
    m = 40
    x = jnp.asarray(np.random.default_rng(2).normal(size=(m, 8))
                    .astype(np.float32))
    seen = []
    real = executors.launch

    def spy(fn, *args):
        seen.append(args)
        return real(fn, *args)

    monkeypatch.setattr(executors, "launch", spy)
    TRACER.clear()
    make_executor("fused").run_pairs(x, plan, _block_fn("dot", False), m)
    ((xt, yt, buckets, _srcmap),) = seen
    assert xt is x and yt is None
    assert len(buckets) == len(plan.buckets)
    for (xi, xm, yi, ym), b in zip(buckets, plan.buckets):
        assert yi is None and ym is None
        np.testing.assert_array_equal(np.asarray(xi), b.idx)
    (up,) = [s for s in TRACER.spans() if s.name == "upload"]
    assert up.attrs["bytes"] == m * m * 4 + sum(
        b.idx.nbytes + b.mask.nbytes for b in plan.buckets)
    assert all(y_side(b)[0] is b.idx for b in plan.buckets)


def test_selfjoin_and_x2y_share_one_program_family():
    """A self-join and an X2Y request on the fused executor run the same
    jitted program (one cache key, hit by the second request); no key of
    a square-only program exists."""
    rng = np.random.default_rng(3)
    m = 24
    x = jnp.asarray(rng.normal(size=(m, 8)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(m, 8)).astype(np.float32))
    ex = make_executor("fused")
    w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
    plan = build_plan(plan_a2a(w, 1.0))
    ex.run_pairs(x, plan, _block_fn("dot", False), m)
    key = "fused-x2y|dot|None|None|False|False|128"
    before = engine_mod.jit_cache_stats()["per_key"][key]
    x2y_similarity(x, y, q=1.0, wx=w, wy=w, executor=ex)
    per_key = engine_mod.jit_cache_stats()["per_key"]
    assert per_key[key] == before + 1
    assert ex.stats()["calls"] == 2 and ex.stats()["fallbacks"] == 0
    fused = [k for k in per_key if k.startswith("fused")]
    assert fused and all(k.startswith("fused-x2y|") for k in fused)


def test_sharded_selfjoin_groups_read_the_x_side():
    """The sharded executor stacks a self-join's groups through the one
    rect stacking: each group's Y side is its X side (the same arrays),
    and the answer equals the dense executor's."""
    plan = _plan("zipf", 31)
    m = 31
    x = jnp.asarray(np.random.default_rng(4).normal(size=(m, 5))
                    .astype(np.float32))
    fn = _block_fn("l2", False)
    ex = make_executor("sharded")
    got = ex.run_pairs(x, plan, fn, m)
    (groups,) = plan.__dict__["_shard_groups_cache"].values()
    for xi, xm, yi, ym, _rows in groups:
        assert yi is xi and ym is xm
    want = get_executor("dense").run_pairs(x, plan, fn, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
