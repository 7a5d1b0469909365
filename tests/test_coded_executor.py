"""Coded executor + replicated partitioning: differential and ledger tests.

The coded executor trades replication for cross-shard assembly traffic
(Afrati et al., arXiv:1206.4377).  It must stay a pure execution-plan
change — identical outputs to the dense/bucketed executors on random,
Zipf-skewed, and degenerate schemas — while ``partition_plan(...,
replication=r)`` keeps the coverage/capacity/comm ledgers exact: the
primary LPT assignment is untouched, every reducer is held by exactly r
shards, and the replica slot ledger sums to exactly r x the unreplicated
shipped weight.  The in-process tests run at the main process's device
count (1 on plain CPU); the subprocess test forces an 8-device CPU mesh
to exercise the real residual all-to-all and compare its measured HLO
bytes against the sharded executor's assembly all-gather.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import partition_plan, plan_a2a, plan_x2y
from repro.mapreduce import (
    build_plan,
    build_x2y_plan,
    get_executor,
    list_executors,
    make_executor,
    pairwise_similarity,
    x2y_similarity,
)
from repro.mapreduce import executors
from repro.mapreduce.executors import (
    _coded_maps,
    _stacked_rect_groups,
    choose_replication,
    coded_assembly_model,
)


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


def _zipf_plan(m: int, seed: int = 0):
    w = _weights("zipf", m, seed=seed)
    return build_plan(plan_a2a(w, 1.0)), w


# ------------------------------------------------- replicated partitioning
def _check_replication_ledger(plan, num_shards, r):
    part = partition_plan(plan, num_shards, replication=r)
    base = partition_plan(plan, num_shards)
    R0 = plan.num_reducers

    # primary assignment identical to the unreplicated partition
    for rows, brows in zip(part.shard_rows, base.shard_rows):
        np.testing.assert_array_equal(rows, brows)
    # coverage/capacity untouched: sub-plans carry idx/mask verbatim
    for rows, sub in zip(part.shard_rows, part.shards):
        assert sub.num_reducers == len(rows)
        np.testing.assert_array_equal(sub.idx, plan.idx[rows])
        np.testing.assert_array_equal(sub.mask, plan.mask[rows])
    assert float(part.comm_cost.sum()) == pytest.approx(plan.comm_cost)

    # every reducer held by exactly r shards, holder sets nest the
    # primary assignment (replication only ever ADDS holders)
    held = np.zeros((num_shards, R0), dtype=np.int64)
    for s, rows in enumerate(part.replica_rows):
        held[s, np.asarray(rows, dtype=np.int64)] += 1
        assert set(np.asarray(part.shard_rows[s]).tolist()) <= set(
            np.asarray(rows).tolist())
    if R0:
        np.testing.assert_array_equal(held.max(axis=0), np.ones(R0))
        np.testing.assert_array_equal(held.sum(axis=0), np.full(R0, r))

    # replica ledger: exactly r x the unreplicated shipped weight
    assert int(part.replica_slots.sum()) == r * int(part.shipped_rows.sum())
    assert int(part.shipped_rows.sum()) == int(plan.mask.sum())
    rep = part.report()
    assert rep["replication"] == r
    assert rep["replica_balance_factor"] >= 1.0 or R0 == 0
    return part


class TestReplicatedPartition:
    @pytest.mark.parametrize("kind", ["uniform", "zipf", "one-giant"])
    @pytest.mark.parametrize("num_shards,r", [(4, 2), (8, 2), (8, 4),
                                              (8, 8), (3, 3)])
    def test_ledger_exact(self, kind, num_shards, r):
        m = 37
        plan = build_plan(plan_a2a(_weights(kind, m, seed=m), 1.0))
        _check_replication_ledger(plan, num_shards, r)

    def test_r1_matches_unreplicated(self):
        plan, _ = _zipf_plan(40)
        part = partition_plan(plan, 4, replication=1)
        assert part.replication == 1
        for rows, rrows in zip(part.shard_rows, part.replica_rows):
            np.testing.assert_array_equal(np.sort(rows), np.sort(rrows))

    def test_replication_out_of_range_rejected(self):
        plan, _ = _zipf_plan(20)
        with pytest.raises(AssertionError):
            partition_plan(plan, 4, replication=5)
        with pytest.raises(AssertionError):
            partition_plan(plan, 4, replication=0)

    def test_holder_sets_nested_across_rates(self):
        """Raising r only adds holders — the monotone-frontier invariant
        (a block served locally at rate r stays local at r+1)."""
        plan, _ = _zipf_plan(64)
        prev = None
        for r in (1, 2, 4, 8):
            part = partition_plan(plan, 8, replication=r)
            cur = [set(np.asarray(rows).tolist())
                   for rows in part.replica_rows]
            if prev is not None:
                for a, b in zip(prev, cur):
                    assert a <= b
            prev = cur

    def test_empty_plan(self):
        plan = build_plan(plan_a2a([], 1.0))
        part = partition_plan(plan, 4, replication=2)
        assert part.replication == 2
        assert all(len(rows) == 0 for rows in part.replica_rows)

    @given(st.integers(min_value=5, max_value=60),
           st.integers(min_value=0, max_value=2 ** 31 - 1),
           st.integers(min_value=2, max_value=8),
           st.integers(min_value=2, max_value=8))
    @settings(max_examples=25, deadline=None)
    def test_property_ledger_exact(self, m, seed, num_shards, r):
        """Property: for any Zipf profile and any 2 <= r <= S, replication
        preserves coverage/capacity and the replica ledger sums to exactly
        r x the unreplicated shipped weight."""
        if r > num_shards:
            r = num_shards
        rng = np.random.default_rng(seed)
        w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
        plan = build_plan(plan_a2a(w, 1.0))
        _check_replication_ledger(plan, num_shards, r)


# ------------------------------------------------------------- differential
KINDS = ["uniform", "zipf", "one-giant"]


class TestCodedExecutorDifferential:
    def test_registered(self):
        assert "coded" in list_executors()
        assert get_executor("coded").name == "coded"

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("m", [5, 29])
    def test_pairwise_coded_matches_dense(self, kind, m):
        w = _weights(kind, m, seed=m)
        rng = np.random.default_rng(m)
        x = _rand(rng, (m, 6))
        schema = plan_a2a(w, 1.0)
        s_d, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="dense")
        s_c, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="coded")
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_d),
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
        s_c, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        metric=metric, executor="coded")
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    def test_x2y_coded_matches_bucketed(self):
        rng = np.random.default_rng(11)
        nx, ny, d = 21, 17, 5
        xw = rng.uniform(0.05, 0.3, nx)
        yw = rng.uniform(0.05, 0.3, ny)
        xt = _rand(rng, (nx, d))
        yt = _rand(rng, (ny, d))
        s_b, _, sch = x2y_similarity(xt, yt, q=1.0, wx=xw, wy=yw,
                                     executor="bucketed")
        s_c, _, _ = x2y_similarity(xt, yt, q=1.0, wx=xw, wy=yw, schema=sch,
                                   executor="coded")
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_b),
                                   rtol=1e-4, atol=1e-4)

    def test_single_input_degenerate(self):
        x = jnp.ones((1, 4), jnp.float32)
        s_c, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="coded")
        s_b, _, _ = pairwise_similarity(x, q=1.0, weights=[0.3],
                                        executor="bucketed")
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_b))

    def test_non_gram_reducer_falls_back(self):
        m = 17
        w = _weights("zipf", m, seed=3)
        plan = build_plan(plan_a2a(w, 1.0))
        rng = np.random.default_rng(5)
        x = _rand(rng, (m, 4))

        def colsum(blk, msk):
            return jnp.sum(blk * msk[:, None], axis=0)

        ex = make_executor("coded")
        from repro.mapreduce import run_reducers_bucketed
        out = ex.run(x, plan, colsum)
        buck = run_reducers_bucketed(x, plan, colsum)
        np.testing.assert_allclose(np.asarray(out), np.asarray(buck),
                                   rtol=1e-5, atol=1e-5)
        assert ex.stats()["fallbacks"] == 1

    def test_coded_telemetry_recorded(self):
        m = 19
        w = _weights("uniform", m, seed=2)
        rng = np.random.default_rng(2)
        x = _rand(rng, (m, 4))
        ex = make_executor("coded")
        schema = plan_a2a(w, 1.0)
        pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                            executor=ex)
        stats = ex.stats()
        assert stats["coded"] == 1
        assert stats["replication"] >= 1
        assert 0.0 <= stats["local_fraction"] <= 1.0
        assert stats["local_entries"] + stats["residual_entries"] > 0

    def test_skipped_entries_published(self):
        """``skipped_entries`` reaches the instance stats, the gauge and
        the ``maps`` span that built the coded maps."""
        from repro.obs import REGISTRY, TRACER
        plan, w = _zipf_plan(40)
        x = _rand(np.random.default_rng(4), (40, 4))
        ex = make_executor("coded")
        TRACER.clear()
        pairwise_similarity(x, q=1.0, weights=w, executor=ex)
        got = ex.stats()["skipped_entries"]
        assert got > 0
        assert REGISTRY.snapshot()["gauges"][
            "executor.coded_skipped_entries{executor=coded}"] == got
        (span,) = [sp for sp in TRACER.spans() if sp.name == "maps"
                   and sp.attrs["what"] == "coded_maps"]
        assert span.attrs["skipped_entries"] == got


# ---------------------------------------------------------- traffic model
class TestCodedModelAndChooser:
    def test_entries_conserved_across_rates(self):
        """Every needed Gram entry is served exactly once at every r —
        replication moves entries between the local and residual ledgers,
        it never drops or duplicates them."""
        plan, _ = _zipf_plan(64)
        totals = set()
        for r in (1, 2, 4, 8):
            rec = coded_assembly_model(plan, 8, r, 64)
            totals.add(rec["local_entries"] + rec["residual_entries"])
        assert len(totals) == 1

    def test_local_fraction_tracks_replication(self):
        """With contiguous row-slices, each replica holder serves ~1/S of
        a block's rows locally: local fraction grows with r and hits 1.0
        at full replication."""
        plan, _ = _zipf_plan(64)
        fracs = [coded_assembly_model(plan, 8, r, 64)["local_fraction"]
                 for r in (1, 2, 4, 8)]
        assert all(b >= a for a, b in zip(fracs, fracs[1:]))
        assert fracs[-1] == 1.0

    def test_assembly_bytes_monotone_in_r(self):
        plan, _ = _zipf_plan(96)
        b = [coded_assembly_model(plan, 8, r, 96)[
            "assembly_bytes_per_shard"] for r in (1, 2, 4, 8)]
        assert all(y <= x for x, y in zip(b, b[1:])), b

    def test_chooser_returns_frontier_point(self):
        plan, _ = _zipf_plan(64)
        best_r, frontier = choose_replication(plan, 8, 64, 16)
        assert best_r in [rec["replication"] for rec in frontier]
        best = [rec for rec in frontier
                if rec["replication"] == best_r][0]
        assert all(best["total_comm_bytes"] <= rec["total_comm_bytes"]
                   for rec in frontier)
        # shipping term is exact: r x the schema's comm volume
        for rec in frontier:
            assert rec["shipped_bytes"] == pytest.approx(
                rec["replication"] * plan.comm_cost * 16 * 4)


# ------------------------------------------------------- one source per cell
def _shape(plan, rect=False):
    if rect:
        return plan.num_x, plan.num_y
    m = int(np.asarray(plan.idx)[np.asarray(plan.mask)].max()) + 1
    return m, m


def _maps(plan, S, r, rect=False):
    """The coded maps of ``plan`` on ``S`` shards at rate ``r``, with the
    partition and the replica-stacked groups they came from."""
    part = partition_plan(plan, S, replication=r)
    groups = _stacked_rect_groups(plan, part, rows_by_shard=part.replica_rows)
    shape = _shape(plan, rect)
    rb = -(-shape[0] // S)
    return part, groups, rb, _coded_maps(groups, shape, rb, not rect)


def _cover(plan, reducers=None, rect=False):
    """Brute force over the plan's ``idx``/``mask`` (and ``yidx``/
    ``ymask``): how many of ``reducers`` (default all) cover each cell,
    self-pairs left out of a self-join."""
    xs = np.asarray(plan.idx)
    xm = np.asarray(plan.mask)
    ys = np.asarray(plan.yidx) if rect else xs
    ym = np.asarray(plan.ymask) if rect else xm
    cover = np.zeros(_shape(plan, rect), np.int64)
    for r in range(plan.num_reducers) if reducers is None else reducers:
        cover[np.ix_(xs[r][xm[r]], ys[r][ym[r]])] += 1
    if not rect:
        np.fill_diagonal(cover, 0)
    return cover


def _valid_entries(plan, rect=False):
    xm = np.asarray(plan.mask)[:plan.num_reducers]
    ym = np.asarray(plan.ymask)[:plan.num_reducers] if rect else xm
    return int((xm.sum(axis=1) * ym.sum(axis=1)).sum())


def _assemble(groups, sendmap, srcmap, x, y):
    """The device program's combining stage, in NumPy: per shard the
    value vector of its Gram blocks, the lanes it sends, the lanes it
    receives, then the source-map gather; rows of every shard stacked."""
    S = sendmap.shape[0]
    vals = []
    for s in range(S):
        v = [np.zeros(1)]
        for xi, xm, yi, ym, _rows in groups:
            g = np.einsum("kpd,kqd->kpq", x[xi[s]], y[yi[s]])
            v.append((g * xm[s][:, :, None] * ym[s][:, None, :]).ravel())
        vals.append(np.concatenate(v))
    send = [vals[t][sendmap[t]] for t in range(S)]
    return np.concatenate([
        np.concatenate([vals[s]] + [send[t][s] for t in range(S)])[srcmap[s]]
        for s in range(S)])


def _x2y_plan(seed=11, nx=30, ny=23):
    rng = np.random.default_rng(seed)
    schema = plan_x2y(rng.uniform(0.05, 0.3, nx), rng.uniform(0.05, 0.3, ny),
                      1.0)
    return build_x2y_plan(schema, nx)


RATES = [(4, 1), (4, 2), (4, 4), (8, 1), (8, 2), (8, 8)]


class TestOneSourcePerCell:
    """``_coded_maps`` serves each covered cell from one source, a local
    holder first, checked against counts made by brute force over the
    plan's cells."""

    @pytest.mark.parametrize("num_shards,r", RATES)
    def test_each_covered_cell_served_once(self, num_shards, r):
        plan, _ = _zipf_plan(64)
        *_, (_send, _src, stats) = _maps(plan, num_shards, r)
        covered = int(np.count_nonzero(_cover(plan)))
        assert stats["local_entries"] + stats["residual_entries"] == covered

    @pytest.mark.parametrize("num_shards", [4, 8])
    def test_r1_residual_is_cells_without_owner_holder(self, num_shards):
        plan, _ = _zipf_plan(64)
        part, _g, rb, (_send, _src, stats) = _maps(plan, num_shards, 1)
        m = _shape(plan)[0]
        held = np.stack([_cover(plan, rows) for rows in part.replica_rows])
        local = held[np.arange(m) // rb, np.arange(m)] > 0
        want = int(np.count_nonzero((_cover(plan) > 0) & ~local))
        assert stats["residual_entries"] == want
        assert stats["local_entries"] == int(np.count_nonzero(local))

    @pytest.mark.parametrize("num_shards,r", RATES)
    def test_no_cell_in_two_lanes(self, num_shards, r):
        plan, _ = _zipf_plan(64)
        *_, (sendmap, srcmap, stats) = _maps(plan, num_shards, r)
        shipped = int(np.count_nonzero(sendmap))     # slot 0 pads lanes
        assert shipped == stats["residual_entries"]
        lv = stats["vals_len"]
        for s in range(num_shards):
            recv = srcmap[s][srcmap[s] >= lv] - lv   # lane t, offset e
            assert len(np.unique(recv)) == len(recv)
            t, e = recv // stats["lane_max"], recv % stats["lane_max"]
            assert np.all(sendmap[t, s, e] > 0)
        assert int((srcmap >= lv).sum()) == shipped

    def test_skipped_entries_count_duplicates(self):
        """A self-join plan whose bins hold many inputs covers within-bin
        cells many times: every entry past a cell's first is skipped, with
        the diagonal.  An X2Y plan covers each cell once and skips none."""
        plan = build_plan(plan_a2a(np.full(48, 0.06), 1.0))
        assert _cover(plan).max() > 2
        for num_shards, r in RATES:
            *_, (_s, _m, st) = _maps(plan, num_shards, r)
            want = _valid_entries(plan) - int(np.count_nonzero(_cover(plan)))
            assert st["skipped_entries"] == want > 0
        xplan = _x2y_plan()
        assert _cover(xplan, rect=True).max() == 1
        for num_shards, r in RATES:
            *_, (_s, _m, st) = _maps(xplan, num_shards, r, rect=True)
            assert st["skipped_entries"] == 0
            assert (st["local_entries"] + st["residual_entries"]
                    == _valid_entries(xplan, rect=True))

    @pytest.mark.parametrize("num_shards,r", RATES)
    @pytest.mark.parametrize("chunk", [None, 50])
    def test_assembled_answer_matches_brute_force(self, num_shards, r, chunk,
                                                  monkeypatch):
        """The maps, run through the combining stage in NumPy, give every
        covered cell its dot product and 0 elsewhere, whether the blocks
        claim their cells in one chunk or in many."""
        if chunk is not None:
            monkeypatch.setattr(executors, "_CODED_CHUNK", chunk)
        plan = build_plan(plan_a2a(np.full(40, 0.06), 1.0))
        _p, groups, _rb, (sendmap, srcmap, _st) = _maps(plan, num_shards, r)
        x = np.random.default_rng(num_shards * 10 + r).normal(size=(40, 5))
        got = _assemble(groups, sendmap, srcmap, x, x)[:40]
        want = np.where(_cover(plan) > 0, x @ x.T, 0.0)
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    @pytest.mark.parametrize("num_shards,r", [(4, 1), (8, 2)])
    def test_x2y_answer_matches_brute_force(self, num_shards, r):
        plan = _x2y_plan()
        _p, groups, _rb, (sendmap, srcmap, _st) = _maps(
            plan, num_shards, r, rect=True)
        rng = np.random.default_rng(r)
        x, y = rng.normal(size=(30, 5)), rng.normal(size=(23, 5))
        got = _assemble(groups, sendmap, srcmap, x, y)[:30]
        want = np.where(_cover(plan, rect=True) > 0, x @ y.T, 0.0)
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_lanes_balanced_at_r2(self):
        """Stride split and least-filled lanes apply to the cells each
        block serves: the largest lane stays near the mean lane."""
        plan, _ = _zipf_plan(96)
        *_, (_s, _m, st) = _maps(plan, 8, 2)
        assert st["lane_max"] <= 2 * st["residual_entries"] / (8 * 7)


# ------------------------------------------------- forced 8-device CPU mesh
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import plan_a2a
    from repro.launch.roofline import collective_bytes
    from repro.mapreduce import get_executor, pairwise_similarity

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
        s_d, plan, _ = pairwise_similarity(x, q=1.0, weights=w,
                                           schema=schema, executor="dense")
        s_c, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor="coded")
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_d),
                                   rtol=1e-4, atol=1e-4)
    st = get_executor("coded").stats()
    assert st["num_shards"] == 8, st
    assert st["replication"] == 2, st
    assert st["residual_entries"] > 0, st

    # the coded residual all-to-all must move fewer bytes than the
    # sharded executor's assembly all-gather on the same plan
    hlo_s = get_executor("sharded").lower(
        (m, 6), plan, metric="dot", m=m).compile().as_text()
    hlo_c = get_executor("coded").lower(
        (m, 6), plan, metric="dot", m=m, replication=2).compile().as_text()
    b_s = collective_bytes(hlo_s)["total"]
    b_c = collective_bytes(hlo_c)["total"]
    assert collective_bytes(hlo_c)["all-to-all"] > 0, hlo_c[:2000]
    assert b_c < b_s, (b_c, b_s)

    # many inputs a bin: each bin's within-bin cells are covered by every
    # reducer that holds the bin, and those reducers lie on several shards
    from repro.core import partition_plan
    from repro.mapreduce import make_executor
    m = 64
    w = np.full(m, 0.06)
    x = jnp.asarray(rng.normal(size=(m, 6)).astype(np.float32))
    schema = plan_a2a(w, 1.0)
    s_d, plan, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                       executor="dense")
    owner = {}
    for s, rows in enumerate(partition_plan(plan, 8).shard_rows):
        for r in rows:
            ids = np.asarray(plan.idx)[r][np.asarray(plan.mask)[r]]
            for i in ids:
                for j in ids:
                    if i != j:
                        owner.setdefault((int(i), int(j)), set()).add(s)
    assert max(len(v) for v in owner.values()) > 1
    for r in (1, 2):
        ex = make_executor("coded", replication=r)
        s_c, _, _ = pairwise_similarity(x, q=1.0, weights=w, schema=schema,
                                        executor=ex)
        np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_d),
                                   rtol=1e-4, atol=1e-4)
        assert ex.stats()["skipped_entries"] > 0, ex.stats()
    print("CODED_OK", b_c / b_s)
""")


def test_coded_differential_on_8_device_mesh():
    """coded == dense under a real 8-shard mesh, and the residual
    all-to-all moves fewer HLO bytes than the sharded assembly gather
    (subprocess: the main test process keeps its default device count)."""
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "HOME": os.environ.get("HOME", "/tmp")},
    )
    assert "CODED_OK" in res.stdout, res.stdout + res.stderr
