"""Engine semantics: schema-driven execution == brute-force all-pairs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_a2a, plan_x2y
from repro.mapreduce import (
    build_plan,
    pairwise_similarity,
    skew_join,
)
from repro.mapreduce.engine import run_reducers


class TestAllPairs:
    @pytest.mark.parametrize("metric", ["dot", "l2", "cosine"])
    def test_matches_bruteforce(self, metric):
        rng = np.random.default_rng(0)
        m, d = 23, 16
        x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        w = rng.uniform(0.05, 0.3, size=m)
        sims, plan, schema = pairwise_similarity(
            x, q=1.0, weights=w, metric=metric)
        # brute force
        if metric == "dot":
            ref = x @ x.T
        elif metric == "l2":
            n2 = jnp.sum(x * x, axis=-1)
            ref = n2[:, None] + n2[None, :] - 2 * (x @ x.T)
        else:
            nrm = jnp.linalg.norm(x, axis=-1)
            ref = (x @ x.T) / (nrm[:, None] * nrm[None, :])
        ref = ref * (1 - jnp.eye(m))
        np.testing.assert_allclose(np.asarray(sims), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_kernel_path_matches(self):
        rng = np.random.default_rng(1)
        m, d = 17, 8
        x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        s_ref, _, sch = pairwise_similarity(x, q=5.0, metric="dot")
        s_k, _, _ = pairwise_similarity(x, q=5.0, metric="dot",
                                        schema=sch, use_kernel=True,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_k),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("interpret", [False, True])
    def test_kernel_flag_reaches_pairwise_gram(self, monkeypatch, interpret):
        """``use_kernel=True`` compiles the real kernel unless the caller
        asks for the interpreter: its flag, not a hard-wired one, reaches
        ``pairwise_gram``."""
        from repro.kernels.pairwise import ops
        from repro.mapreduce.allpairs import block_similarity
        seen = []

        def fake_gram(x, y, *, interpret, **kw):
            seen.append(interpret)
            return x @ y.T

        monkeypatch.setattr(ops, "pairwise_gram", fake_gram)
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(13, 7)).astype(np.float32))
        got = block_similarity(x, jnp.ones(13, bool), use_kernel=True,
                               interpret=interpret)
        assert seen == [interpret]
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ x.T),
                                   rtol=1e-5, atol=1e-5)

    def test_comm_cost_equals_gather_volume(self):
        rng = np.random.default_rng(2)
        w = rng.uniform(0.05, 0.3, size=20)
        schema = plan_a2a(w, 1.0)
        plan = build_plan(schema)
        # engine ships one row per (reducer, valid slot): unit-size rows
        assert plan.mask.sum() == sum(len(r) for r in schema.expand())

    def test_run_reducers_mesh_single_device(self):
        mesh = jax.make_mesh((1,), ("data",))
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(10, 4)).astype(np.float32))
        schema = plan_a2a(np.full(10, 0.3), 1.0)
        plan = build_plan(schema, pad_reducers_to=mesh.devices.size)
        out = run_reducers(
            x, plan, lambda blk, msk: jnp.sum(blk * msk[:, None]), mesh=mesh)
        assert out.shape == (plan.R,)
        assert np.isfinite(np.asarray(out)).all()


class TestSkewJoin:
    def test_join_complete(self):
        rng = np.random.default_rng(4)
        mx, my = 14, 9
        xv = jnp.asarray(rng.normal(size=(mx, 3)).astype(np.float32))
        yv = jnp.asarray(rng.normal(size=(my, 2)).astype(np.float32))
        out, schema = skew_join(xv, yv, q=6.0)
        assert out.shape == (mx, my, 5)
        # every (x, y) pair produced with the right payload
        for i in range(mx):
            for j in range(my):
                np.testing.assert_allclose(
                    np.asarray(out[i, j, :3]), np.asarray(xv[i]), rtol=1e-6)
                np.testing.assert_allclose(
                    np.asarray(out[i, j, 3:]), np.asarray(yv[j]), rtol=1e-6)

    def test_weighted_tuples(self):
        rng = np.random.default_rng(5)
        mx, my = 8, 6
        xv = jnp.asarray(rng.normal(size=(mx, 2)).astype(np.float32))
        yv = jnp.asarray(rng.normal(size=(my, 2)).astype(np.float32))
        wx = rng.uniform(0.1, 0.9, mx)
        wy = rng.uniform(0.1, 0.9, my)
        out, schema = skew_join(xv, yv, q=2.0, wx=wx, wy=wy)
        schema.validate("x2y", x_ids=range(mx), y_ids=range(mx, mx + my))
        assert out.shape == (mx, my, 4)
