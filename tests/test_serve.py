"""Serving engine: wave batching produces the same tokens as sequential
decode, handles queues longer than the slot count, and respects limits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.launch.mesh import make_local_mesh
from repro.launch.rules import rules_for
from repro.models import RuntimeFlags, build_model
from repro.serve import BatchedServer, PairwiseService, Request

CFG = ArchConfig(name="tiny-serve", family="dense", num_layers=2,
                 d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                 d_ff=64, vocab_size=128)


def make_model():
    mesh = make_local_mesh()
    flags = RuntimeFlags(param_dtype="float32", compute_dtype="float32",
                         remat="none")
    rules = rules_for(CFG, mesh, flags)
    model = build_model(CFG, flags, rules)
    return model, model.init(jax.random.key(0))


def sequential_decode(model, params, prompt, n_new, max_len):
    cache = model.init_cache(1, max_len)
    out = []
    tok = None
    for t in range(len(prompt) + n_new - 1):
        cur = prompt[t] if t < len(prompt) else out[-1]
        logits, cache = model.decode_step(
            params, cache,
            {"tokens": jnp.asarray([[cur]], jnp.int32),
             "pos": jnp.asarray(t, jnp.int32)})
        nxt = int(jnp.argmax(logits[0, -1]))
        if t >= len(prompt) - 1:
            out.append(nxt)
    return out


@pytest.mark.slow          # model-decode e2e, excluded from test-fast
class TestBatchedServer:
    def test_matches_sequential(self):
        model, params = make_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 128, n).astype(np.int32)
                   for n in (3, 5, 4, 3)]
        server = BatchedServer(model, params, batch_slots=2, max_len=32)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            server.submit(r)
        server.run()
        assert all(r.done for r in reqs)
        for r, p in zip(reqs, prompts):
            want = sequential_decode(model, params, list(map(int, p)), 4, 32)
            assert r.out == want, (r.rid, r.out, want)

    def test_queue_larger_than_slots(self):
        model, params = make_model()
        rng = np.random.default_rng(1)
        server = BatchedServer(model, params, batch_slots=2, max_len=16)
        reqs = [Request(rid=i, prompt=rng.integers(1, 128, 2).astype(
            np.int32), max_new_tokens=2) for i in range(7)]
        for r in reqs:
            server.submit(r)
        server.run()
        assert all(r.done and len(r.out) == 2 for r in reqs)

    def test_max_len_cap(self):
        model, params = make_model()
        server = BatchedServer(model, params, batch_slots=1, max_len=6)
        r = Request(rid=0, prompt=np.asarray([5, 6], np.int32),
                    max_new_tokens=100)
        server.submit(r)
        server.run()
        assert r.done
        assert len(r.out) <= 6


@pytest.mark.slow          # model-decode e2e, excluded from test-fast
class TestKVQuant:
    def test_int8_cache_decode_close_to_fp(self):
        """int8 KV cache: logits close to the fp path; cache 2x smaller."""
        from repro.models.configs_runtime import RuntimeFlags as RF
        import dataclasses
        model, params = make_model()
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 128, 6).astype(np.int32)
        fp = sequential_decode(model, params, list(map(int, prompt)), 3, 16)

        flags_q = dataclasses.replace(model.flags, kv_quant="int8")
        model_q = dataclasses.replace(model, flags=flags_q)
        cache = model_q.init_cache(1, 16)
        k = cache["pos0"]["mixer"]["k"]
        assert k.dtype == jnp.int8
        out = []
        for t in range(len(prompt) + 2):
            cur = int(prompt[t]) if t < len(prompt) else out[-1]
            logits, cache = model_q.decode_step(
                params, cache,
                {"tokens": jnp.asarray([[cur]], jnp.int32),
                 "pos": jnp.asarray(t, jnp.int32)})
            if t >= len(prompt) - 1:
                out.append(int(jnp.argmax(logits[0, -1])))
        # greedy tokens usually agree; require at least the first to match
        assert out[0] == fp[0], (out, fp)


class TestPairwiseService:
    """Paper-workload serving: planned similarity on the bucketed executor."""

    def test_matches_bruteforce_and_reports_telemetry(self):
        rng = np.random.default_rng(0)
        m, d = 24, 8
        x = rng.normal(size=(m, d)).astype(np.float32)
        w = np.clip(rng.zipf(1.7, m) / 30.0, 0.02, 0.45)
        svc = PairwiseService(q=1.0)
        sims, info = svc.similarity(x, weights=w)
        ref = x @ x.T * (1 - np.eye(m, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(sims), ref,
                                   rtol=1e-4, atol=1e-4)
        assert info["executor"] == "bucketed"
        assert info["bucketed_padded_elements"] <= \
            info["dense_padded_elements"]
        assert info["optimality_gap"] is None or info["optimality_gap"] >= 1.0
        assert svc.stats["requests"] == 1

    def test_coded_info_reports_skipped_entries(self):
        """``info["coded"]`` carries the coded executor's serving counts,
        the entries no cell is served from among them."""
        rng = np.random.default_rng(2)
        m = 24
        x = rng.normal(size=(m, 4)).astype(np.float32)
        svc = PairwiseService(q=1.0, executor="coded")
        sims, info = svc.similarity(x, weights=np.full(m, 0.1))
        np.testing.assert_allclose(
            np.asarray(sims), x @ x.T * (1 - np.eye(m, dtype=np.float32)),
            rtol=1e-4, atol=1e-4)
        coded = info["coded"]
        assert coded["skipped_entries"] > 0
        assert coded["residual_entries"] >= 0

    def test_some_pairs_masked_to_request(self):
        rng = np.random.default_rng(1)
        m = 16
        x = rng.normal(size=(m, 4)).astype(np.float32)
        w = np.full(m, 0.2)
        pairs = [(0, 3), (5, 9)]
        svc = PairwiseService(q=1.0)
        sims, info = svc.some_pairs(x, pairs, weights=w)
        want = np.zeros((m, m), dtype=bool)
        for i, j in pairs:
            want[i, j] = want[j, i] = True
        assert np.all(np.asarray(sims)[~want] == 0.0)
        for i, j in pairs:
            np.testing.assert_allclose(float(sims[i, j]),
                                       float(x[i] @ x[j]), rtol=1e-4)
        assert svc.padding_savings >= 1.0
