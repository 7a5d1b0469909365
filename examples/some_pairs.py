"""Some-pairs similarity: only flagged pairs must be compared.

Ullman & Ullman's some-pairs problem ("Some Pairs Problems"): instead of
comparing every pair of inputs (A2A), a blocking step — here a cheap
locality-sensitive signature — flags a subset of candidate pairs, and the
mapping schema only has to co-locate those.  The planner exploits the
sparsity: inputs with no flagged partner are never shipped, and the schema
self-reports its distance from the replication-rate lower bound.

Run:  PYTHONPATH=src python examples/some_pairs.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import plan_a2a, plan_some_pairs
from repro.mapreduce import some_pairs_similarity

M = 60
D = 128
Q = 1.0


def main():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(M, D)).astype(np.float32)
    weights = rng.uniform(0.02, 0.3, M)

    # blocking step: a single random hyperplane signature; only pairs on the
    # same side become candidates (any real blocker works the same way)
    sig = (x @ rng.normal(size=(D, 4)) > 0)
    pairs = [(i, j) for i in range(M) for j in range(i + 1, M)
             if np.all(sig[i] == sig[j])]
    print(f"blocking kept {len(pairs)} of {M * (M - 1) // 2} pairs")

    schema = plan_some_pairs(weights, Q, pairs)
    schema.validate("some", required_pairs=pairs)
    dense = plan_a2a(weights, Q)
    print(f"planner chose      : {schema.algorithm}")
    print(f"portfolio          : "
          f"{ {k: round(v, 1) for k, v in schema.meta['portfolio'].items()} }")
    print(f"communication cost : {schema.communication_cost():.2f} "
          f"(lower bound {schema.lower_bound:.2f}, "
          f"gap {schema.optimality_gap():.2f}x)")
    print(f"vs all-pairs plan  : {dense.communication_cost():.2f} "
          f"({dense.communication_cost() / schema.communication_cost():.1f}x "
          f"more traffic)")

    sims, plan, _ = some_pairs_similarity(
        jnp.asarray(x), pairs, q=Q, weights=weights, schema=schema)

    ref = x @ x.T
    for i, j in pairs:
        np.testing.assert_allclose(float(sims[i, j]), ref[i, j],
                                   rtol=1e-4, atol=1e-4)
    print(f"OK: all {len(pairs)} required similarities match brute force "
          f"(plan: {plan.algorithm}, gap {plan.optimality_gap:.2f}x)")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
