"""Quickstart: the common-friends problem (paper Example 1) end to end.

m people, each with a friend list of a different size; every pair must be
compared.  The planner builds a capacity-q mapping schema, the engine
executes it on JAX, and we check the result against brute force.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import plan_a2a
from repro.mapreduce import pairwise_similarity

M_PEOPLE = 40
N_UNIVERSE = 500        # ids that can appear in a friend list
Q = 1.0                 # reducer capacity (normalized bytes)


def main():
    rng = np.random.default_rng(0)
    # friend lists of very different sizes
    list_sizes = np.clip(rng.lognormal(3.0, 1.0, M_PEOPLE), 5, 400).astype(int)
    friends = [rng.choice(N_UNIVERSE, size=s, replace=False)
               for s in list_sizes]
    # input size w_i proportional to list length (normalized to q units)
    weights = list_sizes / list_sizes.max() * 0.4

    # multi-hot encode: common friends count = dot product
    x = np.zeros((M_PEOPLE, N_UNIVERSE), np.float32)
    for i, f in enumerate(friends):
        x[i, f] = 1.0

    schema = plan_a2a(weights, Q)
    schema.validate("a2a")
    print(f"planner chose      : {schema.algorithm}")
    print(f"reducers           : {schema.num_reducers}")
    print(f"communication cost : {schema.communication_cost():.2f} "
          f"(lower bound {schema.lower_bound:.2f}, "
          f"gap {schema.optimality_gap():.2f}x)")
    print(f"max replication    : {schema.replication().max()} copies")

    sims, plan, _ = pairwise_similarity(
        jnp.asarray(x), q=Q, weights=weights, schema=schema, metric="dot")

    # verify vs brute force
    ref = x @ x.T * (1 - np.eye(M_PEOPLE))
    np.testing.assert_allclose(np.asarray(sims), ref, rtol=1e-5, atol=1e-5)
    i, j = divmod(int(np.argmax(ref)), M_PEOPLE)
    print(f"most common friends: persons {i} & {j} share {int(ref[i, j])}")
    print("OK: schema-driven result == brute force")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
