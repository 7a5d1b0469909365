"""The drug-interaction problem (paper Example 2, after Ullman'12).

Each drug carries a medical-history record of a *different size*; every
pair of drugs must meet at a reducer to test for interaction.  We sweep
reducer capacity q to expose the paper's central tradeoff: communication
cost vs parallelism (number of reducers).

Run:  PYTHONPATH=src python examples/drug_interaction.py [--drugs 120]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import a2a_comm_lower_bound, plan_a2a
from repro.mapreduce import build_plan, pairwise_similarity


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--drugs", type=int, default=120)
    ap.add_argument("--dim", type=int, default=96)
    args = ap.parse_args()

    rng = np.random.default_rng(1)
    # record sizes are heavy-tailed (some drugs have long histories)
    sizes_mb = np.clip(rng.lognormal(1.2, 0.9, args.drugs), 0.2, 30.0)
    x = jnp.asarray(rng.normal(size=(args.drugs, args.dim)) / args.dim ** 0.5,
                    jnp.float32)

    print(f"{args.drugs} drugs, record sizes {sizes_mb.min():.1f}-"
          f"{sizes_mb.max():.1f} MB, total {sizes_mb.sum():.0f} MB")
    print(f"\n{'q (MB)':>8s} {'algorithm':34s} {'reducers':>8s} "
          f"{'comm (MB)':>10s} {'LB':>9s} {'c/LB':>5s} {'max load':>9s}")
    for q in (64.0, 96.0, 160.0, 320.0):
        schema = plan_a2a(sizes_mb, q)
        schema.validate("a2a")
        lb = a2a_comm_lower_bound(sizes_mb, q)
        print(f"{q:8.0f} {schema.algorithm:34s} {schema.num_reducers:8d} "
              f"{schema.communication_cost():10.1f} {lb:9.1f} "
              f"{schema.communication_cost() / lb:5.2f} "
              f"{schema.max_load():9.1f}")

    # execute the q=96 plan: interaction score = similarity of records
    schema = plan_a2a(sizes_mb, 96.0)
    sims, plan, _ = pairwise_similarity(
        x, q=96.0, weights=sizes_mb, schema=schema, metric="dot")
    flat = np.asarray(sims)
    i, j = divmod(int(np.argmax(flat)), args.drugs)
    print(f"\nstrongest interaction candidate: drugs {i} & {j} "
          f"(score {flat[i, j]:.3f}) — checked {args.drugs * (args.drugs - 1) // 2} pairs "
          f"on {plan.num_reducers} reducers")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
