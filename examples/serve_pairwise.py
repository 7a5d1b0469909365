"""Pairwise similarity serving on the fused shuffle executor.

A ``PairwiseService`` answers all-pairs / some-pairs similarity queries
through planned mapping schemas.  This example drives it like a serving
loop and prints the per-request telemetry the dashboards chart:

  * which executor ran (and, for ``fused``, whether the Pallas megakernel,
    its streamed twin, or the bucketed fallback did the work);
  * whether the registry planner's ``PLAN_CACHE`` served the weight
    profile without re-planning (repeat profiles are O(m) cache hits);
  * the engine jit-cache counters (bounded LRU — long loops with fresh
    reducer closures evict instead of growing without limit).

Run:  PYTHONPATH=src python examples/serve_pairwise.py
"""

import numpy as np

from repro.serve import PairwiseService

M, D, Q = 96, 32, 1.0


def main():
    rng = np.random.default_rng(0)
    svc = PairwiseService(q=Q, metric="dot", executor="fused")

    # three weight profiles; profile A repeats, so requests 3+ hit the
    # plan cache and pay neither planning nor schema construction
    profiles = {
        "A-zipf": np.clip(rng.zipf(1.7, M) / 24.0, 0.02, 0.45),
        "B-uniform": rng.uniform(0.05, 0.33, M),
        "C-zipf": np.clip(rng.zipf(1.5, M) / 32.0, 0.02, 0.45),
    }
    requests = ["A-zipf", "B-uniform", "A-zipf", "C-zipf", "A-zipf"]

    print(f"{'req':>3s} {'profile':10s} {'executor':8s} {'path':9s} "
          f"{'plan-cache':>10s} {'algorithm':22s} {'reducers':>8s} "
          f"{'pad-save':>8s} {'jit h/m':>8s} {'wall':>8s}")
    for i, name in enumerate(requests):
        x = rng.normal(size=(M, D)).astype(np.float32)
        sims, info = svc.similarity(x, weights=profiles[name])
        jc = info["jit_cache"]
        print(f"{i:3d} {name:10s} {info['executor']:8s} "
              f"{info['fused_path'] or '-':9s} "
              f"{'hit' if info['plan_cache_hit'] else 'miss':>10s} "
              f"{info['algorithm']:22s} {info['reducers']:8d} "
              f"{info['padding_savings']:7.2f}x "
              f"{jc['hits']:4d}/{jc['misses']:<3d} "
              f"{info['wall_s'] * 1e3:6.1f}ms")

    # one some-pairs request rides the same fused path (X2Y workload)
    pairs = [(0, 1), (5, 17), (30, 31), (2, 64)]
    _, info = svc.some_pairs(rng.normal(size=(M, D)).astype(np.float32),
                             pairs, weights=profiles["B-uniform"])
    print(f"\nsome-pairs request: executor={info['executor']} "
          f"path={info['fused_path']} algorithm={info['algorithm']} "
          f"gap={info['optimality_gap']:.2f}x")

    agg = svc.stats
    print(f"\naggregate over {agg['requests']} requests: "
          f"{agg['plan_cache_hits']} plan-cache hits, "
          f"fused kernel/streamed/fallback = "
          f"{agg['fused_kernel']}/{agg['fused_streamed']}/"
          f"{agg['fused_fallbacks']}, "
          f"padding savings {svc.padding_savings:.2f}x, "
          f"wall {agg['wall_s'] * 1e3:.0f}ms")
    # the service holds its OWN executor instance — these counters are
    # scoped to this service, not shared module globals
    print(f"service executor counters: {svc.executor_stats()}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
