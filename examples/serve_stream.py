"""Live-table similarity serving on the streaming executor.

A ``PairwiseService(executor="streaming")`` adopts a table once
(``load_table``) and then absorbs edits — ``add_input`` /
``remove_input`` / ``update_weight`` — without re-planning or
re-shuffling the world: the ``repro.stream`` subsystem repairs the
maintained mapping schema locally, recomputes only the reducers the edit
dirtied, and patches the cached (m, m) matrix.  This example drives an
edit stream and prints the per-edit telemetry the dashboards chart:

  * the recompute fraction (dirty reducers / total — the paper's
    communication per unit of useful work, made visible per edit);
  * the delta's shipped rows vs what a full re-shuffle would ship;
  * the optimality-gap drift that eventually triggers an amortized full
    re-plan through ``PLAN_CACHE``.

Run:  PYTHONPATH=src python examples/serve_stream.py
"""

import numpy as np

from repro.serve import PairwiseService

M, D, Q = 128, 32, 1.0


def main():
    rng = np.random.default_rng(0)
    svc = PairwiseService(q=Q, metric="dot", executor="streaming")

    x = rng.normal(size=(M, D)).astype(np.float32)
    w = np.clip(rng.zipf(1.6, M) / 32.0, 0.01, 0.30)
    sims, info = svc.load_table(x, w)
    print(f"cold build: [{info['algorithm']}] reducers={info['reducers']} "
          f"gap={info['optimality_gap']:.2f}x "
          f"wall={info['wall_s'] * 1e3:.0f}ms\n")

    print(f"{'edit':12s} {'id':>4s} {'dirty':>11s} {'frac':>6s} "
          f"{'delta/replan':>12s} {'drift':>6s} {'replan':>6s} {'wall':>9s}")
    for step in range(12):
        op = rng.choice(["add", "remove", "reweight"], p=[0.5, 0.3, 0.2])
        act = svc._planner.active_ids()
        if op == "add" or len(act) < 3:
            sims, info = svc.add_input(
                rng.normal(size=D).astype(np.float32),
                float(np.clip(rng.zipf(1.6) / 32.0, 0.01, 0.30)))
        elif op == "remove":
            sims, info = svc.remove_input(int(rng.choice(act)))
        else:
            sims, info = svc.update_weight(
                int(rng.choice(act)),
                float(np.clip(rng.zipf(1.6) / 32.0, 0.01, 0.30)))
        ratio = info["delta_comm_rows"] / max(info["comm_cost"], 1e-12)
        print(f"{info['kind']:12s} {info['input_id']:4d} "
              f"{info['dirty_reducers']:5d}/{info['num_reducers']:<5d} "
              f"{info['recompute_fraction']:6.3f} {ratio:12.4f} "
              f"{info['gap_drift']:6.3f} "
              f"{'yes' if info['full_replan'] else '-':>6s} "
              f"{info['wall_s'] * 1e3:7.1f}ms")

    agg = svc.stats
    print(f"\naggregate over {agg['edits']} edits: "
          f"{agg['dirty_reducers']} dirty reducers of "
          f"{agg['edit_reducers_total']} "
          f"({agg['dirty_reducers'] / max(agg['edit_reducers_total'], 1):.1%}"
          f" recomputed), {agg['stream_replans']} full re-plans, "
          f"wall {agg['wall_s'] * 1e3:.0f}ms")
    print(f"service executor counters: {svc.executor_stats()}")
    print(f"planner counters: {svc._planner.stats}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
