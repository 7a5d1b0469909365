"""Benchmark harness: one function per paper table / claim.

  bench_a2a      — Table 1 (A2A bounds, optimal + approx algorithms)
  bench_x2y      — Table 1 X2Y rows (Thm 25/26)
  bench_engine   — schema comm vs naive replication, end-to-end engine
  bench_engine --fused — dense/bucketed/fused executor shootout on the
                   Zipf workload; emits benchmarks/BENCH_engine.json
                   (wall-clock, padded elements, HBM bytes per executor)
                   so the perf trajectory is machine-readable across PRs
  bench_engine --sharded — bucketed/fused/sharded shootout + LPT balance
                   report (per-shard padded elements, balance factor);
                   merges the engine_sharded section into
                   benchmarks/BENCH_engine.json
  bench_coded    — coded shuffle executor: replication-vs-communication
                   Pareto frontier on a forced 8-device mesh (assembly
                   bytes vs the uncoded sharded gather, Thm-8 LB check);
                   writes benchmarks/BENCH_coded.json
  bench_stream   — streaming-maintenance edits vs full re-planning
  bench_obs      — observability overhead bar: obs-on vs obs-off on the
                   fused Zipf m=512 serving path (< 5%)
                   (first-edit p99, update latency, recompute fraction,
                   sustained achievable gap, delta-vs-replan comm bytes
                   across edit rates on Zipf m=512); writes
                   benchmarks/BENCH_stream.json
  bench_packing  — FFD bins applied to the data pipeline
  bench_kernels  — Pallas kernels vs oracles

Each section runs in a child process of its own, one after another; this
parent never imports JAX, so every child in turn has the accelerator to
itself (a process that has touched JAX holds the chip).  The sharded and
coded sections ask for 8 virtual devices, which only the CPU platform
honours.  Prints a ``name,seconds,rc`` CSV; the roofline table lives in
benchmarks/roofline_report.py (reads dry-run JSON).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_EIGHT = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}

SECTIONS = [
    ("bench_a2a", "bench_a2a.py", (), {}),
    ("bench_x2y", "bench_x2y.py", (), {}),
    ("bench_engine", "bench_engine.py", (), {}),
    ("bench_engine_fused", "bench_engine.py", ("--fused",), {}),
    ("bench_engine_sharded", "bench_engine.py", ("--sharded",), _EIGHT),
    ("bench_coded", "bench_coded.py", (), _EIGHT),
    ("bench_stream", "bench_stream.py", (), {}),
    ("bench_obs", "bench_obs.py", (), {}),
    ("bench_packing", "bench_packing.py", (), {}),
    ("bench_kernels", "bench_kernels.py", (), {}),
]


def main() -> None:
    sys.path.insert(0, os.path.join(_HERE, os.pardir, "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()                  # children inherit the placement
    src = os.path.join(_HERE, os.pardir, "src")
    pythonpath = os.pathsep.join(
        p for p in (src, _HERE, os.path.join(_HERE, os.pardir),
                    os.environ.get("PYTHONPATH")) if p)
    csv = []
    for name, script, args, env in SECTIONS:
        print(f"\n===== {name} " + "=" * (60 - len(name)), flush=True)
        t0 = time.perf_counter()
        res = subprocess.run(
            [sys.executable, os.path.join(_HERE, script), *args],
            env=dict(os.environ, PYTHONPATH=pythonpath, **env))
        csv.append(f"{name},{time.perf_counter() - t0:.1f},"
                   f"{res.returncode}")
    print("\n# name,seconds,rc")
    for line in csv:
        print(line)
    if any(not line.endswith(",0") for line in csv):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
