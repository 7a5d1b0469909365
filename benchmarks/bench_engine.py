"""Benchmark: the MapReduce engine end-to-end — schema comm cost vs naive
replication, and wall time of the sharded execution on the local mesh.

This is the paper's headline claim in executable form: the mapping schema
moves far fewer bytes map->reduce than naive all-pairs replication, at
identical outputs.

``--skewed`` runs the bucketed-executor scenario: Zipf-distributed input
sizes (the paper's *different-sized inputs*, cranked up) make one reducer
far heavier than the rest, so the dense executor pads every reducer to the
global max slot count while the bucketed executor pads each reducer only
to its capacity-bucket width.  The run exits non-zero unless the two
executors produce allclose similarity matrices AND the padded-element
(peak-memory) reduction meets the 2x acceptance bar; the wall-clock
speedup is reported (machine-dependent, informational).  Warmup runs
populate the engine's jit cache, so the timed iterations measure
execution, not tracing.

``--sharded`` runs the shard-balanced multi-device scenario on the same
Zipf workload: the plan is LPT-partitioned over the local device mesh
(``make bench-sharded`` forces an 8-device CPU mesh via ``XLA_FLAGS``)
and executed per shard under ``shard_map``.  Bars: sharded output
allclose to bucketed and fused, and LPT balance factor <= 1.25 on the
8-shard reference partition.  Both ``--fused`` and ``--sharded`` merge
their sections into ``benchmarks/BENCH_engine.json`` for cross-PR
tracking.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import naive_pairs, plan_a2a
from repro.mapreduce import build_plan, pairwise_similarity

try:                                    # run as a script from benchmarks/
    from bench_common import emit_bench_json as _emit_bench_json
except ImportError:                     # imported as benchmarks.bench_engine
    from benchmarks.bench_common import emit_bench_json as _emit_bench_json

BENCH_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "BENCH_engine.json")


def run(m: int = 96, d: int = 64, q: float = 1.0, seed: int = 0):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.05, 0.33, m)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))

    rows = []
    for name, schema in [
        ("planner-auto", plan_a2a(w, q)),
        ("naive-all-pairs", naive_pairs(w, q)),
    ]:
        schema.validate("a2a")
        plan = build_plan(schema)
        t0 = time.perf_counter()
        sims, _, _ = pairwise_similarity(x, q=q, weights=w, schema=schema)
        jax.block_until_ready(sims)
        dt = time.perf_counter() - t0
        rows.append(dict(
            name=name, algo=schema.algorithm,
            comm_cost=round(schema.communication_cost(), 2),
            reducers=schema.num_reducers,
            max_replication=int(schema.replication().max()),
            gather_rows=int(plan.mask.sum()),
            wall_ms=round(dt * 1e3, 1)))
    base = rows[1]["comm_cost"]
    for r in rows:
        r["comm_vs_naive"] = round(r["comm_cost"] / base, 3)
    return rows


def _time_executor(x, q, w, schema, executor, repeats: int = 3):
    """Median wall time over ``repeats`` after a compile warmup."""
    sims = None
    for _ in range(2):                               # warmup / compile
        sims, plan, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, executor=executor)
        jax.block_until_ready(sims)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out, _, _ = pairwise_similarity(
            x, q=q, weights=w, schema=schema, executor=executor)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return sims, plan, float(np.median(times))


def run_skewed(m: int = 512, d: int = 64, q: float = 1.0,
               zipf_a: float = 1.6, seed: int = 0, repeats: int = 3):
    """Zipf-sized inputs: dense executor vs bucketed executor on one plan.

    Returns a dict with the padded-element reduction (peak gather memory),
    per-executor wall times, and the allclose check.  The acceptance bar is
    >= 2x padded-element reduction and a wall-clock win."""
    rng = np.random.default_rng(seed)
    # heavy-tailed sizes in (0, 0.45 q]: many tiny inputs, a few near q/2
    w = np.clip(rng.zipf(zipf_a, m).astype(np.float64) / 32.0,
                0.01, 0.45 * q)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))

    schema = plan_a2a(w, q)
    schema.validate("a2a")

    sims_d, plan, dense_s = _time_executor(x, q, w, schema, "dense", repeats)
    sims_b, _, buck_s = _time_executor(x, q, w, schema, "bucketed", repeats)

    allclose = bool(np.allclose(np.asarray(sims_d), np.asarray(sims_b),
                                rtol=1e-4, atol=1e-4))
    rep = {
        "m": m, "d": d, "q": q, "zipf_a": zipf_a,
        "algorithm": schema.algorithm,
        "reducers": plan.num_reducers,
        "dense_width": plan.L,
        "bucket_widths": plan.bucket_widths(),
        "dense_padded_elements": plan.dense_padded_elements,
        "bucketed_padded_elements": plan.bucketed_padded_elements,
        "padded_reduction": round(plan.padding_savings, 3),
        "dense_wall_ms": round(dense_s * 1e3, 1),
        "bucketed_wall_ms": round(buck_s * 1e3, 1),
        "speedup": round(dense_s / max(buck_s, 1e-12), 3),
        "allclose": allclose,
    }
    return rep


def _executor_hlo(x_shape, plan, executor: str) -> str:
    """Compiled single-host HLO text of one executor's program (no mesh),
    dispatched through the executor registry."""
    from repro.mapreduce.allpairs import _block_fn
    from repro.mapreduce.executors import get_executor

    lowered = get_executor(executor).lower(
        x_shape, plan, reducer_fn=_block_fn("dot", False), metric="dot",
        mesh=None)
    return lowered.compile().as_text()


def _kernel_model(plan, d: int, itemsize: int = 4) -> dict:
    from repro.kernels.pairwise.fused_gather_gram import fused_traffic_model
    return {k: int(v)
            for k, v in fused_traffic_model(plan.buckets, d,
                                            itemsize).items()}


def run_fused(m: int = 512, d: int = 64, q: float = 1.0,
              zipf_a: float = 1.6, seed: int = 0, repeats: int = 3):
    """Fused-executor acceptance run on the Zipf skewed workload.

    Times all three executors on one plan, checks allclose, measures the
    HBM bytes of each lowered program, and verifies from the compiled HLO
    that the fused program never materializes the dense (R, L, d) gather
    buffer that the dense executor does.  Bars: fused >= 1.5x wall-clock
    over bucketed, no dense gather buffer in the fused HLO.
    """
    from repro.launch.hlo_analysis import analyze_hlo_text, has_buffer_shape

    rng = np.random.default_rng(seed)
    w = np.clip(rng.zipf(zipf_a, m).astype(np.float64) / 32.0,
                0.01, 0.45 * q)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    schema = plan_a2a(w, q)
    schema.validate("a2a")

    sims_d, plan, dense_s = _time_executor(x, q, w, schema, "dense", repeats)
    sims_b, _, buck_s = _time_executor(x, q, w, schema, "bucketed", repeats)
    sims_f, _, fused_s = _time_executor(x, q, w, schema, "fused", repeats)

    allclose = bool(
        np.allclose(np.asarray(sims_b), np.asarray(sims_f),
                    rtol=1e-4, atol=1e-4)
        and np.allclose(np.asarray(sims_d), np.asarray(sims_f),
                        rtol=1e-4, atol=1e-4))

    gather_shape = (plan.R, plan.L, d)
    hlo = {name: _executor_hlo((m, d), plan, name)
           for name in ("dense", "fused")}
    hbm = {name: analyze_hlo_text(text).hbm_bytes
           for name, text in hlo.items()}
    # tiled dataflow check: with bl below the bucket widths, multi-tile
    # buckets must stream (Rb, bl, d) tiles — their full (Rb, Lb, d)
    # gather must not appear anywhere in the lowered program
    from repro.mapreduce.executors import get_executor
    tiled_bl = 8
    tiled_hlo = get_executor("fused").lower(
        (m, d), plan, metric="dot", mesh=None,
        bl=tiled_bl).compile().as_text()
    tiled_gathers = {
        f"{b.idx.shape[0]}x{b.idx.shape[1]}x{d}": has_buffer_shape(
            tiled_hlo, (b.idx.shape[0], b.idx.shape[1], d))
        for b in plan.buckets if b.idx.shape[1] > tiled_bl}
    # bucketed: per-bucket programs, terms summed (runs back-to-back)
    from repro.mapreduce.allpairs import _block_fn
    from repro.mapreduce.engine import _gather_reduce
    from functools import partial
    buck_bytes = 0.0
    run = jax.jit(partial(_gather_reduce, reducer_fn=_block_fn("dot", False)))
    for b in plan.buckets:
        lowered = run.lower(
            jax.ShapeDtypeStruct((m, d), jnp.float32),
            jax.ShapeDtypeStruct(b.idx.shape, jnp.int32),
            jax.ShapeDtypeStruct(b.mask.shape, jnp.bool_))
        buck_bytes += analyze_hlo_text(lowered.compile().as_text()).hbm_bytes
    hbm["bucketed"] = buck_bytes

    rep = {
        "m": m, "d": d, "q": q, "zipf_a": zipf_a,
        "algorithm": schema.algorithm,
        "reducers": plan.num_reducers,
        "dense_width": plan.L,
        "bucket_widths": plan.bucket_widths(),
        "padded_elements": {
            "dense": plan.dense_padded_elements,
            "bucketed": plan.bucketed_padded_elements,
            "fused": plan.bucketed_padded_elements,   # same buckets, no HBM
        },
        "wall_ms": {
            "dense": round(dense_s * 1e3, 1),
            "bucketed": round(buck_s * 1e3, 1),
            "fused": round(fused_s * 1e3, 1),
        },
        "hbm_bytes": {k: int(v) for k, v in hbm.items()},
        # the TPU kernel's analytic dataflow (VMEM streaming is a kernel
        # property the CPU-lowered streamed twin can't exhibit)
        "hbm_bytes_fused_kernel_model": _kernel_model(plan, d),
        "speedup_fused_vs_bucketed": round(buck_s / max(fused_s, 1e-12), 3),
        "speedup_fused_vs_dense": round(dense_s / max(fused_s, 1e-12), 3),
        "allclose": allclose,
        "dense_gather_buffer": list(gather_shape),
        "gather_buffer_in_dense_hlo": has_buffer_shape(hlo["dense"],
                                                       gather_shape),
        "gather_buffer_in_fused_hlo": has_buffer_shape(hlo["fused"],
                                                       gather_shape),
        # per-bucket full gathers in the bl=8 tiled lowering (must all be
        # False for buckets wider than one tile)
        "bucket_gather_in_tiled_fused_hlo": tiled_gathers,
    }
    return rep


def run_sharded(m: int = 512, d: int = 64, q: float = 1.0,
                zipf_a: float = 1.6, seed: int = 0, repeats: int = 3,
                balance_shards: int = 8):
    """Sharded-executor acceptance run on the Zipf skewed workload.

    Times bucketed / fused / sharded on one plan (the sharded executor uses
    all local devices — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a real
    multi-shard CPU mesh, which is what ``make bench-sharded`` does),
    checks allclose against both baselines, and reports the LPT partition:
    per-shard padded elements, shipped rows, and the balance factor over
    ``balance_shards`` shards.  Bars: allclose, and balance factor <= 1.25
    on the Zipf m=512 reference partition.
    """
    import jax as _jax
    from repro.core import partition_plan

    rng = np.random.default_rng(seed)
    w = np.clip(rng.zipf(zipf_a, m).astype(np.float64) / 32.0,
                0.01, 0.45 * q)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    schema = plan_a2a(w, q)
    schema.validate("a2a")

    sims_b, plan, buck_s = _time_executor(x, q, w, schema, "bucketed",
                                          repeats)
    sims_f, _, fused_s = _time_executor(x, q, w, schema, "fused", repeats)
    sims_s, _, shard_s = _time_executor(x, q, w, schema, "sharded", repeats)

    allclose = bool(
        np.allclose(np.asarray(sims_b), np.asarray(sims_s),
                    rtol=1e-4, atol=1e-4)
        and np.allclose(np.asarray(sims_f), np.asarray(sims_s),
                        rtol=1e-4, atol=1e-4))

    # the acceptance partition: LPT balance over the reference shard count
    # (independent of how many devices this host happens to expose)
    part = partition_plan(plan, balance_shards)
    rep_part = part.report()
    # the partition actually executed on this host's devices
    n_dev = len(_jax.devices())
    exec_part = partition_plan(plan, n_dev).report()

    rep = {
        "m": m, "d": d, "q": q, "zipf_a": zipf_a,
        "algorithm": schema.algorithm,
        "reducers": plan.num_reducers,
        "devices": n_dev,
        "bucket_widths": plan.bucket_widths(),
        "wall_ms": {
            "bucketed": round(buck_s * 1e3, 1),
            "fused": round(fused_s * 1e3, 1),
            "sharded": round(shard_s * 1e3, 1),
        },
        "speedup_sharded_vs_bucketed": round(buck_s / max(shard_s, 1e-12),
                                             3),
        "speedup_sharded_vs_fused": round(fused_s / max(shard_s, 1e-12), 3),
        "allclose": allclose,
        "balance_shards": balance_shards,
        "balance_factor": rep_part["balance_factor"],
        "padded_elements_per_shard": rep_part["padded_elements_per_shard"],
        "shipped_rows_per_shard": rep_part["shipped_rows"],
        "executed_num_shards": n_dev,
        "executed_balance_factor": exec_part["balance_factor"],
    }
    return rep


def emit_bench_json(payload: dict, path: str = BENCH_JSON):
    """Merge ``payload`` into BENCH_engine.json (canonical implementation
    lives in bench_common; this wrapper keeps the historical import site
    ``from bench_engine import emit_bench_json`` working)."""
    return _emit_bench_json(payload, path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--skewed", action="store_true",
                    help="Zipf input sizes: dense vs bucketed executor")
    ap.add_argument("--fused", action="store_true",
                    help="Zipf input sizes: fused vs bucketed vs dense; "
                         "writes BENCH_engine.json")
    ap.add_argument("--sharded", action="store_true",
                    help="Zipf input sizes: sharded vs bucketed vs fused "
                         "over the local device mesh; writes "
                         "BENCH_engine.json")
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--zipf-a", type=float, default=1.6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.fused:
        rep = run_fused(m=args.m or 512, d=args.d, zipf_a=args.zipf_a,
                        seed=args.seed)
        print(f"fused A2A  m={rep['m']} d={rep['d']} "
              f"zipf_a={rep['zipf_a']} [{rep['algorithm']}] "
              f"reducers={rep['reducers']}")
        for name in ("dense", "bucketed", "fused"):
            print(f"  {name:8s} wall={rep['wall_ms'][name]:8.1f}ms "
                  f"padded={rep['padded_elements'][name]:9d} "
                  f"hbm_bytes={rep['hbm_bytes'][name]:.3e}")
        print(f"  fused speedup: {rep['speedup_fused_vs_bucketed']:.2f}x "
              f"vs bucketed, {rep['speedup_fused_vs_dense']:.2f}x vs dense  "
              f"allclose: {rep['allclose']}")
        print(f"  dense (R,L,d) gather buffer {rep['dense_gather_buffer']}: "
              f"in dense HLO: {rep['gather_buffer_in_dense_hlo']}  "
              f"in fused HLO: {rep['gather_buffer_in_fused_hlo']}")
        print(f"  tiled (bl=8) fused HLO bucket gathers: "
              f"{rep['bucket_gather_in_tiled_fused_hlo']}")
        path = emit_bench_json({"engine_fused": rep})
        print(f"  wrote {path}")
        if not rep["allclose"]:
            raise SystemExit("FAIL: fused output diverges")
        if rep["gather_buffer_in_fused_hlo"]:
            raise SystemExit("FAIL: fused HLO materializes the (R, L, d) "
                             "gather buffer")
        if not rep["gather_buffer_in_dense_hlo"]:
            raise SystemExit("FAIL: buffer check is vacuous — dense HLO "
                             "does not show the (R, L, d) gather")
        if any(rep["bucket_gather_in_tiled_fused_hlo"].values()):
            raise SystemExit("FAIL: tiled fused HLO materializes a full "
                             "per-bucket gather")
        if rep["speedup_fused_vs_bucketed"] < 1.5:
            raise SystemExit(
                f"FAIL: fused speedup {rep['speedup_fused_vs_bucketed']:.2f}x"
                f" below the 1.5x bar")
        return rep

    if args.sharded:
        rep = run_sharded(m=args.m or 512, d=args.d, zipf_a=args.zipf_a,
                          seed=args.seed)
        print(f"sharded A2A  m={rep['m']} d={rep['d']} "
              f"zipf_a={rep['zipf_a']} [{rep['algorithm']}] "
              f"reducers={rep['reducers']} devices={rep['devices']}")
        for name in ("bucketed", "fused", "sharded"):
            print(f"  {name:8s} wall={rep['wall_ms'][name]:8.1f}ms")
        print(f"  sharded speedup: {rep['speedup_sharded_vs_bucketed']:.2f}x"
              f" vs bucketed, {rep['speedup_sharded_vs_fused']:.2f}x vs "
              f"fused  allclose: {rep['allclose']}")
        print(f"  LPT balance over {rep['balance_shards']} shards: "
              f"{rep['balance_factor']:.3f}  padded/shard: "
              f"{rep['padded_elements_per_shard']}  shipped/shard: "
              f"{rep['shipped_rows_per_shard']}")
        path = emit_bench_json({"engine_sharded": rep})
        print(f"  wrote {path}")
        if not rep["allclose"]:
            raise SystemExit("FAIL: sharded output diverges")
        if rep["balance_factor"] > 1.25:
            raise SystemExit(
                f"FAIL: LPT balance factor {rep['balance_factor']:.3f} "
                f"above the 1.25 bar")
        return rep

    if args.skewed:
        rep = run_skewed(m=args.m or 512, d=args.d, zipf_a=args.zipf_a,
                         seed=args.seed)
        print(f"skewed A2A  m={rep['m']} d={rep['d']} zipf_a={rep['zipf_a']} "
              f"[{rep['algorithm']}] reducers={rep['reducers']}")
        print(f"  dense    width={rep['dense_width']:5d} "
              f"padded={rep['dense_padded_elements']:9d} "
              f"wall={rep['dense_wall_ms']:8.1f}ms")
        print(f"  bucketed widths={rep['bucket_widths']} "
              f"padded={rep['bucketed_padded_elements']:9d} "
              f"wall={rep['bucketed_wall_ms']:8.1f}ms")
        print(f"  padded-elements reduction: {rep['padded_reduction']:.2f}x  "
              f"speedup: {rep['speedup']:.2f}x  allclose: {rep['allclose']}")
        if not rep["allclose"]:
            raise SystemExit("FAIL: bucketed output diverges from dense")
        if rep["padded_reduction"] < 2.0:
            raise SystemExit(
                f"FAIL: padded-element reduction "
                f"{rep['padded_reduction']:.2f}x below the 2x bar")
        return rep

    rows = run(m=args.m or 96, d=args.d, seed=args.seed)
    for r in rows:
        print(f"{r['name']:16s} comm={r['comm_cost']:9.2f} "
              f"({r['comm_vs_naive']:.3f}x naive) reducers={r['reducers']:5d} "
              f"gather_rows={r['gather_rows']:6d} wall={r['wall_ms']:7.1f}ms "
              f"[{r['algo']}]")
    return rows


if __name__ == "__main__":
    main()
