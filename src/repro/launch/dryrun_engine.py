import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Dry-run of the paper's own workload on the production mesh: the A2A
all-pairs engine, planner schema vs naive replication.

Lowers `run_reducers` (gather + per-reducer Gram matmul) for both plans on
the 16x16 mesh and reports HLO-measured roofline terms.  The headline: the
schema's communication-cost reduction shows up 1:1 as gather/collective
bytes in the compiled program.

Usage: PYTHONPATH=src python -m repro.launch.dryrun_engine [--m 1024]
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import naive_pairs, plan_a2a
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import DRYRUN_TARGET, combine_hlo_stats, hw_for
from repro.launch.hlo_analysis import analyze_hlo_text
from repro.kernels.pairwise.fused_gather_gram import fused_traffic_model
from repro.mapreduce.allpairs import _block_fn
from repro.mapreduce.engine import build_plan
from repro.mapreduce.executors import get_executor
from repro.obs import span as _obs_span


def _traced(fn):
    """Wrap an ``analyze_*`` stage in an obs span so a dry run exports a
    per-stage Chrome trace alongside its JSON report."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with _obs_span(fn.__name__, stage="dryrun"):
            return fn(*args, **kwargs)
    return wrapper

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "results", "dryrun")


def _stats_rec(plan, name, stats, padded_elements, extra=None):
    hw = hw_for(DRYRUN_TARGET)
    rec = {
        "name": name,
        "reducers": plan.num_reducers,
        "slots": int(plan.mask.sum()),
        "padded_elements": int(padded_elements),
        "schema_comm_cost_rows": float(plan.comm_cost),
        "flops_per_device": stats.flops,
        "hbm_bytes_per_device": stats.hbm_bytes,
        "collective_bytes_per_device": stats.collective_bytes,
        "t_compute": stats.flops / hw.peak_flops,
        "t_memory": stats.hbm_bytes / hw.hbm_bw,
        "t_collective": stats.collective_bytes / hw.link_bw,
    }
    if extra:
        rec.update(extra)
    return rec


@_traced
def analyze(plan, m, d, mesh, name):
    """Dense path: one program padded to the global max slot count."""
    lowered = get_executor("dense").lower(
        (m, d), plan, reducer_fn=_block_fn("dot", False), mesh=mesh,
        dtype=jnp.bfloat16)
    compiled = lowered.compile()
    stats = analyze_hlo_text(compiled.as_text(),
                             num_partitions=mesh.devices.size)
    return _stats_rec(plan, name, stats, plan.dense_padded_elements)


@_traced
def analyze_bucketed(plan, m, d, mesh, name):
    """Bucketed path: one program per capacity bucket; terms are summed
    (the bucket programs run back-to-back on the same mesh)."""
    per_bucket = get_executor("bucketed").lower(
        (m, d), plan, reducer_fn=_block_fn("dot", False), mesh=mesh,
        dtype=jnp.bfloat16)
    stats = combine_hlo_stats([
        analyze_hlo_text(lowered.compile().as_text(),
                         num_partitions=mesh.devices.size)
        for _, lowered in per_bucket
    ])
    return _stats_rec(
        plan, name, stats, plan.bucketed_padded_elements,
        extra={"bucket_widths": plan.bucket_widths(),
               "padding_savings": float(plan.padding_savings)})


@_traced
def analyze_fused(plan, m, d, mesh, name, bucketed_rec=None):
    """Fused path: ONE program for all capacity buckets, gather streamed.

    Lowers the streamed twin of the fused gather+Gram kernel (the jnp
    program with the kernel's tile dataflow — the Pallas kernel itself is
    Mosaic/TPU-only) and reports the HBM bytes it saves over the bucketed
    executor, next to the schema's communication cost and lower bound: the
    saved bytes are the materialized-gather round trip, i.e. the on-device
    copy of exactly the traffic the paper's objective minimizes."""
    lowered = get_executor("fused").lower((m, d), plan, metric="dot",
                                          mesh=mesh, dtype=jnp.bfloat16)
    stats = analyze_hlo_text(lowered.compile().as_text(),
                             num_partitions=mesh.devices.size)
    itemsize = 2                                     # bf16 table rows
    model = fused_traffic_model(plan.buckets, d, itemsize)
    extra = {
        "bucket_widths": plan.bucket_widths(),
        "padding_savings": float(plan.padding_savings),
        "fused_model": model,
        # schema-level shuffle volume for scale: shipped rows x row bytes
        "schema_comm_bytes": float(plan.comm_cost) * d * itemsize,
        "schema_lower_bound_bytes": (
            float(plan.lower_bound) * d * itemsize
            if plan.lower_bound else None),
    }
    if bucketed_rec is not None:
        saved = (bucketed_rec["hbm_bytes_per_device"]
                 - stats.hbm_bytes)
        extra["saved_hbm_bytes_per_device_vs_bucketed"] = saved
        extra["saved_hbm_vs_schema_comm"] = (
            saved * mesh.devices.size / max(extra["schema_comm_bytes"], 1))
    return _stats_rec(plan, name, stats, plan.bucketed_padded_elements,
                      extra=extra)


@_traced
def analyze_streaming(w, q, m, d, name):
    """Streaming path: lower the DELTA program of one single-input edit.

    Builds an ``IncrementalPlanner`` on the profile, applies one insert,
    and lowers what each side would actually execute: the delta's
    dirty-reducer sub-plan vs the full post-edit plan (single-host
    lowering — the delta-vs-replan comparison is per-program, not
    per-mesh).  Reports HLO bytes next to the schema-level ledger: delta
    comm bytes (dirty reducers' shipped rows), full re-plan comm bytes,
    and the instance's replication-rate lower bound — the static planner
    pays the middle number on *every* edit, the streaming planner pays the
    first."""
    from repro.stream import IncrementalPlanner

    ip = IncrementalPlanner(q, w, check=False)
    delta = ip.insert(float(np.median(w)))
    plan = ip.plan()
    ex = get_executor("streaming")
    fn = _block_fn("dot", False)

    def hbm(lowered_list):
        return combine_hlo_stats([
            analyze_hlo_text(lo.compile().as_text())
            for _, lo in lowered_list]).hbm_bytes

    delta_hbm = hbm(ex.lower((m + 1, d), plan, reducer_fn=fn, mesh=None,
                             dtype=jnp.bfloat16, delta=delta))
    full_hbm = hbm(ex.lower((m + 1, d), plan, reducer_fn=fn, mesh=None,
                            dtype=jnp.bfloat16))
    itemsize = 2                                     # bf16 table rows
    lb = float(delta.lower_bound)
    rec = {
        "name": name,
        "edit": delta.kind,
        "reducers": int(delta.num_reducers),
        "dirty_reducers": int(len(delta.dirty_rows)),
        "recompute_fraction": float(delta.recompute_fraction),
        "gap_drift": float(delta.gap_drift),
        "delta_hbm_bytes": delta_hbm,
        "full_hbm_bytes": full_hbm,
        "delta_comm_bytes": float(delta.delta_comm_rows()) * d * itemsize,
        "replan_comm_bytes": float(delta.comm_cost) * d * itemsize,
        "schema_lower_bound_bytes": lb * d * itemsize,
    }
    rec["delta_vs_replan_bytes"] = (
        rec["delta_comm_bytes"] / max(rec["replan_comm_bytes"], 1e-12))
    return rec


@_traced
def analyze_sharded(plan, m, d, mesh, name):
    """Sharded path: ONE shard_map program, reducers LPT-balanced.

    Lowers the sharded executor's program (per-shard fused tile pipeline +
    the single cross-shard assembly gather) on the production mesh and
    reports the *per-shard* HLO bytes next to the schema's per-shard share
    of the communication lower bound: with S shards, a balanced partition
    ships ~comm_cost/S rows per shard, so per-shard HLO bytes should track
    ``lower_bound * d * itemsize / S`` times the plan's optimality gap —
    the partition report quantifies how close LPT gets."""
    ex = get_executor("sharded")
    S = mesh.devices.size
    part = ex.partition(plan, S)
    lowered = ex.lower((m, d), plan, metric="dot", mesh=mesh,
                       dtype=jnp.bfloat16)
    stats = analyze_hlo_text(lowered.compile().as_text(),
                             num_partitions=S)
    itemsize = 2                                     # bf16 table rows
    lb_rows = float(plan.lower_bound) if plan.lower_bound else None
    rep = part.report()
    extra = {
        "num_shards": S,
        "balance_factor": rep["balance_factor"],
        "shipped_rows_per_shard_max": int(max(rep["shipped_rows"])),
        "shipped_rows_per_shard_mean": float(np.mean(rep["shipped_rows"])),
        "padded_elements_per_shard_max": int(
            max(rep["padded_elements_per_shard"])),
        # per-shard HLO bytes vs the schema lower bound's per-shard share
        "per_shard_hbm_bytes": stats.hbm_bytes,
        "schema_lb_bytes_per_shard": (
            lb_rows * d * itemsize / S if lb_rows else None),
        "per_shard_hbm_vs_lb": (
            stats.hbm_bytes / (lb_rows * d * itemsize / S)
            if lb_rows else None),
    }
    return _stats_rec(plan, name, stats, plan.bucketed_padded_elements,
                      extra=extra)


@_traced
def analyze_coded(plan, m, d, name, num_shards: int = 16):
    """Coded path: the replication x communication sweep.

    Lowers the coded executor's program (per-shard rect tile pipeline +
    the residual all-to-all) at several replication rates r on a 1-D
    ``num_shards``-device submesh (the coded combining stage is a 1-D
    shard-group exchange; a full 16x16 lowering adds nothing but compile
    time) and emits the replication-vs-communication Pareto frontier:
    measured per-shard assembly bytes (HLO collectives) fall with r while
    the input-shipping term ``r x comm_cost`` rises, and every point's
    total stays above the Thm-8 lower bound — replication never tunnels
    under it, it only re-shapes where the bytes are paid.
    ``choose_replication`` marks the knee."""
    from repro.compat import make_mesh
    from repro.launch.roofline import collective_bytes
    from repro.mapreduce.executors import choose_replication

    ex = get_executor("coded")
    mesh = make_mesh((num_shards,), ("shard",))
    S = num_shards
    itemsize = 2                                     # bf16 table rows
    lb_rows = float(plan.lower_bound) if plan.lower_bound else None
    lb_bytes = lb_rows * d * itemsize if lb_rows else None
    shipped_bytes = float(plan.comm_cost) * d * itemsize
    best_r, model_frontier = choose_replication(
        plan, S, m, d, itemsize=itemsize)
    frontier = []
    for rec in model_frontier:
        r = rec["replication"]
        lowered = ex.lower((m, d), plan, metric="dot", mesh=mesh,
                           dtype=jnp.bfloat16, m=m, replication=r)
        coll = collective_bytes(lowered.compile().as_text())
        point = {
            "replication": r,
            "measured_assembly_bytes_per_shard": coll["total"],
            "model_assembly_bytes_per_shard":
                rec["assembly_bytes_per_shard"],
            "local_fraction": rec["local_fraction"],
            "shipped_bytes": rec["shipped_bytes"],
            "total_comm_bytes": (rec["shipped_bytes"]
                                 + S * coll["total"]),
            "ge_lower_bound": (
                rec["shipped_bytes"] + S * coll["total"] >= lb_bytes
                if lb_bytes else None),
        }
        frontier.append(point)
    return {
        "name": name,
        "reducers": plan.num_reducers,
        "num_shards": S,
        "best_replication": best_r,
        "schema_comm_bytes": shipped_bytes,
        "schema_lower_bound_bytes": lb_bytes,
        "pareto_frontier": frontier,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=1024)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--q", type=float, default=32.0)
    ap.add_argument("--zipf", action="store_true",
                    help="Zipf-skewed input sizes (bucketed-executor case)")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=False)
    n_dev = mesh.devices.size
    if args.zipf:
        rng = np.random.default_rng(0)
        w = np.clip(rng.zipf(1.6, args.m) / 16.0, 0.05,
                    args.q * 0.45)
    else:
        w = np.ones(args.m)

    schema = plan_a2a(w, args.q)
    plan_opt = build_plan(schema, pad_reducers_to=n_dev)
    plan_nv = build_plan(naive_pairs(w, args.q), pad_reducers_to=n_dev)

    bucketed_rec = analyze_bucketed(plan_opt, args.m, args.d, mesh,
                                    f"planner-bucketed[{schema.algorithm}]")
    rows = [
        analyze(plan_opt, args.m, args.d, mesh,
                f"planner[{schema.algorithm}]"),
        bucketed_rec,
        analyze_fused(plan_opt, args.m, args.d, mesh,
                      f"planner-fused[{schema.algorithm}]",
                      bucketed_rec=bucketed_rec),
        analyze_sharded(plan_opt, args.m, args.d, mesh,
                        f"planner-sharded[{schema.algorithm}]"),
        analyze(plan_nv, args.m, args.d, mesh, "naive-all-pairs"),
    ]
    base = rows[-1]
    for r in rows:
        r["shuffle_bytes_vs_naive"] = (
            r["hbm_bytes_per_device"] / max(base["hbm_bytes_per_device"], 1))
        r["comm_cost_vs_naive"] = (
            r["schema_comm_cost_rows"] / base["schema_comm_cost_rows"])
        print(f"{r['name']:40s} reducers={r['reducers']:8d} "
              f"gather_rows={r['slots']:9d} "
              f"padded={r['padded_elements']:10d} "
              f"t_m={r['t_memory']:.4f}s t_x={r['t_collective']:.4f}s "
              f"bytes_vs_naive={r['shuffle_bytes_vs_naive']:.3f} "
              f"(schema comm ratio {r['comm_cost_vs_naive']:.3f})")
        if "saved_hbm_bytes_per_device_vs_bucketed" in r:
            mdl = r["fused_model"]
            print(f"{'':40s} fused saves "
                  f"{r['saved_hbm_bytes_per_device_vs_bucketed']/1e6:.1f} "
                  f"MB/device HBM vs bucketed "
                  f"({r['saved_hbm_vs_schema_comm']:.2f}x the schema's "
                  f"comm volume of {r['schema_comm_bytes']/1e6:.1f} MB; "
                  f"kernel model: {mdl['saved_bytes']/1e6:.1f} MB global "
                  f"gather round-trip removed)")
        if "num_shards" in r:
            lb = r["schema_lb_bytes_per_shard"]
            print(f"{'':40s} sharded over {r['num_shards']} shards: "
                  f"LPT balance {r['balance_factor']:.3f}, "
                  f"per-shard HLO {r['per_shard_hbm_bytes']/1e6:.1f} MB vs "
                  f"lower-bound share "
                  f"{(lb or 0)/1e6:.1f} MB"
                  + (f" ({r['per_shard_hbm_vs_lb']:.2f}x)" if lb else ""))
    cr = analyze_coded(plan_opt, args.m, args.d,
                       f"coded-frontier[{schema.algorithm}]")
    rows.append(cr)
    print(f"{cr['name']:40s} shards={cr['num_shards']} "
          f"knee r={cr['best_replication']} "
          f"(LB {(cr['schema_lower_bound_bytes'] or 0)/1e6:.2f} MB)")
    for p in cr["pareto_frontier"]:
        print(f"{'':40s} r={p['replication']:2d} assembly "
              f"{p['measured_assembly_bytes_per_shard']/1e6:.2f} MB/shard, "
              f"shipped {p['shipped_bytes']/1e6:.2f} MB, total "
              f"{p['total_comm_bytes']/1e6:.2f} MB "
              f">=LB:{p['ge_lower_bound']}")
    sr = analyze_streaming(w, args.q, args.m, args.d,
                           "streaming-delta[insert]")
    rows.append(sr)
    print(f"{sr['name']:40s} dirty={sr['dirty_reducers']:5d}"
          f"/{sr['reducers']:8d} "
          f"(recompute {sr['recompute_fraction']:.3f}) "
          f"delta HLO {sr['delta_hbm_bytes']/1e6:.1f} MB vs full "
          f"{sr['full_hbm_bytes']/1e6:.1f} MB")
    print(f"{'':40s} delta comm {sr['delta_comm_bytes']/1e6:.2f} MB vs "
          f"re-plan {sr['replan_comm_bytes']/1e6:.2f} MB "
          f"({sr['delta_vs_replan_bytes']:.3f}x) vs lower bound "
          f"{sr['schema_lower_bound_bytes']/1e6:.2f} MB")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "engine_a2a__pod_16x16.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
