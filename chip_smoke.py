#!/usr/bin/env python3
"""Chip smoke test: the served pairwise path, end to end, on a TPU.

    python chip_smoke.py [--seed N]             # P1-P4 on one chip
    python chip_smoke.py --chips 4 [--seed N]   # sharded + coded, 4 chips

Drives ``repro.serve.PairwiseService`` through the calls a user makes, at
sizes a deployment holds on one v5e chip:

  P1 similarity  fused executor, m=8192 x d=128 f32 (SIFT-128 row width),
                 3 requests on one Zipf weight profile (plan cache hits);
  P2 x2y         fused rect kernel, 4096 x 4096 x 128, Zipf on both sides;
  P3 block       1,000,000 x 128 f32 table (SIFT-1M's shape), four
                 2048 x 2048 tiles, one on the diagonal, on the
                 million-input profile of benchmarks/bench_hierarchy.py;
  P4 streaming   m=2048 live table, a few add/remove/reweight edits.

``--chips 4`` runs only P1's profile through the ``sharded`` executor and
the ``coded`` executor at replication 1 and 2 over all four chips.

Every phase prints one JSON line: shapes, reducer count and bucket widths,
the fused dispatch counts (kernel / streamed / fallbacks), the largest
error against the reference beside its tolerance, first-call and warm wall
times, the seconds spent compiling (and how many programs came from the
persistent compile cache), and ``peak_bytes_in_use`` /
``peak_bytes_reserved`` (buffers / buffers plus program scratch).  The last line is
``{"ok": true, "device": {...}}`` only if every phase ran the Pallas
kernel path (no streamed twin, no fallback) and met its reference.  With
no TPU the script exits non-zero and prints no result.

Reference: float64 NumPy on the host, independent of the planner —
``x @ x.T`` with a zero diagonal, or the matching tile / cross product.
Tolerance: a length-d fp32 dot product is off by at most
``gamma_d * sum_k |a_k b_k| <= gamma_d * |a| |b|`` with
``gamma_d = d u / (1 - d u)`` and ``u = 2**-24`` (Higham, "Accuracy and
Stability of Numerical Algorithms", Sec. 3.1).  The check uses
``gamma_d * max|a| * max|b|`` over the rows compared: about 1.5e-3 for
128-wide N(0, 1) rows, where one bf16 rounding of the operands (relative
2**-9 each) already costs ~3e-2.  Data comes from ``--seed``: Gaussian
rows, and Zipf(1.6)/32 input weights clipped to ``[0.01, 0.45 q]`` at
q=8 — except P3, whose million inputs take the repository's block-serving
profile (rank**-0.6 weights, at most 0.24 q, q=25): at q=8 the Zipf
profile is planned as an overlapping hybrid schema, which block serving
cannot hold.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
Q = 8.0                                  # reducer capacity of every phase


# ----------------------------------------------------------------- data
def zipf_weights(rng, m: int, q: float = Q) -> np.ndarray:
    return np.clip(rng.zipf(1.6, m).astype(np.float64) / 32.0,
                   0.01, 0.45 * q)


def powerlaw_weights(rng, m: int, q: float) -> np.ndarray:
    """Rank**-0.6 weights, shuffled, at most 0.24 q (block serving)."""
    w = 1.0 / np.arange(1, m + 1) ** 0.6
    w = np.clip(w / w.max(), None, 0.24 * q)
    rng.shuffle(w)
    return w


def table(rng, m: int, d: int) -> np.ndarray:
    return rng.standard_normal((m, d), dtype=np.float32)


# ------------------------------------------------------------ reference
def gram64(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return a.astype(np.float64) @ b.astype(np.float64).T


def fp32_tol(a: np.ndarray, b: np.ndarray) -> float:
    """gamma_d * max|a_i| * max|b_j|: the fp32 dot-product error bound."""
    d = a.shape[1]
    u = 2.0 ** -24
    gamma = d * u / (1 - d * u)
    na = np.einsum("ij,ij->i", a, a, dtype=np.float64).max()
    nb = np.einsum("ij,ij->i", b, b, dtype=np.float64).max()
    return float(gamma * math.sqrt(na * nb))


def max_err(got, ref: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref)))


# ---------------------------------------------------------------- phases
def _counts(before: dict) -> dict:
    from repro.mapreduce.engine import fused_stats
    after = fused_stats()
    return {k: after[k] - before.get(k, 0)
            for k in ("kernel", "streamed", "fallbacks")}


def _kernel_only(counts: dict) -> bool:
    return counts["kernel"] > 0 and not counts["streamed"] \
        and not counts["fallbacks"]


def phase_similarity(rng, *, m=8192, d=128, requests=3, **svc_kw) -> dict:
    """P1: repeated all-pairs requests on one weight profile."""
    from repro.mapreduce.engine import fused_stats
    from repro.serve import PairwiseService
    svc = PairwiseService(q=Q, executor="fused", **svc_kw)
    w = zipf_weights(rng, m)
    before = fused_stats()
    reqs, info = [], {}
    for _ in range(requests):
        x = table(rng, m, d)
        sims, info = svc.similarity(x, weights=w)
        ref = gram64(x, x)
        np.fill_diagonal(ref, 0.0)
        reqs.append({"wall_s": info["wall_s"],
                     "plan_cache_hit": info["plan_cache_hit"],
                     "fused_path": info["fused_path"],
                     "max_abs_err": max_err(sims, ref),
                     "tol": fp32_tol(x, x)})
        del sims, ref
    counts = _counts(before)
    ok = (_kernel_only(counts)
          and all(r["fused_path"] == "kernel" for r in reqs)
          and all(r["max_abs_err"] <= r["tol"] for r in reqs)
          and all(r["plan_cache_hit"] for r in reqs[1:]))
    return {"ok": ok, "shape": [m, d], "dtype": "float32",
            "algorithm": info["algorithm"], "reducers": info["reducers"],
            "bucket_widths": info["bucket_widths"], "counts": counts,
            "max_abs_err": max(r["max_abs_err"] for r in reqs),
            "tol": min(r["tol"] for r in reqs),
            "first_s": reqs[0]["wall_s"],
            "warm_s": [r["wall_s"] for r in reqs[1:]], "requests": reqs}


def phase_x2y(rng, *, m=4096, d=128, requests=2, **svc_kw) -> dict:
    """P2: rectangular cross similarity, Zipf weights on both sides."""
    from repro.mapreduce.engine import fused_stats
    from repro.serve import PairwiseService
    svc = PairwiseService(q=Q, executor="fused", **svc_kw)
    wx, wy = zipf_weights(rng, m), zipf_weights(rng, m)
    x, y = table(rng, m, d), table(rng, m, d)
    ref, tol = gram64(x, y), fp32_tol(x, y)
    before = fused_stats()
    reqs, info = [], {}
    for _ in range(requests):
        sims, info = svc.x2y(x, y, wx=wx, wy=wy)
        reqs.append({"wall_s": info["wall_s"],
                     "fused_path": info["fused_path"],
                     "max_abs_err": max_err(sims, ref)})
        del sims
    counts = _counts(before)
    ok = (_kernel_only(counts)
          and all(r["fused_path"] == "kernel" for r in reqs)
          and all(r["max_abs_err"] <= tol for r in reqs))
    return {"ok": ok, "shape": [m, m, d], "dtype": "float32",
            "algorithm": info["algorithm"], "reducers": info["reducers"],
            "bucket_widths": info["bucket_widths"], "counts": counts,
            "max_abs_err": max(r["max_abs_err"] for r in reqs), "tol": tol,
            "first_s": reqs[0]["wall_s"],
            "warm_s": [r["wall_s"] for r in reqs[1:]]}


def phase_block(rng, *, m=1_000_000, d=128, tile=2048, tiles=4, q=25.0,
                **svc_kw) -> dict:
    """P3: block serving from a SIFT-1M-shaped table."""
    from repro.mapreduce.engine import fused_stats
    from repro.serve import PairwiseService
    svc = PairwiseService(q=q, executor="fused", **svc_kw)
    x = table(rng, m, d)
    load = svc.load_block_table(x, weights=powerlaw_weights(rng, m, q))
    diag = int(rng.integers(0, m - tile))
    corners = [(diag, diag)] + [tuple(int(v) for v in
                                      rng.integers(0, m - tile, 2))
                                for _ in range(tiles - 1)]
    before = fused_stats()
    reqs = []
    for i0, j0 in corners:
        blk, info = svc.block(i0, i0 + tile, j0, j0 + tile)
        a, b = x[i0:i0 + tile], x[j0:j0 + tile]
        ref = gram64(a, b)
        g = np.arange(max(i0, j0), min(i0, j0) + tile)  # global diagonal
        ref[g - i0, g - j0] = 0.0
        reqs.append({"tile": [i0, j0], "wall_s": info["wall_s"],
                     "max_abs_err": max_err(blk, ref),
                     "tol": fp32_tol(a, b)})
        del blk
    counts = _counts(before)
    ok = (counts["kernel"] == tiles and _kernel_only(counts)
          and all(r["max_abs_err"] <= r["tol"] for r in reqs))
    return {"ok": ok, "shape": [m, d], "dtype": "float32", "tile": tile,
            "q": q,
            "algorithm": load["algorithm"], "reducers": load["reducers"],
            "bins": load["bins"], "load_s": load["wall_s"],
            "counts": counts,
            "max_abs_err": max(r["max_abs_err"] for r in reqs),
            "tol": min(r["tol"] for r in reqs),
            "first_s": reqs[0]["wall_s"],
            "warm_s": [r["wall_s"] for r in reqs[1:]], "requests": reqs,
            "note": "block() uploads the whole table on every call"}


def phase_streaming(rng, *, m=2048, d=128, **svc_kw) -> dict:
    """P4: a live table under add / remove / reweight edits."""
    from repro.mapreduce.engine import fused_stats
    from repro.obs import TRACER
    from repro.serve import PairwiseService
    svc = PairwiseService(q=Q, executor="streaming", **svc_kw)
    rows = table(rng, m, d)
    removed: set = set()

    def check(sims) -> tuple[float, float]:
        ref = gram64(rows, rows)
        gone = sorted(removed)
        ref[gone, :] = 0.0
        ref[:, gone] = 0.0
        np.fill_diagonal(ref, 0.0)
        return max_err(sims, ref), fp32_tol(rows, rows)

    before = fused_stats()
    sims, load = svc.load_table(rows, weights=zipf_weights(rng, m))
    build = [s for s in TRACER.spans() if s.name == "request"
             and s.attrs.get("workload") == "load_table"]
    err, tol = check(sims)
    edits = [{"kind": "load_table", "wall_s": load["wall_s"],
              "max_abs_err": err, "tol": tol}]
    plan = [("add", None), ("add", None), ("remove", 5), ("reweight", 7),
            ("add", None)]
    for op, i in plan:
        if op == "add":
            row = table(rng, 1, d)
            sims, info = svc.add_input(row[0], float(zipf_weights(rng, 1)[0]))
            rows = np.concatenate([rows, row])
        elif op == "remove":
            sims, info = svc.remove_input(i)
            removed.add(i)
        else:
            sims, info = svc.update_weight(i, float(zipf_weights(rng, 1)[0]))
        err, tol = check(sims)
        edits.append({"kind": info["kind"], "wall_s": info["wall_s"],
                      "dirty_reducers": info["dirty_reducers"],
                      "recompute_fraction": info["recompute_fraction"],
                      "max_abs_err": err, "tol": tol})
    counts = _counts(before)
    ex = svc.executor_stats()
    ok = (_kernel_only(counts) and ex["fallbacks"] == 0
          and all(e["max_abs_err"] <= e["tol"] for e in edits))
    cold = build[-1].duration if build else None
    return {"ok": ok, "shape": [m, d], "dtype": "float32",
            "algorithm": load["algorithm"], "reducers": load["reducers"],
            "counts": counts, "stream_fallbacks": ex["fallbacks"],
            "warmed_shapes": load["warmed_shapes"],
            "load_s": load["wall_s"], "cold_build_s": cold,
            "plan_and_warmup_s": (load["wall_s"] - cold
                                  if cold is not None else None),
            "max_abs_err": max(e["max_abs_err"] for e in edits),
            "tol": min(e["tol"] for e in edits),
            "first_s": edits[1]["wall_s"],
            "warm_s": [e["wall_s"] for e in edits[2:]], "edits": edits}


class CompileClock:
    """Seconds JAX spends in backend compilation (a persistent-cache hit
    counts only its read), and the number of cache hits."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.cache_hits


def _device_bytes(key: str) -> list:
    import jax
    return [(dev.memory_stats() or {}).get(key, 0) for dev in jax.devices()]


def phase_multichip(rng, *, m=8192, d=128, **svc_kw) -> dict:
    """P1's profile through the sharded and coded (r=1, r=2) executors on
    a mesh over every device; each device must hold part of the work."""
    import jax
    from repro.serve import PairwiseService
    w = zipf_weights(rng, m)
    x = table(rng, m, d)
    ref = gram64(x, x)
    np.fill_diagonal(ref, 0.0)
    tol = fp32_tol(x, x)
    runs = []
    for name, opts in (("sharded", {}), ("coded", {"replication": 1}),
                       ("coded", {"replication": 2})):
        svc = PairwiseService(q=Q, executor=name, executor_options=opts,
                              **svc_kw)
        sims, info = svc.similarity(x, weights=w)
        shards = info["sharded"]["num_shards"]
        comm = info.get("comm") or {}
        runs.append({
            "executor": name, "replication": opts.get("replication", 1),
            "num_shards": shards,
            "balance_factor": info["sharded"]["balance_factor"],
            "local_fraction": (info.get("coded") or {}).get(
                "local_fraction"),
            "assembly_bytes_per_shard": (
                comm["assembled_bytes"] / shards if comm else None),
            "max_abs_err": max_err(sims, ref),
            "bytes_in_use_with_result": _device_bytes("bytes_in_use"),
            "first_s": info["wall_s"]})
        del sims
    peaks = _device_bytes("peak_bytes_in_use")
    # LPT spreads the reducers evenly and every map is placed straight into
    # its shard, so the devices' peaks agree; a device left out of the
    # work would hold only the replicated table
    every_device = min(peaks) >= 0.5 * max(peaks) > 0
    ok = (every_device and all(r["num_shards"] == jax.device_count()
                               for r in runs)
          and all(r["max_abs_err"] <= tol for r in runs))
    return {"ok": ok, "shape": [m, d], "dtype": "float32", "tol": tol,
            "max_abs_err": max(r["max_abs_err"] for r in runs),
            "peak_bytes_in_use_per_device": peaks,
            "peak_bytes_reserved_per_device": _device_bytes(
                "peak_bytes_reserved"),
            "memory_stats_per_device": [dev.memory_stats()
                                        for dev in jax.devices()],
            "every_device_worked": every_device, "runs": runs}


# ------------------------------------------------------------------ main
def run_phase(name: str, fn, seed: int, clock: CompileClock) -> bool:
    import jax
    t0 = time.perf_counter()
    c0 = clock.snapshot()
    try:
        rec = fn(np.random.default_rng(seed))
    except Exception as e:                       # report, keep going
        traceback.print_exc(file=sys.stderr)
        rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
    c1 = clock.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    rec = {"phase": name, **rec,
           "phase_s": time.perf_counter() - t0,
           "compile_s": c1[0] - c0[0], "compiles": c1[1] - c0[1],
           "compile_cache_hits": c1[2] - c0[2],
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "peak_bytes_reserved": stats.get("peak_bytes_reserved")}
    print(json.dumps(rec, default=float), flush=True)
    return bool(rec["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded/coded phase, on 4 chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.chips == 4:
        if len(devices) != 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phases = [("multichip", phase_multichip)]
    else:
        phases = [("P1_similarity", phase_similarity),
                  ("P2_x2y", phase_x2y),
                  ("P3_block", phase_block),
                  ("P4_streaming", phase_streaming)]
    clock = CompileClock()
    ok = True
    for i, (name, fn) in enumerate(phases):
        ok &= run_phase(name, fn, args.seed + i, clock)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
