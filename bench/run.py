#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, measured on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
deployment's tables, input sizes, executor and the limit of its check)
and a traffic mix (``bench/traffic/<mix>.json``, read by the one
generator in ``traffic.py``), whose request kind is a module of its own,
``bench/kinds/<kind>.py``.  Metrics are readers of their own,
``bench/metrics/<metric>.py``.  Each is found by its name; adding a cell
is adding files and entries, and nothing here names a cell.

A run makes its data from ``--seed``, serves every request shape once
(set-up, compilation included), then runs a closed loop with one client
for ``--seconds``: each request goes out when the previous answer is back
on the host, and the window ends when the last request started in it
returns.  After the window the answers, or a sample of ``CHECKED`` of
them drawn from the seed, are compared with the float64 reference
(``reference.py``).  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs the window under the profiler and prints the
per-layer metrics instead.  The last line of standard output is the
result's JSON object; the numbers compared, beside their limits, are the
last lines of standard error and the last key of that object.

With no TPU, or fewer chips than the cell asks for, it prints no result
and exits 3.  JAX's persistent compilation cache lives in ``.jax_cache/``
at the root of the checkout.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import shutil
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # the last traced run's
# Answers the check compares: a uniform sample of the window's, drawn from
# the seed.  A 2048 x 2048 tile is 16 MiB; holding and comparing all ~500 of
# a tiles window filled the 40 GiB of a one-chip v5e host.
CHECKED = 64


def _process_start() -> float:
    """``time.perf_counter()`` reading of this process's start (Linux),
    or of this module's import where /proc cannot say."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        return now - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()


# ---------------------------------------------------------------- spec
def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Spec:
    """One cell of ``BENCHMARK.json`` with its configuration, traffic mix,
    request kind and the metrics it reports."""

    def __init__(self, workload: str, root: str = ROOT):
        bench = _load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.bench, self.cell = bench, cells[workload]
        entry = {c["name"]: c for c in bench["configs"]}[self.cell["config"]]
        self.config = _load_json(root, entry["file"])
        self.traffic = _load_json(BENCH, "traffic",
                                  self.cell["traffic"] + ".json")
        self.kind = _module("kinds", self.traffic["request"])
        self.chips = int(self.cell["chips"])

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        name = self.cell["name"]
        return [m for m in self.bench[kind]
                if name in m.get("workloads", [name])]


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded from its path."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _module("metrics", name).read


class Sample:
    """A uniform sample of at most ``size`` of the items offered, drawn
    from ``seed`` (reservoir sampling): bounded memory however many the
    window returns, and every item where there are no more than ``size``."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.items = size, 0, []
        self._rng = random.Random(seed)

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = self._rng.randrange(self.seen + 1)
            if j < self.size:
                self.items[j] = item
        self.seen += 1


# ------------------------------------------------------------ compiles
class CompileClock:
    """Programs JAX compiles (or reads from the persistent cache), the
    seconds that takes, and the persistent cache's hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "programs": self.programs,
                "cache_hits": self.hits, "cache_misses": self.misses}


def _use_cache_dir() -> None:
    """Every program in the persistent cache, at a fixed path in the
    checkout; set before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def _devices(chips: int):
    """The cell's chips; ``None`` where JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    return devs[:chips]


def _peak_bytes(devs) -> int:
    """Most bytes any of the cell's chips held: buffers plus program
    scratch where the runtime reports it."""
    peak = 0
    for dev in devs:
        st = dev.memory_stats() or {}
        peak = max(peak, st.get("peak_bytes_reserved", 0),
                   st.get("peak_bytes_in_use", 0))
    return peak


def _service(spec: Spec, overrides: dict):
    from repro.serve import PairwiseService
    c = spec.config
    kw = dict(q=c["q"], executor=c["executor"],
              executor_options=c.get("executor_options") or None)
    kw.update(overrides)
    return PairwiseService(**kw)


# ---------------------------------------------------------------- run
def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             devices, serve=None, service_overrides=None) -> dict:
    """Set up, warm, measure and check one run; returns the result object.

    ``serve(mix, svc, req)`` replaces the service call (the control puts
    the reference in the program's place); ``service_overrides`` go to
    ``PairwiseService`` (kernel in interpret mode on a CPU)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.obs import REGISTRY, TRACER

    import reference
    import traffic
    import work

    clock = CompileClock()
    mix = traffic.Mix(spec.config, spec.traffic, seed, spec.kind)
    svc = _service(spec, service_overrides or {})
    if serve is None:
        serve = traffic.Mix.serve
    t0 = time.perf_counter()
    mix.setup(svc)
    t1 = time.perf_counter()
    for req in mix.warm_requests():
        serve(mix, svc, req)
    setup_s = time.perf_counter() - T_PROCESS
    print(f"bench: set-up: start {t0 - T_PROCESS:.3f} s, data "
          f"{t1 - t0:.3f} s, warm-up {setup_s - (t1 - T_PROCESS):.3f} s",
          file=sys.stderr)
    at_setup = clock.snapshot()

    if trace:
        from jax.profiler import ProfileOptions
        opts = ProfileOptions()
        opts.python_tracer_level = 0          # host annotations only
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    counters0 = REGISTRY.counter_total("ledger.gathered_bytes")
    TRACER.clear()
    done = []                                  # (req, seconds, answered)
    checked = Sample(CHECKED, seed)            # (req, answer)
    failed = 0
    with TraceAnnotation("bench.window"):
        t_win = time.perf_counter()
        while not done or time.perf_counter() - t_win < seconds:
            req = mix.next_request()
            t0 = time.perf_counter()
            try:
                with TraceAnnotation("bench.request"):
                    out = serve(mix, svc, req)
            except Exception as e:             # a request that fails counts
                print(f"bench: request {req.key} failed: {e!r}",
                      file=sys.stderr)
                failed += 1
                out = None
            done.append((req, time.perf_counter() - t0, out is not None))
            if out is not None:
                checked.offer((req, out))
            out = None                         # an answer not kept goes now
        window_s = time.perf_counter() - t_win
    if trace:
        jax.profiler.stop_trace()
    at_window = clock.snapshot()
    spans = [(s.name, s.duration) for s in TRACER.spans()]
    gathered = REGISTRY.counter_total("ledger.gathered_bytes") - counters0
    peak = _peak_bytes(devices)
    del svc                                    # the program's state goes

    served = [r for r, _s, ok in done if ok]
    entries = sum(mix.entries(r) for r in served)
    least = [work.least_work(r) for r in served]
    flops = sum(w["flops"] for w in least)
    nbytes = sum(w["bytes"] for w in least)
    dev = devices[0]
    least_s, bound = work.least_time(flops, nbytes,
                                     work.peaks(dev.device_kind), len(devices))
    compiles_in_window = at_window["programs"] - at_setup["programs"]
    print(f"bench: set-up {setup_s:.3f} s; compiles in set-up "
          f"{json.dumps(at_setup)}; compiles in the window "
          f"{compiles_in_window}", file=sys.stderr)
    print(f"bench: {len(done)} requests in {window_s:.3f} s; least time "
          f"{least_s} s ({bound} bound)", file=sys.stderr)

    result = {"correct": None, "attempted": len(done), "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak},
              "setup": {**at_setup, "setup_s": setup_s},
              "compiles_in_window": compiles_in_window}
    ctx = types.SimpleNamespace(
        window_s=window_s, request_s=[s for _r, s, _ok in done],
        entries=entries, requests=len(served), peak_bytes=peak,
        setup_s=setup_s, least_s=least_s, chips=len(devices), spans=spans,
        counters={"ledger.gathered_bytes": gathered}, trace=None)
    if trace:
        import trace_reduce
        red = ctx.trace = trace_reduce.reduce_trace(
            trace_reduce.find_trace(TRACE_DIR))
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        top = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(red["gap_s"].items(), key=lambda kv: -kv[1])[:10]
        chips = red["chips"] or 1
        result["breakdown"] = {
            "device_ops": [[n, s / chips] for n, s in top],
            "idle_gaps": [[n, s / chips] for n, s in gaps]}
    for m in spec.metrics("per_layer" if trace else "end_to_end"):
        value = _reader(m["name"])(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    # the check, once the window has closed and the peak is read
    t_check = time.perf_counter()
    worst = reference.worst_gap_ratio(checked.items)
    print(f"bench: check of {len(checked.items)} of {len(served)} answers "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    limit = spec.config["check"]["max_gap_over_fp32_bound"]
    checks = {"max_gap_over_fp32_bound": {"value": worst, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    result["correct"] = bool(worst <= limit and failed == 0)
    result["checks"] = checks                  # the last key
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    spec = Spec(args.workload)
    _use_cache_dir()
    if args.trace:
        os.environ["REPRO_OBS_XPROF"] = "1"   # program spans in the trace
    devices = _devices(spec.chips)
    if devices is None:
        return 3
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
