"""Reduce a profiler trace (``.xplane.pb``) to device time per operation,
busy time and idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing of the program.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation the device ran, named by its HLO instruction
(``%fused_gather_gram_rect.15 = f32[4808,256,256]... custom-call(...)``).
An operation's short name is the instruction's name without its number
(``fused_gather_gram_rect``).  Host spans are ``jax.profiler.
TraceAnnotation`` events on the host plane: the benchmark's
``bench.window`` (the measured window) and ``bench.request`` (one client
request), and the program's ``request`` / ``plan`` / ``execute`` /
``compile`` spans, which ``repro.obs`` forwards to the profiler under
``REPRO_OBS_XPROF=1``.  The profiler puts host and device events on one
clock.

Operations are told apart:

- the kernel, by short name: ``fused_gather_gram_rect``, the jitted wrapper of the Pallas
  call in ``kernels/pairwise/fused_gather_gram.py`` (``KERNEL``);
- collectives, by their HLO opcode (``%all_to_all.4 = f32[...]
  all-to-all(...)``): ``all-to-all``, ``all-gather``, ``all-reduce``,
  ``reduce-scatter``, ``collective-permute`` (``COLLECTIVE``);
- everything else the request's programs run (assembly).
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["reduce_trace", "find_trace", "short_name", "KERNEL",
           "COLLECTIVE"]

KERNEL = re.compile(r"^fused_gather_gram")
COLLECTIVE = re.compile(r"\s(all-to-all|all-gather|all-reduce|reduce-scatter"
                        r"|collective-permute)(-start|-done)?\(")
HOST_SPANS = ("bench.window", "bench.request", "request", "plan",
              "execute", "compile")
_LABEL = {"bench.request": "client", "bench.window": "none"}


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _union(intervals) -> list:
    """Merge [start, end) intervals (sorted, disjoint output)."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _timeline(spans) -> list:
    """Host time cut into segments ``(start, end, label)``, each labelled
    by the innermost span open in it (the latest to start)."""
    cuts = sorted({t for _n, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    segs, open_, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        if open_:
            name = max(open_, key=lambda sp: sp[1])[0]
            segs.append((a, b, _LABEL.get(name, name)))
    return segs


def _label_gaps(gaps, segs, out: dict) -> None:
    """Add each gap's time to the labels of the host segments it overlaps
    (time under no span is ``none``); both lists sorted."""
    j = 0
    for s, e in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        i = j
        while i < len(segs) and segs[i][0] < e:
            a, b, lab = segs[i]
            dt = min(b, e) - max(a, s)
            if dt > 0:
                out[lab] = out.get(lab, 0.0) + dt * 1e-9
                covered += dt
            i += 1
        if e - s > covered:
            out["none"] = out.get("none", 0.0) + (e - s - covered) * 1e-9


def reduce_trace(path: str) -> dict:
    """Seconds in the measured window, summed over the devices.

    Returns ``{"chips", "window_s", "busy_s" (mean over devices),
    "op_s" {short name: s}, "kernel_s", "kernel_events", "collective_s",
    "other_s", "gap_s" {host label: s}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if ev.name in HOST_SPANS]
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    if win:
        lo, hi = win[-1]
    else:
        ends = [t for ops in devices for _n, s, e in ops for t in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0, 0)
    segs = _timeline([sp for sp in spans if sp[2] > lo and sp[1] < hi])
    red = {"chips": len(devices), "window_s": (hi - lo) * 1e-9,
           "busy_s": 0.0, "op_s": {}, "kernel_s": 0.0, "kernel_events": 0,
           "collective_s": 0.0, "other_s": 0.0, "gap_s": {}}
    for ops in devices:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for hlo, s, e in inside:
            dt = (e - s) * 1e-9
            n = short_name(hlo)
            red["op_s"][n] = red["op_s"].get(n, 0.0) + dt
            if KERNEL.search(n):
                red["kernel_s"] += dt
                red["kernel_events"] += 1
            elif COLLECTIVE.search(hlo):
                red["collective_s"] += dt
            else:
                red["other_s"] += dt
        busy = _union([s, e] for _n, s, e in inside)
        red["busy_s"] += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        _label_gaps(gaps, segs, red["gap_s"])
    if devices:
        red["busy_s"] /= len(devices)
    return red
