"""Roofline terms from a compiled dry-run artifact (no hardware needed).

Peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; the dry-runs
model the chip named by ``DRYRUN_TARGET``.  A device kind without published
peaks is an error, never a default.

  compute    = HLO_FLOPs_per_device / peak_FLOPS
  memory     = HLO_bytes_per_device / HBM_bw
  collective = sum over collective ops of per-device moved bytes / link_bw
               (ring model: AG (n-1)/n * out, AR 2(n-1)/n * in,
                RS (n-1)/n * in, A2A (n-1)/n * in, permute = in)

cost_analysis()/as_text() describe the SPMD-partitioned per-device module,
so all three terms are per-device seconds directly comparable against each
other; the bottleneck is the max term.  The roofline fraction we report is
compute / max(all terms) — the fraction of time the MXU would be busy under
perfect overlap.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = ["DRYRUN_TARGET", "HW", "PEAKS", "RooflineReport",
           "analyze_compiled", "collective_bytes", "combine_hlo_stats",
           "hw_for", "model_flops"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float                   # bf16 FLOP/s per chip
    hbm_bw: float                       # B/s
    link_bw: float                      # B/s per ICI link


# Published per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# interchip interconnect over four links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9),
}
DRYRUN_TARGET = "TPU v5 lite"           # the chip the dry-run meshes model


def hw_for(device_kind: str) -> HW:
    """Peaks of ``device_kind``; unknown kinds raise ``KeyError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to roofline.PEAKS with "
                       f"their source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9_\[\],{} ]+?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
# XLA annotates wide tuple types with /*index=N*/ comments; strip them or
# the type char-class above rejects >5-way tuple collectives (e.g. the
# coded executor's 8-way all-to-all).
_HLO_COMMENT_RE = re.compile(r"/\*.*?\*/")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)   # iota format [ngroups,size]
    if m:
        return int(m.group(2))
    return 2


def collective_bytes(hlo_text: str) -> dict:
    """Per-device moved bytes by collective kind (ring cost model)."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0, "ops": 0}
    for line in hlo_text.splitlines():
        line = _HLO_COMMENT_RE.sub("", line)
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done" in line.split("=")[1][:60]:
            continue
        result_bytes = _shape_bytes(m.group(1))
        n = max(_group_size(line), 1)
        kind = m.group(2)
        frac = (n - 1) / n if n > 1 else 0.0
        if kind == "all-gather":
            moved = result_bytes * frac
        elif kind == "all-reduce":
            moved = 2.0 * result_bytes * frac
        elif kind == "reduce-scatter":
            moved = result_bytes  # result is the scattered shard; input = n*out
        elif kind == "all-to-all":
            moved = result_bytes * frac
        else:  # collective-permute
            moved = result_bytes
        out[kind] += moved
        out["ops"] += 1
    out["total"] = sum(v for k, v in out.items()
                       if k not in ("ops", "total"))
    return out


def model_flops(cfg, shape_name: str) -> float:
    """Global useful FLOPs per step: 6 N_active D (train), 2 N D (prefill),
    2 N B (decode step) + attention term."""
    from repro.configs.base import SHAPES
    seq, batch, kind = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if kind == "train":
        base = 6.0 * n_active * seq * batch
    elif kind == "prefill":
        base = 2.0 * n_active * seq * batch
    else:
        base = 2.0 * n_active * batch      # one token per request
    return base


def combine_hlo_stats(stats_list):
    """Sum per-device HLO stats over several compiled programs.

    The bucketed shuffle executor runs one XLA program per capacity bucket
    back-to-back on the same mesh, so its roofline terms are the sums of
    the per-bucket terms.  Returns a single HloStats."""
    from .hlo_analysis import HloStats

    out = HloStats()
    for s in stats_list:
        out.flops += s.flops
        out.hbm_bytes += s.hbm_bytes
        out.collective_bytes += s.collective_bytes
        out.collective_ops += s.collective_ops
        for k, v in s.collective_by_kind.items():
            out.collective_by_kind[k] = out.collective_by_kind.get(k, 0) + v
        out.while_trip_counts.extend(s.while_trip_counts)
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    num_devices: int
    flops_per_device: float
    bytes_per_device: float
    collectives: dict
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    roofline_fraction: float
    model_flops_global: float
    useful_flops_ratio: float
    memory_per_device: Optional[dict] = None
    hbm_bytes_kernel_resident: float = 0.0
    t_memory_kernel_resident: float = 0.0
    roofline_fraction_kernel_resident: float = 0.0
    bottleneck_kernel_resident: str = ""

    def row(self) -> dict:
        return dataclasses.asdict(self)


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     num_devices: int, hw: HW, cfg=None) -> RooflineReport:
    from .hlo_analysis import analyze_hlo_text

    # XLA's cost_analysis counts while (lax.scan) bodies once; our HLO-text
    # walker applies trip-count multipliers (see hlo_analysis.py).
    text = compiled.as_text()
    stats = analyze_hlo_text(text, num_partitions=num_devices)
    stats_res = analyze_hlo_text(text, num_partitions=num_devices,
                                 attn_resident=True)
    flops = stats.flops
    bytes_acc = stats.hbm_bytes
    coll = dict(stats.collective_by_kind)
    coll["total"] = stats.collective_bytes
    coll["ops"] = stats.collective_ops
    coll["while_trip_counts"] = stats.while_trip_counts
    t_c = flops / hw.peak_flops
    t_m = bytes_acc / hw.hbm_bw
    t_x = coll["total"] / hw.link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    frac = t_c / max(max(terms.values()), 1e-30)
    mf = model_flops(cfg, shape) if cfg is not None else 0.0
    ratio = mf / max(flops * num_devices, 1e-30)
    # flash-kernel accounting: attention score tiles VMEM-resident
    t_m_res = stats_res.hbm_bytes / hw.hbm_bw
    terms_res = {"compute": t_c, "memory": t_m_res, "collective": t_x}
    frac_res = t_c / max(max(terms_res.values()), 1e-30)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0) or 0),
        }
    except Exception:
        pass
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, num_devices=num_devices,
        flops_per_device=flops, bytes_per_device=bytes_acc,
        collectives=coll, t_compute=t_c, t_memory=t_m, t_collective=t_x,
        bottleneck=bottleneck, roofline_fraction=frac,
        model_flops_global=mf, useful_flops_ratio=ratio,
        memory_per_device=mem,
        hbm_bytes_kernel_resident=stats_res.hbm_bytes,
        t_memory_kernel_resident=t_m_res,
        roofline_fraction_kernel_resident=frac_res,
        bottleneck_kernel_resident=max(terms_res, key=terms_res.get))
