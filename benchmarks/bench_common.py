"""Shared benchmark plumbing: BENCH_*.json emission + run metadata.

Every ``bench_*.py`` writes its machine-readable perf trajectory through
:func:`emit_bench_json` (one canonical copy — bench_engine/bench_hierarchy/
bench_stream used to carry three identical private copies).  Sections merge
into the existing file so e.g. ``--fused`` and ``--sharded`` runs
accumulate instead of clobbering each other's history, and every write
stamps a uniform ``meta`` block (git revision, jax version, device kind)
so a stored number is traceable to the build that produced it.  A backend
that cannot be queried is an error: no number is stored without the device
it ran on.
"""

from __future__ import annotations

import json
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))


def bench_json_path(name: str) -> str:
    """Absolute path of ``benchmarks/BENCH_<name>.json``."""
    return os.path.join(_HERE, f"BENCH_{name}.json")


def _git_rev() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_HERE,
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() if out.returncode == 0 else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def bench_metadata() -> dict:
    """Uniform provenance block stamped into every BENCH_*.json write."""
    import jax
    dev = jax.devices()[0]
    return {"git_rev": _git_rev(), "jax_version": jax.__version__,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def emit_bench_json(payload: dict, path: str) -> str:
    """Merge ``payload`` (plus a fresh ``meta`` block) into ``path``.

    Machine-readable perf trajectory read by CI across PRs: existing
    sections survive, same-named sections are replaced.
    """
    existing = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f)
        except (json.JSONDecodeError, OSError):
            existing = {}
    existing.update(payload)
    existing["meta"] = bench_metadata()
    with open(path, "w") as f:
        json.dump(existing, f, indent=1, sort_keys=True)
        f.write("\n")
    return os.path.abspath(path)
