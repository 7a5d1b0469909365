"""The readers of the program's host spans (``lower``, ``maps``, ``upload``,
``launch``) find something in every cell they list: a run of each cell at
its test sizes on the CPU, read as a ``--trace 1`` run reads it."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from unittest import mock

import pytest

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
NAMES = ("lower.host_ms", "maps.host_ms", "upload.host_ms",
         "upload.mb_per_request", "launch.host_ms")
SPAN_METRICS = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
                if m["name"] in NAMES}
CELLS = {w["name"]: w["chips"] for w in BENCH["workloads"]}


def traced(cell: str, trace_dir: str) -> dict:
    """The per-layer metrics of a traced run of ``cell`` at its test
    sizes, past the run's look for a chip."""
    import jax

    import small
    import work
    s = small.spec(cell)
    with mock.patch.object(work, "peaks", lambda kind: small._V5E), \
            mock.patch.object(run, "TRACE_DIR", trace_dir):
        res = run.run_cell(s, 12_345_678_901, 0.5, True,
                           devices=jax.devices()[:s.chips],
                           service_overrides=small.INTERPRET)
    assert res["correct"], res["checks"]
    return {k: v["value"] for k, v in res["metrics"].items()}


def test_every_span_metric_lists_all_cells():
    assert sorted(SPAN_METRICS) == sorted(NAMES)
    for cells in SPAN_METRICS.values():
        assert sorted(cells) == sorted(CELLS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_span_metrics_read_in_a_traced_run(cell, tmp_path):
    if CELLS[cell] == 1:
        got = traced(cell, str(tmp_path))
    else:                     # four CPU devices stand in for the chips
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), cell,
             str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-4000:]
        got = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, cells in SPAN_METRICS.items():
        if cell in cells:
            assert got.get(name) is not None, (name, got)
            assert got[name] >= 0.0, (name, got)
    assert got["upload.mb_per_request"] > 0.0


if __name__ == "__main__":          # one cell, for a mesh of its own
    print(json.dumps(traced(sys.argv[1], sys.argv[2])))
