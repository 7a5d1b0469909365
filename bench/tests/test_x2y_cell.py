"""The X2Y cell at its test sizes, and the two readers it brought
(``schema.host_ms``, ``blocks.bytes_per_pair``) in every cell they list."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import run
import small

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = {w["name"]: w["chips"] for w in BENCH["workloads"]}
NEW = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
       if m["name"] in ("schema.host_ms", "blocks.bytes_per_pair")}
X2Y = "x2y.sift128-4k"


def test_x2y_cell_reads_correct():
    res = small.run_small(X2Y)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["max_gap_over_fp32_bound"]["value"] < 1.0
    for name in ("pairs_per_s", "request_p95_s", "setup_s"):
        assert res["metrics"][name]["value"] > 0.0, name


def test_new_metrics_list_only_cells_of_the_benchmark():
    assert sorted(NEW) == ["blocks.bytes_per_pair", "schema.host_ms"]
    for cells in NEW.values():
        assert X2Y in cells and set(cells) <= set(CELLS)


def _traced(cell: str, trace_dir: str) -> dict:
    """Per-layer metrics of a traced run of ``cell`` at its test sizes;
    a four-chip cell runs on four CPU devices in a process of its own."""
    if CELLS[cell] == 1:
        from test_span_metrics import traced
        return traced(cell, trace_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = os.path.join(os.path.dirname(__file__), "test_span_metrics.py")
    proc = subprocess.run([sys.executable, script, cell, trace_dir],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_new_metrics_read_where_listed(cell, tmp_path):
    got = _traced(cell, str(tmp_path))
    for name, cells in NEW.items():
        if cell in cells:
            assert got.get(name) is not None, (name, got)
            assert got[name] >= 0.0, (name, got)
    if cell == X2Y:           # the request's host path, as in the self-join
        assert got.get("dispatch.host_ms") is not None, got
    if cell in NEW["blocks.bytes_per_pair"]:
        # every block is at least one answer cell of float32
        assert got["blocks.bytes_per_pair"] >= 4.0, got


def test_block_bytes_per_pair_is_the_plans_count(tmp_path):
    """At test sizes the x2y cell reads Σ R·Lx·Ly·4 of its plan's buckets
    over the mx·my entries of an answer, exactly."""
    from repro.core import plan_x2y
    from repro.mapreduce.engine import build_x2y_plan
    from traffic import sizes
    c = small.spec(X2Y).config
    wx = sizes(c["sizes_x"], c["mx"], c["q"])
    wy = sizes(c["sizes_y"], c["my"], c["q"])
    plan = build_x2y_plan(plan_x2y(wx, wy, c["q"]), c["mx"])
    want = sum(b.idx.shape[0] * b.idx.shape[1] * b.yidx.shape[1] * 4
               for b in plan.buckets) / (c["mx"] * c["my"])
    got = _traced(X2Y, str(tmp_path))
    assert got["blocks.bytes_per_pair"] == pytest.approx(want, rel=1e-12)
