"""The check that decides ``correct`` passes a sound run of every cell and
fails each fault the cell can have, and the lower-precision control."""

import json
import os
import subprocess
import sys

import pytest

import run
import small

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
MESH = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]
FAULTS = ("answer_altered", "half_left_out")


def _assert_cases(got: dict) -> None:
    assert got["sound"]["correct"], got
    for case, r in got.items():
        if case != "sound":
            assert r["correct"] is False, (case, got)
            assert r["gap"] > 3 * got["sound"]["gap"], (case, got)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_cell(cell):
    _assert_cases(small.cases(cell, FAULTS))


@pytest.mark.parametrize("cell", MESH)
def test_mesh_cell(cell):
    """Four CPU devices stand in for the chips; the exchange between
    them is one more fault."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "small.py"),
         cell, *FAULTS, "exchange_left_out"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    _assert_cases(json.loads(proc.stdout.strip().splitlines()[-1]))
