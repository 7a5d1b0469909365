"""``BENCHMARK.json`` holds to the benchmark's contract, every name it
gives has its file, and the command prints no result without a chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_units_and_files():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        assert json.load(open(os.path.join(run.ROOT, c["file"])))["name"] \
            == c["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"])
        assert os.path.exists(os.path.join(run.BENCH, "traffic",
                                           w["traffic"] + ".json"))
        kind = run.Spec(w["name"]).kind
        assert all(callable(getattr(kind, f))
                   for f in ("setup", "warm", "serve"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(run._reader(m["name"]))
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough(cell):
    spec = run.Spec(cell)
    e2e = {m["name"] for m in spec.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics("per_layer")


def test_sample_is_bounded_seeded_and_whole_when_small():
    def draw(n, seed):
        sample = run.Sample(8, seed)
        for i in range(n):
            sample.offer(i)
        return sample.items
    assert draw(5, 1) == list(range(5))
    assert len(draw(1000, 1)) == 8 and len(set(draw(1000, 1))) == 8
    assert draw(1000, 2 ** 31 + 9) == draw(1000, 2 ** 31 + 9)
    assert draw(1000, 1) != draw(1000, 2)
    assert max(draw(1000, 3)) >= 8             # later items get in too


def test_run_seconds_fit_a_check_of_24_cells():
    s = BENCH["run_seconds"]
    runs = 2 + 14 * 24
    assert 1 <= s <= 51
    assert runs * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def _bench(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000000",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    proc = _bench(run.ROOT)
    assert proc.returncode != 0 and proc.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path)
    assert proc.returncode != 0 and proc.stdout == ""
