"""A cell of ``BENCHMARK.json`` cut to a size a CPU test run holds, and the
faults the check has to catch, planted in the program underneath."""

from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

import run  # noqa: E402
import work  # noqa: E402

INTERPRET = {"use_kernel": True, "interpret": True}
_V5E = work.peaks("TPU v5 lite")          # a CPU has no published peaks


def spec(cell: str) -> run.Spec:
    """The cell at the test sizes its configuration and traffic files
    give under ``small``."""
    s = run.Spec(cell)
    s.config.update(s.config.get("small", {}))
    s.traffic.update(s.traffic.get("small", {}))
    return s


def run_small(cell: str, seed: int = 12_345_678_901, **kw) -> dict:
    """Everything a run does after its look for a chip, on the CPU."""
    s = spec(cell)
    with mock.patch.object(work, "peaks", lambda kind: _V5E):
        return run.run_cell(s, seed, 0.5, False,
                            devices=jax.devices()[:s.chips],
                            service_overrides=INTERPRET, **kw)


def _fresh_programs() -> None:
    """Drop the engine's jitted programs, so that a fault planted in a
    module is traced into the next request's program."""
    from repro.mapreduce import engine
    engine._JIT_CACHE.clear()
    jax.clear_caches()


@contextlib.contextmanager
def fault(name: str):
    """Plant one fault in the program for the duration of the block:

    ``answer_altered``   the kernel's last reducer block of every call
                         comes out 1.0 too high (the last writer of its
                         cells in the source map);
    ``half_left_out``    the kernel's blocks of the second half of the
                         reducers come out 0;
    ``exchange_left_out``  the coded executor's all-to-all returns each
                         shard its own lanes."""
    import repro.compat as compat
    from repro.kernels.pairwise import fused_gather_gram as fgg

    saved = {"rect": fgg.fused_gather_gram_rect,
             "square": fgg.fused_gather_gram,
             "all_to_all": compat.all_to_all}
    orig = saved["rect"]

    def broken(g):
        if name == "answer_altered":
            return g.at[-1].add(1.0)
        return g.at[g.shape[0] // 2:].set(0.0)

    def rect(*args, **kw):
        return broken(orig(*args, **kw))

    def square(x, idx, mask, **kw):
        return rect(x, x, idx, mask, idx, mask, **kw)

    if name == "exchange_left_out":
        compat.all_to_all = lambda x, axes: x
    else:
        fgg.fused_gather_gram_rect, fgg.fused_gather_gram = rect, square
    _fresh_programs()
    try:
        yield
    finally:
        fgg.fused_gather_gram_rect = saved["rect"]
        fgg.fused_gather_gram = saved["square"]
        compat.all_to_all = saved["all_to_all"]
        _fresh_programs()


def cases(cell: str, faults) -> dict:
    """``correct`` and the compared number of a sound run, of each fault
    and of the control, in this process."""
    import reference
    out = {}
    for case in ("sound", *faults, "control"):
        kw = {}
        if case == "control":
            kw["serve"] = lambda mix, svc, req: reference.control(req)
        with fault(case) if case not in ("sound", "control") \
                else contextlib.nullcontext():
            res = run_small(cell, **kw)
        out[case] = {"correct": res["correct"],
                     "gap": res["checks"]["max_gap_over_fp32_bound"]["value"]}
    return out


if __name__ == "__main__":          # one cell's cases, for a mesh of its own
    import json
    print(json.dumps(cases(sys.argv[1], sys.argv[2:])))
