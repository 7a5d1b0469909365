#!/usr/bin/env python3
"""Readings that a cell's limit is set from, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        [--seeds <n> ...] --control-seeds <n> ... [--control-seconds <s>]

For each ``--seeds`` seed, a run of the cell as ``run.py`` makes it (the
timed path at the timed sizes, with a window of ``--seconds``); for each
``--control-seeds`` seed, the same run with the lower-precision control
(``reference.control``) in the program's place, with a window of
``--control-seconds`` (the control answers in milliseconds).  Prints one JSON line per
run with its compared numbers, then a summary: the program's largest
reading (the lower end of a limit) and the control's smallest (the upper
end).  Set-up compiles once for all the seeds.  The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    spec = run.Spec(args.workload)
    run._use_cache_dir()
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    devices = run._devices(spec.chips)
    if devices is None:
        return 3
    import reference

    readings = {"program": [], "control": []}
    cases = [("program", s) for s in args.seeds] + \
        [("control", s) for s in args.control_seeds]
    for case, seed in cases:
        serve, seconds = None, args.seconds
        if case == "control":
            seconds = args.control_seconds

            def serve(mix, svc, req):
                return reference.control(req)
        res = run.run_cell(spec, seed, seconds, False, devices=devices,
                           serve=serve)
        gap = res["checks"]["max_gap_over_fp32_bound"]["value"]
        readings[case].append(gap)
        print(json.dumps({"case": case, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "gap": gap}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": max(readings["program"], default=None),
                      "upper": min(readings["control"]),
                      "program": readings["program"],
                      "control": readings["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
