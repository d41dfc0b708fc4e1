"""Readings that set the limit of ``correct``: the program's and the
control's, over many seeds in one process.

    python3 kbench/control.py --workload a256.sweep --seeds 1,2,3 --seconds 5

For each seed it makes one run of the cell (set-up, a short window, the
check) and checks the same window twice: the program's paths against the
reference (the lower reading), and the reference computed in bfloat16, the
precision below the configuration's float32, put in the program's place
(the control, the upper reading). One JSON line per seed, then a summary.
The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import time

T_ENTRY = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from kbench import harness, registry

    try:
        devices = harness.look_for_chip(
            registry.cell(args.workload, ROOT).chips)
    except (KeyError, harness.NoChip) as exc:
        print(f"kbench: {exc}", file=sys.stderr)
        return 3
    harness.enable_cache()
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               time.perf_counter(), root=ROOT,
                               devices=devices, control=CONTROL)
        p = out["check"]["paths_differing"]["value"]
        c = out["control"]["check"]["paths_differing"]["value"]
        program.append(p)
        control.append(c)
        print(json.dumps({"seed": seed, "program": p, "control": c,
                          "program_correct": out["correct"],
                          "control_correct": out["control"]["correct"]}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(program),
                      "lower_reading": max(program),
                      "upper_reading": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
