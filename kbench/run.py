"""Run one cell of the benchmark once.

    python3 kbench/run.py --workload a256.sweep --seed 7 --seconds 20 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``check``, the numbers compared with the
reference beside their limits, which also end standard error. Lines
before it are informational. Where JAX finds no TPU, or fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_ENTRY = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("kbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from kbench import harness, registry

    try:
        cell = registry.cell(args.workload, ROOT)
        devices = harness.look_for_chip(cell.chips)
    except (KeyError, harness.NoChip) as exc:
        print(f"kbench: {exc}", file=sys.stderr)
        return 3
    harness.info(f"compile cache: {harness.enable_cache()}")
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_ENTRY, root=ROOT,
                           devices=devices)
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
