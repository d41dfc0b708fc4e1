"""Benchmark harness entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``
prints ``name,us_per_call,derived`` CSV rows (+ section headers on stderr).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import emit

SECTIONS = [
    ("work_depth", "benchmarks.work_depth"),            # paper §III-F
    ("correctness", "benchmarks.correctness"),          # Table II + §IV-C
    ("fixed_workload", "benchmarks.fixed_workload"),    # Table IV
    ("throughput_sweep", "benchmarks.throughput_sweep"),# Table III / Fig 3-4
    ("latency", "benchmarks.latency"),                  # Fig 6
    ("memory_footprint", "benchmarks.memory_footprint"),# Table V / Fig 5
    ("emergent_dynamics", "benchmarks.emergent_dynamics"),  # Fig 7
    ("scenario_sweep", "benchmarks.scenario_sweep"),    # scenario engine
    ("roofline", "benchmarks.roofline_report"),         # EXPERIMENTS §Roofline
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[s for s, _ in SECTIONS] + [None])
    args = ap.parse_args()
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    failures = 0
    for name, module in SECTIONS:
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===", file=sys.stderr, flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            emit(mod.run())
        except Exception as e:  # report and continue: partial results beat none
            failures += 1
            print(f"{name},0.0,BENCH_ERROR:{type(e).__name__}:{e}",
                  flush=True)
        print(f"# === {name} done in {time.time() - t0:.1f}s ===",
              file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
