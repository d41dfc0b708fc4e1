"""Shared benchmark utilities: timing, CSV rows, scale configuration.

This container is CPU-only, so the paper's absolute GPU numbers cannot be
reproduced; every benchmark reproduces the paper's *structure* (same sweeps,
same metrics, same baseline set) at CPU-tractable scale. ``FULL_SCALE=1``
in the environment switches to the paper's exact configuration for runs on
real hardware.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

FULL = bool(int(os.environ.get("FULL_SCALE", "0")))

# (paper value, CPU-reduced value)
MARKET_SWEEP = [64, 256, 1024, 4096, 16384] if FULL else [16, 64, 256]
AGENT_SWEEP = [16, 64, 256, 1024] if FULL else [16, 64, 256]
FIXED_M = 8192 if FULL else 128
FIXED_A = 256 if FULL else 128
STEPS = 500 if FULL else 50

Row = Tuple[str, float, str]


def time_call(fn: Callable, *args, trials: int = 5, warmup: int = 1,
              **kwargs) -> Tuple[float, object]:
    """Median wall-time (seconds) over ``trials``; returns (t, last_result)."""
    result = None
    for _ in range(warmup):
        result = fn(*args, **kwargs)
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        result = fn(*args, **kwargs)
        _block(result)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), result


def _block(x):
    try:
        import jax

        jax.block_until_ready(
            [l for l in jax.tree_util.tree_leaves(x)
             if hasattr(l, "block_until_ready")])
    except Exception:
        pass


def events_per_s(cfg, seconds: float) -> float:
    return cfg.events() / seconds if seconds > 0 else float("nan")


def emit(rows: List[Row], json_path: Optional[str] = None,
         benchmark: Optional[str] = None) -> None:
    """Print CSV rows; optionally also write a machine-readable JSON artifact
    (the seed of the ``BENCH_*.json`` trajectory uploaded by CI)."""
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
        sys.stdout.flush()
    if json_path:
        write_json(rows, json_path, benchmark or "benchmark")


def write_json(rows: List[Row], path: str, benchmark: str) -> None:
    payload = {
        "benchmark": benchmark,
        "full_scale": FULL,
        "rows": [
            {"name": name, "us": us,
             "derived": dict(kv.split("=", 1) for kv in derived.split(";")
                             if "=" in kv)}
            for name, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
