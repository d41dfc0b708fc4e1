"""A tiny benchmark root for tests: a copy of ``kbench`` with one small
configuration and two small traffic mixes added as data alone."""
from __future__ import annotations

import json
import os
import shutil

from kbench import registry

CONFIG = {"num_markets": 16, "num_agents": 16, "num_levels": 16,
          "num_steps": 20}
TRAFFIC = {
    "tiny-sweep": {"mode": "sweep", "episode_steps": 20, "chunk": 8,
                   "check": {"segments": 8, "segment_rows": 2}},
    "tiny-step": {"mode": "step", "episode_steps": 20,
                  "orders": {"p_buy": 0.5, "max_offset_ticks": 8,
                             "max_qty": 8},
                  "check": {"segments": 8, "segment_rows": 2}},
}


def make_root(tmp: str) -> str:
    """``tmp`` as a benchmark root holding cells ``tiny.sweep`` and
    ``tiny.step``; nothing but data files is added to the copy."""
    shutil.copytree(os.path.join(registry.ROOT, "kbench"),
                    os.path.join(tmp, "kbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(registry.ROOT, "kbench", "configs",
                           "tableIV-a256.json")) as f:
        cfg = json.load(f)
    cfg.update(CONFIG, name="tiny")
    for d in cfg["episode_draws"]:
        if d["field"] == "shock_step":
            d["integers"] = [3, 15]
    for b in cfg["blocks"]:
        if "shock_step" in b["set"]:
            b["set"]["shock_step"] = 10
    with open(os.path.join(tmp, "kbench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    for name, t in TRAFFIC.items():
        with open(os.path.join(tmp, "kbench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(t, f)
    bench = registry.benchmark()
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "kbench/configs/tiny.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"] += [
        {"name": "tiny.sweep", "config": "tiny", "traffic": "tiny-sweep",
         "chips": 1, "why": "tiny"},
        {"name": "tiny.step", "config": "tiny", "traffic": "tiny-step",
         "chips": 1, "why": "tiny"}]
    for m in bench["end_to_end"]:
        if m["name"] == "agent_events_per_s":
            m["workloads"].append("tiny.sweep")
    # The step traffic's metric, read by kbench/metrics/latency_p95_ms.py
    # (the a256.step cell is not in BENCHMARK.json yet; see PERF.md).
    bench["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny.step"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
