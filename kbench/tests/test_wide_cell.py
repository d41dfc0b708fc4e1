"""A whole run of a tiny cell on a grid wider than 1024 levels, on the CPU:
the same ensemble as ``tiny.sweep`` on 2048 levels, added as data alone."""
from __future__ import annotations

import json
import os
import time

import pytest

from kbench import harness
from kbench.tests import tiny

SEED = 2 ** 31 + 12345   # a large seed, as the driver's are
WORKLOAD = "tiny-l2048.sweep"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tiny``'s root with the config ``tiny-l2048`` (``tiny`` on 2048
    levels) and the cell ``tiny-l2048.sweep``; data files only."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    configs = os.path.join(root, "kbench", "configs")
    with open(os.path.join(configs, "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(configs, "tiny-l2048.json"), "w") as f:
        json.dump(dict(cfg, name="tiny-l2048", num_levels=2048), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-l2048", "source": "tests",
                             "file": "kbench/configs/tiny-l2048.json",
                             "reduced": [], "why": "tiny, wide grid"})
    bench["workloads"].append({"name": WORKLOAD, "config": "tiny-l2048",
                               "traffic": "tiny-sweep", "chips": 1,
                               "why": "tiny, wide grid"})
    for m in bench["end_to_end"]:
        if m["name"] == "agent_events_per_s":
            m["workloads"].append(WORKLOAD)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def run(root, **kw):
    return harness.run_cell(WORKLOAD, SEED, 0.5, False, time.perf_counter(),
                            root=root, **kw)


def test_wide_grid_cell_runs_correct(root):
    out = run(root)
    assert out["correct"] is True
    assert out["check"] == {"paths_differing": {"value": 0, "limit": 0}}
    assert list(out)[-1] == "check"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "agent_events_per_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_control_in_lower_precision_fails_on_a_wide_grid(root):
    """The bfloat16 control on a 2048-level grid never checks correct.
    It cannot give a reading yet: bfloat16 holds integers exactly only up
    to 256, and ``kbench/reference.py`` clips ticks in its float type, so
    the top level, 2047, rounds to 2048, one row past the grid, and the
    reference's binning raises (PERF.md, Open questions 10). Once the clip
    is made in integers, a harness edit, the control reads here, and must
    read not correct while the program on the same grid reads correct."""
    try:
        out = run(root, control="bfloat16")
    except ValueError as exc:
        # The clip's defect and no other: a bin past the 2048 levels.
        assert "reshape" in str(exc) and ",2048)" in str(exc)
        return
    assert out["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["check"]["paths_differing"]["value"] > 0
