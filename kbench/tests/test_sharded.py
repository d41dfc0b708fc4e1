"""The four-chip path on four virtual CPU devices: a tiny sharded,
ring-coupled sweep checks correct, and comes out not correct with the
exchange between chips left out."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from kbench import registry
from kbench.tests import tiny

SCRIPT = textwrap.dedent("""
    import json, sys, time
    import jax
    sys.path[:0] = [{root!r}, {src!r}]
    from kbench import harness
    if {fault!r} == "no_halo":
        # Each chip keeps its own mids instead of passing them round the
        # ring: a peer on another chip is read wrong.
        jax.lax.ppermute = lambda x, axis_name, perm: x
    out = harness.run_cell("tiny4.sweep", 2 ** 31 + 99, 0.5, False,
                           time.perf_counter(), root={bench!r})
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench4")))
    with open(os.path.join(root, "kbench", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny4", num_markets=32, chips=4)
    with open(os.path.join(root, "kbench", "configs", "tiny4.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny4", "source": "tests",
                         "file": "kbench/configs/tiny4.json", "reduced": [],
                         "why": "tiny sharded"})
    b["workloads"].append({"name": "tiny4.sweep", "config": "tiny4",
                           "traffic": "tiny-sweep", "chips": 4,
                           "why": "tiny sharded"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root


@pytest.mark.parametrize("fault,correct", [(None, True), ("no_halo", False)])
def test_sharded_sweep(bench, fault, correct):
    script = SCRIPT.format(root=registry.ROOT,
                           src=os.path.join(registry.ROOT, "src"),
                           bench=bench, fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct
    assert (out["check"]["paths_differing"]["value"] == 0) is correct
