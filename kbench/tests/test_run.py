"""The measurement path refuses a CPU, and a checkout without the program;
neither prints a result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from kbench import registry


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "kbench", "run.py"),
         "--workload", "a256.sweep", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_cpu():
    p = _run(registry.ROOT)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_checkout_without_program(tmp_path):
    shutil.copytree(os.path.join(registry.ROOT, "kbench"),
                    tmp_path / "kbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_compile_counter_counts_only_while_entered():
    import jax

    from kbench.harness import CompileCounter

    with CompileCounter() as inside:
        jax.jit(lambda x: x * 3 + 1)(1.0)
    with CompileCounter() as idle:
        pass
    jax.jit(lambda x: x * 5 - 2)(1.0)
    assert inside.count == 1 and idle.count == 0
