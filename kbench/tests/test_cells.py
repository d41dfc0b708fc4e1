"""Whole runs of tiny cells on the CPU: a cell added as data alone runs and
checks correct; the control and each planted fault come out not correct."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from kbench import harness
from kbench.tests import tiny
from repro.kernels.ops import PallasChunkRunner

SEED = 2 ** 31 + 12345   # a large seed, as the driver's are


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def run(root, workload, **kw):
    return harness.run_cell(workload, SEED, 0.5, False, time.perf_counter(),
                            root=root, **kw)


@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.step"])
def test_data_only_cell_runs_correct(root, workload):
    out = run(root, workload)
    assert out["correct"] is True
    assert out["check"] == {"paths_differing": {"value": 0, "limit": 0}}
    assert list(out)[-1] == "check"
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = ("agent_events_per_s" if workload.endswith("sweep")
           else "latency_p95_ms")
    assert set(out["metrics"]) == {"setup_s", e2e}
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.step"])
def test_control_in_lower_precision_is_not_correct(root, workload):
    out = run(root, workload, control="bfloat16")
    assert out["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["check"]["paths_differing"]["value"] > 0


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _unchanged(orig):
    def run_(self, state, params, aux, step0, n, ext, stats=None):
        before = _copy(state)
        _, aux, batch, stats = orig(self, state, params, aux, step0, n, ext,
                                    stats)
        return before, aux, batch, stats
    return run_


def _half_batch(orig):
    def run_(self, state, params, aux, step0, n, ext, stats=None):
        before = _copy(state)
        new, aux, batch, stats = orig(self, state, params, aux, step0, n,
                                      ext, stats)
        half = new.bid.shape[0] // 2
        keep = (jnp.arange(new.bid.shape[0]) < half)[:, None]
        new = jax.tree.map(lambda a, b: jnp.where(keep, a, b), new, before)
        batch = jax.tree.map(lambda x: jnp.where(keep, x, 0.0), batch)
        return new, aux, batch, stats
    return run_


def _altered(orig):
    def run_(self, state, params, aux, step0, n, ext, stats=None):
        new, aux, batch, stats = orig(self, state, params, aux, step0, n,
                                      ext, stats)
        return new, aux, batch._replace(
            price=batch.price.at[:, 0].add(1.0)), stats
    return run_


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.step"])
def test_planted_fault_is_not_correct(root, workload, fault, monkeypatch):
    monkeypatch.setattr(PallasChunkRunner, "run",
                        fault(PallasChunkRunner.run))
    out = run(root, workload)
    assert out["correct"] is False
    assert out["check"]["paths_differing"]["value"] > 0
