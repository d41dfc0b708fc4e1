"""Operations subsystem (repro.ops): metrics, warm-start, OOM degradation.

Tier-1 acceptance for the ops hardening:
  * metrics collection causes **zero additional traces** and results stay
    bitwise-identical to a metrics-off session (the zero-hot-path
    guarantee), on every compiled backend;
  * ``Engine.warm(specs)`` precompiles the full ``(static_key, chunk)``
    trace set so the first open/run/step after warm never retraces, and
    ``readiness()`` reports warm/cold keys truthfully;
  * an OOM-shaped autotune sweep (every tile candidate fails) degrades to
    the conservative heuristic tile — bitwise-identical results, never a
    crash.
"""
import contextlib
import dataclasses

import numpy as np
import pytest

from repro.core.config import MarketConfig
from repro.core.session import DEFAULT_CHUNK, Engine
from repro.kernels import autotune as tune
from repro.ops import force_autotune_oom
from repro.ops.metrics import MetricsRegistry, span

CFG = MarketConfig(num_markets=4, num_agents=16, num_levels=16, num_steps=12,
                   seed=3)

COMPILED_BACKENDS = ["jax-scan", "jax-per-step", "pallas-naive",
                     "pallas-kinetic"]
ALL_BACKENDS = ["numpy", "numpy-splitmix64", "numpy-pcg64"] + COMPILED_BACKENDS


def _batches_equal(a, b):
    a, b = a.to_numpy(), b.to_numpy()
    return all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))


# ---- metrics: zero traces, bitwise parity ----

@contextlib.contextmanager
def _profiler(trace_dir):
    """A ``jax.profiler`` trace recording host spans while entered."""
    import jax

    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("backend,profiled", [
    ("numpy-pcg64", False), ("jax-scan", False), ("pallas-kinetic", False),
    ("jax-scan", True), ("pallas-kinetic", True),
], ids=["numpy-pcg64", "jax-scan", "pallas-kinetic", "jax-scan-profiled",
        "pallas-kinetic-profiled"])
def test_metrics_zero_traces_and_bitwise(backend, profiled, tmp_path):
    """The headline guarantee: a metrics-on session produces bitwise the
    same stream as a metrics-off session and causes traces_delta == 0 —
    also while a profiler records every span."""
    eng = Engine(backend)
    off = eng.open(CFG, metrics=False)
    batch_off = off.run(12)
    traces_before = eng.trace_count

    with (_profiler(tmp_path) if profiled else contextlib.nullcontext()):
        on = eng.open(CFG)  # metrics on by default
        assert isinstance(on.metrics, MetricsRegistry)
        batch_on = on.run(12)
        assert _batches_equal(batch_off, batch_on)
    assert eng.trace_count - traces_before == 0, "metrics caused a retrace"
    snap = on.metrics.snapshot()
    assert snap["counters"]["steps_total"] == 12
    assert snap["counters"]["chunks_total"] == 1
    assert snap["counters"].get("traces", 0) == 0  # warm engine
    assert snap["timings"]["chunk_dispatch_seconds"]["count"] == 1
    assert snap["timings"]["chunk_dispatch_seconds"]["total"] > 0


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_metrics_recorded_series(backend):
    """Every session records the documented counters/timings/gauges."""
    eng = Engine(backend)
    with eng.open(CFG) as sess:
        sess.run(8)
        sess.step()
        snap_dict = sess.snapshot()
        sess.restore(snap_dict)
        m = sess.metrics.snapshot()
    assert m["counters"]["steps_total"] == 9
    assert m["counters"]["snapshots_total"] == 1
    assert m["counters"]["restores_total"] == 1
    assert m["gauges"]["num_markets"] == CFG.num_markets
    for series in ("chunk_dispatch_seconds", "step_dispatch_seconds",
                   "snapshot_seconds", "restore_seconds"):
        assert m["timings"][series]["count"] >= 1, series
    if backend.startswith("pallas"):
        assert m["gauges"]["autotune_vmem_bytes"] > 0
        assert m["gauges"]["tile_mb"] >= 1


def test_metrics_disabled_engine_wide_and_per_open():
    eng = Engine("numpy", metrics=False)
    assert eng.open(CFG).metrics is None
    assert eng.open(CFG, metrics=True).metrics is not None
    eng2 = Engine("numpy")
    assert eng2.open(CFG, metrics=False).metrics is None
    assert eng2.open(CFG).metrics is not None


def test_metrics_registry_aggregates():
    m = MetricsRegistry()
    m.inc("c")
    m.inc("c", 4)
    for v in (0.5, 1.5, 1.0):
        m.observe("t", v)
    m.gauge("g", 7)
    snap = m.snapshot()
    assert m.counter("c") == 5 and m.counter("missing") == 0
    agg = snap["timings"]["t"]
    assert agg["count"] == 3 and agg["min"] == 0.5 and agg["max"] == 1.5
    assert agg["total"] == pytest.approx(3.0)
    assert agg["mean"] == pytest.approx(1.0)
    assert snap["gauges"]["g"] == 7
    # Dispatch time is not a throughput: the snapshot derives none.
    assert set(snap) == {"counters", "gauges", "timings", "windows"}


def test_span_records_into_a_registry_only_when_given_one():
    m = MetricsRegistry()
    with span("outer", m, series="outer_seconds"):
        with span("inner", m, n=3) as inner:
            pass
        with span("quiet"):
            pass
    with pytest.raises(RuntimeError):
        with span("failed", m):
            raise RuntimeError("a failed body is not observed")
    timings = m.snapshot()["timings"]
    assert set(timings) == {"outer_seconds", "inner"}
    assert timings["outer_seconds"]["total"] >= timings["inner"]["total"]
    assert timings["inner"]["count"] == 1
    assert timings["inner"]["total"] == inner.seconds    # kept once closed


def test_spans_nest_on_the_profiler_host_thread(tmp_path):
    import glob

    from jax.profiler import ProfileData

    with _profiler(tmp_path):
        with span("kinetic.outer", session=7, markets=4):
            with span("kinetic.inner") as inner:
                inner.annotate(bytes=64)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("kinetic."):
                    got[ev.name] = (plane.name, line.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    {k: v for k, v in ev.stats})
    outer, inner = got["kinetic.outer"], got["kinetic.inner"]
    assert outer[:2] == inner[:2]                  # one host thread
    assert outer[2] <= inner[2] and inner[3] <= outer[3]
    assert outer[4] == {"session": 7, "markets": 4}
    assert inner[4] == {"bytes": 64}


# ---- warm-start controller ----

@pytest.mark.parametrize("backend", COMPILED_BACKENDS)
def test_warm_precompiles_whole_trace_set(backend):
    """After warm(), the first open/run/step triggers zero new traces."""
    eng = Engine(backend)
    ready = eng.warm(CFG)
    assert ready.ready
    traces = eng.trace_count
    assert traces >= 2  # chunk executable + the single-step executable
    with eng.open(CFG) as sess:
        sess.run(12)
        sess.run(5)
        sess.step()
    assert eng.trace_count == traces, "first request retraced after warm"


def test_warm_numpy_is_a_ready_noop():
    eng = Engine("numpy")
    ready = eng.warm(CFG)
    assert ready.ready and eng.trace_count == 0
    for entry in ready.entries:
        assert entry.warm and entry.traces == 0


def test_readiness_cold_to_warm_transition():
    eng = Engine("pallas-kinetic")
    assert eng.readiness().ready  # vacuously: no cached executables yet
    runner = eng._runner(CFG, 12)  # build without compiling
    probe = eng.readiness()
    assert not probe.ready
    assert probe.cold_keys() and not probe.warm_keys()
    eng.warm(CFG, include_step=False)
    probe = eng.readiness()
    assert probe.ready and not probe.cold_keys()
    entry = probe.entries[0]
    assert entry.chunk == 12 and entry.static_key[-1] == CFG.seed
    assert runner.trace_count == 1


def test_warm_multiple_specs_and_chunk_sizes():
    eng = Engine("jax-scan")
    other = dataclasses.replace(CFG, num_steps=24, seed=4)
    ready = eng.warm([CFG, other], chunk_sizes=[6], include_step=False)
    assert ready.ready
    # default chunk per spec (12 and 24) plus the explicit 6 for each spec
    chunks = sorted(e.chunk for e in ready.entries)
    assert chunks == [6, 6, 12, 24]
    traces = eng.trace_count
    eng.warm([CFG, other], chunk_sizes=[6], include_step=False)  # idempotent
    assert eng.trace_count == traces


def test_warm_default_chunk_matches_open():
    big = dataclasses.replace(CFG, num_steps=10 * DEFAULT_CHUNK)
    eng = Engine("jax-scan")
    eng.warm(big, include_step=False)
    traces = eng.trace_count
    with eng.open(big) as sess:
        sess.run(DEFAULT_CHUNK)
    assert eng.trace_count == traces


# ---- OOM-shaped autotune failure degrades to the conservative tile ----

def test_autotune_oom_degrades_to_heuristic_tile():
    """Every tile candidate failing OOM-shaped must fall back to the
    heuristic tile with bitwise-identical results — never crash."""
    with Engine("pallas-kinetic").open(CFG) as sess:
        want = sess.run(12)
    tune.clear_tune_cache()
    try:
        with force_autotune_oom():
            eng = Engine("pallas-kinetic", autotune=True)
            with eng.open(CFG) as sess:
                got = sess.run(12)
                runner = sess._runner
        report = tune.last_sweep_report()
        assert report is not None and report.fell_back
        assert len(report.failures) == len(report.tried) >= 1
        assert all("RESOURCE_EXHAUSTED" in f for f in report.failures)
        heuristic = tune.auto_tile(CFG.num_markets, CFG.num_agents)
        assert runner.tile == heuristic
        assert _batches_equal(want, got)
    finally:
        tune.clear_tune_cache()


def test_is_oom_error_markers():
    assert tune.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert tune.is_oom_error(MemoryError("out of memory"))
    assert tune.is_oom_error(ValueError("exceeded VMEM limit"))
    assert not tune.is_oom_error(ValueError("shape mismatch"))


def test_estimate_vmem_bytes_scales_with_tile():
    small = tune.TileChoice(mb=8, m_padded=8, agent_chunk=64)
    big = tune.TileChoice(mb=16, m_padded=16, agent_chunk=None)
    a = tune.estimate_vmem_bytes(small, num_levels=32, num_agents=256)
    b = tune.estimate_vmem_bytes(big, num_levels=32, num_agents=256)
    assert 0 < a < b
    # dominated by the [MB, L, Ac] one-hot intermediate
    assert a >= 4 * 8 * 64 * 32
    # A level tile bounds the one-hot to [MB, Lt, Ac]; the level-wide
    # working set still grows with L.
    wide = tune.estimate_vmem_bytes(big, num_levels=4096, num_agents=256)
    tiled = tune.estimate_vmem_bytes(big._replace(level_tile=512),
                                     num_levels=4096, num_agents=256)
    assert tiled < wide
    assert wide - tiled == 4 * 16 * 256 * (4096 - 512)
    assert tiled > tune.estimate_vmem_bytes(
        big._replace(level_tile=512), num_levels=2048, num_agents=256)
    assert tune.estimate_vmem_bytes(big._replace(level_tile=4096), 128,
                                    256) == tune.estimate_vmem_bytes(
                                        big, 128, 256)
