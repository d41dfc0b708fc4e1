"""``Session.stream`` keeps one chunk in flight on asynchronous runners.

Before it yields chunk k, the stream dispatches chunk k+1 from a device
copy of the post-k state, so a caller copying chunk k to the host overlaps
chunk k+1 on the device. What these tests hold it to:

  * the lookahead is invisible in the results: a streamed session is
    bitwise equal to ``run()`` and to ``numpy``, also when a ``step()``
    with orders or an early close falls between two yields;
  * between two yields the session reads as after the last yielded chunk
    (cursor, snapshot, books), though the next chunk is already dispatched;
  * ``stream_ahead_total`` counts the chunks dispatched ahead and
    ``stream_ahead_discarded_total`` those dropped unconsumed; host-loop
    backends never dispatch ahead.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.config import scenario_config
from repro.core.session import Engine, ExternalOrders, StepBatch

# A shock straddling the 4-step chunk boundary at step 6.
CFG = scenario_config("flash-crash", num_markets=4, num_agents=16,
                      num_levels=16, num_steps=16, shock_step=6, seed=3)
CHUNK = 4
ASYNC = ["jax-scan", "pallas-kinetic"]
HOST_LOOP = ["numpy", "numpy-pcg64"]
STATE_FIELDS = ("bid", "ask", "last_price", "prev_mid")

_ENGINES = {}


def _engine(backend: str) -> Engine:
    if backend not in _ENGINES:
        _ENGINES[backend] = Engine(backend, chunk_size=CHUNK)
    return _ENGINES[backend]


def _host(batches) -> StepBatch:
    return StepBatch.concatenate([b.to_numpy() for b in batches])


def _assert_equal(a: StepBatch, b: StepBatch, ctx: str) -> None:
    for f, x, y in zip(StepBatch._fields, a.to_numpy(), b.to_numpy()):
        assert x.shape == y.shape and (x == y).all(), f"{ctx}: {f} differs"


def _assert_snap_equal(a, b, ctx: str) -> None:
    assert a["t"] == b["t"], ctx
    for f in STATE_FIELDS:
        assert (np.asarray(a[f]) == np.asarray(b[f])).all(), f"{ctx}: {f}"


def _counters(sess):
    c = sess.metrics.snapshot()["counters"]
    return (c.get("stream_ahead_total", 0),
            c.get("stream_ahead_discarded_total", 0))


def _orders():
    M, L = CFG.num_markets, CFG.num_levels
    return ExternalOrders(np.arange(M) % 2 == 0,
                          np.full(M, L // 2, np.int32),
                          np.full(M, 3.0, np.float32))


@pytest.mark.parametrize("backend", ASYNC)
def test_full_stream_matches_run_and_numpy(backend):
    eng = _engine(backend)
    with eng.open(CFG) as sess:
        streamed = _host(sess.stream())
        assert _counters(sess) == (CFG.num_steps // CHUNK - 1, 0)
        snap = sess.snapshot()
    with eng.open(CFG) as sess:
        ran = sess.run()
        ran_snap = sess.snapshot()
    with _engine("numpy").open(CFG) as sess:
        ref = sess.run()
        ref_snap = sess.snapshot()
    _assert_equal(streamed, ran, "stream vs run")
    _assert_equal(streamed, ref, "stream vs numpy")
    _assert_snap_equal(snap, ran_snap, "stream vs run")
    _assert_snap_equal(snap, ref_snap, "stream vs numpy")


@pytest.mark.parametrize("backend", ASYNC)
def test_first_yield_has_the_next_chunk_in_flight(backend):
    with _engine(backend).open(CFG) as sess:
        it = sess.stream()
        first = next(it)
        assert _counters(sess) == (1, 0)
        assert sess.step_count == CHUNK == first.num_steps
        assert sess.metrics.counter("steps_total") == CHUNK
        assert sess.metrics.counter("chunks_total") == 1
        # the session's own state is not the copy the ahead chunk donated
        assert all(not x.is_deleted() for x in sess.state)
        assert sum(b.num_steps for b in it) == CFG.num_steps - CHUNK


@pytest.mark.parametrize("backend", ASYNC)
def test_early_close_keeps_the_last_yielded_chunk(backend):
    eng = _engine(backend)
    with eng.open(CFG) as ref:
        ref.run(2 * CHUNK)
        ref_snap = ref.snapshot()
        ref_rest = ref.run(CFG.num_steps)
    with eng.open(CFG) as sess:
        it = sess.stream()
        next(it)
        next(it)
        it.close()
        assert _counters(sess) == (2, 1)
        assert sess.step_count == 2 * CHUNK
        _assert_snap_equal(sess.snapshot(), ref_snap, "after early close")
        for f, x in zip(STATE_FIELDS, sess.state):
            assert (np.asarray(x) == ref_snap[f]).all(), f
        _assert_equal(sess.run(CFG.num_steps), ref_rest, "run after close")


@pytest.mark.parametrize("backend", ASYNC)
def test_step_between_yields_matches_numpy(backend):
    def calls(sess):
        out = []
        it = sess.stream(3 * CHUNK)
        out.append(next(it).to_numpy())
        out.append(sess.step(_orders()).to_numpy())
        out.extend(b.to_numpy() for b in it)
        return StepBatch.concatenate(out), sess.snapshot()

    with _engine(backend).open(CFG) as sess:
        got, snap = calls(sess)
        assert _counters(sess) == (2, 1)
    with _engine("numpy").open(CFG) as sess:
        want, ref_snap = calls(sess)
    assert got.num_steps == 3 * CHUNK + 1
    _assert_equal(got, want, "stream + step vs numpy")
    _assert_snap_equal(snap, ref_snap, "stream + step vs numpy")


@pytest.mark.parametrize("backend", ASYNC)
def test_stats_only_stream_keeps_the_accumulators(backend):
    eng = Engine(backend, chunk_size=CHUNK, stats_only=True)
    with eng.open(CFG) as sess:
        sess.run()
        want = sess.stats
    with eng.open(CFG) as sess:
        it = sess.stream()
        next(it)
        mid_stream = sess.stats
        list(it)
        got = sess.stats
        assert _counters(sess) == (CFG.num_steps // CHUNK - 1, 0)
    with eng.open(CFG) as sess:
        sess.run(CHUNK)
        first = sess.stats
    for f, a, b, c, d in zip(got._fields, got, want, mid_stream, first):
        assert (np.asarray(a) == np.asarray(b)).all(), f
        assert (np.asarray(c) == np.asarray(d)).all(), f


@pytest.mark.parametrize("backend", HOST_LOOP)
def test_host_loop_backends_never_dispatch_ahead(backend):
    with _engine(backend).open(CFG) as sess:
        it = sess.stream()
        next(it)
        assert sess.step_count == CHUNK
        list(it)
        sess.run(CHUNK)
        assert _counters(sess) == (0, 0)


def test_sharded_stream_matches_single_device_subprocess():
    """A two-device sharded stream, paths and ``stats_only``, equals the
    single-device run bitwise: the state copy keeps the row sharding the
    chunk executable's inputs are pinned to."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.core.config import scenario_config
        from repro.core.session import Engine, StepBatch
        cfg = scenario_config("flash-crash", num_markets=10, num_agents=16,
                              num_levels=32, num_steps=20, shock_step=9,
                              seed=7)

        def streamed(**opts):
            with Engine("pallas-kinetic", chunk_size=6, **opts).open(cfg) as s:
                batches = [b.to_numpy() for b in s.stream()]
                c = s.metrics.snapshot()["counters"]
                assert c["stream_ahead_total"] == 3, c
                return StepBatch.concatenate(batches), s.snapshot()

        def stats(**opts):
            eng = Engine("pallas-kinetic", chunk_size=6, stats_only=True,
                         **opts)
            with eng.open(cfg) as s:
                list(s.stream())
                return s.stats

        with Engine("pallas-kinetic", chunk_size=6).open(cfg) as s:
            want, want_snap = s.run().to_numpy(), s.snapshot()
        got, snap = streamed(devices=2)
        for f, a, b in zip(want._fields, want, got):
            assert (a == b).all(), f
        for f in ("bid", "ask", "last_price", "prev_mid"):
            assert (want_snap[f] == snap[f]).all(), f
        single, sharded = stats(), stats(devices=2)
        for f, a, b in zip(single._fields, single, sharded):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "OK"
