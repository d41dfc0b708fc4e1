"""Zero-hot-path observability: profiler spans, counters, gauges, timings.

Two surfaces, both sampled on the *host* strictly outside every jitted or
Pallas function, so no value becomes an operand of a compiled executable:
metrics cause **zero additional traces** and results stay bitwise-identical
to a metrics-off session, with or without a profiler recording (asserted by
``tests/test_ops.py::test_metrics_zero_traces_and_bitwise``).

**Spans.** :class:`span` opens a ``jax.profiler.TraceAnnotation``: while a
profiler records, the span lands on its host plane on the same clock as the
device planes, its keyword arguments as the event's stats; parentage is
nesting on the host thread. With no profiler recording a span costs about
a microsecond. Given a :class:`MetricsRegistry` it also ``observe``s its
duration. The program's spans (``kbench/spans.py`` reduces them):

  ``kinetic.open``               ``Engine.open`` until the Session is ready
                                 (``session``, a per-engine counter;
                                 ``markets``)
    ``kinetic.open.runner``      the runner lookup: cache hit or factory
                                 (``levels``; on the Pallas engines the
                                 resolved tile: ``mb``, ``agent_chunk``,
                                 ``level_tile``, each the whole axis
                                 where untiled)
      ``kinetic.open.autotune``  a Pallas tile sweep (cache misses only;
                                 ``candidates``, ``pruned``: those over
                                 the VMEM budget, never compiled); under
                                 ``kinetic.step`` when a first step builds
                                 its runner
    ``kinetic.open.place``       state, params, aux and stats placed on the
                                 device (``bytes``)
  ``kinetic.step``               ``Session.step``
    ``kinetic.step.orders``      validation and lowering of the orders to
                                 dense [M, L] (``bytes``)
  ``kinetic.dispatch``           one runner call (``session``, ``step0``,
                                 ``n``, ``kind`` = chunk/step, ``ahead`` =
                                 1 for a chunk ``Session.stream``
                                 dispatched before the caller consumed the
                                 previous one, else 0); nests in
                                 ``kinetic.step`` for a step
    ``kinetic.dispatch.copy``    an ahead chunk: the device copy of the
                                 state it is fed (``bytes``; enqueue)
    ``kinetic.dispatch.operands``  Pallas runners: the step0/n_valid
                                 scalars and the external orders (``bytes``)
    ``kinetic.dispatch.launch``  the jitted chunk call (enqueue)
    ``kinetic.dispatch.slice``   the ``[:, :n]`` path slices
  ``kinetic.to_host``            ``StepBatch.to_numpy`` (``bytes``)
    ``kinetic.to_host.wait``     waiting for the device to finish the batch
    ``kinetic.to_host.copy``     the device-to-host copies: enqueued before
                                 the wait, started by the runtime when it
                                 sees the device done, as the wait returns
  ``kinetic.swap``, ``kinetic.snapshot``, ``kinetic.restore``
                                 the Session's slot splice, snapshot and
                                 restore
  ``kinetic.gateway.checkpoint_snapshot``  the gateway checkpoint's
                                 device-to-host mirror (``seq``); it times
                                 ``checkpoint_snapshot_seconds``

**Registry.** A :class:`MetricsRegistry` is attached to every session
``Engine.open`` creates (disable with ``Engine(backend, metrics=False)`` or
per-session ``open(spec, metrics=False)``). The session records:

  counters  ``steps_total``, ``chunks_total`` (advanced: a chunk counts
            once it is yielded), ``traces`` (retrace counter: 0 on a warm
            engine; two integer reads per dispatch), ``snapshots_total``,
            ``restores_total``, ``swaps_total``,
            ``stream_ahead_total`` (chunks ``Session.stream`` dispatched
            while the previous batch was not yet consumed),
            ``stream_ahead_discarded_total`` (of those, dropped unyielded
            by an early close or a mid-stream ``step``/``run``/``close``)
  timings   (count/total/min/max aggregates, timed by their spans)
            ``chunk_dispatch_seconds``, ``step_dispatch_seconds`` — the
            host time to *enqueue* a chunk or step (``kinetic.dispatch``;
            the device may still be working when it ends: not a latency
            and not a throughput), ``swap_seconds``, ``snapshot_seconds``,
            ``restore_seconds``
  gauges    ``chunk``, ``num_markets``, and on the Pallas engines the
            autotune tile pressure: ``autotune_vmem_bytes``, ``tile_mb``,
            ``tile_agent_chunk``, ``tile_level_tile``

The registry is generic — any consumer may ``inc``/``observe``/``gauge``
additional series. The serving gateway (:mod:`repro.serve`) records:

  counters  ``frames_published_total``, ``frames_dropped_total``,
            ``sessions_opened_total``, ``sessions_closed_total``,
            ``reconnects_total``, ``swaps_total`` (slot attach/detach rows),
            ``checkpoints_saved_total`` (committed by the async writer),
            ``journal_entries_total`` (splices journaled),
            ``journal_compactions_total`` /
            ``journal_entries_compacted_total`` (GC-driven compaction),
            ``recoveries_total`` (successful supervised recovery passes),
            ``recovery_attempts_total`` (including retried failures),
            ``faults_coalesced_total`` (extra faults folded into one pass)
  gauges    ``queue_depth.<client>`` per-client fan-out queue depths,
            ``clients_connected``, ``slots_attached``,
            ``checkpoint_writer_pending`` (snapshots not yet committed,
            0–2 by the lag bound), ``checkpoints_skipped`` (saves dropped
            by the latest-wins mailbox), ``degraded`` (0/1)
  windows   (bounded :class:`QuantileWindow` series, p50/p99 read by
            ``benchmarks/serve_bench.py``)
            ``chunk_latency_seconds`` — dispatch to materialized frames,
            ``checkpoint_snapshot_seconds`` — the engine-thread cost of a
            checkpoint (device→host mirror only; its span's duration),
            ``checkpoint_write_seconds`` — the background writer's
            serialize+fsync+commit latency (never on the engine thread)
"""
from __future__ import annotations

import bisect
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation


class Aggregate:
    """count/total/min/max running aggregate of host-side observations."""

    __slots__ = ("count", "total", "min", "max")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def add(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def summary(self) -> Dict[str, float]:
        mean = self.total / self.count if self.count else 0.0
        return {"count": self.count, "total": self.total, "mean": mean,
                "min": self.min if self.count else 0.0,
                "max": self.max if self.count else 0.0}


class QuantileWindow:
    """Bounded sliding window of the last ``size`` observations with exact
    percentile reads — the latency-summary shape a serving layer needs
    (p50/p99 over *recent* traffic, not a run-lifetime mean).

    A ring buffer holds arrival order while a parallel sorted list supports
    O(log n) insert/remove, so :meth:`percentile` is an O(1) index into the
    sorted view. Memory is O(size) however long the gateway runs; ``size``
    defaults to 1024 observations.
    """

    __slots__ = ("size", "count", "_ring", "_next", "_sorted")

    def __init__(self, size: int = 1024) -> None:
        if size < 1:
            raise ValueError(f"window size must be >= 1, got {size}")
        self.size = int(size)
        self.count = 0            # lifetime observations (window may be full)
        self._ring: List[float] = []
        self._next = 0            # ring slot the next add overwrites
        self._sorted: List[float] = []

    def add(self, value: float) -> None:
        value = float(value)
        if len(self._ring) < self.size:
            self._ring.append(value)
        else:
            evicted = self._ring[self._next]
            self._sorted.pop(bisect.bisect_left(self._sorted, evicted))
            self._ring[self._next] = value
        self._next = (self._next + 1) % self.size
        bisect.insort(self._sorted, value)
        self.count += 1

    def __len__(self) -> int:
        return len(self._sorted)

    def percentile(self, q: float) -> float:
        """Exact nearest-rank percentile of the current window (q in
        [0, 100]); 0.0 on an empty window."""
        n = len(self._sorted)
        if not n:
            return 0.0
        rank = min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))
        return self._sorted[rank]

    def summary(self) -> Dict[str, float]:
        return {"count": self.count, "window": len(self._sorted),
                "p50": self.percentile(50), "p90": self.percentile(90),
                "p99": self.percentile(99),
                "min": self._sorted[0] if self._sorted else 0.0,
                "max": self._sorted[-1] if self._sorted else 0.0}


class MetricsRegistry:
    """Per-session metrics: counters, gauges, timing aggregates, and
    bounded-window quantile summaries.

    Thread-safe (one lock around the tiny dict updates) so a streaming
    consumer thread may read :meth:`snapshot` while the session advances.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, Any] = {}
        self._timings: Dict[str, Aggregate] = {}
        self._windows: Dict[str, QuantileWindow] = {}

    # ---- write side (host-only; never called from inside a trace) ----
    def inc(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def gauge(self, name: str, value: Any) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            agg = self._timings.get(name)
            if agg is None:
                agg = self._timings[name] = Aggregate()
            agg.add(value)

    def observe_window(self, name: str, value: float,
                       size: int = 1024) -> None:
        """Record into a bounded :class:`QuantileWindow` series (created on
        first use with ``size``; later calls ignore ``size``)."""
        with self._lock:
            win = self._windows.get(name)
            if win is None:
                win = self._windows[name] = QuantileWindow(size)
            win.add(value)

    # ---- read side ----
    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def gauge_value(self, name: str, default: Any = None) -> Any:
        with self._lock:
            return self._gauges.get(name, default)

    def window(self, name: str) -> Optional[QuantileWindow]:
        with self._lock:
            return self._windows.get(name)

    def snapshot(self) -> Dict[str, Any]:
        """Plain-python view: {'counters', 'gauges', 'timings', 'windows'}."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timings": {k: v.summary() for k, v in self._timings.items()},
                "windows": {k: v.summary() for k, v in self._windows.items()},
            }


class span:
    """A host span: ``with span("kinetic.dispatch", registry, n=64): ...``.

    Opens ``jax.profiler.TraceAnnotation(name, **args)``, which records
    only while a profiler is recording. Given a ``registry``, also
    ``observe``s the span's duration in seconds under ``series`` (default:
    ``name``) when the body completes without raising. The duration is
    ``seconds`` once the span has closed. Host code only: never inside a
    jitted or Pallas function.
    """

    __slots__ = ("_ann", "_registry", "_series", "_t0", "seconds")

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None,
                 series: Optional[str] = None, **args: Any) -> None:
        self._ann = TraceAnnotation(name, **args)
        self._registry = registry
        self._series = series or name

    def annotate(self, **args: Any) -> None:
        """Add arguments known only once the span's work is done."""
        self._ann.set_metadata(**args)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        if self._registry is not None and exc_type is None:
            self._registry.observe(self._series, self.seconds)
