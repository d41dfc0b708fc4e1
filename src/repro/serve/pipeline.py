"""Double-buffered device→host stats/frame pipeline (lag-one transfer).

The engine's chunk dispatch is asynchronous on the JAX/Pallas backends:
``Session.run`` returns future-backed device arrays while the chunk is
still executing. Materializing those outputs immediately
(``block_until_ready`` inside ``to_numpy``) would serialize every chunk as
``[compute | transfer | compute | transfer]``. The gateway instead runs a
two-deep pipeline:

    dispatch chunk k          (device starts computing, host returns)
    materialize chunk k-1     (its compute overlapped chunk k's dispatch —
                               usually already done, so the host copy is
                               pure transfer)
    stream chunk k-1 frames

:class:`DoubleBuffer` is that lag-one stage: :meth:`push` stores the fresh
device batch and returns the *previous* one converted to host, so
streaming per-chunk frames to clients never blocks the next chunk's
dispatch. The cost is one chunk of latency on the stream — the classic
throughput-for-latency trade of double buffering — which
:meth:`flush` repays at end of stream. Output buffers are safe to hold
across dispatches because chunk outputs are freshly allocated (only the
carried *state* buffers are donated).

On host-loop backends (numpy) conversion is free and the pipeline
degenerates to a one-item delay line — same semantics, no overlap to win.

``Session.stream`` now overlaps for its own streaming callers (it
dispatches chunk k+1 before yielding chunk k); the gateway advances through
``Session.run``, which does not dispatch ahead, and keeps this lag-one.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Generic, Optional, Tuple, TypeVar

T = TypeVar("T")
U = TypeVar("U")


class DoubleBuffer(Generic[T, U]):
    """Lag-one conversion pipeline: ``push(x_k) -> convert(x_{k-1})``.

    ``convert`` is the (blocking) device→host materialization; it runs on
    the item pushed one call earlier, after the *next* chunk has already
    been dispatched. ``conversion_seconds`` accumulates the observed
    blocking time so the gateway can report how much transfer the overlap
    actually hid.
    """

    def __init__(self, convert: Callable[[T], U]) -> None:
        self._convert = convert
        self._pending: Optional[Tuple[Any, T]] = None
        self.conversions = 0
        self.conversion_seconds = 0.0

    @property
    def depth(self) -> int:
        """Items currently in flight (0 or 1)."""
        return 0 if self._pending is None else 1

    def push(self, tag: Any, item: T) -> Optional[Tuple[Any, U]]:
        """Store ``item`` (freshly dispatched, possibly still computing on
        device) and return the previously pushed ``(tag, converted)`` pair,
        or ``None`` on the first call."""
        done = self._drain()
        self._pending = (tag, item)
        return done

    def flush(self) -> Optional[Tuple[Any, U]]:
        """Convert and return the in-flight item (end of stream), if any."""
        return self._drain()

    def _drain(self) -> Optional[Tuple[Any, U]]:
        if self._pending is None:
            return None
        tag, item = self._pending
        self._pending = None
        t0 = time.perf_counter()
        out = self._convert(item)
        self.conversion_seconds += time.perf_counter() - t0
        self.conversions += 1
        return tag, out
