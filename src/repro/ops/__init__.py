"""Operations subsystem: failure injection, warm-start, observability.

Three parts, wired through :class:`repro.core.session.Engine`:

  * :mod:`repro.ops.chaos`   — deterministic fault injection at chunk
    boundaries (device loss, checkpoint corruption, OOM-shaped autotune
    failures) plus the harness the ``chaos`` test tier drives;
  * :mod:`repro.ops.warmup`  — ``Engine.warm(specs)`` precompiles the
    ``(M, A, L, seed) × chunk`` trace set at open so first-request latency
    is deterministic, and ``Engine.readiness()`` reports which static keys
    are warm;
  * :mod:`repro.ops.metrics` — host spans on the profiler's clock and a
    per-session :class:`MetricsRegistry`, both sampled entirely outside the
    jitted graph (zero additional traces, bitwise-invisible to results).

The chaos names load on first use: :mod:`repro.core.session` imports
:mod:`repro.ops.metrics`, and the chaos harness imports the session.
"""
from repro.ops.metrics import MetricsRegistry, span  # noqa: F401
from repro.ops.warmup import Readiness, readiness, warm  # noqa: F401

_CHAOS = frozenset((
    "AutotuneOOM", "ChaosReport", "CheckpointCorruption", "DeviceLoss",
    "FaultEvent", "FaultPlan", "ServeChaosReport", "SimulatedCrash",
    "TornCheckpointWrite", "corrupt_checkpoint", "count_write_ops",
    "crash_during_write", "force_autotune_oom", "run_plan", "run_serve_plan",
))


def __getattr__(name):
    if name in _CHAOS:
        from repro.ops import chaos

        return getattr(chaos, name)
    raise AttributeError(f"module 'repro.ops' has no attribute {name!r}")
