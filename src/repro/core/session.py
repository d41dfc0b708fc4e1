"""Stateful session API: open/step/close engine lifecycle with compile-once
reuse, chunked streaming, and an RL stepping hook.

The paper's headline regime — 22.1µs warm per-step latency, HBM traffic
independent of step count — is about *persistent state across step
boundaries*. This module is the front door to that regime:

    eng = Engine("pallas-kinetic")
    with eng.open(spec) as sess:          # device-resident MarketState
        for batch in sess.stream(10_000): # chunked StepBatch slices
            consume(batch)
        obs = sess.step(actions)          # gym-style RL hook

Design:

  * :class:`Engine` opens sessions on an :class:`repro.core.params
    .EnsembleSpec` — a heterogeneous per-market parameter ensemble — or on
    a plain :class:`MarketConfig`, which coerces to a homogeneous spec
    bitwise-identically. Compiled chunk executables are cached per
    (static-shape, chunk-length) key — ``EnsembleSpec.static_key()``:
    ``(M, A, L, seed)`` — so *any* scenario mixture, and any change of
    parameter values, reuses one warm trace. Opening a second session with
    the same shape triggers **zero** retraces.
  * Each backend supplies a :class:`ChunkRunner`: a fixed ``chunk``-length
    compiled entry taking runtime ``(step0, n_valid)`` scalars plus the
    per-market :class:`MarketParams` operands, so one trace serves any
    requested step count *and* any parameter values; partial tails are
    gated branch-free.
  * State buffers are **donated** back to the executable on every chunk
    (``jax.jit(..., donate_argnums=(0,))``); the params operands are *not*
    donated — they persist device-resident across the session's life.
  * Chunked execution is bitwise-identical to one-shot: the RNG is a pure
    function of the absolute step coordinate and the scenario overlay keys
    on the absolute step, so chunk boundaries are invisible to the stream.
  * :meth:`Session.step` injects external orders through a reserved slot in
    the incoming flow (``simulate_step``'s ``ext_buy``/``ext_ask``) — the
    gym-style hook for future RL workloads; ``actions=None`` is a bitwise
    no-op relative to :meth:`Session.run`.
  * :meth:`Session.snapshot` / :meth:`Session.restore` round-trip the full
    session state (books, step cursor, stateful RNG, the per-market
    parameter operands, and any ``stats_only`` accumulators) exactly, and
    wire into :class:`repro.checkpoint.manager.CheckpointManager` via
    :meth:`Session.save_checkpoint` / :meth:`Session.restore_checkpoint`.
  * Sessions are device-layout transparent: a runner may shard the market
    axis over a ``("markets",)`` mesh (``Engine(backend, devices=N)``) and
    every advancement/snapshot API behaves identically — bitwise — to the
    single-device session; heterogeneous params shard row-wise with the
    books. In ``stats_only`` mode the per-step paths are replaced by
    carried per-market aggregates (:attr:`Session.stats`), making session
    output traffic Θ(M) independent of horizon.

Horizon semantics: ``num_steps`` is the session **horizon** — the default
length of :meth:`Session.run` / :meth:`Session.stream` and the bound every
scenario event is validated against (``shock_step < num_steps``). Advancing
*past* the horizon with an explicit ``n_steps`` is permitted (the RNG and
overlays key on the absolute step, so post-horizon steps are well defined;
a shock that already fired never re-fires), but the default-length form
``run()``/``stream()`` raises once the cursor has reached the horizon —
running "the configured scenario" from there could never fire any of its
events, which previously failed silently.

``engine.simulate()`` / ``engine.simulate_scenario()`` remain as thin
compatibility wrappers over a one-session run.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple, Union

import numpy as np

from repro.core import params as params_mod
from repro.core.config import MarketConfig
from repro.core.params import EnsembleSpec, MarketParams
from repro.core.result import SimResult
from repro.core.stats import MarketStats, init_stats
from repro.core.step import MarketState, initial_state

#: Default compiled chunk length (steps per device call) for streaming runs.
DEFAULT_CHUNK = 64

# backend name -> factory(spec, chunk, **backend_opts) -> ChunkRunner
_FACTORIES: Dict[str, Callable[..., "ChunkRunner"]] = {}
# backend name -> reason string for backends whose registration failed
_FAILED: Dict[str, str] = {}


class StepBatch(NamedTuple):
    """A contiguous slice of per-step outputs streamed from a session."""

    price: Any   # float32[M, n] clearing price (last price when no cross)
    volume: Any  # float32[M, n] transacted volume
    mid: Any     # float32[M, n] pre-clearing mid used for agent decisions

    @property
    def num_steps(self) -> int:
        return int(self.price.shape[-1])

    def to_numpy(self) -> "StepBatch":
        return StepBatch(*(np.asarray(x) for x in self))

    @staticmethod
    def concatenate(batches: "list[StepBatch]", xp=np) -> "StepBatch":
        if len(batches) == 1:
            return batches[0]
        return StepBatch(*(xp.concatenate(parts, axis=-1)
                           for parts in zip(*batches)))


class ExternalOrders(NamedTuple):
    """One external limit order per market for :meth:`Session.step` and
    :meth:`repro.env.MarketEnv.step`.

    Each field is broadcastable to ``[M]``: ``side_buy`` bool, ``price``
    int tick index on the grid ``[0, L)``, ``qty`` float lots ``>= 0``
    (``qty == 0`` is a bitwise no-op order). Shapes/dtypes — and values,
    when concrete — are validated eagerly with a clear ``ValueError``
    (see :func:`repro.env.actions.validate_actions`) instead of a deep
    backend trace error.
    """

    side_buy: Any
    price: Any
    qty: Any


class ChunkRunner:
    """Backend adapter: a compiled (or host-loop) fixed-chunk executor.

    Subclasses set ``chunk`` and ``xp`` and implement :meth:`run`; stateful
    RNG backends additionally override the ``aux`` hooks. A runner is
    immutable and shared by every session opened with the same *static
    shape* — all per-session mutable state, including the per-market
    :class:`MarketParams` operands, lives in :class:`Session`.
    """

    chunk: int = 1
    xp: Any = np
    #: True when :meth:`run` dispatches a compiled executable (jax/pallas) —
    #: i.e. there is something for ``Engine.warm`` to precompile; host-loop
    #: runners leave this False and are always "warm".
    compiled: bool = False
    #: Runners opened with ``stats_only=True`` replace per-step path outputs
    #: with carried :class:`repro.core.stats.MarketStats` accumulators.
    stats_only: bool = False
    #: True when :meth:`env_step_fn` returns a jax-traceable pure function
    #: (embeddable in the RL env's jit/vmap/lax.scan rollouts).
    env_traceable: bool = False
    #: True when the step core accepts a *runtime* RNG seed override (the
    #: env's vmap-over-seeds operand); False when the seed is baked into
    #: the compiled trace (Pallas kernels) or a stateful stream (PCG64).
    env_runtime_seed: bool = False

    def __init__(self) -> None:
        self._trace_count = 0

    @property
    def trace_count(self) -> int:
        """Times the underlying executable was (re)traced; 0 for host loops."""
        return self._trace_count

    def init_state(self, spec: EnsembleSpec) -> MarketState:
        return initial_state(spec, self.xp)

    def to_device(self, state: MarketState) -> MarketState:
        return MarketState(*(self.xp.asarray(np.asarray(x), dtype=self.xp.float32)
                             for x in state))

    def params_to_device(self, params: MarketParams) -> MarketParams:
        """Place the per-market parameter operands (dtype-preserving)."""
        return params.asarray(self.xp)

    # ---- stats_only accumulators (None unless the runner enables them) ----
    def init_stats(self, spec: EnsembleSpec) -> Optional[MarketStats]:
        if not self.stats_only:
            return None
        return init_stats(spec.num_markets, self.xp)

    def stats_to_device(self, stats: MarketStats) -> MarketStats:
        return MarketStats(*(self.xp.asarray(np.asarray(x),
                                             dtype=self.xp.float32)
                             for x in stats))

    # ---- functional env core (repro.env) ----
    def env_step_fn(self) -> Optional[Callable]:
        """Pure per-step core for :class:`repro.env.MarketEnv`, or ``None``.

        The returned callable has the uniform signature

            ``fn(market: MarketState, params: MarketParams, t, ext_buy,
            ext_ask, seed, aux) -> (MarketState, StepOutput, aux)``

        where ``t`` is the absolute step (scalar, traced ok), ``ext_buy`` /
        ``ext_ask`` are float32[M, L] injected order quantities, ``seed`` is
        an optional runtime RNG override (``None`` for the trace-static
        seed) and ``aux`` is the stateful-RNG payload threaded through
        unchanged by counter-RNG backends. It is the *same* ``simulate_step``
        entry the chunked Session path compiles, so the two APIs cannot
        drift; traceable backends (``env_traceable``) return a function that
        embeds in jit/vmap/``lax.scan`` with no host transfer per step.
        """
        return None

    # ---- stateful-RNG hooks (identity for counter-based backends) ----
    def init_aux(self, spec: EnsembleSpec) -> Any:
        return None

    def aux_state(self, aux: Any) -> Any:
        """JSON-serializable payload capturing ``aux``, or None."""
        return None

    def restore_aux(self, payload: Any) -> Any:
        return None

    def run(self, state: MarketState, params: MarketParams, aux: Any,
            step0: int, n: int, ext: Optional[Tuple[Any, Any]],
            stats: Optional[MarketStats] = None,
            ) -> Tuple[MarketState, Any, StepBatch, Optional[MarketStats]]:
        """Advance ``n <= self.chunk`` steps from absolute step ``step0``.

        ``params`` carries the session's per-market scenario operands
        (placed via :meth:`params_to_device`; never donated). ``ext`` is an
        optional ``(ext_buy, ext_ask)`` float32[M, L] pair injected at the
        first step of the chunk. Returns the new state, new aux, a
        :class:`StepBatch` whose paths have exactly ``n`` columns, and the
        updated stats accumulators. In ``stats_only`` mode the carried
        ``stats`` must be threaded through every call (the batch comes back
        with zero-width paths); otherwise ``stats`` is ignored and returned
        as ``None``.
        """
        raise NotImplementedError


def register_backend(name: str):
    """Register a session factory ``f(spec, chunk, **opts) -> ChunkRunner``."""
    def deco(fn):
        _FACTORIES[name] = fn
        _FAILED.pop(name, None)
        return fn
    return deco


def _ensure_builtin() -> None:
    if "numpy" in _FACTORIES:
        return
    from repro.core import jax_backend, numpy_backend  # noqa: F401 (register)

    for mode in ("kinetic", "splitmix64", "pcg64"):
        name = "numpy" if mode == "kinetic" else f"numpy-{mode}"
        _FACTORIES[name] = _numpy_factory(mode)
    _FACTORIES["jax-scan"] = _jax_factory("scan")
    _FACTORIES["jax-per-step"] = _jax_factory("per-step")
    try:
        from repro.kernels import ops as _kernel_ops  # noqa: F401 (register)
    except ImportError as exc:
        # Record the reason instead of swallowing it: surfaced by
        # backend_available() and by Engine/simulate KeyErrors.
        reason = f"{type(exc).__name__}: {exc}"
        for name in ("pallas-naive", "pallas-kinetic"):
            _FAILED.setdefault(name, reason)


def _numpy_factory(rng_mode: str):
    def factory(spec, chunk, **opts):
        from repro.core import numpy_backend

        return numpy_backend.open_chunk_runner(spec, chunk, rng_mode=rng_mode,
                                               **opts)
    return factory


def _jax_factory(mode: str):
    def factory(spec, chunk, **opts):
        from repro.core import jax_backend

        return jax_backend.open_chunk_runner(spec, chunk, mode=mode, **opts)
    return factory


def backends() -> "list[str]":
    _ensure_builtin()
    return sorted(_FACTORIES)


def backend_available(name: str) -> Union[bool, str]:
    """True if ``name`` is registered, the recorded failure-reason string if
    its registration failed (e.g. a Pallas ImportError), False if unknown."""
    _ensure_builtin()
    if name in _FACTORIES:
        return True
    if name in _FAILED:
        return _FAILED[name]
    return False


def _unknown_backend_error(name: str) -> KeyError:
    if name in _FAILED:
        return KeyError(
            f"backend {name!r} failed to register: {_FAILED[name]}")
    return KeyError(f"unknown backend {name!r}; have {sorted(_FACTORIES)}")


def run_runner_to_result(runner: ChunkRunner, spec) -> SimResult:
    """One-session run over ``spec.num_steps`` on a bare runner — the shared
    body of every backend's ``simulate()`` compatibility wrapper."""
    if runner.stats_only:
        # A SimResult has nowhere to carry the accumulators — returning
        # zero-width paths would silently lose every output.
        raise ValueError(
            "stats_only is a Session-API mode: open a session and read "
            "Session.stats instead of using the one-shot simulate() wrappers")
    spec = EnsembleSpec.coerce(spec)
    state = runner.init_state(spec)
    params = runner.params_to_device(spec.params)
    aux = runner.init_aux(spec)
    stats = runner.init_stats(spec)
    batches, t = [], 0
    while t < spec.num_steps:
        n = min(runner.chunk, spec.num_steps - t)
        state, aux, batch, stats = runner.run(state, params, aux, t, n, None,
                                              stats)
        batches.append(batch)
        t += n
    if batches:
        batch = StepBatch.concatenate(batches, xp=runner.xp)
    else:
        empty = runner.xp.zeros((spec.num_markets, 0), runner.xp.float32)
        batch = StepBatch(empty, empty, empty)
    return SimResult(bid=state.bid, ask=state.ask,
                     last_price=state.last_price, prev_mid=state.prev_mid,
                     price_path=batch.price, volume_path=batch.volume)


class Engine:
    """Compiled-executable cache + session factory for one backend.

    ``backend_opts`` are backend-specific knobs (``scan=`` and
    ``binning=`` for the NumPy/JAX engines; ``mb=`` and the scaling
    knobs ``devices=``/``mesh=`` market-axis sharding, ``stats_only=``
    in-kernel statistics, ``autotune=``/``agent_chunk=`` tile selection —
    see ``repro.kernels.ops``) folded into every runner this engine
    builds. Executables are cached per (static-shape, chunk-length) —
    :meth:`EnsembleSpec.static_key` + chunk — and shared across sessions:
    re-opening the same shape never recompiles, *whatever* the scenario
    parameter values, because every value-like field rides in the
    :class:`MarketParams` operands rather than the trace.
    ``num_steps`` itself is not part of the key, but it does cap the
    *default* chunk length at ``min(DEFAULT_CHUNK, num_steps)`` — pass an
    explicit ``chunk_size`` to share one executable across specs whose
    ``num_steps`` differ below ``DEFAULT_CHUNK``.
    """

    def __init__(self, backend: str = "jax-scan", *,
                 chunk_size: Optional[int] = None, metrics: bool = True,
                 **backend_opts: Any):
        _ensure_builtin()
        if backend not in _FACTORIES:
            raise _unknown_backend_error(backend)
        self.backend = backend
        self.chunk_size = chunk_size
        self.metrics = bool(metrics)
        self.backend_opts = dict(backend_opts)
        self._runners: Dict[Tuple[Any, ...], ChunkRunner] = {}
        # RL env executables (repro.env), cached under the same
        # shape-semantic keys as the chunk runners: any scenario mixture of
        # one shape trains against one compile.
        self._env_traces: Dict[Tuple[Any, ...], Dict[Any, Any]] = {}

    @property
    def trace_count(self) -> int:
        """Total traces across all cached executables (retrace detector)."""
        return sum(r.trace_count for r in self._runners.values())

    def clear_cache(self) -> None:
        """Drop all cached executables (long-lived shape-sweep processes)."""
        self._runners.clear()
        self._env_traces.clear()

    def _runner(self, spec, chunk: int) -> ChunkRunner:
        spec = EnsembleSpec.coerce(spec)
        key = spec.static_key() + (chunk,)
        runner = self._runners.get(key)
        if runner is None:
            runner = _FACTORIES[self.backend](spec, chunk, **self.backend_opts)
            self._runners[key] = runner
        return runner

    def open(self, spec: Union[EnsembleSpec, MarketConfig], *,
             chunk_size: Optional[int] = None,
             metrics: Optional[bool] = None) -> "Session":
        """Open a live session holding a device-resident :class:`MarketState`.

        ``spec`` is an :class:`EnsembleSpec` or a :class:`MarketConfig`
        (coerced through ``EnsembleSpec.homogeneous`` — bitwise-identical
        to the historical scalar-config path).

        Every session carries a :class:`repro.ops.metrics.MetricsRegistry`
        by default (``Session.metrics``), sampled strictly outside the
        jitted graph — zero additional traces, bitwise-invisible to
        results. Disable per-session with ``metrics=False`` or engine-wide
        with ``Engine(backend, metrics=False)``.
        """
        spec = EnsembleSpec.coerce(spec)
        chunk = chunk_size or self.chunk_size \
            or min(DEFAULT_CHUNK, spec.num_steps)
        registry = None
        if self.metrics if metrics is None else metrics:
            from repro.ops.metrics import MetricsRegistry

            registry = MetricsRegistry()
        return Session(self, spec, self._runner(spec, max(1, chunk)),
                       metrics=registry)

    def warm(self, specs, *, chunk_sizes=None, include_step: bool = True):
        """Precompile every executable ``specs`` will need (see
        :func:`repro.ops.warmup.warm`); returns the post-warm readiness
        probe, so ``engine.warm(specs).ready`` gates serving traffic."""
        from repro.ops import warmup

        return warmup.warm(self, specs, chunk_sizes=chunk_sizes,
                           include_step=include_step)

    def readiness(self):
        """Which cached ``(static_key, chunk)`` executables are warm
        (see :func:`repro.ops.warmup.readiness`)."""
        from repro.ops import warmup

        return warmup.readiness(self)

    def env(self, spec: Union[EnsembleSpec, MarketConfig], **env_opts: Any):
        """Open a pure-functional RL environment over this engine's backend.

        Returns a :class:`repro.env.MarketEnv` whose step core is this
        engine's single-step executable (the one :meth:`Session.step` uses)
        and whose jitted step/rollout traces are cached on the engine under
        the shape-semantic :meth:`EnsembleSpec.static_key` — two envs opened
        on different scenario mixtures of the same shape share every
        compile. ``env_opts`` are :class:`repro.env.MarketEnv` keyword
        options (``obs=``, ``reward=``, ``horizon=``, ``auto_reset=``).
        """
        from repro.env.core import MarketEnv

        return MarketEnv(spec, engine=self, **env_opts)

    def trainer(self, spec: Union[EnsembleSpec, MarketConfig], config=None,
                **env_opts: Any):
        """Open a PPO trainer over this engine (see :mod:`repro.train`).

        Sugar for ``PPOTrainer(self.env(spec, **env_opts), config)``. The
        compiled train step — rollout + GAE + minibatched updates as ONE
        executable — caches on the engine under the same shape-semantic
        ``static_key`` as rollouts, so trainers over different scenario
        mixtures of the same shape share the warm trace.
        """
        from repro.train.ppo import PPOConfig, PPOTrainer

        env = self.env(spec, **env_opts)
        return PPOTrainer(env, config or PPOConfig())


class Session:
    """A live simulation: device-resident books + an absolute step cursor.

    Obtained from :meth:`Engine.open`; usable as a context manager. All
    advancement APIs (:meth:`run`, :meth:`stream`, :meth:`step`) move the
    same cursor, so they interleave freely with bitwise-reproducible
    results — any chunking of S steps equals one ``run(S)`` call.
    """

    def __init__(self, engine: Engine, spec: EnsembleSpec,
                 runner: ChunkRunner, metrics=None):
        self._engine = engine
        self.spec = spec
        self._runner = runner
        self._step_runner: Optional[ChunkRunner] = None
        self._state = runner.init_state(spec)
        self._params = runner.params_to_device(spec.params)
        self._aux = runner.init_aux(spec)
        self._stats = runner.init_stats(spec)
        self._t = 0
        self._closed = False
        self._active_streams = 0
        self.metrics = metrics
        if metrics is not None:
            metrics.gauge("chunk", runner.chunk)
            metrics.gauge("num_markets", spec.num_markets)
            tile = getattr(runner, "tile", None)
            if tile is not None:  # Pallas engines: autotune tile pressure
                from repro.kernels import autotune as tune

                metrics.gauge("tile_mb", tile.mb)
                metrics.gauge("tile_agent_chunk", tile.agent_chunk)
                metrics.gauge("autotune_vmem_bytes", tune.estimate_vmem_bytes(
                    tile, spec.num_levels, spec.num_agents, runner.chunk))

    @property
    def cfg(self) -> EnsembleSpec:
        """The session's ensemble spec (kept under the historical name)."""
        return self.spec

    # ---- lifecycle ----
    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Release the device-resident state (the executables stay cached)."""
        self._state = None
        self._params = None
        self._aux = None
        self._stats = None
        self._closed = True

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("session is closed")

    # ---- introspection ----
    @property
    def state(self) -> MarketState:
        """Current device-resident state. Do not hold across :meth:`run`:
        the buffers are donated to the next chunk call."""
        self._check_open()
        return self._state

    @property
    def params(self) -> MarketParams:
        """Device-resident per-market scenario operands (never donated)."""
        self._check_open()
        return self._params

    @property
    def step_count(self) -> int:
        """Absolute number of steps advanced since open/restore."""
        return self._t

    @property
    def horizon(self) -> int:
        """The configured horizon ``spec.num_steps`` — the default run
        length, and the bound every scenario event is validated against."""
        return self.spec.num_steps

    @property
    def stats(self) -> Optional[MarketStats]:
        """Running per-market statistics (``stats_only`` sessions; else None).

        The accumulators are device-resident and carried through every chunk
        call — reading them here materializes a host copy. Use
        ``stats.mean_mid()`` / ``stats.var_mid()`` for the derived moments.
        """
        self._check_open()
        if self._stats is None:
            return None
        return self._stats.to_numpy()

    # ---- advancement ----
    def _resolve_steps(self, n_steps: Optional[int]) -> int:
        """Horizon semantics for the default-length form (see module doc).

        ``n_steps=None`` means "run the configured horizon" — which is only
        meaningful while the cursor is still inside it. Advancing a session
        that already reached ``num_steps`` would re-run a horizon's worth of
        steps in which no configured scenario event (every ``shock_step`` is
        validated ``< num_steps``) can ever fire — historically a silent
        no-shock run. Pass an explicit ``n_steps`` to stream past the
        horizon deliberately.
        """
        if n_steps is not None:
            n = int(n_steps)
            if n < 0:
                raise ValueError(f"n_steps must be >= 0, got {n}")
            return n
        remaining = self.spec.num_steps - self._t
        if remaining <= 0:
            raise ValueError(
                f"session cursor is at step {self._t} with "
                f"{max(remaining, 0)} steps remaining of the configured "
                f"horizon num_steps={self.spec.num_steps}: run()/stream() "
                "with no argument means 'run the remaining horizon', and "
                "every scenario event lies inside it — pass an explicit "
                "n_steps to advance past the horizon")
        return remaining

    def stream(self, n_steps: Optional[int] = None) -> Iterator[StepBatch]:
        """Advance ``n_steps`` steps, yielding one :class:`StepBatch` per
        compiled chunk as it completes.

        ``n_steps=None`` runs to the configured horizon (``spec.num_steps``)
        from the current cursor, and raises a clear error if the cursor is
        already past it; an explicit ``n_steps`` may advance arbitrarily far
        beyond the horizon (absolute-step RNG keeps post-horizon steps well
        defined — scenario events simply lie behind the cursor). The step
        count (and any horizon error) resolves at the *call*, not lazily at
        first iteration, so the iterator's length is fixed when created.
        """
        self._check_open()
        return self._stream(self._resolve_steps(n_steps))

    def _dispatch(self, runner: ChunkRunner, n: int, ext,
                  kind: str) -> StepBatch:
        """One runner dispatch with host-side metrics sampling around it.

        All sampling is strictly outside the jitted call: wall-clock reads
        and two integer trace-counter reads. Nothing here becomes an
        operand of (or inserts a sync into) the compiled executable, so a
        metrics-on session is bitwise-identical to a metrics-off one.
        """
        m = self.metrics
        if m is not None:
            traces0 = runner.trace_count
            t0 = time.perf_counter()
        self._state, self._aux, batch, self._stats = runner.run(
            self._state, self._params, self._aux, self._t, n, ext,
            self._stats)
        if m is not None:
            m.observe(f"{kind}_seconds", time.perf_counter() - t0)
            m.inc("steps_total", n)
            if kind == "chunk":
                m.inc("chunks_total")
            traced = runner.trace_count - traces0
            if traced:
                m.inc("traces", traced)
        self._t += n
        return batch

    def _stream(self, remaining: int) -> Iterator[StepBatch]:
        self._active_streams += 1
        try:
            while remaining > 0:
                n = min(self._runner.chunk, remaining)
                yield self._dispatch(self._runner, n, None, "chunk")
                remaining -= n
        finally:
            self._active_streams -= 1

    def run(self, n_steps: Optional[int] = None) -> StepBatch:
        """Advance ``n_steps`` and return the concatenated
        :class:`StepBatch` for exactly those steps. ``n_steps=None`` runs to
        the configured horizon (see :meth:`stream` for the semantics)."""
        self._check_open()
        batches = list(self._stream(self._resolve_steps(n_steps)))
        if not batches:
            M = self.spec.num_markets
            empty = self._runner.xp.zeros((M, 0), self._runner.xp.float32)
            return StepBatch(empty, empty, empty)
        return StepBatch.concatenate(batches, xp=self._runner.xp)

    def step(self, actions: Optional[Any] = None) -> StepBatch:
        """Gym-style hook: advance exactly one step, optionally injecting
        external orders through the reserved slot.

        ``actions`` is an :class:`ExternalOrders` (or a ``(side_buy, price,
        qty)`` triple / mapping with those keys), one order per market;
        ``None`` advances the market untouched — bitwise-identical to a
        one-step :meth:`run`. Uses a dedicated single-step executable (shared
        through the engine cache) so warm per-step latency has no chunk
        overhead. Returns the one-column :class:`StepBatch` observation.
        """
        self._check_open()
        if self._step_runner is None:
            self._step_runner = self._engine._runner(self.spec, 1)
        return self._dispatch(self._step_runner, 1, self._build_ext(actions),
                              "step")

    def _build_ext(self, actions: Any) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        if actions is None:
            return None
        from repro.env import actions as actions_mod

        orders = actions_mod.validate_actions(
            actions, self.spec.num_markets, self.spec.num_levels)
        return actions_mod.lower_actions(
            orders, self.spec.num_markets, self.spec.num_levels, np)

    # ---- slot mutation (the serving gateway's attach/detach hook) ----
    def swap_markets(self, slots, sub: Union[EnsembleSpec, MarketConfig],
                     *, reset_books: bool = True) -> None:
        """Chunk-boundary slot mutation: replace markets ``slots`` with the
        rows of ``sub`` (an ``len(slots)``-market spec/config), in place.

        This is the serving gateway's attach/detach primitive: a client's
        market is spliced into a running ensemble as a pure *value* update
        — new per-market params rows plus (``reset_books``) that market's
        fresh opening book — so the session keeps its static shape, its
        warm executable (zero retraces), and bitwise-identical trajectories
        for every **other** market: the step loop is row-independent and
        the RNG keys on ``(seed, global market id, absolute step)``, so
        rows outside ``slots`` never see the splice. Detaching is the same
        call with :meth:`EnsembleSpec.parked` rows.

        ``sub`` must agree with the session spec on every static field
        (``num_agents``/``num_levels``/``seed``/``num_steps``); the splice
        happens on host mirrors and re-places state/params through the
        runner, so it works identically on single-device and sharded
        sessions. Like :meth:`restore`, it is rejected during an active
        ``stream()`` — call it between chunks (the engine's only coherent
        preemption points).
        """
        self._check_open()
        if self._active_streams:
            raise RuntimeError(
                "swap_markets() during an active stream(): slot mutations "
                "apply at chunk boundaries — exhaust or close() the "
                "iterator first")
        sub = EnsembleSpec.coerce(sub)
        t0 = time.perf_counter()
        new_spec = self.spec.replace_markets(slots, sub)  # validates slots
        idx = np.asarray(slots, dtype=np.int64).reshape(-1)
        new_state = self._state
        if reset_books:
            host = [np.array(np.asarray(x), np.float32) for x in self._state]
            fresh = initial_state(sub, np)
            for leaf, src in zip(host, fresh):
                leaf[idx] = np.asarray(src, np.float32)
            new_state = self._runner.to_device(MarketState(*host))
        new_stats = self._stats
        if self._stats is not None:
            shost = [np.array(np.asarray(x), np.float32)
                     for x in self._stats]
            zero = init_stats(idx.size, np)
            for leaf, src in zip(shost, zero):
                leaf[idx] = np.asarray(src, np.float32)
            new_stats = self._runner.stats_to_device(MarketStats(*shost))
        # Commit only after every placement succeeded (restore()-style
        # all-or-nothing: a failed splice leaves the session untouched).
        self._params = self._runner.params_to_device(new_spec.params)
        self._state, self._stats = new_state, new_stats
        self.spec = new_spec
        if self.metrics is not None:
            self.metrics.observe("swap_seconds", time.perf_counter() - t0)
            self.metrics.inc("swaps_total", int(idx.size))

    # ---- results ----
    def to_result(self, batch: StepBatch) -> SimResult:
        """Assemble a terminal :class:`SimResult` from the final books plus a
        streamed batch — the one-shot ``simulate()`` compatibility shape."""
        self._check_open()
        if self._runner.stats_only:
            # A SimResult has nowhere to carry the accumulators — returning
            # zero-width paths would silently lose every output.
            raise ValueError(
                "stats_only sessions have no path outputs: read "
                "Session.stats instead of the one-shot SimResult shape")
        s = self._state
        return SimResult(bid=s.bid, ask=s.ask, last_price=s.last_price,
                         prev_mid=s.prev_mid, price_path=batch.price,
                         volume_path=batch.volume)

    def run_to_result(self, n_steps: Optional[int] = None) -> SimResult:
        return self.to_result(self.run(n_steps))

    # ---- snapshot / restore ----
    def snapshot(self) -> Dict[str, Any]:
        """Exact host-side capture: books, step cursor, stateful RNG, and
        the per-market parameter operands (a snapshot is self-contained —
        it restores the scenario mixture it was taken under).

        Mid-``stream()`` snapshots are **chunk-boundary-aligned**: the
        session cursor only ever advances one whole compiled chunk at a
        time (a partial tail is itself dispatched as one gated chunk), so a
        snapshot taken between yielded batches captures the state exactly
        after the last yielded chunk — ``snap["t"]`` equals the steps
        consumed so far, never a mid-chunk step. There is no misaligned
        call to guard against; :meth:`restore` during an active stream is
        rejected instead (the in-flight iterator would keep the old
        cursor).
        """
        self._check_open()
        t0 = time.perf_counter()
        snap: Dict[str, Any] = {
            field: np.asarray(value)
            for field, value in zip(MarketState._fields, self._state)
        }
        snap["t"] = self._t
        snap["rng"] = self._runner.aux_state(self._aux)
        snap["seed"] = self.spec.seed
        snap["num_agents"] = self.spec.num_agents
        snap["num_steps"] = self.spec.num_steps
        # Run-length encoded labels: O(blocks), not O(M), in the JSON meta.
        snap["scenarios"] = [[name, len(list(group))] for name, group
                             in itertools.groupby(self.spec.scenarios)]
        snap["params"] = {
            field: np.asarray(value)
            for field, value in zip(MarketParams._fields, self._params)
        }
        snap["init"] = {
            "quote_qty": np.asarray(self.spec.initial_quote_qty),
            "spread": np.asarray(self.spec.initial_spread),
        }
        if self._stats is not None:
            snap["stats"] = {
                field: np.asarray(value)
                for field, value in zip(MarketStats._fields, self._stats)
            }
        if self.metrics is not None:
            self.metrics.observe("snapshot_seconds", time.perf_counter() - t0)
            self.metrics.inc("snapshots_total")
        return snap

    def restore(self, snap: Dict[str, Any]) -> None:
        """Restore from :meth:`snapshot` — resumes the exact stream,
        including the snapshot's per-market parameters and horizon, so
        ``self.spec`` keeps describing the *live* mixture after a
        cross-spec restore (pre-params snapshots keep the session's
        current operands). Everything that can fail — placement, spec
        validation — happens before any session field is touched, so a
        failed restore leaves the session exactly as it was.

        Snapshots are device-layout agnostic: a snapshot taken on a
        single-device session restores into a sharded one (and vice versa)
        bitwise, because the runner re-places state/params/stats on restore.
        """
        self._check_open()
        if self._active_streams:
            raise RuntimeError(
                "restore() during an active stream(): the in-flight "
                "iterator would keep advancing from the pre-restore cursor. "
                "Exhaust or close() the iterator first (snapshot() stays "
                "safe mid-stream — it is chunk-boundary-aligned).")
        from repro.checkpoint.manager import CheckpointShapeError

        t_start = time.perf_counter()
        # seed and num_agents are baked into the compiled trace (they are
        # in the static cache key) yet appear in no restored array's shape
        # (params are [M, 1]; books are [M, L]), so a mismatch would
        # silently resume on a different random stream — reject loudly.
        # num_agents gets the typed shape error (it is a config-shape
        # field); a CheckpointShapeError is a ValueError, so older callers
        # catching ValueError keep working.
        for field, have, cls in (
                ("seed", self.spec.seed, ValueError),
                ("num_agents", self.spec.num_agents, CheckpointShapeError)):
            got = snap.get(field)
            if got is not None and int(got) != have:
                raise cls(
                    f"snapshot was taken under {field}={int(got)} but this "
                    f"session's executable is compiled for {field}={have}; "
                    f"open the session on a spec with the snapshot's "
                    f"{field} to resume its stream")
        # Shape-validate every array leaf against the live session *before*
        # touching any field — the historical failure mode here was an
        # opaque broadcast/unflatten error deep inside placement.
        M, L = self.spec.num_markets, self.spec.num_levels
        for name, want, blame in (
                ("bid", (M, L), "num_levels"), ("ask", (M, L), "num_levels"),
                ("last_price", (M, 1), "num_markets"),
                ("prev_mid", (M, 1), "num_markets")):
            arr = np.asarray(snap[name])
            if tuple(arr.shape) != want:
                if arr.ndim < 1 or arr.shape[0] != M:
                    blame = "num_markets"
                raise CheckpointShapeError(
                    f"snapshot field {name!r} has shape {tuple(arr.shape)} "
                    f"but this session expects {want} — mismatched {blame} "
                    f"(session has num_markets={M}, num_levels={L}); open "
                    f"the session on a spec matching the snapshot")
        if snap.get("params") is not None:
            # Older snapshots predate some fields (filled inert below) —
            # only shape-check the leaves the payload actually carries.
            for pname in MarketParams._fields:
                if pname not in snap["params"]:
                    continue
                arr = np.asarray(snap["params"][pname])
                if tuple(arr.shape) != (M, 1):
                    raise CheckpointShapeError(
                        f"snapshot params leaf {pname!r} has shape "
                        f"{tuple(arr.shape)}, expected ({M}, 1) — "
                        f"mismatched num_markets (session has "
                        f"num_markets={M})")
        new_state = self._runner.to_device(
            MarketState(*(snap[f] for f in MarketState._fields)))
        new_t = int(snap["t"])
        new_spec, new_params = self.spec, self._params
        params = snap.get("params")
        if params is not None:
            host = params_mod.params_from_dict(params, M, L)
            labels = snap.get("scenarios")
            if labels is not None:  # run-length encoded [name, count] pairs
                labels = tuple(itertools.chain.from_iterable(
                    (name,) * int(count) for name, count in labels))
            init = snap.get("init")
            new_spec = dataclasses.replace(
                self.spec, params=host,
                num_steps=int(snap.get("num_steps", self.spec.num_steps)),
                scenarios=labels if labels is not None
                else ("<restored>",) * self.spec.num_markets,
                **({"initial_quote_qty":
                        np.asarray(init["quote_qty"], np.float32),
                    "initial_spread": np.asarray(init["spread"], np.int32)}
                   if init is not None else {}))
            new_params = self._runner.params_to_device(host)
        rng = snap.get("rng")
        new_aux = (self._runner.restore_aux(rng) if rng is not None
                   else self._runner.init_aux(new_spec)
                   if self._aux is not None else None)
        new_stats = self._stats
        if self._runner.stats_only:
            stats = snap.get("stats")
            new_stats = (self._runner.stats_to_device(
                MarketStats(*(stats[f] for f in MarketStats._fields)))
                if stats is not None else self._runner.init_stats(new_spec))
        self._state, self._t = new_state, new_t
        self.spec, self._params = new_spec, new_params
        self._aux, self._stats = new_aux, new_stats
        if self.metrics is not None:
            self.metrics.observe("restore_seconds",
                                 time.perf_counter() - t_start)
            self.metrics.inc("restores_total")

    def save_checkpoint(self, manager, step: Optional[int] = None,
                        *, wait: bool = True) -> int:
        """Persist the session through a ``CheckpointManager``; returns the
        checkpoint step (defaults to the session's step cursor).

        ``wait=False`` returns as soon as the snapshot is handed to the
        manager's background writer (device→host mirror only — the serving
        gateway's non-blocking checkpoint path); the caller is responsible
        for a later ``manager.wait()`` before relying on durability.
        """
        from repro.checkpoint import manager as ckpt

        step = self._t if step is None else int(step)
        manager.save(step, ckpt.session_tree(self.snapshot()))
        if wait:
            manager.wait()
        return step

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> int:
        """Restore from a ``CheckpointManager``; returns the restored step."""
        from repro.checkpoint import manager as ckpt

        tree = manager.restore(step)
        if tree is None:
            raise FileNotFoundError(
                f"no checkpoint found in {manager.dir}")
        self.restore(ckpt.snapshot_from_tree(tree))
        return self._t
