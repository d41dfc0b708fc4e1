"""Per-market scenario parameters as device operands — the ensemble front door.

The seed API baked every scenario knob (shock step, flow intensities, agent
mixture) into the compiled trace as Python scalars, so a 72-config parity
sweep cost 72 compiles. This module makes the scenario axis *data*:

  * :class:`MarketParams` — a pytree of per-market ``[M, 1]`` arrays, one
    leaf per scenario-varying :class:`~repro.core.config.MarketConfig`
    field. Every backend (NumPy host loop, both JAX regimes, both Pallas
    kernels) takes it as an explicit runtime operand, so one warm trace
    serves *any* parameter values — and any per-market mixture of them.
  * :class:`EnsembleSpec` — the builder API. ``EnsembleSpec.homogeneous(cfg)``
    broadcasts one config over its markets (``Engine.open(cfg)`` wraps this
    and stays bitwise-identical to the scalar-config path);
    ``EnsembleSpec.from_scenarios([...])`` concatenates scenario blocks into
    one heterogeneous ensemble; ``EnsembleSpec.product(base, sweep=...)``
    expands a cartesian parameter sweep into one launch.

Because markets are row-independent and the RNG is a pure function of
(seed, global market id, step, channel), market ``m`` of a heterogeneous
ensemble is bitwise-identical to market ``m`` of the homogeneous ensemble
built from its scenario alone — the property the mixed-preset parity tests
in ``tests/test_ensemble.py`` assert on every backend.

Static vs dynamic split: array shapes (``M``, ``A``, ``L``) and the RNG
``seed`` fix the trace and form :meth:`EnsembleSpec.static_key`, the
engine's executable cache key; *everything else* rides in
:class:`MarketParams`, so parameter changes never retrace. The horizon
``num_steps`` is also Python-static (blocks of one ensemble must agree on
it, and scenario events are validated against it) but no trace depends on
it — specs differing only in horizon share one warm executable.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Iterable, NamedTuple, Sequence, Tuple, Union

import numpy as np

from repro.core.config import (
    MarketConfig,
    assign_agent_types,
    check_num_levels,
    scenario_config,
    seed_books,
)


class MarketParams(NamedTuple):
    """Scenario-varying parameters, one ``[M, 1]`` column per market.

    Float leaves are float32, count/step leaves int32 — the dtypes the
    kernels consume directly (per-market rows are fetched into each grid
    tile alongside the global market-id operand). ``fundamental`` is the
    *resolved* fundamentalist target (the config's negative-means-midpoint
    convention is applied at build time, since ``L`` is static).
    """

    shock_step: Any           # int32[M, 1] flash-crash step (< 0 → disabled)
    shock_intensity: Any      # f32[M, 1] P(agent panic-sells at the shock)
    shock_cancel: Any         # f32[M, 1] fraction of resting bids withdrawn
    p_marketable: Any         # f32[M, 1] P(order is marketable)
    q_max: Any                # f32[M, 1] max order quantity (integer-valued)
    noise_delta: Any          # f32[M, 1] noise-trader price offset half-width
    maker_half_spread: Any    # f32[M, 1] maker quote half-spread
    fundamental: Any          # f32[M, 1] resolved fundamentalist target
    fundamentalist_kappa: Any # f32[M, 1] mean-reversion strength
    num_makers: Any           # int32[M, 1] leading agents assigned MAKER
    num_momentum: Any         # int32[M, 1] next block assigned MOMENTUM
    num_fundamentalists: Any  # int32[M, 1] next block assigned FUNDAMENTALIST
    num_whales: Any           # int32[M, 1] next block assigned WHALE
    num_hft: Any              # int32[M, 1] next block assigned HFT
    num_informed: Any         # int32[M, 1] next block assigned INFORMED
    num_arbitrageurs: Any     # int32[M, 1] next block assigned ARBITRAGEUR
    whale_size: Any           # f32[M, 1] lots per whale sweep (integer-valued)
    whale_period: Any         # int32[M, 1] steps between whale sweeps (>= 1)
    hft_threshold: Any        # f32[M, 1] |book imbalance| HFT trigger
    informed_horizon: Any     # int32[M, 1] steps of early shock knowledge
    arb_kappa: Any            # f32[M, 1] arbitrageur gap-chasing strength
    coupling_peer: Any        # int32[M, 1] peer market feeding arbs (<0: self)

    def to_numpy(self) -> "MarketParams":
        return MarketParams(*(np.asarray(x) for x in self))

    @property
    def num_markets(self) -> int:
        return int(np.shape(self.shock_step)[0])

    @staticmethod
    def field_dtype(field: str):
        return np.int32 if field in _INT_FIELDS else np.float32

    def asarray(self, xp) -> "MarketParams":
        """Dtype-preserving placement into array module ``xp`` — the
        session's placement hook for every backend's params."""
        return MarketParams(*(
            xp.asarray(np.asarray(leaf), dtype=MarketParams.field_dtype(f))
            for f, leaf in zip(MarketParams._fields, self)))

    @classmethod
    def zeros(cls, num_markets: int, xp) -> "MarketParams":
        """Valid all-zero parameter columns (timing/padding operands)."""
        return cls(*(xp.zeros((num_markets, 1), cls.field_dtype(f))
                     for f in cls._fields))


#: MarketParams leaves carried as int32 (counts and step/index coordinates).
_INT_FIELDS = ("shock_step", "num_makers", "num_momentum",
               "num_fundamentalists", "num_whales", "num_hft",
               "num_informed", "num_arbitrageurs", "whale_period",
               "informed_horizon", "coupling_peer")

#: Inert per-field values: the value each leaf takes when its archetype is
#: absent (counts 0, self-coupling) — the back-compat fill for snapshots
#: and journals recorded before a field existed, and the parked-slot rows.
#: ``fundamental`` is shape-dependent (grid midpoint) and handled by
#: callers explicitly.
INERT_PARAM_VALUES: Dict[str, float] = {
    "shock_step": -1, "shock_intensity": 0.0, "shock_cancel": 0.0,
    "p_marketable": 0.0, "q_max": 1.0, "noise_delta": 0.0,
    "maker_half_spread": 0.0, "fundamentalist_kappa": 0.0,
    "num_makers": 0, "num_momentum": 0, "num_fundamentalists": 0,
    "num_whales": 0, "num_hft": 0, "num_informed": 0,
    "num_arbitrageurs": 0, "whale_size": 1.0, "whale_period": 1,
    "hft_threshold": 0.0, "informed_horizon": 0, "arb_kappa": 0.0,
    "coupling_peer": -1,
}


def params_from_dict(values: Dict[str, Any], num_markets: int,
                     num_levels: int) -> MarketParams:
    """Rebuild host params from a ``{field: array}`` mapping (snapshot /
    journal payloads), default-filling fields the payload predates.

    Older payloads are valid ensembles whose missing leaves were
    definitionally inert (the archetype/coupling did not exist when they
    were written), so the fill is value-invisible by construction.
    """
    M = int(num_markets)
    leaves = []
    for f in MarketParams._fields:
        if f in values:
            leaves.append(np.asarray(values[f],
                                     dtype=MarketParams.field_dtype(f)))
        else:
            fill = (float(num_levels // 2) if f == "fundamental"
                    else INERT_PARAM_VALUES[f])
            leaves.append(np.full((M, 1), fill, MarketParams.field_dtype(f)))
    return MarketParams(*leaves)


def replace_rows(params: MarketParams, slots, rows: MarketParams,
                 ) -> MarketParams:
    """Host-side row splice: ``params`` with markets ``slots`` replaced by
    the rows of ``rows`` (a ``len(slots)``-market params pytree).

    The serving gateway's slot mutation primitive: attaching/detaching a
    client's market into a running ensemble is a pure value update — the
    result has identical shapes/dtypes, so re-placing it on device reuses
    the warm executable (shape-semantic cache keys) and every *other* row
    is carried over bitwise-untouched.
    """
    idx = np.asarray(slots, dtype=np.int64).reshape(-1)
    M = params.num_markets
    if idx.size != rows.num_markets:
        raise ValueError(
            f"replace_rows got {idx.size} slots but {rows.num_markets} "
            "replacement rows")
    if idx.size != np.unique(idx).size:
        raise ValueError(f"slots must be unique, got {idx.tolist()}")
    if ((idx < 0) | (idx >= M)).any():
        raise ValueError(f"slots {idx.tolist()} out of range [0, {M})")
    out = []
    for f, leaf, src in zip(MarketParams._fields, params, rows):
        leaf = np.array(np.asarray(leaf), dtype=MarketParams.field_dtype(f))
        leaf[idx] = np.asarray(src, dtype=leaf.dtype)
        out.append(leaf)
    return MarketParams(*out)


def _config_values(cfg: MarketConfig) -> Dict[str, float]:
    """One config's scenario-varying values, keyed by MarketParams field."""
    return {
        "shock_step": cfg.shock_step,
        "shock_intensity": cfg.shock_intensity,
        "shock_cancel": cfg.shock_cancel,
        "p_marketable": cfg.p_marketable,
        "q_max": cfg.q_max,
        "noise_delta": cfg.noise_delta,
        "maker_half_spread": cfg.maker_half_spread,
        "fundamental": cfg.fundamental,
        "fundamentalist_kappa": cfg.fundamentalist_kappa,
        "num_makers": cfg.num_makers,
        "num_momentum": cfg.num_momentum,
        "num_fundamentalists": cfg.num_fundamentalists,
        "num_whales": cfg.num_whales,
        "num_hft": cfg.num_hft,
        "num_informed": cfg.num_informed,
        "num_arbitrageurs": cfg.num_arbitrageurs,
        "whale_size": cfg.whale_size,
        "whale_period": cfg.whale_period,
        "hft_threshold": cfg.hft_threshold,
        "informed_horizon": cfg.informed_horizon,
        "arb_kappa": cfg.arb_kappa,
        # Peer wiring is an ensemble-level concern (repro.scenario
        # .CouplingSpec); a plain config always self-couples.
        "coupling_peer": -1,
    }


def params_from_config(cfg: MarketConfig, num_markets: int = None,
                       xp=np) -> MarketParams:
    """Homogeneous per-market params: broadcast one config over M rows."""
    M = cfg.num_markets if num_markets is None else int(num_markets)
    vals = _config_values(cfg)
    return MarketParams(**{
        f: xp.full((M, 1), vals[f], dtype=MarketParams.field_dtype(f))
        for f in MarketParams._fields
    })


def scalar_params(cfg: MarketConfig, xp) -> MarketParams:
    """Broadcastable ``[1, 1]`` constant params of one scalar config, as
    the jitted reference oracle (:mod:`repro.kernels.ref`) passes them:
    built with ``xp``, they fold to constants inside its trace."""
    return params_from_config(cfg, num_markets=1, xp=xp)


def agent_types(params: MarketParams, num_agents: int, xp):
    """Per-market strategy-class lattice: int32 broadcastable to [M, A].

    The single shared assignment rule
    (:func:`repro.core.config.assign_agent_types`) driven by the per-market
    count operands, so each ensemble row carries its own population mix —
    and the scalar path can never drift from it.
    """
    return assign_agent_types(xp, num_agents, params.num_makers,
                              params.num_momentum,
                              params.num_fundamentalists,
                              params.num_whales, params.num_hft,
                              params.num_informed, params.num_arbitrageurs)


# ---------------------------------------------------------------------------
# EnsembleSpec: the builder front door
# ---------------------------------------------------------------------------

#: Fields every block of a heterogeneous ensemble must agree on: they are
#: Python-static (they fix array shapes / the RNG key / the horizon).
_STATIC_FIELDS = ("num_agents", "num_levels", "num_steps", "seed")


@dataclasses.dataclass(frozen=True, eq=False)
class EnsembleSpec:
    """A heterogeneous market ensemble: static shape + per-market params.

    The engine-facing twin of :class:`MarketConfig`. ``Engine.open`` accepts
    either; a config is coerced through :meth:`homogeneous`, which is
    bitwise-identical to the historical scalar-config path. Specs compare by
    identity (they hold arrays) — the executable cache keys on
    :meth:`static_key`, never on parameter values.
    """

    num_markets: int
    num_agents: int
    num_levels: int
    num_steps: int
    seed: int
    params: MarketParams               # host numpy [M, 1] leaves
    initial_quote_qty: np.ndarray      # f32[M] opening book depth
    initial_spread: np.ndarray         # int32[M] opening spread (ticks)
    scenarios: Tuple[str, ...] = ()    # per-market preset labels (metadata)

    # ---- constructors ----
    @classmethod
    def homogeneous(cls, cfg: MarketConfig) -> "EnsembleSpec":
        """Broadcast one config over its ``num_markets`` markets."""
        M = cfg.num_markets
        return cls(
            num_markets=M, num_agents=cfg.num_agents,
            num_levels=cfg.num_levels, num_steps=cfg.num_steps,
            seed=cfg.seed, params=params_from_config(cfg),
            initial_quote_qty=np.full(M, cfg.initial_quote_qty, np.float32),
            initial_spread=np.full(M, cfg.initial_spread, np.int32),
            scenarios=(cfg.scenario,) * M,
        )

    @classmethod
    def from_scenarios(cls, blocks: Sequence[Union[MarketConfig, str]],
                       **common: Any) -> "EnsembleSpec":
        """Concatenate scenario blocks into one heterogeneous ensemble.

        Each element is a :class:`MarketConfig` (contributing its
        ``num_markets`` rows) or a preset name (resolved through
        :func:`repro.core.config.scenario_config`). The ``common``
        overrides (e.g. ``num_markets=8, num_agents=64``) apply to *every*
        block — names and configs alike, the latter via
        ``dataclasses.replace`` — so one call site pins the shared shape.
        Blocks must agree on the static fields (A, L, S, seed); a mismatch
        is a loud error — per-market *seeds* are not supported because the
        stateful PCG64 reference RNG has a single stream.

        Market ``m`` of the result is bitwise-identical, on every backend,
        to market ``m`` of ``homogeneous(block)`` for the block covering
        row ``m`` (padded to the full ensemble width) — block boundaries are
        invisible to the per-market streams.
        """
        cfgs = [scenario_config(b, **common) if isinstance(b, str)
                else (dataclasses.replace(b, **common) if common else b)
                for b in blocks]
        if not cfgs:
            raise ValueError("from_scenarios needs at least one block")
        first = cfgs[0]
        for i, c in enumerate(cfgs[1:], start=1):
            for f in _STATIC_FIELDS:
                if getattr(c, f) != getattr(first, f):
                    raise ValueError(
                        f"ensemble blocks must agree on static field {f!r}: "
                        f"block 0 has {getattr(first, f)}, block {i} "
                        f"({c.scenario}) has {getattr(c, f)}")
        specs = [cls.homogeneous(c) for c in cfgs]
        return cls.concatenate(specs)

    @classmethod
    def product(cls, base: MarketConfig, sweep: Dict[str, Iterable[Any]],
                markets_per_config: int = None) -> "EnsembleSpec":
        """Cartesian parameter sweep as one ensemble.

        ``sweep`` maps :class:`MarketConfig` field names to value lists;
        every combination contributes ``markets_per_config`` (default
        ``base.num_markets``) rows built via ``dataclasses.replace``. The
        whole sweep then runs in one compile and one launch per chunk —
        the regime ``benchmarks/scenario_sweep.py`` measures against the
        per-config loop.
        """
        if not sweep:
            raise ValueError("product() needs a non-empty sweep")
        M = base.num_markets if markets_per_config is None \
            else int(markets_per_config)
        names = list(sweep)
        cfgs = [
            dataclasses.replace(base, num_markets=M,
                                **dict(zip(names, combo)))
            for combo in itertools.product(*(sweep[n] for n in names))
        ]
        return cls.from_scenarios(cfgs)

    @classmethod
    def concatenate(cls, specs: Sequence["EnsembleSpec"]) -> "EnsembleSpec":
        """Stack already-built specs along the market axis."""
        if not specs:
            raise ValueError("concatenate needs at least one spec")
        first = specs[0]
        for s in specs[1:]:
            for f in _STATIC_FIELDS:
                if getattr(s, f) != getattr(first, f):
                    raise ValueError(
                        f"ensemble blocks must agree on static field {f!r}")
        return cls(
            num_markets=sum(s.num_markets for s in specs),
            num_agents=first.num_agents, num_levels=first.num_levels,
            num_steps=first.num_steps, seed=first.seed,
            params=MarketParams(*(
                np.concatenate([np.asarray(getattr(s.params, f))
                                for s in specs], axis=0)
                for f in MarketParams._fields)),
            initial_quote_qty=np.concatenate(
                [s.initial_quote_qty for s in specs]),
            initial_spread=np.concatenate([s.initial_spread for s in specs]),
            scenarios=tuple(itertools.chain.from_iterable(
                s.scenarios for s in specs)),
        )

    @classmethod
    def coerce(cls, obj: Union["EnsembleSpec", MarketConfig]) -> "EnsembleSpec":
        """The front-door normalizer: configs become homogeneous specs."""
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, MarketConfig):
            return cls.homogeneous(obj)
        raise TypeError(
            f"expected MarketConfig or EnsembleSpec, got {type(obj).__name__}")

    def __post_init__(self):
        self.validate()

    # ---- derived API mirroring MarketConfig (duck-typed by the runners) ----
    @property
    def mid0(self) -> float:
        return float(self.num_levels // 2)

    def events(self) -> int:
        """Total agent events M*A*S (paper's throughput denominator)."""
        return self.num_markets * self.num_agents * self.num_steps

    def initial_books(self, xp) -> Tuple[Any, Any]:
        """(bid, ask) float32[M, L] per-market opening books.

        Delegates to the single shared seeding rule
        (:func:`repro.core.config.seed_books`) with this spec's per-market
        depth/spread — a homogeneous spec produces bitwise the books the
        scalar path does, by construction.
        """
        return seed_books(
            xp, self.num_levels,
            xp.asarray(np.asarray(self.initial_quote_qty, np.float32)),
            xp.asarray(np.asarray(self.initial_spread, np.int32)))

    def static_key(self) -> Tuple[Any, ...]:
        """Executable cache key: shape/structure-semantic only.

        Everything that fixes the *trace* — array shapes and the RNG seed
        baked into the counter hash — and nothing that is merely a value:
        two specs with equal keys share one compiled executable, whatever
        their scenario mixture.
        """
        return (self.num_markets, self.num_agents, self.num_levels, self.seed)

    # ---- builders for parameter updates (no retrace: same static key) ----
    def replace_markets(self, slots, sub: "EnsembleSpec") -> "EnsembleSpec":
        """New spec with markets ``slots`` replaced by the rows of ``sub``.

        The spec-level twin of :func:`replace_rows`, carrying the scenario
        labels and per-market opening-book fields along with the params —
        the serving gateway's attach/detach bookkeeping. ``sub`` must agree
        with this spec on every static field (shapes/seed/horizon), so the
        result keeps this spec's :meth:`static_key` and therefore its warm
        executable.
        """
        for f in _STATIC_FIELDS:
            if getattr(sub, f) != getattr(self, f):
                raise ValueError(
                    f"replace_markets rows must agree on static field {f!r}:"
                    f" this spec has {getattr(self, f)}, the replacement has"
                    f" {getattr(sub, f)}")
        idx = np.asarray(slots, dtype=np.int64).reshape(-1)
        scenarios = list(self.scenarios or ("?",) * self.num_markets)
        quote = np.array(self.initial_quote_qty, np.float32)
        spread = np.array(self.initial_spread, np.int32)
        params = replace_rows(self.params, idx, sub.params)  # validates idx
        quote[idx] = np.asarray(sub.initial_quote_qty, np.float32)
        spread[idx] = np.asarray(sub.initial_spread, np.int32)
        for k, slot in enumerate(idx):
            scenarios[slot] = (sub.scenarios[k] if k < len(sub.scenarios)
                               else "?")
        return dataclasses.replace(
            self, params=params, initial_quote_qty=quote,
            initial_spread=spread, scenarios=tuple(scenarios))

    def with_values(self, **fields: Any) -> "EnsembleSpec":
        """New spec with some :class:`MarketParams` leaves replaced.

        Values broadcast over the market axis (scalars or ``[M]``/``[M, 1]``
        arrays). Shapes stay fixed, so sessions opened on the result reuse
        the warm executable of this spec's engine; the per-market scenario
        labels gain a trailing ``*`` to mark them customized (metadata
        honesty in repr and snapshots). Note ``fundamental`` is
        the *resolved* target price — unlike ``MarketConfig
        .fundamental_price`` there is no negative-means-midpoint sentinel
        here (pass ``num_levels // 2`` for the grid midpoint); validation
        rejects negative values.
        """
        unknown = set(fields) - set(MarketParams._fields)
        if unknown:
            raise KeyError(f"unknown MarketParams fields: {sorted(unknown)}")
        leaves = {}
        for f in MarketParams._fields:
            if f in fields:
                v = np.asarray(fields[f], MarketParams.field_dtype(f))
                if v.ndim:
                    v = v.reshape(-1, 1)
                leaves[f] = np.ascontiguousarray(
                    np.broadcast_to(v, (self.num_markets, 1)))
            else:
                leaves[f] = np.asarray(getattr(self.params, f))
        # A trailing '*' marks customized presets, so repr and snapshot/
        # checkpoint metadata never claim an unmodified preset mixture for
        # params the preset did not produce.
        labels = tuple(n if n.endswith("*") else n + "*"
                       for n in self.scenarios)
        return dataclasses.replace(self, params=MarketParams(**leaves),
                                   scenarios=labels)

    # ---- validation (the scalar path's __post_init__, per market) ----
    def validate(self) -> None:
        M, A, L = self.num_markets, self.num_agents, self.num_levels
        check_num_levels(L)
        p = self.params.to_numpy()
        for f in MarketParams._fields:
            arr = np.asarray(getattr(p, f))
            if arr.shape != (M, 1):
                raise ValueError(
                    f"params.{f} must have shape ({M}, 1), got {arr.shape}")
            # Eager finiteness gate: NaN/inf must never reach a kernel —
            # NaN in particular sails through every range check below
            # (all comparisons are False) and would silently poison the
            # whole trajectory. Name the offending field and markets.
            bad = ~np.isfinite(arr.astype(np.float64))
            if bad.any():
                rows = np.where(bad[:, 0])[0]
                raise ValueError(
                    f"params.{f} contains non-finite values "
                    f"(nan/inf) in markets {rows[:8].tolist()}; "
                    "parameter operands must be finite")
        for name in ("initial_quote_qty", "initial_spread"):
            arr = np.asarray(getattr(self, name))
            if arr.shape != (M,):
                raise ValueError(
                    f"{name} must have shape ({M},), got {arr.shape}")
        spread = np.asarray(self.initial_spread)
        half = spread // 2 + spread % 2
        off_grid = (spread < 0) | (half > L // 2 - 1)
        if off_grid.any():
            bad = np.where(off_grid)[0]
            raise ValueError(
                f"initial_spread must place both opening quotes on the "
                f"grid (0 <= spread, ceil(spread/2) <= {L // 2 - 1} for "
                f"num_levels={L}); markets {bad[:8].tolist()} violate it")
        if (np.asarray(self.initial_quote_qty) < 0).any():
            raise ValueError("initial_quote_qty must be >= 0")
        for name in ("shock_intensity", "shock_cancel", "p_marketable"):
            arr = getattr(p, name)
            if ((arr < 0.0) | (arr > 1.0)).any():
                bad = np.where((arr < 0.0) | (arr > 1.0))[0]
                raise ValueError(
                    f"{name} must be in [0, 1]; markets {bad[:8].tolist()} "
                    "violate it")
        if (p.q_max < 1.0).any():
            bad = np.where((p.q_max < 1.0)[:, 0])[0]
            raise ValueError(
                f"q_max must be >= 1 (qty = 1 + floor(u * q_max) would go "
                f"non-positive); markets {bad[:8].tolist()} violate it")
        if (p.fundamental < 0.0).any():
            bad = np.where((p.fundamental < 0.0)[:, 0])[0]
            raise ValueError(
                f"fundamental must be a resolved price >= 0 (the config's "
                f"negative-means-midpoint sentinel is applied at build time; "
                f"use num_levels // 2 = {L // 2} for the grid midpoint); "
                f"markets {bad[:8].tolist()} violate it")
        assigned = (p.num_makers + p.num_momentum + p.num_fundamentalists
                    + p.num_whales + p.num_hft + p.num_informed
                    + p.num_arbitrageurs)
        if (assigned > A).any():
            bad = np.where((assigned > A)[:, 0])[0]
            raise ValueError(
                f"agent mixture assigns more than num_agents={A} agents in "
                f"markets {bad[:8].tolist()}")
        if ((p.num_makers < 0) | (p.num_momentum < 0)
                | (p.num_fundamentalists < 0) | (p.num_whales < 0)
                | (p.num_hft < 0) | (p.num_informed < 0)
                | (p.num_arbitrageurs < 0)).any():
            raise ValueError("archetype counts must be >= 0")
        if ((p.whale_size < 1.0)
                | (p.whale_size != np.floor(p.whale_size))).any():
            bad = np.where(((p.whale_size < 1.0)
                            | (p.whale_size != np.floor(p.whale_size)))[:, 0])[0]
            raise ValueError(
                f"whale_size must be an integer-valued lot count >= 1 "
                f"(exact in f32); markets {bad[:8].tolist()} violate it")
        if (p.whale_period < 1).any():
            bad = np.where((p.whale_period < 1)[:, 0])[0]
            raise ValueError(
                f"whale_period must be >= 1; markets {bad[:8].tolist()} "
                "violate it")
        if ((p.hft_threshold < 0.0) | (p.hft_threshold > 1.0)).any():
            bad = np.where(((p.hft_threshold < 0.0)
                            | (p.hft_threshold > 1.0))[:, 0])[0]
            raise ValueError(
                f"hft_threshold must be in [0, 1] (book imbalance is "
                f"normalized); markets {bad[:8].tolist()} violate it")
        if (p.informed_horizon < 0).any():
            raise ValueError("informed_horizon must be >= 0")
        if (p.arb_kappa < 0.0).any():
            raise ValueError("arb_kappa must be >= 0")
        # Coupling peers index the *global* market axis; -1 self-couples.
        if ((p.coupling_peer < -1) | (p.coupling_peer >= M)).any():
            bad = np.where(((p.coupling_peer < -1)
                            | (p.coupling_peer >= M))[:, 0])[0]
            raise ValueError(
                f"coupling_peer must be -1 (self) or a market index in "
                f"[0, {M}); markets {bad[:8].tolist()} violate it")
        # Horizon semantics (see Session.stream): every scenario event must
        # lie inside [0, num_steps) — a shock placed at or past the horizon
        # would silently never fire in a default-length run.
        beyond = p.shock_step >= self.num_steps
        if beyond.any():
            bad = np.where(beyond[:, 0])[0]
            raise ValueError(
                f"shock_step must be < num_steps={self.num_steps} (the "
                f"session horizon); markets {bad[:8].tolist()} place the "
                "shock at or past it and a default-length run would "
                "silently never fire it")

    @classmethod
    def parked(cls, like: "EnsembleSpec", num_markets: int = None,
               ) -> "EnsembleSpec":
        """A minimal-activity ensemble agreeing with ``like`` on every
        static field — the serving gateway's *parked slot* rows.

        A detached slot keeps simulating (the step loop is branch-free and
        shape-static; removing a row would retrace), so parked rows are
        built to make that dead work as inert as possible: no scenario
        events (``shock_step=-1``), all agents quoting passively at the mid
        with zero offset and unit size (``p_marketable=0``,
        ``noise_delta=0``, ``q_max=1``, no maker/momentum/fundamentalist
        blocks), and empty opening books. The slot still costs its share of
        the ensemble's fixed per-chunk work — what it never costs is an
        extra trace, host sync, or any effect on other rows.
        """
        M = like.num_markets if num_markets is None else int(num_markets)
        values = dict(INERT_PARAM_VALUES,
                      fundamental=float(like.num_levels // 2))
        return cls(
            num_markets=M, num_agents=like.num_agents,
            num_levels=like.num_levels, num_steps=like.num_steps,
            seed=like.seed,
            params=MarketParams(**{
                f: np.full((M, 1), values[f], MarketParams.field_dtype(f))
                for f in MarketParams._fields}),
            initial_quote_qty=np.zeros(M, np.float32),
            initial_spread=np.zeros(M, np.int32),
            scenarios=("parked",) * M,
        )

    def __repr__(self) -> str:  # arrays make the dataclass repr unreadable
        kinds = [f"{name}×{len(list(group))}"
                 for name, group in itertools.groupby(self.scenarios)]
        return (f"EnsembleSpec(M={self.num_markets}, A={self.num_agents}, "
                f"L={self.num_levels}, S={self.num_steps}, seed={self.seed}, "
                f"scenarios=[{', '.join(kinds) or '?'}])")
