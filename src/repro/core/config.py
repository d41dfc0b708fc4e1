"""Configuration for the KineticSim market engine.

Besides the raw simulation shape (M, A, L, S) this module owns the two axes
of the scenario engine:

  * the **archetype mixture** — static per-config fractions of the agent
    population assigned to each strategy class (paper §III-C plus the
    fundamentalist/mean-reversion class), resolved to a deterministic
    ``int32[A]`` type vector by agent index so every backend sees the exact
    same population; and
  * the **scenario** — named presets (baseline, flash-crash, high/low
    volatility regimes, wide/thin opening books) expressed purely as config
    fields, so scenario dispatch compiles to branch-free ``where`` selects
    inside the fused step and never breaks the persistent kernel.

A ``MarketConfig`` is the *scalar* surface: one value per field, uniform
over the ensemble. The engine-facing generalization is
:class:`repro.core.params.EnsembleSpec`, which stacks per-market values of
every scenario-varying field into device operands — ``Engine.open(cfg)``
coerces a config through ``EnsembleSpec.homogeneous`` bitwise-identically.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

# Agent strategy classes (paper §III-C + fundamentalist extension + the
# coupled-scenario classes: whale / HFT / informed / cross-market arb)
NOISE = 0
MOMENTUM = 1
MAKER = 2
FUNDAMENTALIST = 3
WHALE = 4
HFT = 5
INFORMED = 6
ARBITRAGEUR = 7

# RNG channels
CH_SIDE = 0
CH_PRICE = 1
CH_MKT = 2
CH_QTY = 3
CH_SHOCK = 4

#: The widest price grid the chunk kernels are compiled for (L=4096 in
#: ``tests/test_tpu_compile.py``); wider grids are refused.
MAX_LEVELS = 4096


def check_num_levels(L: int) -> None:
    """Refuse a grid the kernels cannot run: ``L`` must be a power of two
    in ``[4, MAX_LEVELS]``."""
    if L < 4 or (L & (L - 1)) != 0:
        raise ValueError(f"num_levels must be a power of two >= 4, got {L}")
    if L > MAX_LEVELS:
        raise ValueError(f"num_levels={L} is above {MAX_LEVELS}, the widest "
                         "grid the chunk kernels are compiled for")


@dataclasses.dataclass(frozen=True)
class MarketConfig:
    """Parameters of the uniform-price call-auction ensemble (paper §III).

    Defaults follow the paper's benchmarked configuration: L=128 price ticks,
    S=500 steps, population mix 15% makers / 15% momentum / 70% noise.
    """

    num_markets: int = 64          # M — independent markets
    num_agents: int = 256          # A — agents per market
    num_levels: int = 128          # L — price grid ticks (power of two)
    num_steps: int = 500           # S — simulation steps
    seed: int = 0

    # Agent behaviour (paper §III-C)
    q_max: int = 8                 # max order quantity
    p_marketable: float = 0.1      # P_mkt — probability of a marketable order
    noise_delta: float = 8.0       # Δ_noise — uniform price offset half-width
    maker_half_spread: float = 2.0 # Δ_maker_half_spread

    # Population mix (paper §IV-J: α_maker fixed at 0.15, α_mom swept).
    # Static weights: agents [0, A·α_maker) are makers, the next A·α_mom are
    # momentum, the next A·α_fund fundamentalists, the remainder noise.
    alpha_maker: float = 0.15
    alpha_momentum: float = 0.15
    alpha_fundamentalist: float = 0.0

    # Fundamentalist behaviour: mean reversion toward ``fundamental_price``
    # (defaults to the grid midpoint when negative) at strength kappa.
    fundamental_price: float = -1.0
    fundamentalist_kappa: float = 0.5

    # Coupled-scenario archetypes (repro.scenario): whales sweep the book
    # with large marketable orders every ``whale_period`` steps; HFTs react
    # to resting-book imbalance beyond ``hft_threshold``; informed traders
    # see the fundamental shock ``informed_horizon`` steps early and
    # front-run it; arbitrageurs trade the gap to a coupled peer market's
    # previous-chunk mid (peer wiring lives on EnsembleSpec via
    # ``repro.scenario.CouplingSpec`` — a plain config always self-couples).
    alpha_whale: float = 0.0
    alpha_hft: float = 0.0
    alpha_informed: float = 0.0
    alpha_arbitrageur: float = 0.0
    whale_size: float = 32.0       # lots per whale sweep (integer-valued)
    whale_period: int = 16         # steps between sweeps (>= 1)
    hft_threshold: float = 0.2     # |imbalance| trigger, in [0, 1]
    informed_horizon: int = 8      # steps of early shock knowledge (>= 0)
    arb_kappa: float = 0.5         # gap-chasing strength (>= 0)

    # Scenario (presets below; "baseline" leaves every knob at its default).
    scenario: str = "baseline"
    shock_step: int = -1           # flash-crash step (< 0 → disabled)
    shock_intensity: float = 0.0   # P(agent panic-sells marketably at shock)
    shock_cancel: float = 0.0      # fraction of resting bids withdrawn at shock

    # Opening book seeding (paper Alg.1 line 3); quotes straddle L/2.
    initial_quote_qty: float = 10.0
    initial_spread: int = 2        # opening bid at L/2 - spread/2 ... ask at +

    def __post_init__(self):
        check_num_levels(self.num_levels)
        mix_total = (self.alpha_maker + self.alpha_momentum
                     + self.alpha_fundamentalist + self.alpha_whale
                     + self.alpha_hft + self.alpha_informed
                     + self.alpha_arbitrageur)
        if not (0.0 <= mix_total <= 1.0):
            raise ValueError("agent fractions must sum to <= 1")
        assigned = (self.num_makers + self.num_momentum
                    + self.num_fundamentalists + self.num_whales
                    + self.num_hft + self.num_informed
                    + self.num_arbitrageurs)
        if assigned > self.num_agents:
            raise ValueError(
                f"per-class rounding assigns {assigned} agents > "
                f"num_agents={self.num_agents}; adjust alphas or num_agents")
        if not (0.0 <= self.shock_intensity <= 1.0):
            raise ValueError("shock_intensity must be in [0, 1]")
        if not (0.0 <= self.shock_cancel <= 1.0):
            raise ValueError("shock_cancel must be in [0, 1]")
        if self.shock_step >= self.num_steps:
            raise ValueError("shock_step must be < num_steps")
        if self.whale_size < 1 or self.whale_size != int(self.whale_size):
            raise ValueError("whale_size must be an integer-valued lot "
                             "count >= 1 (exact in f32)")
        if self.whale_period < 1:
            raise ValueError("whale_period must be >= 1")
        if not (0.0 <= self.hft_threshold <= 1.0):
            raise ValueError("hft_threshold must be in [0, 1] (book "
                             "imbalance is normalized)")
        if self.informed_horizon < 0:
            raise ValueError("informed_horizon must be >= 0")
        if self.arb_kappa < 0:
            raise ValueError("arb_kappa must be >= 0")

    # ---- derived population counts (deterministic by agent index) ----
    @property
    def num_makers(self) -> int:
        return int(round(self.num_agents * self.alpha_maker))

    @property
    def num_momentum(self) -> int:
        return int(round(self.num_agents * self.alpha_momentum))

    @property
    def num_fundamentalists(self) -> int:
        return int(round(self.num_agents * self.alpha_fundamentalist))

    @property
    def num_whales(self) -> int:
        return int(round(self.num_agents * self.alpha_whale))

    @property
    def num_hft(self) -> int:
        return int(round(self.num_agents * self.alpha_hft))

    @property
    def num_informed(self) -> int:
        return int(round(self.num_agents * self.alpha_informed))

    @property
    def num_arbitrageurs(self) -> int:
        return int(round(self.num_agents * self.alpha_arbitrageur))

    @property
    def mid0(self) -> float:
        return float(self.num_levels // 2)

    @property
    def fundamental(self) -> float:
        """Resolved fundamental price (grid midpoint unless overridden)."""
        return self.mid0 if self.fundamental_price < 0 else self.fundamental_price

    def mixture(self) -> Dict[int, float]:
        """Static archetype weights {type_id: fraction}, summing to 1."""
        noise = 1.0 - (self.alpha_maker + self.alpha_momentum
                       + self.alpha_fundamentalist + self.alpha_whale
                       + self.alpha_hft + self.alpha_informed
                       + self.alpha_arbitrageur)
        return {
            NOISE: noise,
            MOMENTUM: self.alpha_momentum,
            MAKER: self.alpha_maker,
            FUNDAMENTALIST: self.alpha_fundamentalist,
            WHALE: self.alpha_whale,
            HFT: self.alpha_hft,
            INFORMED: self.alpha_informed,
            ARBITRAGEUR: self.alpha_arbitrageur,
        }

    def archetype_counts(self) -> Dict[int, int]:
        """Resolved population {type_id: agent count} (sums to num_agents)."""
        nm, nmo, nf = self.num_makers, self.num_momentum, self.num_fundamentalists
        nw, nh, ni, na = (self.num_whales, self.num_hft, self.num_informed,
                          self.num_arbitrageurs)
        return {
            NOISE: self.num_agents - (nm + nmo + nf + nw + nh + ni + na),
            MOMENTUM: nmo,
            MAKER: nm,
            FUNDAMENTALIST: nf,
            WHALE: nw,
            HFT: nh,
            INFORMED: ni,
            ARBITRAGEUR: na,
        }

    def agent_types(self, xp) -> "xp.ndarray":
        """int32[A] strategy class per agent index.

        Delegates to the single shared assignment rule
        (:func:`assign_agent_types`) with this config's scalar counts, so
        the scalar path and the per-market ensemble path
        (``repro.core.params.agent_types``) can never drift apart.
        """
        return assign_agent_types(
            xp, self.num_agents, self.num_makers, self.num_momentum,
            self.num_fundamentalists, self.num_whales, self.num_hft,
            self.num_informed, self.num_arbitrageurs)[0]

    def initial_books(self, xp) -> Tuple["xp.ndarray", "xp.ndarray"]:
        """(bid, ask) float32[M, L] opening books."""
        M = self.num_markets
        return seed_books(
            xp, self.num_levels,
            xp.full((M,), self.initial_quote_qty, dtype=xp.float32),
            xp.full((M,), self.initial_spread, dtype=xp.int32))

    def events(self) -> int:
        """Total agent events M*A*S (paper's throughput denominator)."""
        return self.num_markets * self.num_agents * self.num_steps


def assign_agent_types(xp, num_agents: int, num_makers, num_momentum,
                       num_fundamentalists, num_whales=0, num_hft=0,
                       num_informed=0, num_arbitrageurs=0):
    """int32 strategy-class lattice broadcastable to [M, A].

    The single live copy of the deterministic assignment rule — makers
    first, then momentum, then fundamentalists, then whales, HFTs,
    informed traders, arbitrageurs, then noise, by agent index — shared by
    the scalar :meth:`MarketConfig.agent_types` (scalar counts → one row)
    and the per-market ``repro.core.params.agent_types`` (``[M, 1]`` count
    columns → ``[M, A]``), so every backend derives the identical
    population without any device-side state. With the new class counts at
    zero the block boundaries are unchanged, so legacy populations are
    bitwise-identical to the four-class rule.
    """
    a = xp.arange(num_agents, dtype=xp.int32)[None, :]
    blocks = (
        (MAKER, num_makers),
        (MOMENTUM, num_momentum),
        (FUNDAMENTALIST, num_fundamentalists),
        (WHALE, num_whales),
        (HFT, num_hft),
        (INFORMED, num_informed),
        (ARBITRAGEUR, num_arbitrageurs),
    )
    # Cumulative upper bounds per block; fold highest-threshold first so
    # each earlier (smaller) block overrides the later ones.
    uppers = []
    cum = xp.asarray(0, dtype=xp.int32)
    for tid, count in blocks:
        cum = cum + xp.asarray(count, dtype=xp.int32)
        uppers.append((tid, cum))
    out = xp.full_like(a, xp.int32(NOISE))
    for tid, upper in reversed(uppers):
        out = xp.where(a < upper, xp.int32(tid), out)
    return out


def seed_books(xp, num_levels: int, quote_qty, spread) -> Tuple:
    """(bid, ask) float32[M, L] opening books (paper Alg.1 line 3).

    The single live copy of the book-seeding rule, vectorized over
    per-market ``quote_qty`` (f32[M]) and ``spread`` (int32[M]) — quotes
    straddle L/2 at ``ceil(spread / 2)`` ticks. Shared by the scalar
    :meth:`MarketConfig.initial_books` and the per-market
    ``EnsembleSpec.initial_books`` so the homogeneous path stays
    bitwise-identical by construction.
    """
    L = num_levels
    half = spread // 2 + spread % 2                      # int32[M]
    pb = (xp.int32(L // 2) - half)[:, None]              # int32[M, 1]
    pa = (xp.int32(L // 2) + half)[:, None]
    q = xp.asarray(quote_qty, dtype=xp.float32)[:, None] # f32[M, 1]
    levels = xp.arange(L, dtype=xp.int32)[None, :]
    bid = (levels == pb).astype(xp.float32) * q
    ask = (levels == pa).astype(xp.float32) * q
    return bid, ask


# ---------------------------------------------------------------------------
# Scenario presets. Each preset is a function (num_steps) -> field overrides;
# taking num_steps lets flash-crash place its shock mid-run by default.
# ---------------------------------------------------------------------------
SCENARIO_PRESETS: Dict[str, Callable[[int], dict]] = {}


def register_scenario(name: str):
    def deco(fn):
        SCENARIO_PRESETS[name] = fn
        return fn
    return deco


@register_scenario("baseline")
def _baseline(num_steps: int) -> dict:
    return {}


@register_scenario("flash-crash")
def _flash_crash(num_steps: int) -> dict:
    # Mid-run shock: 60% of non-maker agents dump marketably while half the
    # resting bid support is withdrawn at the same step.
    return {
        "shock_step": num_steps // 2,
        "shock_intensity": 0.6,
        "shock_cancel": 0.5,
    }


@register_scenario("high-vol")
def _high_vol(num_steps: int) -> dict:
    return {"noise_delta": 16.0, "p_marketable": 0.25}


@register_scenario("low-vol")
def _low_vol(num_steps: int) -> dict:
    return {"noise_delta": 2.0, "p_marketable": 0.05}


@register_scenario("whale")
def _whale(num_steps: int) -> dict:
    # A small population of large infrequent sweepers over a momentum-rich
    # high-vol base: each whale crosses the spread with `whale_size` lots
    # every `whale_period` steps and sits out in between.
    return {"noise_delta": 16.0, "p_marketable": 0.25, "alpha_maker": 0.15,
            "alpha_momentum": 0.40, "alpha_whale": 0.05,
            "whale_size": 32.0, "whale_period": 16}


@register_scenario("hft")
def _hft(num_steps: int) -> dict:
    # Book-imbalance reactive traders: join the heavy side one tick inside
    # the mid whenever |imbalance| clears the threshold. The population is
    # small and the trigger strict — larger/looser HFT crowds amplify
    # one-sided books so hard that volume decouples from volatility and
    # the stylized-facts gate (repro.scenario.validate) fails.
    return {"noise_delta": 16.0, "p_marketable": 0.25, "alpha_maker": 0.15,
            "alpha_momentum": 0.35, "alpha_hft": 0.03,
            "hft_threshold": 0.5}


@register_scenario("informed")
def _informed(num_steps: int) -> dict:
    # Informed traders see the flash-crash shock `informed_horizon` steps
    # early and sell marketably through the pre-shock window. Kept to 5% of
    # the crowd: a larger informed cohort drags the volume/volatility
    # correlation negative (see repro.scenario.validate).
    return {"noise_delta": 16.0, "p_marketable": 0.25, "alpha_maker": 0.15,
            "alpha_momentum": 0.40, "alpha_informed": 0.05,
            "shock_step": num_steps // 2, "shock_intensity": 0.3,
            "informed_horizon": 8}


@register_scenario("wide-book")
def _wide_book(num_steps: int) -> dict:
    return {"initial_quote_qty": 64.0, "initial_spread": 8}


@register_scenario("thin-book")
def _thin_book(num_steps: int) -> dict:
    return {"initial_quote_qty": 1.0, "initial_spread": 2}


def scenario_names() -> Tuple[str, ...]:
    return tuple(sorted(SCENARIO_PRESETS))


def scenario_config(name: str, **overrides) -> MarketConfig:
    """Preset constructor: build a MarketConfig for a named scenario.

    Explicit ``overrides`` win over preset fields, so e.g. the flash-crash
    shock step stays configurable: ``scenario_config("flash-crash",
    shock_step=7, num_steps=20)``.
    """
    if name not in SCENARIO_PRESETS:
        raise KeyError(
            f"unknown scenario {name!r}; have {scenario_names()}")
    if overrides.get("scenario", name) != name:
        raise ValueError(
            f"scenario={overrides['scenario']!r} override conflicts with "
            f"preset name {name!r}")
    num_steps = overrides.get(
        "num_steps", MarketConfig.__dataclass_fields__["num_steps"].default)
    fields = dict(SCENARIO_PRESETS[name](num_steps))
    fields.update(overrides)
    fields["scenario"] = name
    return MarketConfig(**fields)
