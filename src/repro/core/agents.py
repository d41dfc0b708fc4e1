"""Agent archetype registry (paper §III-C), array-module polymorphic.

Every backend — NumPy reference, JAX step/scan engines, and both Pallas
kernels — executes *this exact function* for agent decisions (the paper's
"shared device-side decide()"), which is what makes the bitwise-identity
experiments meaningful.

Archetypes are registered per strategy-class id; ``decide`` evaluates every
registered archetype on the full [M, A] lattice and selects per-agent with
``where`` masks derived from the **per-market** population counts in
:class:`repro.core.params.MarketParams`. The dispatch is branch-free by
construction — no data-dependent control flow — so the same code fuses
inside the persistent Pallas clearing kernel, lax.scan, and the NumPy host
loop without specialization, and one compiled trace serves *any* scenario
mixture: every scenario-varying knob (noise width, maker spread,
fundamentalist target/strength, marketable-flow probability, quantity cap,
flash-crash schedule, archetype counts) is a ``[M, 1]`` runtime operand
broadcast over the agent axis.

All five RNG channels are drawn every step, for every market. For the
counter-based generators this is free of semantic weight (channels are
independent pure functions of the coordinate, and inactive draws are masked
off), and it is what keeps the *stateful* PCG64 reference per-market
decomposable: the draw schedule no longer depends on which scenario a
market runs, so market ``m`` of a mixed ensemble consumes exactly the rows
the homogeneous run consumed.

All float math is float32 with explicit casts so NumPy (which would otherwise
promote to float64) and JAX produce identical bit patterns.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import numpy as np

from repro.core import params as params_mod
from repro.core import rng
from repro.core.config import (
    ARBITRAGEUR,
    CH_MKT,
    CH_PRICE,
    CH_QTY,
    CH_SHOCK,
    CH_SIDE,
    FUNDAMENTALIST,
    HFT,
    INFORMED,
    MAKER,
    MOMENTUM,
    NOISE,
    WHALE,
)
from repro.core.params import MarketParams


class ArchetypeContext(NamedTuple):
    """Per-step inputs every archetype sees (all already [M, A]-broadcastable)."""

    params: MarketParams  # per-market [M, 1] scenario parameters
    xp: "module"
    mid: "array"        # float32[M, 1] current mid price
    prev_mid: "array"   # float32[M, 1] previous step's mid price
    step_i: "array"     # int32 scalar step index (traced ok)
    agent_ids: "array"  # int32[1, A] agent indices within a market
    u_side: "array"     # float32[M, A] side-channel uniforms
    u_price: "array"    # float32[M, A] price-channel uniforms
    imbalance: "array"  # float32[M, 1] resting-book imbalance in [-1, 1]
    peer_mid: "array"   # float32[M, 1] coupled peer's previous-chunk mid
    num_levels: int     # L — static price-grid width


def _select_bool(mask, a, b):
    """``where(mask, a, b)`` over boolean operands, as boolean algebra:
    the same values, and Mosaic cannot select between boolean vectors."""
    return (mask & a) | (~mask & b)


# type_id -> (name, fn(ctx) -> (side_buy, price_f)); ids match config constants.
_ARCHETYPES: Dict[int, Tuple[str, Callable]] = {}


def register_archetype(type_id: int, name: str):
    def deco(fn):
        _ARCHETYPES[type_id] = (name, fn)
        return fn
    return deco


def archetype_names() -> Dict[int, str]:
    return {tid: name for tid, (name, _) in sorted(_ARCHETYPES.items())}


@register_archetype(NOISE, "noise")
def _noise(ctx: ArchetypeContext):
    """Random side; price = mid + U[-Δ, Δ] with per-market Δ."""
    f32 = ctx.xp.float32
    side_buy = ctx.u_side < f32(0.5)
    delta = ctx.xp.asarray(ctx.params.noise_delta, dtype=f32)
    eta = (ctx.u_price * f32(2.0) - f32(1.0)) * delta
    return side_buy, ctx.mid + eta


@register_archetype(MOMENTUM, "momentum")
def _momentum(ctx: ArchetypeContext):
    """Trend follower: side = sgn(mid_t - mid_{t-1}); price = mid ± 1."""
    xp, f32 = ctx.xp, ctx.xp.float32
    ret = xp.sign(ctx.mid - ctx.prev_mid)  # float32[M, 1]
    ret = ret + xp.zeros_like(ctx.u_side)  # broadcast [M, A]
    side_buy = _select_bool(ret != f32(0.0), ret > f32(0.0),
                            ctx.u_side < f32(0.5))
    price_f = ctx.mid + xp.where(side_buy, f32(1.0), f32(-1.0))
    return side_buy, price_f


@register_archetype(MAKER, "maker")
def _maker(ctx: ArchetypeContext):
    """Market maker: alternate on parity of (a + s); per-market half-spread."""
    xp, f32 = ctx.xp, ctx.xp.float32
    side_buy = ((ctx.agent_ids + ctx.step_i) % xp.int32(2)) == xp.int32(0)
    half = xp.asarray(ctx.params.maker_half_spread, dtype=f32)
    price_f = xp.where(side_buy, ctx.mid - half, ctx.mid + half)
    return side_buy, price_f


@register_archetype(FUNDAMENTALIST, "fundamentalist")
def _fundamentalist(ctx: ArchetypeContext):
    """Mean reversion toward the per-market fundamental price F.

    Buys when mid < F (random side at the fixed point), quoting part-way back
    toward F (per-market strength kappa) with a unit jitter so
    fundamentalists do not collapse onto a single tick.
    """
    xp, f32 = ctx.xp, ctx.xp.float32
    fundamental = xp.asarray(ctx.params.fundamental, dtype=f32)
    dev = fundamental - ctx.mid               # float32[M, 1]
    dev = dev + xp.zeros_like(ctx.u_side)     # broadcast [M, A]
    side_buy = _select_bool(dev != f32(0.0), dev > f32(0.0),
                            ctx.u_side < f32(0.5))
    jitter = ctx.u_price * f32(2.0) - f32(1.0)
    kappa = xp.asarray(ctx.params.fundamentalist_kappa, dtype=f32)
    price_f = ctx.mid + dev * kappa + jitter
    return side_buy, price_f


@register_archetype(WHALE, "whale")
def _whale(ctx: ArchetypeContext):
    """Large infrequent sweeps: a marketable block order of ``whale_size``
    lots every ``whale_period`` steps, random side; silent in between.

    The sweep cadence is expressed through the *quantity* (``decide``
    zeroes whale quantities off-cadence), so the fixed draw schedule and
    the branch-free dispatch are untouched — an idle whale submits a
    zero-quantity order that bins to nothing.
    """
    xp, f32 = ctx.xp, ctx.xp.float32
    side_buy = ctx.u_side < f32(0.5)
    L = ctx.num_levels
    price_f = xp.where(side_buy, f32(L - 1), f32(0.0)) + xp.zeros_like(ctx.u_side)
    return side_buy, price_f


@register_archetype(HFT, "hft")
def _hft(ctx: ArchetypeContext):
    """Book-imbalance reactive: join the pressure side one tick through the
    mid when |imbalance| exceeds the per-market trigger, noise side below.
    """
    xp, f32 = ctx.xp, ctx.xp.float32
    imb = ctx.imbalance + xp.zeros_like(ctx.u_side)  # broadcast [M, A]
    thr = xp.asarray(ctx.params.hft_threshold, dtype=f32)
    side_buy = _select_bool(xp.abs(imb) > thr, imb > f32(0.0),
                            ctx.u_side < f32(0.5))
    price_f = ctx.mid + xp.where(side_buy, f32(1.0), f32(-1.0))
    return side_buy, price_f


@register_archetype(INFORMED, "informed")
def _informed(ctx: ArchetypeContext):
    """Sees the fundamental shock early: sells marketably through the
    ``informed_horizon`` steps before ``shock_step``, noise-like otherwise
    (markets with no shock scheduled never open the window)."""
    xp, f32 = ctx.xp, ctx.xp.float32
    shock_step = xp.asarray(ctx.params.shock_step, dtype=xp.int32)
    horizon = xp.asarray(ctx.params.informed_horizon, dtype=xp.int32)
    false_b = xp.zeros_like(ctx.u_side) > f32(0.0)  # all-False [M, A]
    window = ((shock_step >= xp.int32(0))
              & (ctx.step_i >= shock_step - horizon)
              & (ctx.step_i < shock_step)) | false_b
    calm_side = ctx.u_side < f32(0.5)
    calm_price = ctx.mid + (ctx.u_price * f32(2.0) - f32(1.0))
    side_buy = ~window & calm_side
    price_f = xp.where(window, f32(0.0), calm_price)
    return side_buy, price_f


@register_archetype(ARBITRAGEUR, "arbitrageur")
def _arbitrageur(ctx: ArchetypeContext):
    """Cross-market arbitrage: chase the gap to the coupled peer market's
    previous-chunk mid (self-coupled markets see gap relative to their own
    frozen mid). Buys when the peer trades higher, quoting part-way toward
    the peer with a unit jitter."""
    xp, f32 = ctx.xp, ctx.xp.float32
    gap = ctx.peer_mid - ctx.mid              # float32[M, 1]
    gap = gap + xp.zeros_like(ctx.u_side)     # broadcast [M, A]
    side_buy = _select_bool(gap != f32(0.0), gap > f32(0.0),
                            ctx.u_side < f32(0.5))
    kappa = xp.asarray(ctx.params.arb_kappa, dtype=f32)
    jitter = ctx.u_price * f32(2.0) - f32(1.0)
    price_f = ctx.mid + gap * kappa + jitter
    return side_buy, price_f


def decide(cfg, params: MarketParams, mid, prev_mid, step, market_ids,
           agent_ids, xp, uniform_fn=None, atype=None, seed=None,
           imbalance=None, peer_mid=None):
    """Vectorized agent decisions for one step.

    Args:
      cfg:        the static shape carrier (``MarketConfig`` or
                  ``EnsembleSpec``) supplying ``num_agents``, ``num_levels``
                  and the RNG ``seed`` — the only fields baked into traces.
      params:     :class:`MarketParams` of per-market ``[M, 1]`` operands
                  (``[1, 1]`` constants on the legacy scalar path).
      mid:        float32[M, 1] current mid price per market.
      prev_mid:   float32[M, 1] previous step's mid price.
      step:       int32 scalar (traced ok) step index.
      market_ids: int32[M, 1] global market indices (for the RNG coordinate).
      agent_ids:  int32[1, A] (or [A]) agent indices within a market.
      uniform_fn: optional ``f(gid, step, channel) -> float32[M, A]`` RNG
        override (used by the statistical-equivalence reference backends);
        defaults to the production kinetic_hash32 stream.
      atype:      optional precomputed per-market type lattice
        (:func:`repro.core.params.agent_types`) — it is step-invariant, so
        loop drivers hoist it out of the step loop; ``None`` recomputes it
        here (value-identical).
      seed:       optional runtime seed override for the production counter
        stream (scalar, traced ok — the RL env's vmap-over-seeds operand).
        ``None`` uses the trace-static ``cfg.seed``; a concrete value equal
        to ``cfg.seed`` is bitwise-identical to ``None``. Ignored when
        ``uniform_fn`` is supplied (the override owns its own stream).
      imbalance:  optional float32[M, 1] resting-book imbalance
        ``(Σbid - Σask) / (Σbid + Σask)`` feeding the HFT archetype
        (``None`` → zeros: HFTs fall back to their noise side).
      peer_mid:   optional float32[M, 1] coupled peer market's frozen
        (previous-chunk) mid feeding the arbitrageur archetype (``None``
        → ``prev_mid``, i.e. self-coupling).

    Returns:
      side_buy: bool[M, A], price: int32[M, A], qty: float32[M, A]
    """
    A = cfg.num_agents
    L = cfg.num_levels
    f32 = xp.float32

    agent_ids = xp.reshape(xp.asarray(agent_ids, dtype=xp.int32), (1, -1))
    market_ids = xp.reshape(xp.asarray(market_ids, dtype=xp.int32), (-1, 1))
    gid = (market_ids * xp.int32(A) + agent_ids).astype(xp.uint32)  # [M, A]
    step_u = xp.asarray(step).astype(xp.uint32)

    if uniform_fn is None:
        seed = cfg.seed if seed is None else seed

        def u(channel):
            return rng.uniform32(seed, gid, step_u, channel, xp)
    else:
        def u(channel):
            return uniform_fn(gid, step_u, channel)

    # Fixed five-channel draw schedule — scenario-independent by design, so
    # the sequential PCG64 reference stays per-market decomposable across
    # ensemble mixtures (see module docstring). The one exception is the
    # production counter stream (uniform_fn=None): its channels are pure
    # functions of the coordinate, so when every market's shock intensity
    # is a concrete host zero the CH_SHOCK draw is skipped outright —
    # bitwise-invisible, and it spares the NumPy reference a full [M, A]
    # hash channel per step on baseline runs.
    u_side = u(CH_SIDE)
    u_price = u(CH_PRICE)
    u_mkt = u(CH_MKT)
    u_qty = u(CH_QTY)
    skip_shock = (uniform_fn is None
                  and isinstance(params.shock_intensity, np.ndarray)
                  and not params.shock_intensity.any())
    u_shock = None if skip_shock else u(CH_SHOCK)

    if atype is None:  # int32[M, A]-broadcastable per-market type lattice
        atype = params_mod.agent_types(params, A, xp)
    mid = xp.asarray(mid, dtype=xp.float32)
    prev_mid = xp.asarray(prev_mid, dtype=xp.float32)
    step_i = xp.asarray(step).astype(xp.int32)
    imbalance = (xp.zeros_like(mid) if imbalance is None
                 else xp.asarray(imbalance, dtype=xp.float32))
    peer_mid = (prev_mid if peer_mid is None
                else xp.asarray(peer_mid, dtype=xp.float32))

    ctx = ArchetypeContext(params=params, xp=xp, mid=mid, prev_mid=prev_mid,
                           step_i=step_i, agent_ids=agent_ids,
                           u_side=u_side, u_price=u_price,
                           imbalance=imbalance, peer_mid=peer_mid,
                           num_levels=L)

    # Branch-free archetype dispatch: evaluate every registered archetype on
    # the full lattice, select by the per-market type lattice. Masks are
    # disjoint, so the fold order only needs to be deterministic (ascending
    # type id) for bitwise reproducibility; because the final value at each
    # agent is exactly its own archetype's output, evaluating unpopulated
    # archetypes is value-invisible — which is what lets one trace serve
    # any population mixture. The NumPy host loop cannot constant-fold a
    # dead select, so an archetype whose count column is a *concrete* host
    # array of zeros is skipped outright (its mask would be all-False —
    # value-identical); traced backends always see the full fold.
    count_cols = {MAKER: params.num_makers, MOMENTUM: params.num_momentum,
                  FUNDAMENTALIST: params.num_fundamentalists,
                  WHALE: params.num_whales, HFT: params.num_hft,
                  INFORMED: params.num_informed,
                  ARBITRAGEUR: params.num_arbitrageurs}

    def concretely_empty(tid):
        col = count_cols.get(tid)
        return isinstance(col, np.ndarray) and not col.any()

    zero_f = xp.zeros_like(u_side)
    zero_b = zero_f > f32(0.0)  # all-False bool[M, A] broadcast template
    ids = sorted(_ARCHETYPES)
    _, fn0 = _ARCHETYPES[ids[0]]
    side_buy, price_f = fn0(ctx)
    side_buy = side_buy | zero_b
    price_f = price_f + zero_f
    for tid in ids[1:]:
        if concretely_empty(tid):
            continue
        _, fn = _ARCHETYPES[tid]
        s, p = fn(ctx)
        mask = atype == xp.int32(tid)
        side_buy = _select_bool(mask, s, side_buy)
        price_f = xp.where(mask, p + zero_f, price_f)

    is_maker = atype == MAKER

    # Marketable orders (never for makers): force to the grid boundary.
    p_mkt = xp.asarray(params.p_marketable, dtype=f32)
    marketable = (u_mkt < p_mkt) & ~is_maker
    price_f = xp.where(
        marketable,
        xp.where(side_buy, f32(L - 1), f32(0.0)),
        price_f,
    )

    # Scenario overlay: flash-crash panic, keyed on the per-market shock
    # schedule (branch-free; markets whose shock_step is < 0 or elsewhere
    # see an all-False mask and an untouched stream). Panicking non-makers
    # sell marketably. Skipped when the shock channel was concretely
    # elided above — the panic mask would be all-False.
    if u_shock is not None:
        shock_step = xp.asarray(params.shock_step, dtype=xp.int32)
        shock_int = xp.asarray(params.shock_intensity, dtype=f32)
        at_shock = (step_i == shock_step) | zero_b
        panic = (u_shock < shock_int) & ~is_maker & at_shock
        side_buy = ~panic & side_buy
        price_f = xp.where(panic, f32(0.0) + zero_f, price_f)

    # Round-half-even (identical in NumPy & JAX), prune to the grid (paper
    # §III-A: out-of-window orders are clipped / made marketable).
    price = xp.clip(xp.round(price_f), f32(0.0), f32(L - 1)).astype(xp.int32)

    # Integer quantity q = 1 + floor(u * q_max) in {1..q_max}, kept in f32
    # (exact-integer arithmetic => associative adds => bitwise reproducible).
    q_max = xp.asarray(params.q_max, dtype=f32)
    qty = f32(1.0) + xp.floor(u_qty * q_max)

    # Whale cadence overlay: whales trade ``whale_size`` lots on sweep steps
    # and zero lots otherwise (a zero-quantity order bins to nothing), so
    # their burstiness lives entirely in the quantity lattice and the draw
    # schedule stays fixed. Same concrete-zero elision as the dispatch fold.
    if not concretely_empty(WHALE):
        is_whale = (atype == xp.int32(WHALE)) | zero_b
        period = xp.maximum(
            xp.asarray(params.whale_period, dtype=xp.int32), xp.int32(1))
        at_sweep = ((step_i % period) == xp.int32(0)) | zero_b
        wq = xp.asarray(params.whale_size, dtype=f32) + zero_f
        qty = xp.where(is_whale, xp.where(at_sweep, wq, zero_f), qty)
    return side_buy, price, qty
