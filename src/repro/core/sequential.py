"""Sequential-clearing reference mechanism (Steinbacher et al.).

The production engine clears each step as one uniform-price call auction
over the *aggregate* order flow (:mod:`repro.core.auction`) — the
mechanism that makes the step embarrassingly parallel over agents. The
classical ABM literature instead matches orders **one agent at a time**
against the resting book (continuous-double-auction style), and
Steinbacher et al. show the choice of mechanism itself changes the
emergent dynamics. This module is the sequential reference the repo uses
to *quantify* that gap:

  * identical agent decisions — the same :func:`repro.core.agents.decide`
    draws on the same fixed five-channel schedule, so any trajectory
    difference is attributable to the clearing mechanism alone;
  * order-by-order immediate matching in agent-index order, vectorized
    over the market axis: a buy at limit ``p`` fills against resting asks
    at levels ``<= p`` (lowest first), the residual rests at ``p``; sells
    are symmetric against resting bids (highest first);
  * exact-integer f32 arithmetic throughout (cumsum/min/clip of integer
    masses), so the NumPy host loop and the jitted ``lax.scan`` reference
    (:func:`repro.kernels.ref.simulate_reference_sequential`) are
    **bitwise identical** — the same reproducibility bar the parallel
    engine clears.

Exposed as ``Engine("numpy", clearing="sequential")`` through the session
layer and re-exported by :mod:`repro.scenario` for the mechanism-gap
reports.
"""
from __future__ import annotations

import numpy as np

from repro.core import agents, auction
from repro.core.params import MarketParams
from repro.core.step import (
    MarketState,
    StepOutput,
    apply_scenario_shock,
)


def match_order(bid, ask, exec_price, side_buy, price, qty, xp):
    """Match ONE order per market against the resting books, immediately.

    All operands are per-market columns: ``side_buy`` bool[M, 1], ``price``
    int32[M, 1] (limit level), ``qty`` f32[M, 1] (integer-valued lots);
    ``bid``/``ask`` are the resting f32[M, L] books. Returns
    ``(bid, ask, fill, exec_price)`` where ``fill`` is the executed
    quantity and ``exec_price`` carries the marginal executed level (the
    previous value where nothing traded).

    Both sides are evaluated branch-free and selected by the side mask, so
    the jitted ``lax.fori_loop`` driver and the NumPy agent loop run the
    identical op sequence. Every quantity is an exact integer in f32
    (cumsums of book masses stay far below 2^24), so fills, residuals and
    book updates are bitwise reproducible across backends.
    """
    f32 = xp.float32
    L = bid.shape[-1]
    levels = xp.arange(L, dtype=xp.int32)[None, :]
    onehot = (levels == price).astype(f32)            # [M, L] at the limit

    # Buy: sweep asks at levels <= p, lowest first.
    s_cum = xp.cumsum(ask, axis=-1)                   # prefix supply
    elig_b = xp.take_along_axis(s_cum, price, axis=-1)
    fill_b = xp.minimum(qty, elig_b)
    below = s_cum - ask                               # supply strictly below l
    traded_a = xp.clip(fill_b - below, f32(0.0), ask)
    bid_buy = bid + onehot * (qty - fill_b)           # residual rests at p
    ask_buy = ask - traded_a
    lvl_b = xp.max(xp.where(traded_a > f32(0.0), levels, xp.int32(-1)),
                   axis=-1, keepdims=True)            # marginal (highest) level

    # Sell: sweep bids at levels >= p, highest first.
    d_cum = xp.flip(xp.cumsum(xp.flip(bid, -1), axis=-1), -1)  # suffix demand
    elig_s = xp.take_along_axis(d_cum, price, axis=-1)
    fill_s = xp.minimum(qty, elig_s)
    above = d_cum - bid                               # demand strictly above l
    traded_b = xp.clip(fill_s - above, f32(0.0), bid)
    bid_sell = bid - traded_b
    ask_sell = ask + onehot * (qty - fill_s)
    lvl_s = xp.min(xp.where(traded_b > f32(0.0), levels, xp.int32(L)),
                   axis=-1, keepdims=True)            # marginal (lowest) level

    new_bid = xp.where(side_buy, bid_buy, bid_sell)
    new_ask = xp.where(side_buy, ask_buy, ask_sell)
    fill = xp.where(side_buy, fill_b, fill_s)
    lvl = xp.where(side_buy, lvl_b, lvl_s)
    exec_price = xp.where(fill > f32(0.0), lvl.astype(f32), exec_price)
    return new_bid, new_ask, fill, exec_price


def simulate_step_sequential(
    cfg,
    state: MarketState,
    step_idx,
    market_ids,
    xp,
    *,
    params: MarketParams,
    uniform_fn=None,
    atype=None,
    seed=None,
    peer_mid=None,
):
    """Advance all markets one step under sequential clearing.

    Mirrors :func:`repro.core.step.simulate_step` phase for phase — shock
    overlay, mid estimation, the *identical* ``decide`` call — and then
    replaces the call auction with the agent-ordered matching loop.
    Returns ``(MarketState, StepOutput)`` with the same shapes, so the
    session layer drives it unchanged. The step's reported price is the
    marginal level of the last executing order (the sequential analogue of
    the auction's ``p_star``), falling back to the previous last price on
    no-trade steps.
    """
    f32 = xp.float32
    A = cfg.num_agents

    resting_bid = apply_scenario_shock(params, state.bid, step_idx, xp)
    _, _, mid = auction.best_quotes(resting_bid, state.ask,
                                    state.last_price, xp)

    sum_bid = xp.sum(resting_bid, axis=-1, keepdims=True)
    sum_ask = xp.sum(state.ask, axis=-1, keepdims=True)
    depth = sum_bid + sum_ask
    safe_depth = xp.where(depth > f32(0.0), depth, f32(1.0))
    imbalance = xp.where(depth > f32(0.0), (sum_bid - sum_ask) / safe_depth,
                         xp.zeros_like(depth))

    agent_ids = xp.arange(A, dtype=xp.int32)
    side_buy, price, qty = agents.decide(
        cfg, params, mid, state.prev_mid, step_idx, market_ids, agent_ids, xp,
        uniform_fn=uniform_fn, atype=atype, seed=seed,
        imbalance=imbalance, peer_mid=peer_mid,
    )

    bid, ask = resting_bid, state.ask
    volume = xp.zeros_like(mid)
    exec_price = xp.asarray(state.last_price, dtype=f32) + xp.zeros_like(mid)

    if xp is np:
        for a in range(A):
            bid, ask, fill, exec_price = match_order(
                bid, ask, exec_price,
                side_buy[:, a:a + 1], price[:, a:a + 1], qty[:, a:a + 1], xp)
            volume = volume + fill
    else:
        import jax

        def body(a, carry):
            bid, ask, volume, exec_price = carry
            sb = jax.lax.dynamic_slice_in_dim(side_buy, a, 1, axis=1)
            pr = jax.lax.dynamic_slice_in_dim(price, a, 1, axis=1)
            qt = jax.lax.dynamic_slice_in_dim(qty, a, 1, axis=1)
            bid, ask, fill, exec_price = match_order(
                bid, ask, exec_price, sb, pr, qt, xp)
            return bid, ask, volume + fill, exec_price

        bid, ask, volume, exec_price = jax.lax.fori_loop(
            0, A, body, (bid, ask, volume, exec_price))

    executed = volume > f32(0.0)
    new_last = xp.where(executed, exec_price, state.last_price)
    new_state = MarketState(bid=bid, ask=ask, last_price=new_last,
                            prev_mid=mid)
    out = StepOutput(price=new_last, volume=volume, mid=mid)
    return new_state, out
