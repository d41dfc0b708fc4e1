"""One simulation step (paper Alg. 1 lines 5-22), shared by all backends.

``simulate_step`` is the complete per-step semantics: scenario overlay ->
microstructure state estimation -> agent decisions -> order aggregation ->
cooperative clearing -> residual book update. Backends differ only in *how*
they bin orders (scatter vs one-hot matmul) and how they drive the S-step
loop (host loop, lax.scan, or a persistent Pallas grid) — never in semantics.

Scenario effects are selected by per-market :class:`repro.core.params
.MarketParams` operands and applied with branch-free ``where`` masks on the
traced step index, so *every* scenario — and every per-market mixture of
scenarios — compiles to the same fused kernel as the baseline: no
data-dependent control flow ever reaches the Pallas grid, and no scenario
value is baked into a trace. Legacy scalar-config callers (the one-shot
kernels, the jitted oracle) omit ``params``; the constants are then derived
from ``cfg`` inside the trace, bitwise-identical to the pre-ensemble code
on every counter-RNG backend (the stateful ``numpy-pcg64`` reference —
statistical-equivalence only — shifted by the fixed five-channel draw
schedule; see :mod:`repro.core.agents`).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import numpy as np

from repro.core import agents, auction
from repro.core import params as params_mod
from repro.core.params import MarketParams


class MarketState(NamedTuple):
    bid: "array"        # float32[M, L] resting bid quantities
    ask: "array"        # float32[M, L] resting ask quantities
    last_price: "array" # float32[M, 1]
    prev_mid: "array"   # float32[M, 1]


class StepOutput(NamedTuple):
    price: "array"   # float32[M, 1] clearing price (or last price if no cross)
    volume: "array"  # float32[M, 1] transacted volume
    mid: "array"     # float32[M, 1] mid price used for decisions


def initial_state(cfg, xp) -> MarketState:
    """Opening state for a ``MarketConfig`` or ``EnsembleSpec`` (both expose
    per-market ``initial_books`` plus the static shape fields)."""
    bid, ask = cfg.initial_books(xp)
    m0 = xp.float32(cfg.mid0)
    ones = xp.ones((cfg.num_markets, 1), dtype=xp.float32)
    return MarketState(bid=bid, ask=ask, last_price=ones * m0, prev_mid=ones * m0)


def bin_orders_onehot(side_buy, price, qty, L, xp, agent_chunk=None):
    """Order aggregation as a one-hot contraction (TPU/MXU idiom).

    BUY[m, l] = sum_a qty[m, a] * [price[m, a] == l & side_buy[m, a]]

    This is the TPU-native replacement for the paper's shared-memory
    atomicAdd histogram; exact-integer f32 adds keep it bitwise-identical to
    scatter-based binning.

    ``agent_chunk`` bounds the [M, Ac, L] one-hot intermediate (the dominant
    VMEM term inside the persistent kernel) by accumulating the contraction
    over static slices of the agent axis. Because every partial sum is an
    exact integer in f32, the result is bitwise-identical for any chunking.

    The quantities carry a unit middle axis (``[M, 1, A] x [M, A, L]``):
    Mosaic lowers a batched matmul only when the lhs has a non-contracting
    dimension. Agent slices are cut with plain slices and ``expand_dims``,
    never mixed indexing, which Mosaic would see as a gather.
    """
    levels = xp.arange(L, dtype=xp.int32)
    qb = xp.expand_dims(qty * side_buy.astype(xp.float32), 1)     # [M, 1, A]
    qs = xp.expand_dims(qty * (~side_buy).astype(xp.float32), 1)
    price = xp.expand_dims(price, -1)                              # [M, A, 1]
    A = price.shape[1]
    step = A if not agent_chunk else min(agent_chunk, A)
    buy = sell = None
    for a0 in range(0, A, step):
        a1 = min(a0 + step, A)
        onehot = (price[:, a0:a1] == levels).astype(xp.float32)   # [M, Ac, L]
        b = xp.einsum("mka,mal->mkl", qb[:, :, a0:a1], onehot)[:, 0]
        s = xp.einsum("mka,mal->mkl", qs[:, :, a0:a1], onehot)[:, 0]
        buy, sell = (b, s) if buy is None else (buy + b, sell + s)
    return buy, sell


def exact_ratio(num, den, xp):
    """``num / den`` rounded to nearest even — the IEEE f32 quotient — for
    integer-valued f32 ``|num| <= den < 2**24``, ``den >= 1``.

    The TPU's f32 divider is not correctly rounded (it is off by an ulp on
    about a third of such quotients), so the hardware quotient serves only
    as an estimate: the exact integer quotient ``floor(|num| * 2**s / den)``
    (25 significant bits) is recovered from it with an int32 remainder, and
    rounded once. Wrapping int32 products are exact here because the true
    remainder is small. Bitwise equal to ``num / den`` on every backend.
    """
    i32 = xp.int32
    zero, one = i32(0), i32(1)
    a = xp.abs(num).astype(i32)
    d = den.astype(i32)
    # The estimate is m * 2**(b - 150): 24-bit significand m, biased exponent
    # b. Bit fields, not frexp/ldexp, which compute 2**k through pow.
    bits = xp.maximum(xp.abs(num) / den, xp.float32(2.0 ** -30)).view(i32)
    m = (bits & i32(0x7FFFFF)) | i32(0x800000)
    s = i32(151) - (bits >> i32(23))              # estimate * 2**s = 2m
    q = m + m
    # r = |num| * 2**s - q * den, mod 2**32 (a shift of 32 or more is 0).
    r = xp.where(s > i32(31), zero, a << xp.minimum(s, i32(31))) - q * d
    k = xp.floor(r.astype(xp.float32) / den).astype(i32)
    q, r = q + k, r - k * d
    lo, hi = r < zero, r >= d                         # k may be off by one
    q = q + hi.astype(i32) - lo.astype(i32)
    r = r + xp.where(lo, d, zero) - xp.where(hi, d, zero)
    # Normalize q to 25 bits (the estimate's binade may be one off).
    big = q >= i32(1 << 25)
    sticky = big & ((q & one) == one)
    q, s = xp.where(big, q >> one, q), xp.where(big, s - one, s)
    small = q < i32(1 << 24)
    bit = small & (r + r >= d)
    q = xp.where(small, q + q + bit.astype(i32), q)
    r = xp.where(small, r + r - xp.where(bit, d, zero), r)
    s = xp.where(small, s + one, s)
    # Round the 25th bit away, to nearest even.
    half = (q & one) == one
    q = q >> one
    up = half & ((r != zero) | sticky | ((q & one) == one))
    scale = ((i32(128) - s) << i32(23)).view(xp.float32)         # 2**(1 - s)
    out = (q + up.astype(i32)).astype(xp.float32) * scale
    return xp.where(a == zero, num, xp.where(num < xp.float32(0), -out, out))


def apply_scenario_shock(params: MarketParams, bid, step_idx, xp):
    """Flash-crash liquidity withdrawal (scenario overlay, branch-free).

    At each market's shock step a per-market fraction ``shock_cancel`` of
    every resting bid level is cancelled — buy-side support vanishes just as
    panicking agents market-sell (see :func:`repro.core.agents.decide`).
    ``floor`` keeps the book integer-valued in f32, preserving the
    exact-add bitwise-identity argument (paper §IV-B). Markets with the
    shock disabled (``shock_step < 0``) or scheduled elsewhere see an
    all-False mask — and ``floor(bid * 0) == 0`` — so the overlay is a
    bitwise no-op for them; the same trace serves every schedule. When the
    cancel column is a *concrete* host array of zeros (the NumPy reference
    on no-shock ensembles) the whole overlay is elided outright —
    bitwise-identical, mirroring the ``skip_shock`` elision in
    :func:`repro.core.agents.decide`.
    """
    if (isinstance(params.shock_cancel, np.ndarray)
            and not params.shock_cancel.any()):
        return bid
    f32 = xp.float32
    shock_step = xp.asarray(params.shock_step, dtype=xp.int32)   # [M, 1]
    shock_cancel = xp.asarray(params.shock_cancel, dtype=f32)    # [M, 1]
    at_shock = xp.asarray(step_idx).astype(xp.int32) == shock_step
    cancelled = xp.floor(bid * shock_cancel)
    return xp.where(at_shock, bid - cancelled, bid)


def resolve_peer_mids(prev_mid, coupling_peer, xp, market_ids=None):
    """Gather each market's coupled peer mid over the market axis.

    ``prev_mid`` is the full ``[M, 1]`` (global-axis) mid column at a chunk
    boundary; ``coupling_peer`` holds global peer indices with ``< 0``
    meaning self. ``market_ids`` supplies each row's own global index
    (defaults to ``arange(M)`` — correct whenever ``prev_mid`` spans the
    whole ensemble). Chunk drivers call this once per chunk on the entry
    state, so the value arbitrageurs see is the peer's *previous-chunk*
    mid — frozen at identical boundaries on every backend, which is what
    makes the coupled trajectories bitwise-comparable. The sharded runner
    reconstructs the full column first via a ring halo exchange
    (``lax.ppermute``) and then applies this same gather shard-locally.
    """
    prev_mid = xp.asarray(prev_mid, dtype=xp.float32)
    peer = xp.reshape(xp.asarray(coupling_peer, dtype=xp.int32), (-1, 1))
    if market_ids is None:
        own = xp.arange(prev_mid.shape[0], dtype=xp.int32)[:, None]
    else:
        own = xp.reshape(xp.asarray(market_ids, dtype=xp.int32), (-1, 1))
    resolved = xp.where(peer < xp.int32(0), own, peer)
    return xp.take_along_axis(prev_mid, resolved, axis=0)


def simulate_step(
    cfg,
    state: MarketState,
    step_idx,
    market_ids,
    xp,
    bin_orders: Callable = None,
    scan: str = "cumsum",
    uniform_fn: Callable = None,
    ext_buy=None,
    ext_ask=None,
    agent_chunk=None,
    params: Optional[MarketParams] = None,
    atype=None,
    seed=None,
    peer_mid=None,
):
    """Advance all markets one step. Returns (MarketState, StepOutput).

    ``cfg`` supplies only the static trace parameters (``num_agents``,
    ``num_levels``, ``seed``) — a ``MarketConfig`` or an ``EnsembleSpec``.
    ``params`` carries every scenario-varying value as per-market ``[M, 1]``
    runtime operands; when omitted (legacy scalar-config callers) it is
    derived from ``cfg`` as broadcastable ``[1, 1]`` constants inside the
    trace, which folds to exactly the pre-ensemble computation.

    ``ext_buy``/``ext_ask`` (optional float32[M, L]) are externally injected
    order quantities — the session layer's reserved agent slot for RL-style
    stepping. They join the incoming flow after agent binning, exactly as if
    one extra agent had quoted them this step. Zero arrays are a bitwise
    no-op (exact-integer f32 adds), so gated injection never perturbs the
    stream; ``None`` keeps pre-session traces byte-identical.

    ``agent_chunk`` is forwarded to the default one-hot binning (a pure
    VMEM-footprint knob — bitwise-invisible; see :func:`bin_orders_onehot`).
    ``atype`` optionally carries the precomputed (step-invariant) per-market
    agent-type lattice so loop drivers hoist it out of the step loop.
    ``seed`` optionally overrides the counter-RNG seed at runtime (traced
    ok — see :func:`repro.core.agents.decide`); ``None`` keeps the
    trace-static ``cfg.seed`` bitwise-unchanged.

    ``peer_mid`` (optional float32[M, 1]) is the coupled peer market's
    *frozen* mid feeding arbitrageur agents — chunk drivers compute it once
    per chunk from the entry ``prev_mid`` (see
    :func:`resolve_peer_mids`) so every backend freezes coupling at the
    same boundaries. ``None`` falls back to ``state.prev_mid``
    (self-coupling, per step) — value-identical whenever no arbitrageurs
    are populated, which is every legacy call site.
    """
    if params is None:
        # Built with xp, not host numpy: Pallas kernel bodies reject
        # captured host-array constants, so the legacy traced entries embed
        # xp constants (and keep the dead shock selects for XLA to chew
        # on). The concrete-zero elisions fire where they pay — the NumPy
        # host-loop backends, whose session params are host arrays.
        params = params_mod.scalar_params(cfg, xp)
    if bin_orders is None:
        bin_orders = lambda s, p, q: bin_orders_onehot(
            s, p, q, cfg.num_levels, xp, agent_chunk=agent_chunk)
    f32 = xp.float32

    # Scenario overlay (before quoting: the withdrawal moves the mid too).
    resting_bid = apply_scenario_shock(params, state.bid, step_idx, xp)

    # Phase 2: microstructure state estimation (paper Alg.1 lines 5-7)
    _, _, mid = auction.best_quotes(resting_bid, state.ask, state.last_price, xp)

    # Resting-book imbalance for the HFT archetype: exact-integer f32 sums
    # (book mass stays far below 2^24), one correctly rounded division —
    # deterministic and bitwise-identical across backends, devices,
    # chunkings, and shardings.
    sum_bid = xp.sum(resting_bid, axis=-1, keepdims=True)
    sum_ask = xp.sum(state.ask, axis=-1, keepdims=True)
    depth = sum_bid + sum_ask
    safe_depth = xp.where(depth > f32(0.0), depth, f32(1.0))  # no 0/0 (numpy)
    imbalance = xp.where(depth > f32(0.0),
                         exact_ratio(sum_bid - sum_ask, safe_depth, xp),
                         xp.zeros_like(depth))

    # Phase 3: agent decisions + order aggregation (lines 8-13)
    agent_ids = xp.arange(cfg.num_agents, dtype=xp.int32)
    side_buy, price, qty = agents.decide(
        cfg, params, mid, state.prev_mid, step_idx, market_ids, agent_ids, xp,
        uniform_fn=uniform_fn, atype=atype, seed=seed,
        imbalance=imbalance, peer_mid=peer_mid,
    )
    buy, sell = bin_orders(side_buy, price, qty)

    # Incoming orders join the resting book; clearing runs over the total.
    total_buy = resting_bid + buy
    total_ask = state.ask + sell
    if ext_buy is not None:
        total_buy = total_buy + ext_buy
    if ext_ask is not None:
        total_ask = total_ask + ext_ask

    # Phase 4: cooperative parallel clearing (lines 14-21)
    cleared = auction.clear(total_buy, total_ask, xp, scan=scan)

    # Phase 5: residual book update + state persistence (line 22)
    executed = cleared["volume"] > f32(0.0)
    new_last = xp.where(
        executed, cleared["p_star"].astype(xp.float32), state.last_price
    )
    new_state = MarketState(
        bid=cleared["new_bid"],
        ask=cleared["new_ask"],
        last_price=new_last,
        prev_mid=mid,
    )
    out = StepOutput(price=new_last, volume=cleared["volume"], mid=mid)
    return new_state, out
