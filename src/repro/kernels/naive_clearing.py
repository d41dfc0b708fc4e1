"""Naive per-step kernel — the paper's "Naive Custom CUDA" ablation on TPU.

Identical device-side semantics (same ``agents.decide``, same
``auction.clear``, same RNG), but the two central optimizations removed:

  * **No persistence**: one ``pallas_call`` per simulation step, driven by a
    host-level ``lax.scan``. The book round-trips HBM every step — the
    Θ(S·M·L) global-traffic regime of paper §III-F, plus Θ(S) kernel
    dispatches instead of one.

On TPU the GPU notion of a "one-thread serial scan" has no analogue (the VPU
is always SIMD over lanes), so this ablation isolates the *persistence* axis;
both kernels clear with the same log-depth Hillis-Steele scan. The
performance gap between this and :mod:`kinetic_clearing` is a clean
attribution to state residency (§IV-I).

Scenario configs (archetype mixtures, flash-crash shocks, regimes) dispatch
branch-free inside the shared ``simulate_step``, so this ablation stays
bitwise comparable to the persistent kernel on every scenario — the basis of
the parity matrix in tests/test_parity_matrix.py.

The chunk entry mirrors :func:`kinetic_clearing_chunk`'s full contract —
padded sublane tiles, explicit global ``market_ids`` for sharded callers,
per-market :class:`repro.core.params.MarketParams` operands (``(mb, 1)``
columns fetched into each tile, so one compiled step kernel serves any
scenario mixture), and a ``stats_only`` mode (accumulated in the host scan
carry here, since per-step launches are this ablation's point) — so the
Session/shard layers treat both engines uniformly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import stats as stats_mod
from repro.core.config import MarketConfig
from repro.core.params import MarketParams
from repro.core.step import MarketState, resolve_peer_mids, simulate_step
from repro.kernels.autotune import pad_to_multiple
from repro.kernels.kinetic_clearing import (KERNEL_SCAN, NUM_PARAM_OPERANDS,
                                            _pad_rows, pad_params,
                                            pallas_call, resolve_params,
                                            tile_market_ids)


def _step_kernel_body(
    step_ref,
    bid_ref, ask_ref, last_ref, pmid_ref,
    out_bid_ref, out_ask_ref, out_last_ref, out_pmid_ref,
    price_ref, volume_ref,
    *, cfg: MarketConfig, mb: int,
):
    s = step_ref[0, 0]
    market_ids = tile_market_ids(mb)
    state = MarketState(
        bid=bid_ref[...], ask=ask_ref[...],
        last_price=last_ref[...], prev_mid=pmid_ref[...],
    )
    new_state, out = simulate_step(cfg, state, s, market_ids, jnp,
                                   scan=KERNEL_SCAN)
    out_bid_ref[...] = new_state.bid
    out_ask_ref[...] = new_state.ask
    out_last_ref[...] = new_state.last_price
    out_pmid_ref[...] = new_state.prev_mid
    price_ref[...] = out.price
    volume_ref[...] = out.volume


@functools.partial(jax.jit, static_argnames=("cfg", "mb", "interpret"))
def naive_clearing(
    bid: jax.Array, ask: jax.Array, last: jax.Array, pmid: jax.Array,
    *, cfg: MarketConfig, mb: int = 8, interpret: bool = False,
) -> Tuple[jax.Array, ...]:
    """S launches of a single-step kernel; state resides in HBM between steps."""
    M, L = bid.shape
    S = cfg.num_steps
    if M % mb:
        raise ValueError(f"M={M} not divisible by tile mb={mb}")
    grid = (M // mb,)

    book_spec = pl.BlockSpec((mb, L), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((mb, 1), lambda i: (i, 0))
    step_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((M, L), jnp.float32),
        jax.ShapeDtypeStruct((M, L), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
    )
    step_call = pallas_call(
        functools.partial(_step_kernel_body, cfg=cfg, mb=mb),
        grid=grid,
        in_specs=[step_spec, book_spec, book_spec, scalar_spec, scalar_spec],
        out_specs=(book_spec, book_spec, scalar_spec, scalar_spec,
                   scalar_spec, scalar_spec),
        out_shape=out_shapes,
        interpret=interpret,
    )

    def host_step(carry, s):
        bid, ask, last, pmid = carry
        step_arr = jnp.full((1, 1), s, dtype=jnp.int32)
        bid, ask, last, pmid, price, volume = step_call(
            step_arr, bid, ask, last, pmid
        )
        return (bid, ask, last, pmid), (price[:, 0], volume[:, 0])

    steps = jnp.arange(S, dtype=jnp.int32)
    (bid, ask, last, pmid), (pp, vp) = jax.lax.scan(
        host_step, (bid, ask, last, pmid), steps
    )
    return bid, ask, last, pmid, pp.T, vp.T


def _chunk_step_kernel_body(
    step_ref, mids_ref,
    bid_ref, ask_ref, last_ref, pmid_ref, ext_buy_ref, ext_ask_ref,
    peer_ref,
    *refs,
    cfg, mb: int, agent_chunk: Optional[int],
):
    """Per-step kernel with external-order inputs (Session API variant).

    ``mids_ref`` carries the per-row global market ids (see the kinetic
    chunk kernel) so padded/sharded callers keep exact RNG coordinates;
    ``peer_ref`` is the chunk-frozen coupling column (gathered once per
    chunk by the entry, NOT per launch — same freeze boundary as the
    persistent kernel); the next ``NUM_PARAM_OPERANDS`` refs are this
    tile's per-market :class:`MarketParams` columns.
    """
    s = step_ref[0, 0]
    market_ids = mids_ref[...]
    params = MarketParams(*(r[...] for r in refs[:NUM_PARAM_OPERANDS]))
    (out_bid_ref, out_ask_ref, out_last_ref, out_pmid_ref,
     price_ref, volume_ref, mid_ref) = refs[NUM_PARAM_OPERANDS:]
    state = MarketState(
        bid=bid_ref[...], ask=ask_ref[...],
        last_price=last_ref[...], prev_mid=pmid_ref[...],
    )
    new_state, out = simulate_step(
        cfg, state, s, market_ids, jnp, scan=KERNEL_SCAN,
        ext_buy=ext_buy_ref[...], ext_ask=ext_ask_ref[...],
        agent_chunk=agent_chunk, params=params, peer_mid=peer_ref[...],
    )
    out_bid_ref[...] = new_state.bid
    out_ask_ref[...] = new_state.ask
    out_last_ref[...] = new_state.last_price
    out_pmid_ref[...] = new_state.prev_mid
    price_ref[...] = out.price
    volume_ref[...] = out.volume
    mid_ref[...] = out.mid


def naive_clearing_chunk(
    bid: jax.Array, ask: jax.Array, last: jax.Array, pmid: jax.Array,
    step0: jax.Array, n_valid: jax.Array,
    ext_buy: jax.Array, ext_ask: jax.Array,
    *, cfg, chunk: int, mb: int = 8,
    interpret: bool = False, market_ids: Optional[jax.Array] = None,
    agent_chunk: Optional[int] = None,
    params: Optional[MarketParams] = None,
    peer_mid: Optional[jax.Array] = None,
    stats: Optional[stats_mod.MarketStats] = None, stats_only: bool = False,
) -> Tuple[jax.Array, ...]:
    """Session entry for the launch-per-step regime: ``chunk`` kernel
    launches per call, state round-tripping HBM between launches.

    Mirrors :func:`kinetic_clearing_chunk`'s contract — ``step0``/``n_valid``
    int32[1, 1] runtime scalars, per-market ``params`` operands (one trace
    serves any scenario mixture), external orders injected at the first
    local step, gated state so a partial tail advances exactly ``n_valid``
    steps, padded sublane tiles with explicit global ``market_ids``, and a
    ``stats_only`` mode (accumulated in the scan carry between launches) —
    but keeps the Θ(chunk) dispatches and Θ(chunk·M·L) HBM traffic that this
    ablation exists to exhibit. Not jitted here; the session runner owns jit.
    """
    M, L = bid.shape
    m_padded = pad_to_multiple(M, mb)
    grid = (m_padded // mb,)

    if market_ids is None:
        market_ids = jnp.arange(M, dtype=jnp.int32)
    mids = jnp.reshape(jnp.asarray(market_ids, dtype=jnp.int32), (M, 1))
    if m_padded != M:
        pad_ids = jnp.arange(M, m_padded, dtype=jnp.int32)[:, None]
        mids = jnp.concatenate([mids, pad_ids], axis=0)
    params = resolve_params(cfg, M, params, jnp)
    if peer_mid is None:
        # Chunk-entry coupling freeze over local rows (single-device case);
        # sharded callers pass the halo-exchanged column explicitly.
        peer_mid = resolve_peer_mids(pmid, params.coupling_peer, jnp)
    bid, ask, last, pmid, ext_buy, ext_ask, peer_mid = (
        _pad_rows(x, m_padded) for x in (bid, ask, last, pmid, ext_buy,
                                         ext_ask, peer_mid))
    params = pad_params(params, m_padded)

    book_spec = pl.BlockSpec((mb, L), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((mb, 1), lambda i: (i, 0))
    step_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((m_padded, L), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, L), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
    )
    step_call = pallas_call(
        functools.partial(_chunk_step_kernel_body, cfg=cfg, mb=mb,
                          agent_chunk=agent_chunk),
        grid=grid,
        in_specs=[step_spec, scalar_spec, book_spec, book_spec, scalar_spec,
                  scalar_spec, book_spec, book_spec, scalar_spec]
        + [scalar_spec] * NUM_PARAM_OPERANDS,
        out_specs=(book_spec, book_spec, scalar_spec, scalar_spec,
                   scalar_spec, scalar_spec, scalar_spec),
        out_shape=out_shapes,
        interpret=interpret,
    )

    step0_s = step0[0, 0]
    n_valid_s = n_valid[0, 0]
    zeros_ext = jnp.zeros_like(ext_buy)

    if stats_only and stats is None:
        raise ValueError("stats_only=True requires the carried `stats` "
                         "accumulators (see repro.core.stats.init_stats)")
    st0 = None
    if stats_only:
        st0 = stats_mod.MarketStats(
            *(_pad_rows(jnp.asarray(x, dtype=jnp.float32), m_padded)
              for x in stats))

    def host_step(carry, s):
        if stats_only:
            bid, ask, last, pmid, st = carry
        else:
            bid, ask, last, pmid = carry
        eb = jnp.where(s == jnp.int32(0), ext_buy, zeros_ext)
        ea = jnp.where(s == jnp.int32(0), ext_ask, zeros_ext)
        step_arr = jnp.full((1, 1), step0_s + s, dtype=jnp.int32)
        nbid, nask, nlast, npmid, price, volume, mid = step_call(
            step_arr, mids, bid, ask, last, pmid, eb, ea, peer_mid, *params
        )
        active = s < n_valid_s
        bid = jnp.where(active, nbid, bid)
        ask = jnp.where(active, nask, ask)
        last = jnp.where(active, nlast, last)
        pmid = jnp.where(active, npmid, pmid)
        if stats_only:
            st = stats_mod.accumulate(st, mid, volume, active, jnp)
            return (bid, ask, last, pmid, st), None
        return (bid, ask, last, pmid), (price[:, 0], volume[:, 0], mid[:, 0])

    steps = jnp.arange(chunk, dtype=jnp.int32)
    if stats_only:
        (bid, ask, last, pmid, st), _ = jax.lax.scan(
            host_step, (bid, ask, last, pmid, st0), steps
        )
        return (bid[:M], ask[:M], last[:M], pmid[:M],
                stats_mod.MarketStats(*(x[:M] for x in st)))
    (bid, ask, last, pmid), (pp, vp, mp) = jax.lax.scan(
        host_step, (bid, ask, last, pmid), steps
    )
    return (bid[:M], ask[:M], last[:M], pmid[:M],
            pp.T[:M], vp.T[:M], mp.T[:M])
