"""Naive per-step kernel — the paper's "Naive Custom CUDA" ablation on TPU.

Identical device-side semantics (same ``agents.decide``, same
``auction.clear``, same RNG), but the central optimization removed:

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

Its one entry, :func:`naive_clearing_chunk`, takes the persistent entry's
operands, prepared by the same prologue
(:func:`repro.kernels.kinetic_clearing._chunk_operands`: padded sublane
tiles, explicit global ``market_ids``, per-market
:class:`repro.core.params.MarketParams` columns, the frozen peer mids), so
the Session/shard layers treat both engines uniformly. Its ``stats_only``
mode accumulates in the host scan's carry, since per-step launches are this
ablation's point.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import stats as stats_mod
from repro.core.params import MarketParams
from repro.core.step import MarketState, simulate_step
from repro.kernels.kinetic_clearing import (KERNEL_SCAN, NUM_PARAM_OPERANDS,
                                            _chunk_operands, pallas_call)


def _chunk_step_kernel_body(
    step_ref, mids_ref,
    bid_ref, ask_ref, last_ref, pmid_ref, ext_buy_ref, ext_ask_ref,
    peer_ref,
    *refs,
    cfg, mb: int, agent_chunk: Optional[int], level_tile: Optional[int],
):
    """One step for one tile of ``mb`` markets, with external-order inputs.

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
        agent_chunk=agent_chunk, level_tile=level_tile, params=params,
        peer_mid=peer_ref[...],
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
    *, cfg, chunk: int, params: MarketParams, mb: int = 8,
    interpret: bool = False, market_ids: Optional[jax.Array] = None,
    agent_chunk: Optional[int] = None, level_tile: Optional[int] = None,
    peer_mid: Optional[jax.Array] = None,
    stats: Optional[stats_mod.MarketStats] = None, stats_only: bool = False,
) -> Tuple[jax.Array, ...]:
    """Session entry for the launch-per-step regime: ``chunk`` kernel
    launches per call, state round-tripping HBM between launches.

    Takes :func:`kinetic_clearing_chunk`'s arguments and returns its
    outputs — ``step0``/``n_valid`` int32[1, 1] runtime scalars, the
    required per-market ``params`` operands (one trace serves any scenario
    mixture), external orders injected at the first local step, gated state
    so a partial tail advances exactly ``n_valid`` steps, and a
    ``stats_only`` mode (accumulated in the scan carry between launches) —
    but keeps the Θ(chunk) dispatches and Θ(chunk·M·L) HBM traffic that this
    ablation exists to exhibit. Not jitted here; the session runner owns jit.
    """
    M, L = bid.shape
    m_padded, rows, params, st0 = _chunk_operands(
        bid, ask, last, pmid, ext_buy, ext_ask, mb=mb, params=params,
        market_ids=market_ids, peer_mid=peer_mid, stats=stats,
        stats_only=stats_only)
    mids, bid, ask, last, pmid, ext_buy, ext_ask, peer_mid = rows
    grid = (m_padded // mb,)

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
                          agent_chunk=agent_chunk, level_tile=level_tile),
        name="naive_clearing_step",
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
