"""Pure-jnp oracle for both Pallas kernels.

A single fused ``lax.scan`` over steps on full [M, L] arrays, using the same
shared step semantics. Kernel tests assert *bitwise* equality (not allclose)
against this oracle — valid because all accumulated quantities are exact
small integers in float32 (paper §IV-B's bitwise-identity argument).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import params as params_mod
from repro.core.config import MarketConfig
from repro.core.result import SimResult
from repro.core.step import initial_state, simulate_step


@functools.partial(jax.jit, static_argnames=("cfg", "scan"))
def _run(bid, ask, last, pmid, *, cfg: MarketConfig, scan: str):
    from repro.core.step import MarketState

    market_ids = jnp.arange(cfg.num_markets, dtype=jnp.int32)[:, None]

    def step(state, s):
        new_state, out = simulate_step(
            cfg, state, s, market_ids, jnp, scan=scan,
            params=params_mod.scalar_params(cfg, jnp))
        return new_state, (out.price[:, 0], out.volume[:, 0])

    state0 = MarketState(bid=bid, ask=ask, last_price=last, prev_mid=pmid)
    steps = jnp.arange(cfg.num_steps, dtype=jnp.int32)
    final, (pp, vp) = jax.lax.scan(step, state0, steps)
    return final.bid, final.ask, final.last_price, final.prev_mid, pp.T, vp.T


def simulate_reference(cfg: MarketConfig, scan: str = "cumsum") -> SimResult:
    state = initial_state(cfg, jnp)
    bid, ask, last, pmid, pp, vp = _run(
        state.bid, state.ask, state.last_price, state.prev_mid,
        cfg=cfg, scan=scan,
    )
    return SimResult(bid=bid, ask=ask, last_price=last, prev_mid=pmid,
                     price_path=pp, volume_path=vp)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _run_sequential(bid, ask, last, pmid, *, cfg: MarketConfig):
    from repro.core.sequential import simulate_step_sequential
    from repro.core.step import MarketState

    market_ids = jnp.arange(cfg.num_markets, dtype=jnp.int32)[:, None]

    def step(state, s):
        new_state, out = simulate_step_sequential(
            cfg, state, s, market_ids, jnp,
            params=params_mod.scalar_params(cfg, jnp))
        return new_state, (out.price[:, 0], out.volume[:, 0])

    state0 = MarketState(bid=bid, ask=ask, last_price=last, prev_mid=pmid)
    steps = jnp.arange(cfg.num_steps, dtype=jnp.int32)
    final, (pp, vp) = jax.lax.scan(step, state0, steps)
    return final.bid, final.ask, final.last_price, final.prev_mid, pp.T, vp.T


def simulate_reference_sequential(cfg: MarketConfig) -> SimResult:
    """Jitted sequential-clearing reference (Steinbacher et al.): identical
    agent decisions, order-by-order immediate matching instead of the
    uniform-price call auction. Bitwise-identical to the NumPy host loop
    with ``clearing="sequential"`` — see :mod:`repro.core.sequential` —
    so the parallel-vs-sequential mechanism gap is attributable to the
    clearing rule alone, not to the driver."""
    state = initial_state(cfg, jnp)
    bid, ask, last, pmid, pp, vp = _run_sequential(
        state.bid, state.ask, state.last_price, state.prev_mid, cfg=cfg,
    )
    return SimResult(bid=bid, ask=ask, last_price=last, prev_mid=pmid,
                     price_path=pp, volume_path=vp)
