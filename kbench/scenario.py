"""Ensemble columns from a configuration file and a run's seed.

A configuration file states the deployment: its sizes, the scenario blocks
that make up the ensemble (each a set of parameter values), the coupling,
the RNG seed compiled into the kernels, and the ranges from which each
episode draws its parameter values. This module turns that into per-market
parameter columns, plain NumPy arrays that the harness hands both to the
program (as an ``EnsembleSpec``) and to the reference.

Everything that varies from run to run is drawn from ``--seed``: which
market carries which block (a permutation of equal blocks) and each
episode's parameter values. The compiled RNG seed stays the file's.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

#: Parameter columns, with their dtype, in the program's ``MarketParams``
#: order, and the value each takes when a block does not set it (the
#: program's ``MarketConfig`` defaults, as resolved values).
FIELDS = (
    ("shock_step", np.int32, -1),
    ("shock_intensity", np.float32, 0.0),
    ("shock_cancel", np.float32, 0.0),
    ("p_marketable", np.float32, 0.1),
    ("q_max", np.float32, 8.0),
    ("noise_delta", np.float32, 8.0),
    ("maker_half_spread", np.float32, 2.0),
    ("fundamental", np.float32, None),        # the grid midpoint L // 2
    ("fundamentalist_kappa", np.float32, 0.5),
    ("num_makers", np.int32, None),           # from alpha_* below
    ("num_momentum", np.int32, None),
    ("num_fundamentalists", np.int32, None),
    ("num_whales", np.int32, None),
    ("num_hft", np.int32, None),
    ("num_informed", np.int32, None),
    ("num_arbitrageurs", np.int32, None),
    ("whale_size", np.float32, 32.0),
    ("whale_period", np.int32, 16),
    ("hft_threshold", np.float32, 0.2),
    ("informed_horizon", np.int32, 8),
    ("arb_kappa", np.float32, 0.5),
    ("coupling_peer", np.int32, -1),
)
DTYPES = {name: dt for name, dt, _ in FIELDS}

#: Archetype shares and the count column each resolves to:
#: count = round(num_agents * share), Python's round (half to even).
SHARES = {
    "alpha_maker": ("num_makers", 0.15),
    "alpha_momentum": ("num_momentum", 0.15),
    "alpha_fundamentalist": ("num_fundamentalists", 0.0),
    "alpha_whale": ("num_whales", 0.0),
    "alpha_hft": ("num_hft", 0.0),
    "alpha_informed": ("num_informed", 0.0),
    "alpha_arbitrageur": ("num_arbitrageurs", 0.0),
}
BOOK = {"initial_quote_qty": 10.0, "initial_spread": 2}


class Ensemble(NamedTuple):
    """Whole-ensemble columns: ``params[name]`` is [M]; ``labels`` names
    each market's block."""

    params: Dict[str, np.ndarray]
    quote_qty: np.ndarray
    spread: np.ndarray
    labels: List[str]


def block_values(config: dict, block: dict) -> dict:
    """One block's resolved values: file defaults, then the block's own."""
    vals = dict(config.get("defaults", {}))
    vals.update(block.get("set", {}))
    A, L = config["num_agents"], config["num_levels"]
    out = {name: default for name, _, default in FIELDS
           if default is not None}
    out["fundamental"] = float(L // 2)
    for share, (count, default) in SHARES.items():
        out[count] = int(round(A * float(vals.get(share, default))))
    for k, v in vals.items():
        if k in DTYPES:
            out[k] = v
        elif k not in SHARES and k not in BOOK:
            raise KeyError(f"unknown parameter {k!r} in block "
                           f"{block.get('label')!r}")
    if sum(out[c] for c, _ in SHARES.values()) > A:
        raise ValueError(f"block {block.get('label')!r} assigns more than "
                         f"num_agents={A} agents")
    out["initial_quote_qty"] = float(vals.get("initial_quote_qty",
                                              BOOK["initial_quote_qty"]))
    out["initial_spread"] = int(vals.get("initial_spread",
                                         BOOK["initial_spread"]))
    return out


def build(config: dict, rng: np.random.Generator) -> Ensemble:
    """The run's ensemble: equal blocks, permuted over the markets."""
    M = config["num_markets"]
    blocks = config["blocks"]
    if M % len(blocks):
        raise ValueError(f"num_markets={M} is not a multiple of the "
                         f"{len(blocks)} blocks")
    which = rng.permutation(np.repeat(np.arange(len(blocks)),
                                      M // len(blocks)))
    resolved = [block_values(config, b) for b in blocks]
    params = {name: np.array([resolved[b][name] for b in range(len(blocks))],
                             dtype=dt)[which] for name, dt, _ in FIELDS}
    coupling = config.get("coupling", {})
    if "ring_offset" in coupling:
        params["coupling_peer"] = ((np.arange(M) + coupling["ring_offset"])
                                   % M).astype(np.int32)
    quote = np.array([r["initial_quote_qty"] for r in resolved],
                     np.float32)[which]
    spread = np.array([r["initial_spread"] for r in resolved],
                      np.int32)[which]
    labels = [blocks[b]["label"] for b in which]
    return Ensemble(params, quote, spread, labels)


def draw_episode(config: dict, ens: Ensemble,
                 rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """One episode's parameter values: each draw in the file's
    ``episode_draws`` replaces one column for every market (only where the
    block sets it, with ``where_set``, e.g. a shock step)."""
    M = config["num_markets"]
    out = {}
    for d in config.get("episode_draws", []):
        name = d["field"]
        base = ens.params[name].astype(np.float64)
        if "scale" in d:
            val = base * rng.uniform(*d["scale"], M)
        elif "uniform" in d:
            val = rng.uniform(*d["uniform"], M)
        elif "integers" in d:
            lo, hi = d["integers"]
            val = rng.integers(lo, hi + 1, M).astype(np.float64)
        else:
            raise KeyError(f"draw for {name!r} names no distribution")
        if "clip" in d:
            val = np.clip(val, *d["clip"])
        if d.get("where_set"):
            val = np.where(base >= 0, val, base)
        out[name] = val.astype(DTYPES[name])
    return out


def episode_params(ens: Ensemble, drawn: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    """The ensemble's columns with one episode's draws in place."""
    return {**ens.params, **drawn}


def program_spec(config: dict, ens: Ensemble):
    """The program's ``EnsembleSpec`` for the ensemble (episode values go
    in with ``with_values``)."""
    from repro.core.params import EnsembleSpec, MarketParams

    return EnsembleSpec(
        num_markets=config["num_markets"], num_agents=config["num_agents"],
        num_levels=config["num_levels"], num_steps=config["num_steps"],
        seed=config["rng_seed"],
        params=MarketParams(**{name: ens.params[name].reshape(-1, 1)
                               for name, _, _ in FIELDS}),
        initial_quote_qty=ens.quote_qty, initial_spread=ens.spread,
        scenarios=tuple(ens.labels))


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """The run's seed: any whole number, negative ones included."""
    return np.random.SeedSequence(int(seed) % (1 << 64))
