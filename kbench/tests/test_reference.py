"""The plain reference against the program's own backends, bitwise, on a
small heterogeneous ring-coupled ensemble: chunked streams, single steps
with external orders, and a sampled block of rows with its coupling cone."""
from __future__ import annotations

import numpy as np
import pytest

from kbench import reference, registry, scenario
from kbench.tests import tiny
from repro.core.session import Engine, ExternalOrders

CFG = dict(registry.cell("a256.sweep").config,
           **dict(tiny.CONFIG, num_agents=64, num_levels=32, num_steps=40))
CFG["blocks"] = [dict(b, set={k: (17 if k == "shock_step" else v)
                              for k, v in b["set"].items()})
                 for b in CFG["blocks"]]


@pytest.fixture(scope="module")
def ens():
    e = scenario.build(CFG, np.random.default_rng(5))
    drawn = scenario.draw_episode(
        dict(CFG, episode_draws=[d for d in CFG["episode_draws"]
                                 if d["field"] != "shock_step"]),
        e, np.random.default_rng(6))
    return e, drawn


def _bits_equal(a, b):
    return np.array_equal(np.asarray(a, np.float32).view(np.uint32),
                          np.asarray(b, np.float32).view(np.uint32))


def _program(backend, e, drawn, chunk, orders=None):
    spec = scenario.program_spec(CFG, e).with_values(**drawn)
    eng = Engine(backend)
    with eng.open(spec, chunk_size=chunk) as sess:
        if orders is None:
            b = sess.run(CFG["num_steps"]).to_numpy()
            return {"price": b.price, "volume": b.volume, "mid": b.mid}
        cols = [sess.step(ExternalOrders(*orders[t])).to_numpy()
                for t in range(CFG["num_steps"])]
    return {k: np.concatenate([getattr(c, k) for c in cols], axis=1)
            for k in ("price", "volume", "mid")}


def _reference(e, drawn, chunk, orders=None, rows=None, depth=None):
    params = scenario.episode_params(e, drawn)
    M = CFG["num_markets"]
    rows = np.arange(M) if rows is None else rows
    sub = None if orders is None else {
        t: tuple(np.asarray(x)[rows] for x in o) for t, o in orders.items()}
    return reference.simulate(
        reference.take_rows(rows, params, e.quote_qty, e.spread),
        num_agents=CFG["num_agents"], num_levels=CFG["num_levels"],
        seed=CFG["rng_seed"],
        chunks=reference.chunk_plan(CFG["num_steps"], chunk), orders=sub,
        depth=depth)


def _orders(seed):
    rng = np.random.default_rng(seed)
    M, L = CFG["num_markets"], CFG["num_levels"]
    return {t: (rng.random(M) < 0.5, rng.integers(0, L, M),
                rng.integers(0, 9, M).astype(np.float32))
            for t in range(CFG["num_steps"])}


@pytest.mark.parametrize("backend", ["numpy", "pallas-kinetic"])
def test_stream_matches_program(ens, backend):
    e, drawn = ens
    got = _program(backend, e, drawn, chunk=8)
    want = _reference(e, drawn, chunk=8)
    for k in ("price", "volume", "mid"):
        assert _bits_equal(got[k], want[k]), k


def test_steps_with_orders_match_program(ens):
    e, drawn = ens
    orders = _orders(11)
    got = _program("numpy", e, drawn, chunk=1, orders=orders)
    want = _reference(e, drawn, chunk=1, orders=orders)
    for k in ("price", "volume", "mid"):
        assert _bits_equal(got[k], want[k]), k


@pytest.mark.parametrize("chunk", [1, 8])
def test_cone_rows_match_whole_ensemble(ens, chunk):
    e, drawn = ens
    orders = _orders(12) if chunk == 1 else None
    full = _reference(e, drawn, chunk, orders)
    check = [3, 4, 11]
    n_chunks = len(reference.chunk_plan(CFG["num_steps"], chunk))
    rows, depth = reference.cone(check, e.params["coupling_peer"], n_chunks)
    assert list(rows[:3]) == check and (np.diff(depth) >= 0).all()
    part = _reference(e, drawn, chunk, orders, rows=rows, depth=depth)
    for k in ("price", "volume", "mid"):
        assert _bits_equal(part[k], full[k][check]), k


def test_cone_depth_follows_the_ring():
    peer = (np.arange(10) + 1) % 10
    rows, depth = reference.cone([2, 3], peer, 4)
    assert rows.tolist() == [2, 3, 4, 5, 6]
    assert depth.tolist() == [0, 0, 1, 2, 3]
    rows, depth = reference.cone([2], np.full(10, -1), 4)
    assert rows.tolist() == [2] and depth.tolist() == [0]
