"""The comparison that decides ``correct``.

The episodes of the window are checked (all of them, or a sample drawn
from the seed where the traffic file says so): the reference reruns each
episode's sampled rows (with their coupling cone) from the opening books,
with the episode's parameter values and the orders the caller sent, and the
paths the host received must equal the reference's bit for bit. The program
states exact books, paths and statistics, so the number compared,
``paths_differing`` (entries of the price, volume and mid paths whose bits
differ), has the limit 0.

The control puts the reference, computed in bfloat16, in the program's
place: the same comparison must then come out as not correct.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from kbench import reference, scenario

#: Limits of the numbers compared (exact comparison).
LIMITS = {"paths_differing": 0}


def _merged(cfg: dict, ens: scenario.Ensemble, units: List, chunk: int,
            steps: int):
    """One reference run over several samples ``(episode, sample)``: they
    are independent, so their rows stack into one block (ordered by cone
    depth, as :func:`reference.simulate` drops rows by it)."""
    blocks = [reference.take_rows(s.rows, scenario.episode_params(
        ens, ep.drawn), ens.quote_qty, ens.spread) for ep, s in units]
    offs = np.cumsum([0] + [len(b.ids) for b in blocks])
    depth = np.concatenate([s.depth for _, s in units])
    order = np.argsort(depth, kind="stable")
    where = np.empty_like(order)
    where[order] = np.arange(order.size)
    peer = np.concatenate([b.peer + o for b, o in zip(blocks, offs)])
    rows = reference.Rows(
        ids=np.concatenate([b.ids for b in blocks])[order],
        peer=where[peer[order]],
        params={k: np.concatenate([b.params[k] for b in blocks])[order]
                for k in blocks[0].params},
        quote_qty=np.concatenate([b.quote_qty for b in blocks])[order],
        spread=np.concatenate([b.spread for b in blocks])[order])
    orders = None
    if any(s.orders for _, s in units):
        orders = {}
        for t in range(steps):
            parts = [s.orders.get(t, (np.zeros(len(s.rows), bool),
                                      np.zeros(len(s.rows), np.int64),
                                      np.zeros(len(s.rows), np.float32)))
                     for _, s in units]
            orders[t] = tuple(np.concatenate(x)[order] for x in zip(*parts))
    return dict(rows=rows, num_agents=cfg["num_agents"],
                num_levels=cfg["num_levels"], seed=cfg["rng_seed"],
                chunks=reference.chunk_plan(steps, chunk), orders=orders,
                depth=depth[order])


def _compare(cfg, ens, units, chunk, control: Optional[str]) -> int:
    """Differing path entries of the samples ``units`` against one
    reference run."""
    steps = max(ep.steps for ep, _ in units)
    kw = _merged(cfg, ens, units, chunk, steps)
    want = reference.simulate(**kw)
    if control is not None:
        import ml_dtypes

        cand = reference.simulate(**kw, ftype=getattr(ml_dtypes, control))
    diff, row = 0, 0
    for ep, s in units:
        sl = slice(row, row + s.n_check)
        row += s.n_check
        for k in ("price", "volume", "mid"):
            w = want[k][sl, :ep.steps]
            got = (cand[k][sl, :ep.steps] if control is not None else
                   np.concatenate(s.paths[k], axis=1))
            got = np.asarray(got, np.float32)
            if got.shape != w.shape:
                diff += w.size
                continue
            diff += int((got.view(np.uint32) != w.view(np.uint32)).sum())
    return diff


def run(cfg: dict, ens: scenario.Ensemble, episodes: List, chunk: int,
        sample: Optional[int] = None, rng: Optional[np.random.Generator] = None,
        control: Optional[str] = None) -> dict:
    """Check the episodes (``sample`` of them drawn with ``rng`` when given,
    else all); returns the numbers compared with their limits and whether
    all are within them. ``control`` names a lower precision
    (``"bfloat16"``) to put the reference in the program's place."""
    eps = [ep for ep in episodes if ep.steps]
    if sample is not None and sample < len(eps):
        pick = sorted(rng.choice(len(eps), sample, replace=False))
        eps = [eps[i] for i in pick]
    units = [(ep, s) for ep in eps for s in ep.samples]
    diff = _compare(cfg, ens, units, chunk, control) if units else 0
    return {
        "numbers": {"paths_differing": {"value": diff,
                                        "limit": LIMITS["paths_differing"]}},
        "correct": bool(units) and diff <= LIMITS["paths_differing"],
        "episodes": len(eps),
        "entries": 3 * sum(s.n_check * ep.steps for ep, s in units),
        "rows": sum(s.n_check for _, s in units),
    }
