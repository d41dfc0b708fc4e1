"""Plain NumPy reference of the market semantics the benchmark checks.

It imports nothing of the program under test. It is written from the
semantics (counter RNG, agent archetypes, order binning, uniform-price
call-auction clearing, residual book update, chunk-frozen coupling) in the
most direct form NumPy offers: a histogram (``bincount``) for the binning,
``cumsum`` for the cumulative books, ``argmax`` for the first maximiser and
the IEEE quotient for the book imbalance.

It simulates a block of rows (markets) for a list of chunks. A row whose
coupled peer is outside the block reads its own frozen mid instead; the
caller includes enough peer rows (see :func:`cone`) that every row it
compares is exact.

``ftype`` is the float type the arithmetic is carried out in: float32, the
configuration's stated precision, or ``ml_dtypes.bfloat16`` for the control
that must come out as not correct.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

# Archetype ids and the order in which leading agent blocks are assigned.
NOISE, MOMENTUM, MAKER, FUNDAMENTALIST = 0, 1, 2, 3
WHALE, HFT, INFORMED, ARBITRAGEUR = 4, 5, 6, 7
BLOCK_ORDER = ((MAKER, "num_makers"), (MOMENTUM, "num_momentum"),
               (FUNDAMENTALIST, "num_fundamentalists"),
               (WHALE, "num_whales"), (HFT, "num_hft"),
               (INFORMED, "num_informed"),
               (ARBITRAGEUR, "num_arbitrageurs"))
CH_SIDE, CH_PRICE, CH_MKT, CH_QTY, CH_SHOCK = 0, 1, 2, 3, 4

_U = np.uint32
_M1, _M2 = _U(0x7FEB352D), _U(0x846CA68B)
_GOLDEN, _K_GID = _U(0x9E3779B9), _U(0x85EBCA6B)
_K_STEP, _K_CHAN = _U(0xC2B2AE35), _U(0x27D4EB2F)


def _mix(x):
    x = x ^ (x >> _U(16))
    x = x * _M1
    x = x ^ (x >> _U(15))
    x = x * _M2
    return x ^ (x >> _U(16))


def _sel(mask, a, b):
    """``np.where(mask, a, b)`` for floats of one dtype, as a bit select:
    the same bits, without the branch per element NumPy's ``where`` takes
    on a random mask."""
    a, b = np.broadcast_arrays(a, b)
    ui = np.dtype(f"u{a.dtype.itemsize}")
    m = np.broadcast_to(mask, a.shape).astype(ui) * np.iinfo(ui).max
    return ((np.ascontiguousarray(a).view(ui) & m)
            | (np.ascontiguousarray(b).view(ui) & ~m)).view(a.dtype)


def _pick(mask, a, b):
    """``np.where(mask, a, b)`` for booleans, as boolean algebra."""
    return (mask & a) | (~mask & b)


class Rows(NamedTuple):
    """A block of markets to simulate.

    ``ids`` are the global market ids (the RNG coordinate); ``peer`` gives,
    per row, the row index inside the block of the market its arbitrageurs
    track (its own index for self-coupling or a peer outside the block);
    ``params`` maps each parameter name to a per-row array.
    """

    ids: np.ndarray
    peer: np.ndarray
    params: Dict[str, np.ndarray]
    quote_qty: np.ndarray
    spread: np.ndarray


def cone(check_rows: Iterable[int], coupling_peer: np.ndarray,
         num_chunks: int) -> Tuple[np.ndarray, np.ndarray]:
    """Rows needed to simulate ``check_rows`` exactly over ``num_chunks``
    chunks, and each one's depth.

    A peer's mid is frozen at each chunk entry, so a row depends on its peer
    one chunk back, on the peer's peer two chunks back, and so on. A row at
    depth ``d`` is needed only through chunk ``num_chunks - 1 - d``. Rows come
    back ordered by depth, the checked rows (depth 0) first.
    """
    peer = np.asarray(coupling_peer).reshape(-1)
    order = list(dict.fromkeys(int(r) for r in check_rows))
    depth = {r: 0 for r in order}
    frontier = list(order)
    for d in range(1, max(1, num_chunks)):
        nxt = []
        for r in frontier:
            p = int(peer[r])
            if p >= 0 and p not in depth:
                depth[p] = d
                nxt.append(p)
        if not nxt:
            break
        order += nxt
        frontier = nxt
    return (np.array(order, dtype=np.int64),
            np.array([depth[r] for r in order], dtype=np.int64))


def take_rows(rows: np.ndarray, params: Dict[str, np.ndarray],
              quote_qty: np.ndarray, spread: np.ndarray) -> Rows:
    """The :class:`Rows` block of global markets ``rows`` from whole-ensemble
    columns (``params[name]`` of shape [M] or [M, 1])."""
    rows = np.asarray(rows, dtype=np.int64)
    local = {int(g): i for i, g in enumerate(rows)}
    glob_peer = np.asarray(params["coupling_peer"]).reshape(-1)[rows]
    peer = np.array([local.get(int(p), i) if p >= 0 else i
                     for i, p in enumerate(glob_peer)], dtype=np.int64)
    sub = {k: np.asarray(v).reshape(-1)[rows] for k, v in params.items()}
    return Rows(ids=rows, peer=peer, params=sub,
                quote_qty=np.asarray(quote_qty).reshape(-1)[rows],
                spread=np.asarray(spread).reshape(-1)[rows])


def agent_types(params: Dict[str, np.ndarray], num_agents: int) -> np.ndarray:
    """[R, A] archetype per agent: leading blocks by ``BLOCK_ORDER``, the
    rest noise traders."""
    a = np.arange(num_agents)[None, :]
    out = np.full((len(params["num_makers"]), num_agents), NOISE, np.int32)
    lo = np.zeros((len(params["num_makers"]), 1), np.int64)
    for tid, field in BLOCK_ORDER:
        hi = lo + np.asarray(params[field], np.int64).reshape(-1, 1)
        out[(a >= lo) & (a < hi)] = tid
        lo = hi
    return out


def simulate(rows: Rows, *, num_agents: int, num_levels: int, seed: int,
             chunks: Sequence[Tuple[int, int]],
             orders: Optional[Dict[int, Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]]] = None,
             depth: Optional[np.ndarray] = None,
             ftype=np.float32) -> Dict[str, np.ndarray]:
    """Run ``rows`` from their opening books through ``chunks``.

    ``chunks`` is a list of ``(step0, n)``: absolute first step and step
    count, consecutive. ``orders`` maps an absolute step to one external
    limit order per row, ``(side_buy, tick, qty)``, added to that step's
    incoming flow. ``depth`` (from :func:`cone`, rows ordered by it) drops
    each row once no checked row depends on it any more; the result then
    covers the depth-0 rows only. Returns ``price``, ``volume`` and ``mid``
    paths, float32 [rows, steps].
    """
    ft = ftype
    R, A, L = len(rows.ids), int(num_agents), int(num_levels)
    depth = np.zeros(R, np.int64) if depth is None else np.asarray(depth)
    if (np.diff(depth) < 0).any():
        raise ValueError("rows must be ordered by depth")
    n_out = int((depth == 0).sum()) if depth.any() else R
    levels = np.arange(L)[None, :]
    agent = np.arange(A, dtype=np.int64)[None, :]

    # Row-wise operands, sliced as rows drop out.
    col = {k: np.asarray(v).reshape(-1, 1) for k, v in rows.params.items()}
    row = {"f_" + k: v.astype(np.float32).astype(ft) for k, v in col.items()}
    row.update({"i_" + k: v.astype(np.int64) for k, v in col.items()})
    row["atype"] = agent_types(rows.params, A)
    gid = (rows.ids.astype(np.int64)[:, None] * A + agent).astype(np.uint32)
    with np.errstate(over="ignore"):
        row["h_gid"] = _mix(((_U(seed & 0xFFFFFFFF) ^ _GOLDEN)
                             + gid * _K_GID))

    # Opening books: quotes straddle L/2 at ceil(spread / 2) ticks.
    spread = rows.spread.astype(np.int64)
    half = (spread // 2 + spread % 2)[:, None]
    q0 = rows.quote_qty.astype(np.float32).astype(ft)[:, None]
    zero, one, two = ft(0.0), ft(1.0), ft(2.0)
    bid = np.where(levels == L // 2 - half, q0, zero)
    ask = np.where(levels == L // 2 + half, q0, zero)
    last = np.full((R, 1), L // 2, np.float32).astype(ft)
    prev_mid = last.copy()

    def uniform(h_step, channel):
        with np.errstate(over="ignore"):
            bits = _mix(h_step + _U(channel) * _K_CHAN)
        hi24 = (bits >> _U(8)).astype(np.int32)
        return hi24.astype(ft) * ft(2.0 ** -24)

    out_p, out_v, out_m = [], [], []
    for c, (step0, n) in enumerate(chunks):
        live = int((depth <= len(chunks) - 1 - c).sum())
        peer_mid = prev_mid[rows.peer[:live]]
        peer_mid, bid, ask, last, prev_mid = (
            x[:live] for x in (peer_mid, bid, ask, last, prev_mid))
        row = {k: v[:live] for k, v in row.items()}
        f = {k[2:]: v for k, v in row.items() if k.startswith("f_")}
        i = {k[2:]: v for k, v in row.items() if k.startswith("i_")}
        atype, h_gid = row["atype"], row["h_gid"]
        is_maker = atype == MAKER
        # Each archetype's agents, as flat indices with their rows/agents.
        groups = {}
        for tid in range(ARBITRAGEUR + 1):
            idx = np.flatnonzero(atype.ravel() == tid)
            groups[tid] = (idx, idx // A, idx % A)
        row_off = (np.arange(live, dtype=np.int64) * L)[:, None]
        for step in range(step0, step0 + n):
            # Flash-crash withdrawal of resting bids at the shock step.
            at_shock = i["shock_step"] == step
            shocked = np.flatnonzero(at_shock[:, 0])
            if shocked.size:
                bid = bid.copy()
                bid[shocked] -= np.floor(bid[shocked]
                                         * f["shock_cancel"][shocked])
            has_b, has_a = bid > zero, ask > zero
            bb = np.where(has_b.any(axis=1),
                          L - 1 - np.argmax(has_b[:, ::-1], axis=1), -1)
            ba = np.where(has_a.any(axis=1), np.argmax(has_a, axis=1), L)
            bb, ba = bb[:, None], ba[:, None]
            ok = (bb >= 0) & (ba < L)
            mid = np.where(ok, (bb + ba).astype(ft) * ft(0.5), last)
            sb = bid.sum(axis=1, keepdims=True, dtype=ft)
            sa = ask.sum(axis=1, keepdims=True, dtype=ft)
            depth_ = sb + sa
            with np.errstate(invalid="ignore", divide="ignore"):
                imb = np.where(depth_ > zero, (sb - sa) / depth_, zero)

            with np.errstate(over="ignore"):
                h_step = _mix(h_gid + _U(step & 0xFFFFFFFF) * _K_STEP)
            coin = (uniform(h_step, CH_SIDE) < ft(0.5)).ravel()
            jit = (uniform(h_step, CH_PRICE) * two - one).ravel()

            # Each agent's side and limit price by its archetype.
            side = coin.copy()
            price = np.empty(live * A, dtype=ft)
            m = mid[:, 0]
            col = {k: v[:, 0] for k, v in f.items()}

            I, r, _ = groups[NOISE]
            price[I] = m[r] + jit[I] * col["noise_delta"][r]
            I, r, _ = groups[MOMENTUM]
            d = np.sign(m - prev_mid[:, 0])[r]
            s = _pick(d != zero, d > zero, coin[I])
            side[I], price[I] = s, m[r] + (s.astype(ft) * two - one)
            I, r, a = groups[MAKER]
            s = ((a + step) % 2) == 0
            half_spread = col["maker_half_spread"][r]
            side[I] = s
            price[I] = _sel(s, m[r] - half_spread, m[r] + half_spread)
            I, r, _ = groups[FUNDAMENTALIST]
            dev = (col["fundamental"] - m)[r]
            s = _pick(dev != zero, dev > zero, coin[I])
            side[I] = s
            price[I] = m[r] + dev * col["fundamentalist_kappa"][r] + jit[I]
            I, r, _ = groups[WHALE]
            price[I] = coin[I].astype(ft) * ft(L - 1)
            I, r, _ = groups[HFT]
            im = imb[:, 0][r]
            s = _pick(np.abs(im) > col["hft_threshold"][r], im > zero,
                      coin[I])
            side[I], price[I] = s, m[r] + (s.astype(ft) * two - one)
            I, r, _ = groups[INFORMED]
            window = ((i["shock_step"] >= 0)
                      & (step >= i["shock_step"] - i["informed_horizon"])
                      & (step < i["shock_step"]))[:, 0][r]
            side[I] = ~window & coin[I]
            price[I] = _sel(window, zero, m[r] + jit[I])
            I, r, _ = groups[ARBITRAGEUR]
            gap = (peer_mid[:, 0] - m)[r]
            s = _pick(gap != zero, gap > zero, coin[I])
            side[I] = s
            price[I] = m[r] + gap * col["arb_kappa"][r] + jit[I]

            # Marketable orders go to the edge of the grid (never makers').
            mkt = ((uniform(h_step, CH_MKT) < f["p_marketable"])
                   & ~is_maker).ravel()
            price = _sel(mkt, side.astype(ft) * ft(L - 1), price)
            # At the shock step, panicking non-makers sell at the bottom.
            if shocked.size:
                panic = ((uniform(h_step[shocked], CH_SHOCK)
                          < f["shock_intensity"][shocked])
                         & ~is_maker[shocked])
                side2, price2 = side.reshape(live, A), price.reshape(live, A)
                side2[shocked] &= ~panic
                price2[shocked] = _sel(panic, zero, price2[shocked])
            tick = np.clip(np.round(price), zero,
                           ft(L - 1)).astype(np.int64).reshape(live, A)
            side = side.reshape(live, A)
            qty = (one + np.floor(uniform(h_step, CH_QTY)
                                  * f["q_max"])).ravel()
            I, r, _ = groups[WHALE]
            sweep = (step % np.maximum(i["whale_period"][:, 0], 1)) == 0
            qty[I] = col["whale_size"][r] * sweep[r]
            qty = qty.reshape(live, A)

            # Binning: a histogram of quantity over (row, tick) per side.
            w_buy = (qty * side).astype(np.float64)
            w_sell = qty.astype(np.float64) - w_buy
            flat = (row_off + tick).ravel()
            buy = np.bincount(flat, w_buy.ravel(),
                              minlength=live * L).reshape(live, L)
            sell = np.bincount(flat, w_sell.ravel(),
                               minlength=live * L).reshape(live, L)
            tot_b, tot_a = bid + buy.astype(ft), ask + sell.astype(ft)
            if orders is not None and step in orders:
                o_side, o_tick, o_qty = (np.asarray(x)[:live]
                                         for x in orders[step])
                r = np.arange(live)
                o_qty = np.maximum(o_qty.astype(np.float32), 0).astype(ft)
                o_tick = np.clip(o_tick.astype(np.int64), 0, L - 1)
                o_side = o_side.astype(bool)
                tot_b[r[o_side], o_tick[o_side]] += o_qty[o_side]
                tot_a[r[~o_side], o_tick[~o_side]] += o_qty[~o_side]

            # Uniform-price clearing at the first tick of maximal volume.
            d_cum = np.cumsum(tot_b[:, ::-1], axis=1, dtype=ft)[:, ::-1]
            s_cum = np.cumsum(tot_a, axis=1, dtype=ft)
            match = np.minimum(d_cum, s_cum)
            vol = match.max(axis=1, keepdims=True)
            p_star = np.argmax(match, axis=1)[:, None]
            tb = np.minimum(tot_b, np.maximum(zero, vol - (d_cum - tot_b)))
            ta = np.minimum(tot_a, np.maximum(zero, vol - (s_cum - tot_a)))
            bid, ask = tot_b - tb, tot_a - ta
            last = np.where(vol > zero, p_star.astype(ft), last)
            prev_mid = mid
            out_p.append(last[:n_out])
            out_v.append(vol[:n_out])
            out_m.append(mid[:n_out])

    def cat(cols: List[np.ndarray]) -> np.ndarray:
        if not cols:
            return np.zeros((n_out, 0), np.float32)
        return np.concatenate(cols, axis=1).astype(np.float32)

    return {"price": cat(out_p), "volume": cat(out_v), "mid": cat(out_m)}


def chunk_plan(num_steps: int, chunk: int, step0: int = 0
               ) -> List[Tuple[int, int]]:
    """The ``(step0, n)`` chunks a session streams ``num_steps`` in."""
    out, t = [], 0
    while t < num_steps:
        n = min(chunk, num_steps - t)
        out.append((step0 + t, n))
        t += n
    return out
