"""Uniform-price call-auction clearing (paper §II-A, §IV-C), xp-polymorphic.

Pure clearing math shared verbatim by every backend. The allocation rule is
the closed form of the paper's priority-based allocation (§IV-C): orders with
limits strictly better than the clearing price fill first; the marginal level
p* is rationed. Verified against the paper's analytical L=5 ground truth in
tests/test_auction.py.
"""
from __future__ import annotations


def prefix_sum(x, xp):
    """Inclusive prefix sum over the last axis (cumulative supply)."""
    return xp.cumsum(x, axis=-1, dtype=x.dtype)


def suffix_sum(x, xp):
    """Inclusive suffix sum over the last axis (cumulative demand)."""
    return xp.flip(xp.cumsum(xp.flip(x, axis=-1), axis=-1, dtype=x.dtype), axis=-1)


def hillis_steele_prefix(x, xp):
    """Θ(log L)-depth Hillis–Steele inclusive prefix scan (paper §III-D).

    Faithful transcription of the kernel's strided shared-memory scan: at each
    stride ``off`` every lane accumulates the value ``off`` lanes behind it.
    Exact-integer float adds make this bitwise-identical to ``cumsum``.
    """
    L = x.shape[-1]
    off = 1
    while off < L:
        shifted = xp.concatenate(
            [xp.zeros(x.shape[:-1] + (off,), dtype=x.dtype), x[..., :-off]],
            axis=-1,
        )
        x = x + shifted
        off *= 2
    return x


def hillis_steele_suffix(x, xp):
    """Θ(log L)-depth suffix scan (reads ``off`` lanes ahead)."""
    L = x.shape[-1]
    off = 1
    while off < L:
        shifted = xp.concatenate(
            [x[..., off:], xp.zeros(x.shape[:-1] + (off,), dtype=x.dtype)],
            axis=-1,
        )
        x = x + shifted
        off *= 2
    return x


def best_quotes(bid, ask, last_price, xp):
    """Best bid/ask and mid price (paper Eq. 3).

    Returns (bb int32[M,1], ba int32[M,1], mid float32[M,1]); bb = -1 when no
    bids, ba = L when no asks; mid falls back to last_price.
    """
    L = bid.shape[-1]
    levels = xp.arange(L, dtype=xp.int32)
    has_bid = bid > xp.float32(0.0)
    has_ask = ask > xp.float32(0.0)
    bb = xp.max(xp.where(has_bid, levels, xp.int32(-1)), axis=-1, keepdims=True)
    ba = xp.min(xp.where(has_ask, levels, xp.int32(L)), axis=-1, keepdims=True)
    ok = (bb >= xp.int32(0)) & (ba < xp.int32(L))
    mid = xp.where(
        ok,
        (bb + ba).astype(xp.float32) * xp.float32(0.5),
        xp.asarray(last_price, dtype=xp.float32),
    )
    return bb, ba, mid


def clear(total_buy, total_ask, xp, scan="cumsum"):
    """Clear one step of the uniform-price call auction.

    Args:
      total_buy / total_ask: float32[..., L] aggregate resting+incoming books.
      scan: 'cumsum' (XLA native) or 'hillis-steele' (paper-faithful log-depth
        strided scan) — bitwise-identical results for exact-integer books.
        The Pallas kernels always use 'hillis-steele': Mosaic lowers
        neither ``cumsum`` nor the ``flip`` of the suffix sum.

    Returns dict with p_star int32[...,1], volume float32[...,1],
    new_bid/new_ask float32[...,L], traded_buy/traded_sell float32[...,L].
    """
    f32 = xp.float32
    if scan == "hillis-steele":
        d_cum = hillis_steele_suffix(total_buy, xp)
        s_cum = hillis_steele_prefix(total_ask, xp)
    else:
        d_cum = suffix_sum(total_buy, xp)
        s_cum = prefix_sum(total_ask, xp)

    match = xp.minimum(d_cum, s_cum)  # executable volume V(p)
    # p* is the first (lowest-price) maximizer — the paper's tournament
    # tie-break toward lower ticks — written as two reductions: an argmax
    # leaves the tie-break to each backend's lowering, and a gather of the
    # volume at p* does not lower in Mosaic.
    L = match.shape[-1]
    volume = xp.max(match, axis=-1, keepdims=True)
    levels = xp.arange(L, dtype=xp.int32)
    p_star = xp.min(xp.where(match == volume, levels, xp.int32(L)), axis=-1,
                    keepdims=True)

    # Priority allocation (closed form of paper §IV-C):
    #   demand strictly above p: d_cum[p] - total_buy[p]
    #   traded_buy[p] = min(total_buy[p], max(0, V - demand_above_p))
    zero = f32(0.0)
    demand_above = d_cum - total_buy
    traded_buy = xp.minimum(total_buy, xp.maximum(zero, volume - demand_above))
    supply_below = s_cum - total_ask
    traded_sell = xp.minimum(total_ask, xp.maximum(zero, volume - supply_below))

    new_bid = total_buy - traded_buy
    new_ask = total_ask - traded_sell
    return {
        "p_star": p_star,
        "volume": volume,
        "new_bid": new_bid,
        "new_ask": new_ask,
        "traded_buy": traded_buy,
        "traded_sell": traded_sell,
    }
