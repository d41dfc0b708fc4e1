"""Operations and bytes that the semantics needs, whatever implements it.

The counts are of the work a market step requires, not of the work this
implementation does: each agent's draws and decision, one add per order
into its side's price-level profile (not a one-hot over all ``L`` levels),
two cumulative scans, the clearing and the book update over ``L`` levels.
A kernel that bins or clears with fewer operations therefore never reads
above 100% of its roofline. Only steps whose results are kept count: a
launch that runs a whole chunk for fewer valid steps does work the
semantics does not need.
"""
from __future__ import annotations

from typing import NamedTuple

#: Per agent per step.
RNG_OPS = 10 * 6 + 3 * 5   # the step and five channel absorptions (an add
                           # and a multiply, then the 8-op mixer, each) and
                           # five 24-bit uniform conversions
DECIDE_OPS = 20            # own archetype rule (6), marketable and panic
                           # overlays (6), tick rounding and clipping (3),
                           # quantity (3), whale cadence (2)
BIN_OPS = 1                # one add into its side's price-level profile
AGENT_OPS = RNG_OPS + DECIDE_OPS + BIN_OPS
#: Per market per step per price level: shock withdrawal (4), best quotes
#: (4), depth sums (2), incoming flow joins the book (2), the two
#: cumulative scans (2), matched volume (1), its maximum (1), the first
#: maximiser (2), priority allocation (8), residual books (2).
LEVEL_OPS = 28
#: Per-market parameter columns read once per launch.
PARAM_COLUMNS = 22
F32 = 4


class Work(NamedTuple):
    ops: float
    bytes: float

    def __add__(self, other):
        return Work(self.ops + other.ops, self.bytes + other.bytes)


def launch(num_markets: int, num_agents: int, num_levels: int,
           steps: int, orders: bool = False) -> Work:
    """One kernel launch advancing ``num_markets`` markets ``steps`` valid
    steps (with one external order per market and step if ``orders``)."""
    M, A, L = num_markets, num_agents, num_levels
    per_step = A * AGENT_OPS + L * LEVEL_OPS + (1 if orders else 0)
    ops = M * steps * per_step
    books = 2 * 2 * M * L * F32             # bid and ask, in and out
    scalars = 2 * 2 * M * F32               # last price and mid, in and out
    params = PARAM_COLUMNS * M * F32
    ext = 3 * M * steps * F32 if orders else 0   # side, tick, quantity
    paths = 3 * M * steps * F32             # price, volume, mid out
    return Work(float(ops), float(books + scalars + params + ext + paths))


def least_seconds(work: Work, peak_flops: float, peak_bytes_per_s: float):
    """The least time the chip could take, and which of the two bounds it."""
    t_ops, t_bytes = work.ops / peak_flops, work.bytes / peak_bytes_per_s
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
