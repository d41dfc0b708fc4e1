"""The semantics' operation and byte counts against a count by hand, and
the peak table."""
from __future__ import annotations

import pytest

from kbench import peaks, work


def test_launch_counts_by_hand():
    # 2 markets, 4 agents, 8 levels, 3 valid steps.
    per_agent = (10 * 6 + 3 * 5) + 20 + 1          # draws, decision, bin
    per_step = 4 * per_agent + 8 * 28
    w = work.launch(2, 4, 8, 3)
    assert w.ops == 2 * 3 * per_step == 3648
    books, scalars, params = 2 * 2 * 2 * 8 * 4, 2 * 2 * 2 * 4, 22 * 2 * 4
    paths = 3 * 2 * 3 * 4
    assert w.bytes == books + scalars + params + paths == 536
    orders = work.launch(2, 4, 8, 3, orders=True)
    assert orders.ops == w.ops + 2 * 3
    assert orders.bytes == w.bytes + 3 * 2 * 3 * 4


def test_least_time_names_its_bound():
    w = work.Work(ops=197e12, bytes=1.0)
    assert work.least_seconds(w, 197e12, 819e9) == (1.0, "ops")
    w = work.Work(ops=1.0, bytes=819e9)
    assert work.least_seconds(w, 197e12, 819e9) == (1.0, "bytes")


def test_peak_table():
    p = peaks.peak("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
