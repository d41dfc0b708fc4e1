"""Cells, configurations, traffic mixes and metrics are found by name, and
an unknown name is refused."""
from __future__ import annotations

import pytest

from kbench import registry


def test_every_cell_resolves():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["mode"] in ("sweep", "step")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(registry.reader(m["name"]))
        assert cell.config.get("chips", 1) == cell.chips


@pytest.mark.parametrize("lookup", [
    lambda: registry.cell("no.such.cell"),
    lambda: registry.traffic("no-such-mix"),
    lambda: registry.reader("no_such_metric"),
])
def test_unknown_name_is_refused(lookup):
    with pytest.raises(KeyError):
        lookup()


def test_per_layer_metrics_name_cells_that_report_their_moves():
    bench = registry.benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        moves = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert "workloads" not in moves or w in moves["workloads"]
