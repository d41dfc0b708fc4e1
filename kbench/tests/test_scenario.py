"""The seed draws the block permutation and the episode values, within
their ranges; the same seed gives the same inputs."""
from __future__ import annotations

import numpy as np

from kbench import registry, scenario


def _cfg():
    return registry.cell("a256.sweep").config


def test_same_seed_same_inputs_and_blocks_stay_equal():
    cfg = _cfg()
    a = scenario.build(cfg, np.random.default_rng(scenario.seed_sequence(9)))
    b = scenario.build(cfg, np.random.default_rng(scenario.seed_sequence(9)))
    c = scenario.build(cfg, np.random.default_rng(scenario.seed_sequence(10)))
    assert a.labels == b.labels and a.labels != c.labels
    counts = {lab: a.labels.count(lab) for lab in set(a.labels)}
    assert set(counts.values()) == {cfg["num_markets"] // len(cfg["blocks"])}
    for k in a.params:
        assert np.array_equal(a.params[k], b.params[k])


def test_counts_and_episode_ranges():
    cfg = _cfg()
    ens = scenario.build(cfg, np.random.default_rng(1))
    vals = scenario.block_values(cfg, cfg["blocks"][3])      # whale
    assert vals["num_makers"] == round(256 * 0.15) == 38
    assert vals["num_whales"] == 13 and vals["num_arbitrageurs"] == 13
    drawn = scenario.draw_episode(cfg, ens, np.random.default_rng(2))
    shock = ens.params["shock_step"]
    assert ((drawn["shock_step"] == -1) == (shock == -1)).all()
    assert (drawn["shock_step"][shock >= 0] >= 125).all()
    assert (drawn["shock_step"] < cfg["num_steps"]).all()
    assert ((drawn["q_max"] >= 4) & (drawn["q_max"] <= 8)).all()
    assert ((drawn["p_marketable"] >= 0) & (drawn["p_marketable"] <= 1)).all()
    spec = scenario.program_spec(cfg, ens).with_values(**drawn)
    assert spec.num_markets == cfg["num_markets"]


def test_negative_and_large_seeds():
    for s in (-3, 2 ** 31 + 7, 2 ** 40):
        scenario.seed_sequence(s).spawn(2)
