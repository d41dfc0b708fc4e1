"""Wide price grids (up to ``MAX_LEVELS`` = 4096 levels): the refusal above
the limit, ``pallas-kinetic`` against ``numpy`` bitwise at L=2048 and 4096
for every level tile, and the tile policy that picks the level tile."""
import numpy as np
import pytest

from repro.core.config import MAX_LEVELS, MarketConfig
from repro.core.params import EnsembleSpec
from repro.core.session import Engine
from repro.kernels import autotune as tune

_NUMPY = {}


def _spec(num_levels):
    # Whale sweeps and marketable orders land on levels 0 and L - 1; the
    # flash crash withdraws bids at step 3.
    return EnsembleSpec.from_scenarios(
        ["flash-crash", "whale"], num_markets=4, num_agents=16,
        num_levels=num_levels, num_steps=6, seed=5)


def _run(backend, spec, **opts):
    with Engine(backend, chunk_size=4, **opts).open(spec) as sess:
        batch = sess.run(spec.num_steps).to_numpy()
        snap = sess.snapshot()
        tile = getattr(sess._runner, "tile", None)
    return batch, {f: np.asarray(snap[f])
                   for f in ("bid", "ask", "last_price", "prev_mid")}, tile


def test_grid_above_the_compiled_limit_is_refused():
    with pytest.raises(ValueError, match=str(MAX_LEVELS)):
        MarketConfig(num_levels=2 * MAX_LEVELS)
    with pytest.raises(ValueError, match=str(MAX_LEVELS)):
        _spec(2 * MAX_LEVELS)
    assert _spec(MAX_LEVELS).num_levels == MAX_LEVELS


@pytest.mark.parametrize("level_tile", [128, 512, None])
@pytest.mark.parametrize("num_levels", [2048, 4096])
def test_kinetic_matches_numpy_bitwise_on_wide_grid(num_levels, level_tile,
                                                    monkeypatch):
    spec = _spec(num_levels)
    if num_levels not in _NUMPY:
        _NUMPY[num_levels] = _run("numpy", spec)[:2]
    want_batch, want_state = _NUMPY[num_levels]
    # The tile policy's level tile, set by the test.
    monkeypatch.setattr(tune, "fitting_level_tile",
                        lambda *args, **kwargs: level_tile)
    batch, state, tile = _run("pallas-kinetic", spec, mb=8)
    assert tile.level_tile == level_tile
    for f, a, b in zip(batch._fields, batch, want_batch):
        assert (np.asarray(a) == np.asarray(b)).all(), f
    for f in want_state:
        assert (state[f] == want_state[f]).all(), f
    # The edge levels were traded on: the orders there were binned.
    assert want_batch.volume.sum() > 0


# Today's sweep grid at L=128, A=256 (mb x agent chunk, untiled).
L128_CANDIDATES = [(8, 64, None), (8, 128, None), (8, None, None),
                   (16, 64, None), (16, 128, None), (16, None, None)]


@pytest.mark.parametrize("num_agents", [256, 1024])
def test_candidates_at_128_levels_are_untiled(num_agents):
    cands = tune.candidate_tiles(8192, num_agents, num_levels=128, chunk=64)
    assert [(c.mb, c.agent_chunk, c.level_tile) for c in cands] == [
        (mb, ac, lt) for mb, ac, lt in L128_CANDIDATES]
    assert cands == tune.candidate_tiles(8192, num_agents)


@pytest.mark.parametrize("num_agents", [64, 256, 1024])
def test_sweep_times_only_tiles_that_fit(num_agents, monkeypatch):
    """At L=4096 every (MB, agent-chunk) pair is over the VMEM budget
    untiled: each is swept at its widest fitting level tile, a pair that
    fits at none (MB=16 with a one-hot narrower than a lane, or all of
    A=1024) is left out, and the span counts what was left out."""
    L, A, chunk = 4096, num_agents, 64
    grid = tune.candidate_tiles(2048, A)
    assert not any(tune.fits(c, L, A, chunk) for c in grid)
    cands = tune.candidate_tiles(2048, A, num_levels=L, chunk=chunk)
    assert 0 < len(cands) <= 6
    assert all(c.level_tile is not None and tune.fits(c, L, A, chunk)
               for c in cands)
    assert {(c.mb, c.agent_chunk) for c in cands} == {
        (c.mb, c.agent_chunk) for c in grid
        if tune.fits(c._replace(level_tile=tune.LANES), L, A, chunk)}
    assert any(c.mb == tune.SUBLANES for c in cands)
    spans, timed = [], []
    real_span = tune.span

    def recording_span(name, **args):
        spans.append((name, args))
        return real_span(name, **args)

    def time_candidate(c):
        timed.append(c)
        return float(c.mb)

    monkeypatch.setattr(tune, "span", recording_span)
    tune.clear_tune_cache()
    try:
        got = tune.autotune_tile(
            tune.tune_key(L, A, chunk, kernel="k"), time_candidate, cands,
            num_markets=2048, pruned=len(grid) - len(cands))
    finally:
        tune.clear_tune_cache()
    assert timed == cands and got in cands
    assert spans == [("kinetic.open.autotune",
                      {"candidates": len(cands),
                       "pruned": len(grid) - len(cands)})]


@pytest.mark.parametrize("num_levels", [4, 128, 1024, 2048, 4096])
@pytest.mark.parametrize("num_agents", [16, 256, 1024])
def test_heuristic_tile_fits_at_any_grid(num_levels, num_agents):
    tile = tune.auto_tile(2048, num_agents)
    lt = tune.fitting_level_tile(tile, num_levels, num_agents, 64)
    tile = tile._replace(level_tile=lt)
    assert tune.fits(tile, num_levels, num_agents, 64)
    if num_levels <= 128:
        assert lt is None


# Scoped VMEM, MiB, that Mosaic allocates for the chunk kernel (chunk 64)
# on a described v5e, bisected over its vmem_limit_bytes: (L, A, MB,
# agent chunk, level tile) -> the smallest limit it compiled under.
MOSAIC_VMEM_MIB = {
    (4096, 256, 8, None, None): 5.0,
    (4096, 256, 16, None, None): 7.0,
    (4096, 256, 16, None, 256): 9.0,
    (4096, 256, 8, 128, 2048): 5.0,
    (4096, 256, 8, 64, 512): 11.09,
    (4096, 256, 8, 64, 1024): 14.05,
    (4096, 256, 8, 64, 2048): 17.64,
    (4096, 256, 16, 64, 512): 32.26,
    (4096, 256, 16, 64, 1024): 33.52,
    (4096, 64, 8, None, 1024): 3.5,
    (2048, 256, 8, 64, None): 12.0,
    (1024, 256, 16, 64, 512): 9.5,
}


@pytest.mark.parametrize("shape", sorted(MOSAIC_VMEM_MIB, key=str))
def test_estimate_covers_what_mosaic_allocates(shape):
    """The estimate never falls below the measured allocation, so a tile
    it fits in the budget compiles under Mosaic's default limit; the
    one-hots narrower than a lane (Ac=64) are the tight cases."""
    L, A, mb, ac, lt = shape
    tile = tune.TileChoice(mb=mb, m_padded=mb, agent_chunk=ac,
                           level_tile=lt)
    assert (tune.estimate_vmem_bytes(tile, L, A, 64)
            >= MOSAIC_VMEM_MIB[shape] * (1 << 20))


def test_runner_resolves_a_fitting_level_tile():
    spec = _spec(4096)
    eng = Engine("pallas-kinetic", chunk_size=4)
    runner = eng._runner(spec, 4)
    tile = runner.tile
    assert tile.level_tile is not None and 4096 % tile.level_tile == 0
    assert tune.fits(tile, 4096, spec.num_agents, 4)


def test_runner_sweep_records_what_it_left_out(monkeypatch):
    """The runner's sweep at L=4096: the span counts the candidates it
    times and the pairs the VMEM estimate left out (here MB=16, whose
    16-wide one-hot fits at no level tile)."""
    spec = _spec(4096)
    spans = []
    real_span = tune.span

    def recording_span(name, **args):
        spans.append((name, args))
        return real_span(name, **args)

    monkeypatch.setattr(tune, "span", recording_span)
    monkeypatch.setattr(tune, "time_call", lambda fn, block: 1.0)
    tune.clear_tune_cache()
    try:
        runner = Engine("pallas-kinetic", chunk_size=4,
                        autotune=True)._runner(spec, 4)
    finally:
        tune.clear_tune_cache()
    assert spans == [("kinetic.open.autotune",
                      {"candidates": 1, "pruned": 1})]
    assert (runner.tile.mb, runner.tile.agent_chunk) == (8, None)
    assert tune.fits(runner.tile, 4096, spec.num_agents, 4)
