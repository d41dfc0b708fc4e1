"""The chunk kernels compile through Mosaic for a TPU v5e at real widths.

Nothing runs here: each test compiles for a *described* v5e chip (no
device attached), so what the TPU compiler would reject fails on any host,
at no chip time. Shapes are the paper's Table IV size (M=8192, A=256,
L=128, chunk=64), plus A=1024, and the widest grid the engine accepts
(M=2048, A=256, L=4096) at the tiles the policy picks there; the results
themselves are checked on the chip by ``chip_smoke.py`` and the benchmark.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, so the
worker that runs this file loads it and every other worker collects the
same tests without touching it.
"""
import numpy as np
import pytest

from repro.core import stats as stats_mod
from repro.core.params import EnsembleSpec, MarketParams
from repro.kernels.kinetic_clearing import kinetic_clearing_chunk
from repro.kernels.naive_clearing import naive_clearing_chunk

M, A, L, CHUNK = 8192, 256, 128, 64
KERNELS = {"kinetic": kinetic_clearing_chunk, "naive": naive_clearing_chunk}


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be cached but never read back.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(num_agents=A, num_markets=M, num_levels=L):
    return EnsembleSpec.from_scenarios(
        ["hft", "whale"], num_markets=num_markets // 2, num_agents=num_agents,
        num_levels=num_levels, num_steps=CHUNK, alpha_fundamentalist=0.05,
        alpha_arbitrageur=0.05)


def _shapes(spec, sharding, stats_only):
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    m, levels = spec.num_markets, spec.num_levels
    book, col, scalar = sds((m, levels)), sds((m, 1)), sds((1, 1), jnp.int32)
    params = MarketParams(*(sds((m, 1), MarketParams.field_dtype(f))
                            for f in MarketParams._fields))
    stats = stats_mod.MarketStats(*(col,) * 6) if stats_only else None
    return (book, book, col, col, scalar, scalar, book, book), params, stats


@pytest.mark.parametrize("num_agents", [A, 1024])
@pytest.mark.parametrize("stats_only", [False, True],
                         ids=["paths", "stats_only"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_chunk_kernel_compiles_for_v5e(kernel, stats_only, num_agents,
                                       one_chip):
    import jax

    spec = _spec(num_agents)
    args, params, stats = _shapes(spec, one_chip, stats_only)

    def run(*a, params, stats):
        return KERNELS[kernel](*a, params=params, stats=stats, cfg=spec,
                               chunk=CHUNK, mb=8, agent_chunk=128,
                               interpret=False, stats_only=stats_only)

    compiled = jax.jit(run).lower(*args, params=params, stats=stats).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Tiles at M=2048, L=4096, chunk 64, each at the level tile the policy fits
# to the VMEM budget: name -> (A, mb, agent chunk). The runner's own tile
# without a sweep at A=256 ("heuristic"), at A=64 (a one-hot narrower than
# a lane) and with agent_chunk=64 pinned; with "swept" (the sweep's pick on
# one v5e) and the rest, every candidate of the sweep at A=256.
WIDE_TILES = {"heuristic": (256, 8, 128), "heuristic-a64": (64, 8, None),
              "pinned-ac64": (256, 8, 64), "swept": (256, 16, None),
              "mb16-ac128": (256, 16, 128), "mb8-all-a": (256, 8, None)}
#: The runner's options that resolve each of its own tiles.
RUNNER_TILES = {"heuristic": {}, "heuristic-a64": {},
                "pinned-ac64": {"agent_chunk": 64}}
WIDE_CASES = ([(t, False) for t in sorted(WIDE_TILES)]
              + [("heuristic", True), ("swept", True)])


@pytest.mark.parametrize(
    "tile,stats_only", WIDE_CASES,
    ids=[f"{t}-{'stats_only' if so else 'paths'}" for t, so in WIDE_CASES])
def test_wide_grid_chunk_kernel_compiles_for_v5e(tile, stats_only, one_chip,
                                                 monkeypatch):
    """The kinetic chunk kernel at L=4096 at the tiles the policy picks:
    level-tiled binning, 12-stage scans over 4096 lanes, under Mosaic's
    default scoped VMEM limit."""
    import jax

    from repro.kernels import autotune as tune
    from repro.kernels import ops

    num_agents, mb, agent_chunk = WIDE_TILES[tile]
    spec = _spec(num_agents, num_markets=2048, num_levels=4096)
    choice = tune.TileChoice(mb=mb, m_padded=2048, agent_chunk=agent_chunk)
    choice = choice._replace(level_tile=tune.fitting_level_tile(
        choice, 4096, num_agents, CHUNK))
    assert choice.level_tile is not None
    assert tune.fits(choice, 4096, num_agents, CHUNK)
    if num_agents == A:
        assert choice in tune.candidate_tiles(2048, A, num_levels=4096,
                                              chunk=CHUNK)
    if tile in RUNNER_TILES:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        runner = ops.open_kinetic_runner(spec, CHUNK, autotune=False,
                                         **RUNNER_TILES[tile])
        assert runner.tile == choice
    args, params, stats = _shapes(spec, one_chip, stats_only)

    def run(*a, params, stats):
        return kinetic_clearing_chunk(
            *a, params=params, stats=stats, cfg=spec, chunk=CHUNK, mb=mb,
            agent_chunk=agent_chunk, level_tile=choice.level_tile,
            interpret=False, stats_only=stats_only)

    compiled = jax.jit(run).lower(*args, params=params, stats=stats).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wide_grid_tiles_cover_the_sweep():
    from repro.kernels import autotune as tune

    cands = tune.candidate_tiles(2048, A, num_levels=4096, chunk=CHUNK)
    assert {(c.mb, c.agent_chunk) for c in cands} == {
        (mb, ac) for a, mb, ac in WIDE_TILES.values() if a == A}


def test_sharded_ring_runner_compiles_for_v5e_2x2(topo, monkeypatch):
    """The Engine's 4-chip path: the market axis sharded over a 2x2 mesh,
    with the ``ppermute`` ring halo feeding ring-coupled arbitrageurs."""
    import jax
    from jax.sharding import Mesh

    from repro.core.step import MarketState
    from repro.kernels import ops
    from repro.launch.sharding import market_sharding
    from repro.scenario import CouplingSpec

    spec = CouplingSpec.ring(M).apply(_spec())
    mesh = Mesh(np.array(topo.devices), ("markets",))
    # The runner picks Mosaic from the default backend, which is the CPU
    # here; steer it to the TPU path for this compile only.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner = ops.open_kinetic_runner(spec, CHUNK, mb=8, mesh=mesh)
    assert not runner.interpret
    row = market_sharding(mesh)
    (bid, ask, last, pmid, step0, nvalid, eb, ea), params, _ = _shapes(
        spec, row, False)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    step0, nvalid = (jax.ShapeDtypeStruct((1, 1), step0.dtype, sharding=rep)
                     for _ in range(2))
    compiled = runner._chunk_fn.lower(
        MarketState(bid, ask, last, pmid), None, params, step0, nvalid,
        eb, ea).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


@pytest.mark.parametrize("kernel,chunk,name", [
    ("kinetic", CHUNK, "kinetic_clearing_chunk"),
    ("kinetic", 1, "kinetic_clearing_step"),
    ("naive", CHUNK, "naive_clearing_step"),
])
def test_kernel_carries_its_name_for_v5e(kernel, chunk, name, one_chip):
    """The Mosaic call is the HLO instruction a device trace shows by name."""
    import jax

    spec = _spec()
    args, params, stats = _shapes(spec, one_chip, False)

    def run(*a, params, stats):
        return KERNELS[kernel](*a, params=params, stats=stats, cfg=spec,
                               chunk=chunk, mb=8, agent_chunk=128,
                               interpret=False, stats_only=False)

    text = jax.jit(run).lower(*args, params=params, stats=stats).compile(
        ).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert all(line.lstrip().startswith(f"%{name}") for line in calls)
