"""The chunk kernels compile through Mosaic for a TPU v5e at real widths.

Nothing runs here: each test compiles for a *described* v5e chip (no
device attached), so what the TPU compiler would reject fails on any host,
at no chip time. Shapes are the paper's Table IV size (M=8192, A=256,
L=128, chunk=64), plus A=1024; the results themselves are checked on the
chip by ``chip_smoke.py``.

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


def _spec(num_agents=A):
    return EnsembleSpec.from_scenarios(
        ["hft", "whale"], num_markets=M // 2, num_agents=num_agents,
        num_levels=L, num_steps=CHUNK, alpha_fundamentalist=0.05,
        alpha_arbitrageur=0.05)


def _shapes(spec, sharding, stats_only):
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    m = spec.num_markets
    book, col, scalar = sds((m, L)), sds((m, 1)), sds((1, 1), jnp.int32)
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
