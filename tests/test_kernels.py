"""Per-kernel shape/dtype sweeps against the pure-jnp oracle (bitwise).

Each kernel is reached through its one entry, the chunk entry the session
runner calls, run for a whole horizon in one chunk (``chunk = n_valid = S``)
with zero external orders and the config's parameters broadcast over the
markets.
"""
import dataclasses
import functools

import numpy as np
import pytest

from repro.core.config import MarketConfig
from repro.core.params import params_from_config
from repro.core.step import initial_state
from repro.kernels import ref
from repro.kernels.autotune import auto_tile, pad_to_multiple
from repro.kernels.kinetic_clearing import kinetic_clearing_chunk
from repro.kernels.naive_clearing import naive_clearing_chunk

FIELDS = ("bid", "ask", "last_price", "prev_mid", "price_path", "volume_path")
KERNELS = {"kinetic": kinetic_clearing_chunk, "naive": naive_clearing_chunk}


def _run_kernel(kernel_fn, cfg, mb):
    import jax
    import jax.numpy as jnp

    S = cfg.num_steps
    state = initial_state(cfg, jnp)
    horizon = jnp.full((1, 1), S, jnp.int32)
    zero_orders = jnp.zeros_like(state.bid)
    run = jax.jit(functools.partial(kernel_fn, cfg=cfg, chunk=S, mb=mb,
                                    interpret=True))
    out = run(state.bid, state.ask, state.last_price, state.prev_mid,
              jnp.zeros((1, 1), jnp.int32), horizon, zero_orders,
              zero_orders, params=params_from_config(cfg))
    # The mid path has no counterpart in the oracle's result.
    return tuple(np.asarray(o) for o in out[:len(FIELDS)])


@pytest.mark.parametrize("M,A,L,S", [
    (4, 8, 16, 5),
    (8, 16, 32, 10),
    (16, 33, 64, 8),     # A not divisible by L
    (6, 128, 128, 6),    # A == L (paper's benchmark grid size)
    (2, 300, 256, 4),    # A > 2L, L > 128 (multi-lane-register grid)
    (32, 5, 8, 12),      # tiny L
])
@pytest.mark.parametrize("kernel", ["kinetic", "naive"])
def test_kernel_shape_sweep(M, A, L, S, kernel):
    cfg = MarketConfig(num_markets=M, num_agents=A, num_levels=L,
                       num_steps=S, seed=M * 1000 + A)
    oracle = ref.simulate_reference(cfg).to_numpy()
    out = _run_kernel(KERNELS[kernel], cfg, mb=auto_tile(M).mb)
    for f, got in zip(FIELDS, out):
        want = np.asarray(getattr(oracle, f))
        assert got.shape == want.shape, f
        assert (got == want).all(), f"{kernel} {f} mismatch at {(M, A, L, S)}"


@pytest.mark.parametrize("mb", [1, 2, 4, 8])
def test_kinetic_tile_sweep(mb):
    cfg = MarketConfig(num_markets=8, num_agents=32, num_levels=32,
                       num_steps=6, seed=5)
    oracle = ref.simulate_reference(cfg).to_numpy()
    out = _run_kernel(kinetic_clearing_chunk, cfg, mb=mb)
    for f, got in zip(FIELDS, out):
        assert (got == np.asarray(getattr(oracle, f))).all()


@pytest.mark.parametrize("scan", ["cumsum", "hillis-steele"])
def test_kinetic_scan_modes(scan):
    """The kernel's Hillis-Steele scan against an oracle on either scan."""
    cfg = MarketConfig(num_markets=8, num_agents=64, num_levels=128,
                       num_steps=8, seed=9)
    oracle = ref.simulate_reference(cfg, scan=scan).to_numpy()
    out = _run_kernel(kinetic_clearing_chunk, cfg, mb=4)
    for f, got in zip(FIELDS, out):
        assert (got == np.asarray(getattr(oracle, f))).all()


def test_population_mix_sweep():
    """Fig 7 sweep axis: vary momentum fraction, all engines still agree."""
    for amom in (0.0, 0.3, 0.7):
        cfg = MarketConfig(num_markets=4, num_agents=40, num_levels=32,
                           num_steps=10, alpha_momentum=amom, seed=3)
        oracle = ref.simulate_reference(cfg).to_numpy()
        out = _run_kernel(kinetic_clearing_chunk, cfg, mb=4)
        for f, got in zip(FIELDS, out):
            assert (got == np.asarray(getattr(oracle, f))).all()


def test_volume_bounded_by_mantissa():
    """Paper §IV-B: accumulated tick volume must stay far below 2^24 so f32
    integer adds stay exact (the basis of the bitwise-identity claim)."""
    cfg = MarketConfig(num_markets=4, num_agents=256, num_levels=32,
                       num_steps=50, seed=2)
    r = ref.simulate_reference(cfg).to_numpy()
    assert r.bid.max() < 2**24 / 1024
    assert r.ask.max() < 2**24 / 1024


@pytest.mark.parametrize("kernel", ["kinetic", "naive"])
def test_interpret_mode_refused_on_tpu(kernel, monkeypatch):
    """On a TPU backend the runner picks Mosaic and the kernels refuse the
    interpreter, so nothing can time or ship it there by mistake."""
    import jax

    from repro.kernels import ops

    cfg = MarketConfig(num_markets=8, num_agents=8, num_levels=16,
                       num_steps=2, seed=1)
    open_runner = {"kinetic": ops.open_kinetic_runner,
                   "naive": ops.open_naive_runner}[kernel]
    assert open_runner(cfg, 2, mb=8).interpret  # CPU: interpret mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not open_runner(cfg, 2, mb=8).interpret
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        _run_kernel(KERNELS[kernel], cfg, mb=8)


@pytest.mark.parametrize("num_markets", [63, 13, 7, 3, 1])
@pytest.mark.parametrize("kernel", ["pallas-kinetic", "pallas-naive"])
def test_padded_tile_prime_m_regression(kernel, num_markets):
    """A prime, odd or tiny M runs full MB=8 tiles over a market axis padded
    to a multiple of 8 (M=63 the *same* tile shape as M=64, 8 grid cells),
    never a tile that divides M, and the padded run stays bitwise-identical
    to the unpadded oracle."""
    from repro.core.session import Engine

    eng = Engine(kernel)
    cfg = MarketConfig(num_markets=num_markets, num_agents=16, num_levels=32,
                       num_steps=6, seed=11)
    tile = eng._runner(cfg, 6).tile
    assert tile.mb == 8
    assert tile.m_padded == pad_to_multiple(num_markets, 8)
    if num_markets == 63:
        tile64 = eng._runner(dataclasses.replace(cfg, num_markets=64),
                             6).tile
        assert (tile.mb, tile.m_padded) == (tile64.mb, tile64.m_padded)

    oracle = ref.simulate_reference(cfg).to_numpy()
    got = eng.open(cfg).run_to_result(6).to_numpy()
    for f in FIELDS:
        assert (np.asarray(getattr(got, f))
                == np.asarray(getattr(oracle, f))).all(), f
