"""Paper §IV-B: bitwise identity across engines + statistical equivalence.

Table II reproduced: every backend sharing the kinetic RNG stream produces
*bitwise-identical* books; backends with different RNG streams (SplitMix64,
PCG64 — the paper's CPU reference) agree statistically.
"""
import numpy as np
import pytest

from repro.core import engine
from repro.core.config import MarketConfig
from repro.kernels import ref

CFG = MarketConfig(num_markets=16, num_agents=64, num_levels=64,
                   num_steps=40, seed=11)

FIELDS = ("bid", "ask", "last_price", "prev_mid", "price_path", "volume_path")

BITWISE_BACKENDS = ["numpy", "jax-scan", "jax-per-step", "pallas-naive",
                    "pallas-kinetic"]


@pytest.fixture(scope="module")
def reference():
    return ref.simulate_reference(CFG).to_numpy()


@pytest.mark.parametrize("backend", BITWISE_BACKENDS)
def test_bitwise_identity(backend, reference):
    r = engine.simulate(CFG, backend=backend).to_numpy()
    for f in FIELDS:
        a, b = getattr(r, f), getattr(reference, f)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a == b).all(), f"{backend}: field {f} differs"


def test_hillis_steele_mode_bitwise(reference):
    """The kernels' log-depth scan, selected on the XLA engine, against the
    cumsum reference."""
    r = engine.simulate(CFG, backend="jax-scan",
                        scan="hillis-steele").to_numpy()
    for f in FIELDS:
        assert (getattr(r, f) == getattr(reference, f)).all()


@pytest.mark.parametrize("backend", ["numpy-splitmix64", "numpy-pcg64"])
def test_statistical_equivalence(backend, reference):
    """Different RNG stream -> aggregate stats agree (paper: <0.1% at scale;
    looser here because the test config is far smaller than M=4096)."""
    from repro.core.result import SimResult

    r = engine.simulate(CFG, backend=backend).to_numpy()
    ref_r = SimResult(*reference)
    px_a, px_b = r.mean_clearing_price(), ref_r.mean_clearing_price()
    assert abs(px_a - px_b) / px_b < 0.05
    vol_a, vol_b = r.volume_per_market(), ref_r.volume_per_market()
    assert abs(vol_a - vol_b) / vol_b < 0.10


def test_tile_size_invariance():
    """Grid tiling must not change results (markets are independent)."""
    a = engine.simulate(CFG, backend="pallas-kinetic", mb=2).to_numpy()
    b = engine.simulate(CFG, backend="pallas-kinetic", mb=16).to_numpy()
    for f in FIELDS:
        assert (getattr(a, f) == getattr(b, f)).all()


def test_seed_reproducibility():
    a = engine.simulate(CFG, backend="pallas-kinetic").to_numpy()
    b = engine.simulate(CFG, backend="pallas-kinetic").to_numpy()
    for f in FIELDS:
        assert (getattr(a, f) == getattr(b, f)).all()


def test_seed_sensitivity():
    import dataclasses

    other = dataclasses.replace(CFG, seed=12)
    a = engine.simulate(CFG, backend="numpy").to_numpy()
    b = engine.simulate(other, backend="numpy").to_numpy()
    assert not (a.price_path == b.price_path).all()


class _SloppyQuotient(np.ndarray):
    """An f32 array whose division is off by ``ulps`` ulps in a random
    direction, like a divider that is not correctly rounded."""

    ulps = 0
    rng = np.random.default_rng(5)

    def __truediv__(self, other):
        q = np.asarray(self) / np.asarray(other)
        up = self.rng.random(q.shape) < 0.5
        for _ in range(self.ulps):
            q = np.where(up, np.nextafter(q, np.float32(np.inf)),
                         np.nextafter(q, np.float32(-np.inf)))
        return q.astype(np.float32)


class _SloppyNumpy:
    """numpy, with ``abs`` results dividing sloppily."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def abs(x):
        return np.abs(x).view(_SloppyQuotient)


def _ratio_operands():
    rng = np.random.default_rng(3)
    den = rng.integers(1, 2 ** 24, 200_000)
    num = np.round((rng.random(den.size) * 2 - 1) * den)
    d, n = np.meshgrid(np.arange(1, 301), np.arange(-300, 301))
    keep = np.abs(n) <= d
    return (np.concatenate([num, n[keep], [0.0]]).astype(np.float32),
            np.concatenate([den, d[keep], [7]]).astype(np.float32))


@pytest.mark.parametrize("ulps", [0, 1, 2])
def test_exact_ratio_is_the_ieee_quotient(ulps):
    """The imbalance division is correctly rounded even where the device's
    divider is not (TPU v5e is off by an ulp on about a third of them)."""
    from repro.core.step import exact_ratio

    num, den = _ratio_operands()
    _SloppyQuotient.ulps = ulps
    got = exact_ratio(num, den, _SloppyNumpy())
    want = num / den
    assert got.dtype == np.float32
    assert (got.view(np.uint32) == want.view(np.uint32)).all()


def test_exact_ratio_jnp_matches_numpy():
    import jax
    import jax.numpy as jnp

    from repro.core.step import exact_ratio

    num, den = _ratio_operands()
    got = np.asarray(jax.jit(lambda n, d: exact_ratio(n, d, jnp))(num, den))
    assert (got.view(np.uint32) == (num / den).view(np.uint32)).all()
