"""Scenario subsystem: coupling, new archetypes, sequential clearing,
stylized-facts validation gate, and the session/ensemble satellites.

Multi-device coverage mirrors tests/test_distributed.py: subprocess probes
force 2 host devices for tier-1, `@pytest.mark.distributed` cases run
in-process under the CI distributed tier. The full pinned realism gate is
`@pytest.mark.scenario` (CI `scenario` tier; tier-1 runs the fast checks).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine
from repro.core.config import MarketConfig, scenario_config
from repro.core.params import EnsembleSpec
from repro.core.session import Engine
from repro.kernels import ref
from repro.scenario import (
    CouplingSpec,
    FactCheck,
    ValidationReport,
    coupled_ensemble,
    mechanism_gap,
    validate_spec,
)
from repro.scenario.sequential import GAP_METRICS

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

FIELDS = ("bid", "ask", "last_price", "prev_mid", "price_path", "volume_path")


def _run_probe(code: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _device_count() -> int:
    import jax

    return len(jax.devices())


# ---------------------------------------------------------------------------
# CouplingSpec: construction and validation.
# ---------------------------------------------------------------------------

def test_coupling_none_is_all_self():
    spec = CouplingSpec.none(5)
    assert (spec.peer == -1).all()
    assert spec.num_markets == 5
    assert spec.coupled_markets.size == 0


def test_coupling_ring():
    spec = CouplingSpec.ring(4)
    assert spec.peer.tolist() == [1, 2, 3, 0]
    assert spec.coupled_markets.tolist() == [0, 1, 2, 3]
    back = CouplingSpec.ring(4, offset=-1)
    assert back.peer.tolist() == [3, 0, 1, 2]


def test_coupling_ring_rejects_degenerate():
    with pytest.raises(ValueError, match=">= 2 markets"):
        CouplingSpec.ring(1)
    with pytest.raises(ValueError, match="multiple of num_markets"):
        CouplingSpec.ring(4, offset=8)


def test_coupling_pairs():
    spec = CouplingSpec.pairs(6, [(0, 3), (1, 5)])
    assert spec.peer.tolist() == [3, 5, -1, 0, -1, 1]
    with pytest.raises(ValueError, match="itself"):
        CouplingSpec.pairs(4, [(2, 2)])
    with pytest.raises(ValueError, match="more than one pair"):
        CouplingSpec.pairs(4, [(0, 1), (1, 2)])
    with pytest.raises(ValueError, match="out of range"):
        CouplingSpec.pairs(4, [(0, 7)])


def test_coupling_explicit_and_bounds():
    spec = CouplingSpec.explicit({0: 2, 2: 0}, 3)
    assert spec.peer.tolist() == [2, -1, 0]
    with pytest.raises(ValueError, match="peer ids must be -1"):
        CouplingSpec(np.array([0, 9], np.int32))
    with pytest.raises(ValueError, match="at least one market"):
        CouplingSpec(np.array([], np.int32))


def test_coupling_apply_checks_width():
    spec = EnsembleSpec.coerce(MarketConfig(num_markets=4, num_agents=8,
                                            num_steps=4))
    with pytest.raises(ValueError, match="over 6 markets"):
        CouplingSpec.ring(6).apply(spec)
    coupled = coupled_ensemble(spec, CouplingSpec.ring(4))
    assert np.asarray(coupled.params.coupling_peer).ravel().tolist() \
        == [1, 2, 3, 0]
    # same static key -> same warm executable
    assert coupled.static_key() == spec.static_key()


# ---------------------------------------------------------------------------
# Coupled dynamics: the arbitrage channel bites, and only when populated.
# ---------------------------------------------------------------------------

def _arb_config(**kw):
    base = dict(num_markets=6, num_agents=32, num_levels=32, num_steps=16,
                seed=3, alpha_maker=0.15, alpha_arbitrageur=0.25,
                noise_delta=4.0, p_marketable=0.25)
    base.update(kw)
    return MarketConfig(**base)


def test_coupling_changes_arbitrageur_trajectories():
    """Coupling must bite once peer mids diverge. The peer mid freezes at
    chunk entry, and at step 0 every market still quotes the same opening
    mid (self gap == peer gap), so the run needs more than one chunk."""
    spec = EnsembleSpec.coerce(_arb_config())

    def run(s):
        with Engine("numpy", chunk_size=4).open(s) as sess:
            return sess.run(s.num_steps).to_numpy()

    base = run(spec)
    coupled = run(CouplingSpec.ring(6).apply(spec))
    assert not (np.asarray(base.price) == np.asarray(coupled.price)).all()


def test_coupling_inert_without_arbitrageurs():
    """Applying a coupling to an arb-free spec is bitwise a no-op."""
    spec = EnsembleSpec.coerce(_arb_config(alpha_arbitrageur=0.0))
    base = engine.simulate(spec, backend="numpy").to_numpy()
    coupled = engine.simulate(CouplingSpec.ring(6).apply(spec),
                              backend="numpy").to_numpy()
    for f in FIELDS:
        assert (getattr(base, f) == getattr(coupled, f)).all(), f


@pytest.mark.parametrize("backend", ["jax-scan", "jax-per-step",
                                     "pallas-naive", "pallas-kinetic"])
def test_coupled_backend_parity(backend):
    """Coupled runs are bitwise identical across the counter-RNG backends
    when the chunk lengths (= peer-mid freeze boundaries) agree."""
    spec = CouplingSpec.ring(6).apply(EnsembleSpec.coerce(_arb_config()))

    def run(b):
        with Engine(b, chunk_size=4).open(spec) as s:
            return s.run(spec.num_steps).to_numpy()

    want, got = run("numpy"), run(backend)
    for f, a, b in zip(want._fields, want, got):
        assert (np.asarray(a) == np.asarray(b)).all(), (backend, f)


def test_coupled_sessions_share_warm_executable():
    """Rewiring / toggling the coupling is a value change: sessions over
    any coupling graph of the same spec reuse one compiled executable."""
    spec = EnsembleSpec.coerce(_arb_config())
    eng = Engine("jax-scan", chunk_size=4)
    with eng.open(CouplingSpec.ring(6).apply(spec)) as s:
        s.run(spec.num_steps)
    warm = eng.trace_count
    for coupling in (CouplingSpec.none(6), CouplingSpec.pairs(6, [(0, 5)]),
                     CouplingSpec.ring(6, offset=2)):
        with eng.open(coupling.apply(spec)) as s:
            s.run(spec.num_steps)
    assert eng.trace_count == warm


# ---------------------------------------------------------------------------
# Sharded halo exchange: single-device == 2-device, bitwise (subprocess
# probes for tier-1, in-process variants for the distributed CI tier).
# ---------------------------------------------------------------------------

# Odd M across 2 devices (pads on the sharded layout), ring coupling so
# every shard boundary is a cross-device edge, chunk boundary mid-run.
_COUPLED_CFG = ("dict(num_markets=10, num_agents=16, num_levels=32, "
                "num_steps=20, seed=7, alpha_maker=0.15, "
                "alpha_arbitrageur=0.25, noise_delta=4.0)")

_COUPLED_PARITY_CODE = textwrap.dedent(f"""
    import numpy as np, jax
    from repro.core.config import MarketConfig
    from repro.core.params import EnsembleSpec
    from repro.core.session import Engine
    from repro.scenario import CouplingSpec
    assert len(jax.devices()) >= 2, jax.devices()
    spec = CouplingSpec.ring(10).apply(
        EnsembleSpec.coerce(MarketConfig(**{_COUPLED_CFG})))

    def run(**opts):
        eng = Engine("pallas-kinetic", chunk_size=6, **opts)
        with eng.open(spec) as s:
            batch = s.run(spec.num_steps).to_numpy()
        return batch, eng

    single, _ = run()
    sharded, eng = run(devices=2)
    for f, a, b in zip(single._fields, single, sharded):
        assert (np.asarray(a) == np.asarray(b)).all(), f
    # warm coupled re-run on the sharded engine: no retrace
    warm = eng.trace_count
    with eng.open(CouplingSpec.ring(10, offset=3).apply(spec)) as s:
        s.run(spec.num_steps)
    assert eng.trace_count == warm, (eng.trace_count, warm)
    print("OK")
""")


def test_coupled_sharded_bitwise_parity_subprocess():
    """Ring-coupled ensemble: the ppermute halo exchange reproduces the
    single-device gather bitwise, and rewired coupled runs stay warm."""
    out = _run_probe(_COUPLED_PARITY_CODE, devices=2)
    assert out.strip().splitlines()[-1] == "OK"


@pytest.mark.distributed
@pytest.mark.parametrize("backend", ["pallas-kinetic", "pallas-naive"])
def test_coupled_sharded_bitwise_parity_inprocess(backend):
    if _device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    spec = CouplingSpec.ring(10).apply(EnsembleSpec.coerce(MarketConfig(
        num_markets=10, num_agents=16, num_levels=32, num_steps=20, seed=7,
        alpha_maker=0.15, alpha_arbitrageur=0.25, noise_delta=4.0)))

    def run(**opts):
        with Engine(backend, chunk_size=6, **opts).open(spec) as s:
            return s.run(spec.num_steps).to_numpy()

    single, sharded = run(), run(devices=2)
    for f, a, b in zip(single._fields, single, sharded):
        assert (np.asarray(a) == np.asarray(b)).all(), (backend, f)


# ---------------------------------------------------------------------------
# Sequential-clearing reference.
# ---------------------------------------------------------------------------

_SEQ_CFG = MarketConfig(num_markets=8, num_agents=24, num_levels=32,
                        num_steps=12, seed=5, alpha_maker=0.15,
                        alpha_momentum=0.15)


def test_sequential_numpy_matches_jax_reference_bitwise():
    """The NumPy host loop and the jitted lax.fori_loop reference are
    bitwise identical (exact-integer f32 arithmetic)."""
    host = engine.simulate(_SEQ_CFG, backend="numpy",
                           clearing="sequential").to_numpy()
    jitted = ref.simulate_reference_sequential(_SEQ_CFG).to_numpy()
    for f in FIELDS:
        a, b = getattr(host, f), getattr(jitted, f)
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert (a == b).all(), f


def test_sequential_differs_from_parallel():
    """The mechanism itself must matter: same decisions, different books."""
    par = engine.simulate(_SEQ_CFG, backend="numpy").to_numpy()
    seq = engine.simulate(_SEQ_CFG, backend="numpy",
                          clearing="sequential").to_numpy()
    assert not (par.price_path == seq.price_path).all()


def test_sequential_book_masses_stay_integral():
    """Fills are exact integers in f32: books never accumulate dust."""
    seq = engine.simulate(_SEQ_CFG, backend="numpy",
                          clearing="sequential").to_numpy()
    for f in ("bid", "ask", "volume_path"):
        arr = np.asarray(getattr(seq, f))
        assert (arr == np.round(arr)).all(), f
        assert (arr >= 0).all(), f


def test_sequential_rejects_unknown_mode():
    with pytest.raises(ValueError, match="clearing"):
        engine.simulate(_SEQ_CFG, backend="numpy", clearing="continuous")


def test_mechanism_gap_reports_all_metrics():
    row = mechanism_gap(_SEQ_CFG)
    for m in GAP_METRICS:
        for suffix in ("parallel", "sequential", "delta"):
            assert f"{m}_{suffix}" in row, (m, suffix)
        assert row[f"{m}_delta"] == pytest.approx(
            row[f"{m}_sequential"] - row[f"{m}_parallel"])
    # the parallel column is the production engine's own numbers
    want = engine.simulate(_SEQ_CFG, backend="numpy").to_numpy()
    assert row["mean_clearing_price_parallel"] == pytest.approx(
        want.mean_clearing_price())
    # and the mechanisms genuinely disagree somewhere
    assert any(row[f"{m}_delta"] != 0.0 for m in GAP_METRICS)


# ---------------------------------------------------------------------------
# Stylized-facts gate: typed checks (fast); the pinned CI gate is
# scenario-marked (it runs four 64x500 simulations).
# ---------------------------------------------------------------------------

def test_factcheck_semantics():
    ok = FactCheck.check("kurt", 4.2, ">", 3.0)
    assert ok.passed and "PASS" in str(ok)
    bad = FactCheck.check("kurt", 2.0, ">", 3.0)
    assert not bad.passed and "FAIL" in str(bad)
    assert not FactCheck.check("nan", float("nan"), ">", -1e9).passed
    with pytest.raises(ValueError, match="op"):
        FactCheck.check("kurt", 1.0, ">=", 0.0)


def test_validation_report_structure():
    cfg = scenario_config("high-vol", num_markets=8, num_agents=32,
                          num_steps=24, alpha_maker=0.15,
                          alpha_momentum=0.4, seed=1)
    rep = validate_spec(cfg, backend="numpy", min_excess_kurtosis=-100.0,
                        min_vv_corr=-2.0, require_acf_decay=False)
    assert isinstance(rep, ValidationReport)
    assert rep.scenario == "high-vol" and rep.passed
    assert rep.failures == ()
    d = rep.to_dict()
    assert d["passed"] and {c["name"] for c in d["checks"]} \
        == {"excess_kurtosis", "volume_volatility_corr"}
    # an unsatisfiable threshold flips the report
    rep2 = validate_spec(cfg, backend="numpy", min_vv_corr=2.0,
                         require_acf_decay=False)
    assert not rep2.passed
    assert [c.name for c in rep2.failures] == ["volume_volatility_corr"]
    assert "FAIL" in rep2.summary()


@pytest.mark.scenario
def test_pinned_mixtures_pass_realism_gate():
    """The CI realism gate: every pinned mixture exhibits fat tails,
    positive volume/volatility correlation, and a decaying |r| ACF, and
    the path moments agree with the in-kernel statistics accumulators."""
    from repro.scenario import validate_pinned

    reports = validate_pinned("jax-scan", stats_check=True)
    assert set(reports) == {"high-vol-momentum", "whale", "hft", "informed"}
    failed = {n: r.summary() for n, r in reports.items() if not r.passed}
    assert not failed, failed


# ---------------------------------------------------------------------------
# New-archetype behavior units.
# ---------------------------------------------------------------------------

def test_whale_cadence_drives_volume_spikes():
    cfg = scenario_config("whale", num_markets=16, num_agents=64,
                          num_steps=48, seed=9)
    r = engine.simulate(cfg, backend="numpy").to_numpy()
    vol = np.asarray(r.volume_path)
    steps = np.arange(cfg.num_steps)
    sweep = (steps % cfg.whale_period) == 0
    assert vol[:, sweep].mean() > 1.5 * vol[:, ~sweep].mean()


def test_hft_joins_the_pressure_side():
    from repro.core import agents
    from repro.core import params as params_mod

    cfg = MarketConfig(num_markets=4, num_agents=8, num_levels=32,
                       num_steps=4, alpha_maker=0.0, alpha_momentum=0.0,
                       alpha_hft=1.0, hft_threshold=0.3, p_marketable=0.0,
                       seed=2)
    p = params_mod.scalar_params(cfg, np)
    mid = np.full((4, 1), 16.0, np.float32)
    ids = np.arange(4, dtype=np.int32).reshape(-1, 1)
    agent_ids = np.arange(8, dtype=np.int32)
    # beyond-threshold bid pressure -> every HFT buys one tick through mid
    imb = np.array([[0.9], [-0.9], [0.9], [-0.9]], np.float32)
    side, price, qty = agents.decide(cfg, p, mid, mid, np.int32(1), ids,
                                     agent_ids, np, imbalance=imb)
    assert side[0].all() and side[2].all()
    assert (~side[1]).all() and (~side[3]).all()
    assert (price[0] == 17).all() and (price[1] == 15).all()
    # below threshold the side is the noise draw, not the imbalance sign
    calm, _, _ = agents.decide(cfg, p, mid, mid, np.int32(1), ids,
                               agent_ids, np,
                               imbalance=np.full((4, 1), 0.1, np.float32))
    assert 0 < calm.sum() < calm.size


def test_informed_sell_window_before_shock():
    from repro.core import agents
    from repro.core import params as params_mod

    cfg = MarketConfig(num_markets=2, num_agents=16, num_levels=32,
                       num_steps=20, alpha_maker=0.0, alpha_momentum=0.0,
                       alpha_informed=1.0, shock_step=10,
                       informed_horizon=4, shock_intensity=0.3, seed=2)
    p = params_mod.scalar_params(cfg, np)
    mid = np.full((2, 1), 16.0, np.float32)
    ids = np.arange(2, dtype=np.int32).reshape(-1, 1)
    agent_ids = np.arange(16, dtype=np.int32)
    # inside [shock-horizon, shock): everyone sells marketably at level 0
    side, price, _ = agents.decide(cfg, p, mid, mid, np.int32(7), ids,
                                   agent_ids, np)
    assert (~side).all() and (price == 0).all()
    # outside the window: noise-like (both sides appear)
    side2, price2, _ = agents.decide(cfg, p, mid, mid, np.int32(2), ids,
                                     agent_ids, np)
    assert 0 < side2.sum() < side2.size
    assert (price2 > 0).any()


def test_arbitrageur_chases_peer_gap():
    from repro.core import agents
    from repro.core import params as params_mod

    cfg = MarketConfig(num_markets=2, num_agents=8, num_levels=32,
                       num_steps=4, alpha_maker=0.0, alpha_momentum=0.0,
                       alpha_arbitrageur=1.0, seed=2)
    p = params_mod.scalar_params(cfg, np)
    mid = np.full((2, 1), 16.0, np.float32)
    ids = np.arange(2, dtype=np.int32).reshape(-1, 1)
    agent_ids = np.arange(8, dtype=np.int32)
    peer = np.array([[20.0], [12.0]], np.float32)
    side, _, _ = agents.decide(cfg, p, mid, mid, np.int32(1), ids,
                               agent_ids, np, peer_mid=peer)
    assert side[0].all()      # peer above -> buy
    assert (~side[1]).all()   # peer below -> sell


# ---------------------------------------------------------------------------
# Satellites: session horizon error, NaN/inf rejection, snapshot
# back-compat.
# ---------------------------------------------------------------------------

def test_run_past_horizon_names_cursor_and_remaining():
    cfg = MarketConfig(num_markets=2, num_agents=8, num_steps=6, seed=1)
    with Engine("numpy").open(cfg) as s:
        s.run(6)
        with pytest.raises(ValueError) as exc:
            s.run(None)
    msg = str(exc.value)
    assert "step 6" in msg and "0 steps remaining" in msg
    assert "num_steps=6" in msg and "explicit n_steps" in msg


def test_with_values_rejects_non_finite_naming_field():
    spec = EnsembleSpec.coerce(MarketConfig(num_markets=3, num_agents=8,
                                            num_steps=4))
    with pytest.raises(ValueError, match=r"params\.noise_delta"):
        spec.with_values(noise_delta=float("nan"))
    with pytest.raises(ValueError, match=r"params\.arb_kappa"):
        spec.with_values(arb_kappa=[1.0, float("inf"), 1.0])


def test_product_rejects_non_finite_naming_field():
    base = MarketConfig(num_markets=2, num_agents=8, num_steps=4)
    with pytest.raises(ValueError, match=r"params\.fundamentalist_kappa"):
        EnsembleSpec.product(base,
                             {"fundamentalist_kappa": [0.1, float("nan")]})


def test_snapshot_restore_without_new_param_columns():
    """Snapshots written before the scenario engine (no archetype counts,
    no coupling column) restore with the inert defaults and continue the
    exact stream."""
    cfg = MarketConfig(num_markets=4, num_agents=16, num_levels=32,
                       num_steps=12, seed=6, alpha_maker=0.15)
    eng = Engine("numpy")
    with eng.open(cfg) as s:
        s.run(6)
        snap = s.snapshot()
        want = s.run(6).to_numpy()
    legacy_fields = ("num_whales", "num_hft", "num_informed",
                     "num_arbitrageurs", "whale_size", "whale_period",
                     "hft_threshold", "informed_horizon", "arb_kappa",
                     "coupling_peer")
    for f in legacy_fields:
        snap["params"].pop(f, None)
    with eng.open(cfg) as s:
        s.restore(snap)
        got = s.run(6).to_numpy()
    for f, a, b in zip(want._fields, want, got):
        assert (np.asarray(a) == np.asarray(b)).all(), f
