"""Ensemble-first front door: per-market scenario params as device operands.

Acceptance sweep for the `EnsembleSpec` API:
  * a homogeneous spec is bitwise-identical to the scalar `MarketConfig`
    path on every registered backend;
  * an ensemble mixing *every* scenario preset runs with exactly
    one trace and each market's order book is bitwise-identical to the
    corresponding single-scenario `MarketConfig` run — on all seven
    backends, including the stateful-PCG64 CPU reference (the fixed
    five-channel draw schedule keeps it per-market decomposable);
  * `Engine.trace_count` stays at 1 across arbitrary parameter-value
    changes (the executable cache keys on shape/structure, never values);
  * snapshots carry the per-market params and restore them (including
    through a `CheckpointManager` disk round-trip);
  * a sharded (2-device `shard_map`) mixed ensemble is bitwise-identical to
    the single-device run;
  * builder validation: static-field mismatches, out-of-range params, and
    shocks placed at/past the horizon are loud errors, and the
    default-length `run()`/`stream()` past the horizon raises instead of
    silently re-running a scenario whose events cannot fire.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine
from repro.core.config import MarketConfig, scenario_config, scenario_names
from repro.core.params import EnsembleSpec, MarketParams
from repro.core.session import Engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_BACKENDS = ["numpy", "numpy-splitmix64", "numpy-pcg64", "jax-scan",
                "jax-per-step", "pallas-naive", "pallas-kinetic"]

CFG = MarketConfig(num_markets=6, num_agents=16, num_levels=16, num_steps=10,
                   seed=21)

BATCH_FIELDS = ("price", "volume", "mid")
STATE_FIELDS = ("bid", "ask", "last_price", "prev_mid")

_ENGINES = {}


def _engine(backend: str) -> Engine:
    if backend not in _ENGINES:
        _ENGINES[backend] = Engine(backend)
    return _ENGINES[backend]


def _mixed_spec(num_steps=12, seed=5, markets_per_block=None):
    """One block per registered preset (+ mixture variation).

    Blocks also vary the archetype mixture so the per-market population
    counts — not just the scalar knobs — are exercised as operands. The
    block width is even so the total divides across the 2-device shard
    tests regardless of how many presets are registered.
    """
    presets = scenario_names()                       # every registered preset
    n = len(presets) + 2                             # + two mixture twists
    per = markets_per_block or 6                     # even markets/block
    common = dict(num_markets=per, num_agents=16, num_levels=16,
                  num_steps=num_steps, seed=seed)
    blocks = [scenario_config(p, **common) for p in presets]
    blocks.append(scenario_config(
        "baseline", alpha_maker=0.0, alpha_momentum=0.5,
        alpha_fundamentalist=0.25, **common))
    blocks.append(scenario_config(
        "high-vol", alpha_maker=0.25, alpha_momentum=0.0,
        alpha_fundamentalist=0.5, fundamentalist_kappa=0.9, q_max=3,
        **common))
    spec = EnsembleSpec.from_scenarios(blocks)
    assert spec.num_markets == per * n
    return spec, blocks, per


# ---------------------------------------------------------------------------
# Bitwise parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_homogeneous_spec_matches_config_bitwise(backend):
    """EnsembleSpec.homogeneous(cfg) ≡ MarketConfig, batches + final books."""
    eng = _engine(backend)
    with eng.open(CFG) as a, eng.open(EnsembleSpec.homogeneous(CFG)) as b:
        ba, bb = a.run(CFG.num_steps).to_numpy(), b.run(CFG.num_steps).to_numpy()
        for f, x, y in zip(BATCH_FIELDS, ba, bb):
            assert (x == y).all(), (backend, f)
        for f, x, y in zip(STATE_FIELDS, a.state, b.state):
            assert (np.asarray(x) == np.asarray(y)).all(), (backend, f)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_mixed_ensemble_per_market_bitwise(backend):
    """The acceptance criterion: an all-presets ensemble, each
    market bitwise-equal to the corresponding single-scenario MarketConfig
    run, with exactly one trace and one executable for everything."""
    spec, blocks, per = _mixed_spec()
    eng = Engine(backend)  # fresh: count traces from zero
    with eng.open(spec) as sess:
        mixed = sess.run(spec.num_steps).to_numpy()
        mixed_state = [np.asarray(x) for x in sess.state]
    if backend.startswith(("jax", "pallas")):
        assert eng.trace_count == 1

    for b, block in enumerate(blocks):
        solo_cfg = dataclasses.replace(block, num_markets=spec.num_markets)
        # The homogeneous solo run reuses the SAME executable: the cache
        # keys on (M, A, L, seed), which the blocks share by construction.
        with eng.open(solo_cfg) as sess:
            solo = sess.run(solo_cfg.num_steps).to_numpy()
            solo_state = [np.asarray(x) for x in sess.state]
        rows = slice(b * per, (b + 1) * per)
        for f, x, y in zip(BATCH_FIELDS, mixed, solo):
            assert (x[rows] == y[rows]).all(), (backend, block.scenario, f)
        for f, x, y in zip(STATE_FIELDS, mixed_state, solo_state):
            assert (x[rows] == y[rows]).all(), (backend, block.scenario, f)
    if backend.startswith(("jax", "pallas")):
        assert eng.trace_count == 1, "solo runs retraced the ensemble trace"


def test_mixed_ensemble_initial_books_are_per_market():
    """wide-book / thin-book presets differ only through the opening books —
    the per-market seeding must reproduce each preset's rows exactly."""
    spec, blocks, per = _mixed_spec()
    bid, ask = spec.initial_books(np)
    for b, block in enumerate(blocks):
        sb, sa = dataclasses.replace(
            block, num_markets=spec.num_markets).initial_books(np)
        rows = slice(b * per, (b + 1) * per)
        assert (bid[rows] == sb[rows]).all(), block.scenario
        assert (ask[rows] == sa[rows]).all(), block.scenario


# ---------------------------------------------------------------------------
# Compile-once across parameter changes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax-scan", "pallas-kinetic"])
def test_trace_count_stays_one_across_parameter_changes(backend):
    """Parameter values never enter the executable key: sweeping scenario
    knobs, shock schedules, and population mixtures reuses one trace."""
    eng = Engine(backend, chunk_size=5)  # explicit: shared across horizons
    with eng.open(CFG) as sess:
        sess.run(CFG.num_steps)
    assert eng.trace_count == 1
    variants = [
        dataclasses.replace(CFG, noise_delta=2.5, p_marketable=0.4),
        dataclasses.replace(CFG, q_max=2, maker_half_spread=4.0),
        scenario_config("flash-crash", num_markets=6, num_agents=16,
                        num_levels=16, num_steps=10, seed=21, shock_step=4),
        dataclasses.replace(CFG, alpha_maker=0.5, alpha_momentum=0.25,
                            alpha_fundamentalist=0.25),
        dataclasses.replace(CFG, num_steps=7),  # horizon is not in the key
    ]
    for cfg in variants:
        with eng.open(cfg) as sess:
            sess.run(cfg.num_steps)
    spec = EnsembleSpec.homogeneous(CFG).with_values(
        shock_step=[-1, 2, -1, 3, -1, 4], shock_intensity=0.5,
        shock_cancel=0.25)
    with eng.open(spec) as sess:
        sess.run(spec.num_steps)
    assert eng.trace_count == 1


def test_with_values_broadcasts_and_validates():
    spec = EnsembleSpec.homogeneous(CFG)
    v = spec.with_values(noise_delta=3.0, shock_step=np.arange(6) - 1,
                         shock_intensity=0.1)
    assert np.asarray(v.params.noise_delta).shape == (6, 1)
    assert np.asarray(v.params.shock_step)[:, 0].tolist() == [-1, 0, 1, 2, 3, 4]
    with pytest.raises(KeyError, match="no_such"):
        spec.with_values(no_such=1.0)
    with pytest.raises(ValueError, match="shock_step"):
        spec.with_values(shock_step=CFG.num_steps)  # at the horizon


# ---------------------------------------------------------------------------
# Snapshot / restore round-trips the params
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "numpy-pcg64", "jax-scan",
                                     "pallas-kinetic"])
def test_params_snapshot_restore_roundtrip(backend):
    """A snapshot is self-contained: restoring into a session opened on a
    *different* same-shape spec resumes the snapshot's scenario mixture."""
    spec, _, _ = _mixed_spec()
    eng = _engine(backend)
    with eng.open(spec) as sess:
        sess.run(5)
        snap = sess.snapshot()
        want = sess.run(7).to_numpy()
    other = EnsembleSpec.homogeneous(
        dataclasses.replace(CFG, num_markets=spec.num_markets,
                            num_steps=spec.num_steps, seed=spec.seed))
    with eng.open(other) as sess:
        sess.restore(snap)
        for f, a, b in zip(MarketParams._fields, sess.params, spec.params):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        # the spec tracks the live mixture too (labels + param values)
        assert sess.spec.scenarios == spec.scenarios
        for f, a, b in zip(MarketParams._fields, sess.spec.params,
                           spec.params):
            assert (np.asarray(a) == np.asarray(b)).all(), ("spec", f)
        got = sess.run(7).to_numpy()
    for f, a, b in zip(BATCH_FIELDS, want, got):
        assert (a == b).all(), (backend, f)


def test_restore_adopts_snapshot_horizon_and_is_atomic():
    """A snapshot from a longer-horizon scenario restores into a
    shorter-horizon same-shape session (num_steps is not in the cache key):
    the session adopts the snapshot's horizon instead of failing validation,
    and a genuinely broken snapshot leaves the session untouched."""
    eng = _engine("numpy")
    crash = EnsembleSpec.homogeneous(scenario_config(
        "flash-crash", num_markets=6, num_agents=16, num_levels=16,
        num_steps=40, shock_step=20, seed=21))
    with eng.open(crash) as sess:
        sess.run(5)
        snap = sess.snapshot()
        want = sess.run(20).to_numpy()
    with eng.open(CFG) as sess:  # num_steps=10 < shock_step=20
        sess.restore(snap)
        assert sess.horizon == 40  # adopted from the snapshot
        got = sess.run(20).to_numpy()
        for f, a, b in zip(BATCH_FIELDS, want, got):
            assert (a == b).all(), f
    with eng.open(CFG) as sess:
        sess.run(3)
        before = [np.asarray(x).copy() for x in sess.state]
        bad = dict(snap)
        bad["params"] = {f: np.asarray(v) for f, v in snap["params"].items()}
        bad["params"]["shock_step"] = np.full((6, 1), 99, np.int32)  # >= 40
        with pytest.raises(ValueError, match="shock_step"):
            sess.restore(bad)
        assert sess.step_count == 3  # failed restore mutated nothing
        for f, a, b in zip(STATE_FIELDS, before, sess.state):
            assert (a == np.asarray(b)).all(), f


def test_restore_rejects_seed_or_agent_count_mismatch():
    """seed and num_agents are baked into the executable (they are in the
    cache key) but appear in no restored array's shape, so a cross-spec
    restore must be a loud error, never a silent stream change."""
    eng = _engine("numpy")
    with eng.open(CFG) as sess:
        sess.run(3)
        snap = sess.snapshot()
    for field in ("seed", "num_agents"):
        other = dataclasses.replace(CFG, **{field: getattr(CFG, field) * 2
                                            + 1})
        with eng.open(other) as sess:
            with pytest.raises(ValueError, match=field):
                sess.restore(snap)
            assert sess.step_count == 0  # untouched


def test_params_checkpoint_manager_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    spec, _, _ = _mixed_spec()
    eng = _engine("pallas-kinetic")
    mgr = CheckpointManager(tmp_path, async_write=False)
    with eng.open(spec) as sess:
        sess.run(5)
        sess.save_checkpoint(mgr)
        want = sess.run(7).to_numpy()
    fresh_base = EnsembleSpec.homogeneous(
        dataclasses.replace(CFG, num_markets=spec.num_markets,
                            num_steps=spec.num_steps, seed=spec.seed))
    with eng.open(fresh_base) as sess:
        assert sess.restore_checkpoint(mgr) == 5
        for f, a, b in zip(MarketParams._fields, sess.params, spec.params):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        got = sess.run(7).to_numpy()
    for f, a, b in zip(BATCH_FIELDS, want, got):
        assert (a == b).all(), f


# ---------------------------------------------------------------------------
# Sharded mixed ensembles
# ---------------------------------------------------------------------------

def test_sharded_mixed_ensemble_parity_subprocess():
    """2-device shard_map over a heterogeneous ensemble == single device,
    bitwise (each shard receives its rows of every parameter column)."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.config import scenario_config
        from repro.core.params import EnsembleSpec
        from repro.core.session import Engine
        assert len(jax.devices()) >= 2, jax.devices()
        common = dict(num_markets=4, num_agents=16, num_levels=32,
                      num_steps=20, seed=7)
        spec = EnsembleSpec.from_scenarios(
            ["baseline", "flash-crash", "high-vol"], **common)

        def run(**opts):
            eng = Engine("pallas-kinetic", chunk_size=6, **opts)
            with eng.open(spec) as s:
                batch = s.run(spec.num_steps).to_numpy()
                snap = s.snapshot()
            return batch, snap

        single, ssnap = run()
        sharded, dsnap = run(devices=2)
        for f, a, b in zip(single._fields, single, sharded):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        for f in ("bid", "ask", "last_price", "prev_mid"):
            assert (np.asarray(ssnap[f]) == np.asarray(dsnap[f])).all(), f
        for f, a in ssnap["params"].items():
            assert (a == dsnap["params"][f]).all(), f
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "OK"


@pytest.mark.distributed
def test_sharded_mixed_ensemble_parity_inprocess():
    """In-process variant for the CI `distributed` tier."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    common = dict(num_markets=4, num_agents=16, num_levels=32, num_steps=20,
                  seed=7)
    spec = EnsembleSpec.from_scenarios(["baseline", "flash-crash", "low-vol"],
                                       **common)

    def run(**opts):
        with Engine("pallas-kinetic", chunk_size=6, **opts).open(spec) as s:
            return s.run(spec.num_steps).to_numpy()

    single, sharded = run(), run(devices=2)
    for f, a, b in zip(single._fields, single, sharded):
        assert (np.asarray(a) == np.asarray(b)).all(), f


# ---------------------------------------------------------------------------
# Builders + validation
# ---------------------------------------------------------------------------

def test_product_builder_shape_and_values():
    base = dataclasses.replace(CFG, num_markets=2)
    spec = EnsembleSpec.product(
        base, sweep={"noise_delta": [2.0, 8.0], "p_marketable": [0.1, 0.2,
                                                                 0.3]})
    assert spec.num_markets == 2 * 2 * 3
    nd = np.asarray(spec.params.noise_delta)[:, 0]
    pm = np.asarray(spec.params.p_marketable)[:, 0]
    # cartesian order: noise_delta outer, p_marketable inner, 2 markets each
    assert nd[:6].tolist() == [2.0] * 6 and nd[6:].tolist() == [8.0] * 6
    assert pm[:2].tolist() == [pytest.approx(0.1)] * 2
    assert pm[4:6].tolist() == [pytest.approx(0.3)] * 2
    with pytest.raises(ValueError, match="non-empty"):
        EnsembleSpec.product(base, sweep={})


def test_from_scenarios_accepts_names_and_configs():
    spec = EnsembleSpec.from_scenarios(
        ["baseline", scenario_config("flash-crash", num_markets=4,
                                     num_agents=16, num_levels=16,
                                     num_steps=10, seed=0)],
        num_markets=4, num_agents=16, num_levels=16, num_steps=10, seed=0)
    assert spec.num_markets == 8
    assert spec.scenarios[:4] == ("baseline",) * 4
    assert spec.scenarios[4:] == ("flash-crash",) * 4


def test_from_scenarios_rejects_static_mismatch():
    a = MarketConfig(num_markets=2, num_agents=16, num_levels=16,
                     num_steps=10, seed=0)
    for field, value in (("num_agents", 32), ("num_levels", 32),
                         ("num_steps", 20), ("seed", 1)):
        b = dataclasses.replace(a, **{field: value})
        with pytest.raises(ValueError, match=field):
            EnsembleSpec.from_scenarios([a, b])


def test_spec_validation_rejects_bad_params():
    spec = EnsembleSpec.homogeneous(CFG)
    with pytest.raises(ValueError, match="shock_intensity"):
        spec.with_values(shock_intensity=1.5)
    with pytest.raises(ValueError, match="more than num_agents"):
        spec.with_values(num_makers=CFG.num_agents, num_momentum=1)
    with pytest.raises(ValueError, match="shock_step"):
        spec.with_values(shock_step=[0, 1, 2, 3, 4, CFG.num_steps])
    with pytest.raises(ValueError, match="q_max"):
        spec.with_values(q_max=0)  # qty draw would go non-positive
    with pytest.raises(ValueError, match="fundamental"):
        # no negative-means-midpoint sentinel on the resolved operand
        spec.with_values(fundamental=-1.0)


def test_coerce_rejects_unknown_types():
    with pytest.raises(TypeError, match="MarketConfig or EnsembleSpec"):
        EnsembleSpec.coerce({"num_markets": 4})


# ---------------------------------------------------------------------------
# Horizon semantics (the validation-gap satellite)
# ---------------------------------------------------------------------------

def test_default_run_past_horizon_raises():
    eng = _engine("numpy")
    with eng.open(CFG) as sess:
        sess.run()  # to the horizon
        assert sess.step_count == sess.horizon == CFG.num_steps
        with pytest.raises(ValueError, match="horizon"):
            sess.run()
        with pytest.raises(ValueError, match="horizon"):
            next(sess.stream())
        # explicit n_steps may stream past the horizon deliberately
        assert sess.run(5).num_steps == 5
        with pytest.raises(ValueError, match="n_steps"):
            sess.run(-1)


def test_default_run_completes_remaining_horizon():
    """run() means 'to the horizon', not 'another num_steps': interleaving
    with explicit advances never overshoots scenario events."""
    eng = _engine("numpy")
    with eng.open(CFG) as sess:
        sess.run(4)
        assert sess.run().num_steps == CFG.num_steps - 4
        assert sess.step_count == CFG.num_steps
