"""repro.env: pure-functional RL environment acceptance sweep.

The tentpole claims, each asserted bitwise:
  * a zero-action env rollout equals ``Session.run`` — paths and final
    books — on every registered backend;
  * a fixed *nonzero* action sequence produces identical books on all
    counter-RNG backends (the ext_buy/ext_ask injection parity the matrix
    never covered), and env.step ≡ Session.step per backend;
  * one ``lax.scan`` rollout equals a python loop of ``env.step`` calls;
  * auto-reset at the horizon restores the ensemble's opening books
    in-graph; ``vmap`` over runtime seeds equals solo baked-seed envs;
  * a mixed-scenario ensemble rollout compiles exactly once
    (``Engine.trace_count == 1``) and a second mixture of the same shape
    reuses the warm trace;
  * ``EnvState`` snapshot/restore round-trips through ``CheckpointManager``
    (including the stateful PCG64 reference stream);
  * malformed actions raise eager ``ValueError``s from both front doors.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.config import MarketConfig, scenario_config, scenario_names
from repro.core.params import EnsembleSpec
from repro.core.session import Engine, ExternalOrders
from repro.env import (
    BookWindow,
    Composite,
    InventoryPenalty,
    MarketFeatures,
    PnLReward,
    PortfolioFeatures,
    SpreadCapture,
    StatsFeatures,
    Sum,
    rollout,
)

from repro.train.policies import make_market_maker

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CFG = MarketConfig(num_markets=4, num_agents=16, num_levels=16, num_steps=12,
                   seed=3)

ALL_BACKENDS = ["numpy", "numpy-splitmix64", "numpy-pcg64", "jax-scan",
                "jax-per-step", "pallas-naive", "pallas-kinetic"]
#: Backends sharing the production counter-RNG stream (bitwise-comparable
#: to each other); the splitmix64/pcg64 references run different streams.
BITWISE_BACKENDS = ["numpy", "jax-scan", "jax-per-step", "pallas-naive",
                    "pallas-kinetic"]
TRACEABLE = ["jax-scan", "pallas-kinetic"]

_ENGINES = {}


def _engine(backend: str) -> Engine:
    if backend not in _ENGINES:
        _ENGINES[backend] = Engine(backend)
    return _ENGINES[backend]


def _states_equal(a, b, ctx=""):
    for f, x, y in zip(type(a)._fields, a, b):
        assert (np.asarray(x) == np.asarray(y)).all(), f"{ctx}: {f} differs"


def _fixed_actions(t: int) -> ExternalOrders:
    """A deterministic, step-varying nonzero action sequence."""
    M = CFG.num_markets
    return ExternalOrders(side_buy=np.arange(M) % 2 == 0,
                          price=np.full(M, 5 + (t % 4)),
                          qty=np.full(M, 2.0 + (t % 2)))


# ---------------------------------------------------------------------------
# Zero-action parity: env rollout == Session.run, bitwise, on every backend.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_zero_action_rollout_matches_session(backend):
    eng = _engine(backend)
    env = eng.env(CFG, auto_reset=False)
    final, traj = rollout(env, None, CFG.num_steps)
    sess = eng.open(CFG)
    ref = sess.run(CFG.num_steps).to_numpy()
    assert (np.asarray(traj.price) == ref.price).all(), backend
    assert (np.asarray(traj.volume) == ref.volume).all(), backend
    assert (np.asarray(traj.mid) == ref.mid).all(), backend
    _states_equal(final.market, sess.state, backend)
    # zero actions never fill: the portfolio stays identically flat
    for leaf in final.portfolio:
        assert (np.asarray(leaf) == 0.0).all(), backend


# ---------------------------------------------------------------------------
# Nonzero-action injection parity (satellite: the ext_buy/ext_ask path).
# ---------------------------------------------------------------------------

def _run_action_sequence(backend, n=6):
    eng = _engine(backend)
    sess = eng.open(CFG)
    batches = [sess.step(_fixed_actions(t)).to_numpy() for t in range(n)]
    books = tuple(np.asarray(x) for x in sess.state)
    return batches, books


def test_action_injection_bitwise_across_backends():
    """A fixed nonzero action sequence produces identical books and step
    outputs on every counter-RNG backend (today's parity matrix only
    covers the actions=None path)."""
    ref_batches, ref_books = _run_action_sequence(BITWISE_BACKENDS[0])
    for backend in BITWISE_BACKENDS[1:]:
        batches, books = _run_action_sequence(backend)
        for t, (a, b) in enumerate(zip(ref_batches, batches)):
            for f, x, y in zip(a._fields, a, b):
                assert (np.asarray(x) == np.asarray(y)).all(), \
                    f"{backend} step {t}: {f}"
        for f, x, y in zip(("bid", "ask", "last", "pmid"), ref_books, books):
            assert (x == y).all(), f"{backend}: {f}"


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_env_step_matches_session_step_with_actions(backend):
    """env.step(actions) ≡ Session.step(actions), per backend, including
    the reference backends running their own RNG streams."""
    eng = _engine(backend)
    env = eng.env(CFG, auto_reset=False)
    state, obs = env.reset()
    sess = eng.open(CFG)
    for t in range(6):
        state, obs, reward, done, info = env.step(state, _fixed_actions(t))
        batch = sess.step(_fixed_actions(t)).to_numpy()
        assert (np.asarray(info.price) == batch.price).all(), (backend, t)
        assert (np.asarray(info.volume) == batch.volume).all(), (backend, t)
        assert (np.asarray(info.mid) == batch.mid).all(), (backend, t)
    _states_equal(state.market, sess.state, backend)


# ---------------------------------------------------------------------------
# Scan rollout == python loop of steps (in-graph ≡ eager), bitwise.
# ---------------------------------------------------------------------------

# The deterministic market-maker fixture now lives in
# repro.train.policies (shared with examples/ and the trainer's eval
# baseline); built once so the rollout executable cache keys stay stable.
_mm_policy = make_market_maker(CFG.num_levels)


@pytest.mark.parametrize("backend", TRACEABLE)
def test_scan_rollout_equals_step_loop(backend):
    eng = _engine(backend)
    env = eng.env(CFG)  # auto_reset on: the loop crosses the horizon reset
    final, traj = rollout(env, _mm_policy, CFG.num_steps)
    state, obs = env.reset()
    for t in range(CFG.num_steps):
        state, obs, reward, done, info = env.step(state,
                                                  _mm_policy(obs, state.t))
        assert (np.asarray(reward) == np.asarray(traj.reward[t])).all(), t
        assert (np.asarray(obs) == np.asarray(traj.obs[t])).all(), t
        assert (np.asarray(info.price)
                == np.asarray(traj.price[:, t:t + 1])).all(), t
        assert bool(done) == bool(traj.done[t]), t
    _states_equal(final.market, state.market, backend)
    _states_equal(final.portfolio, state.portfolio, backend)


# ---------------------------------------------------------------------------
# Carried policies: rollout(policy_carry=...) on jitted AND host paths.
# ---------------------------------------------------------------------------

def _carried_policy(obs_like_xp):
    """Stateful quoting policy in the carried signature
    ``policy_fn(carry, obs, t) -> (carry, actions, extras)``: the carry
    threads an own step counter and a reference mid that skews the quote
    offset — state the policy could not recover from (obs, t) alone."""

    def policy(carry, obs, t):
        xp = np if isinstance(obs, np.ndarray) else obs_like_xp
        count, ref_mid = carry
        mid = obs[:, 0]
        side_buy = (count % 2) == 0
        off = xp.where(mid >= ref_mid, 1.0, 2.0)
        price = xp.clip(
            xp.round(mid + xp.where(side_buy, -off, off)).astype(xp.int32),
            0, CFG.num_levels - 1)
        orders = ExternalOrders(side_buy=xp.broadcast_to(side_buy, mid.shape),
                                price=price, qty=xp.ones_like(mid))
        extras = {"mid": mid, "count": count}
        return (count + 1, ref_mid), orders, extras

    return policy


def test_policy_carry_host_loop_matches_jitted():
    """The numpy host loop honours the same policy-carry signature as the
    jitted scan — rewards, stacked extras, and the final carry bitwise."""
    import jax.numpy as jnp

    policy = _carried_policy(jnp)
    carry0 = (np.int32(0), np.float32(CFG.num_levels / 2))
    results = {}
    for backend in ("numpy", "jax-scan"):
        env = _engine(backend).env(CFG)
        final, batch, carry = rollout(env, policy, CFG.num_steps,
                                      policy_carry=carry0)
        results[backend] = (batch, carry)
    ref_b, ref_c = results["numpy"]
    b, c = results["jax-scan"]
    assert (np.asarray(ref_b.reward) == np.asarray(b.reward)).all()
    assert (np.asarray(ref_b.obs) == np.asarray(b.obs)).all()
    for k in ("mid", "count"):
        assert (np.asarray(ref_b.extras[k])
                == np.asarray(b.extras[k])).all(), k
    assert int(np.asarray(ref_c[0])) == int(np.asarray(c[0]))
    assert np.asarray(ref_b.extras["count"]).shape == (CFG.num_steps,)
    assert np.asarray(ref_b.extras["mid"]).shape \
        == (CFG.num_steps, CFG.num_markets)


def test_policy_carry_requires_policy():
    env = _engine("jax-scan").env(CFG)
    with pytest.raises(ValueError, match="policy_carry"):
        rollout(env, None, 4, policy_carry=0)


def test_stateless_rollout_has_no_extras():
    env = _engine("jax-scan").env(CFG)
    _, batch = rollout(env, _mm_policy, 4)
    assert batch.extras is None


# ---------------------------------------------------------------------------
# Auto-reset at the horizon (in-graph, from the carried opening books).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jax-scan"])
def test_auto_reset_at_horizon(backend):
    env = _engine(backend).env(CFG)
    state, obs = env.reset()
    ref0, _ = env.reset()
    for t in range(CFG.num_steps):
        state, obs, reward, done, info = env.step(state)
        assert bool(done) == (t == CFG.num_steps - 1), t
    assert int(np.asarray(state.t)) == 0
    _states_equal(state.market, ref0.market, "auto-reset books")
    for leaf in state.portfolio:
        assert (np.asarray(leaf) == 0.0).all()
    assert (np.asarray(obs) == np.asarray(env.observe(ref0))).all()
    # the second episode replays the first bitwise (deterministic replay)
    state, obs, reward, done, info = env.step(state)
    s1, o1, r1, d1, i1 = env.step(ref0)
    assert (np.asarray(info.price) == np.asarray(i1.price)).all()


def test_no_auto_reset_keeps_counting():
    env = _engine("jax-scan").env(CFG, auto_reset=False)
    state, obs = env.reset()
    for t in range(CFG.num_steps + 2):
        state, obs, reward, done, info = env.step(state)
    assert int(np.asarray(state.t)) == CFG.num_steps + 2
    assert bool(done)


def test_custom_horizon():
    env = _engine("jax-scan").env(CFG, horizon=5)
    state, obs = env.reset()
    for t in range(5):
        state, obs, reward, done, info = env.step(state)
    assert bool(done) and int(np.asarray(state.t)) == 0


# ---------------------------------------------------------------------------
# vmap over runtime seeds (counter-RNG jax backends).
# ---------------------------------------------------------------------------

def test_vmap_over_seeds_matches_solo_envs():
    import jax

    eng = _engine("jax-scan")
    env = eng.env(CFG, auto_reset=False)
    seeds = np.array([3, 11, 42], np.uint32)
    states, obs = jax.vmap(env.reset)(seeds)
    for _ in range(4):
        states, obs, rewards, done, info = jax.vmap(
            lambda s: env.step(s))(states)
    for i, sd in enumerate(seeds):
        solo_env = eng.env(dataclasses.replace(CFG, seed=int(sd)),
                           auto_reset=False)
        st, ob = solo_env.reset()
        for _ in range(4):
            st, ob, r, d, inf = solo_env.step(st)
        assert (np.asarray(ob) == np.asarray(obs[i])).all(), int(sd)
        for f, x, y in zip(st.market._fields, st.market, states.market):
            assert (np.asarray(x) == np.asarray(y[i])).all(), (int(sd), f)


def test_runtime_seed_rejected_where_baked():
    for backend in ("pallas-kinetic", "numpy-pcg64"):
        env = _engine(backend).env(CFG)
        with pytest.raises(ValueError, match="seed"):
            env.reset(seed=7)


# ---------------------------------------------------------------------------
# One compile for any scenario mixture (the ensemble tentpole, RL edition).
# ---------------------------------------------------------------------------

def _mixture(blocks):
    return EnsembleSpec.from_scenarios(blocks, num_markets=2, num_agents=16,
                                       num_levels=16, num_steps=10, seed=0)


@pytest.mark.parametrize("backend", TRACEABLE)
def test_mixed_ensemble_rollout_single_trace(backend):
    eng = Engine(backend)  # fresh engine: exact trace accounting
    spec = _mixture(list(scenario_names()))
    env = eng.env(spec, auto_reset=False)
    final, traj = rollout(env, None, spec.num_steps)
    assert eng.trace_count == 1, f"{backend}: rollout retraced"
    assert traj.reward.shape == (spec.num_steps, spec.num_markets)
    # A different mixture of the same shape reuses every warm executable.
    env2 = eng.env(_mixture(["baseline"] * len(scenario_names())),
                   auto_reset=False)
    final2, traj2 = rollout(env2, None, spec.num_steps)
    assert eng.trace_count == 1, f"{backend}: second mixture retraced"
    # Per-market parity: mixture rows equal the homogeneous spec's rows.
    solo = eng.env(_mixture(["baseline"] * len(scenario_names())),
                   auto_reset=False)
    assert solo._cache is env2._cache


def test_mixed_rollout_rows_match_solo_scenarios():
    """Market rows of a mixed-ensemble rollout are bitwise the rows of the
    per-scenario homogeneous rollouts (row-independence through the env)."""
    eng = _engine("pallas-kinetic")
    names = sorted(scenario_names())
    spec = _mixture(names)
    final, traj = rollout(eng.env(spec, auto_reset=False), None, 10)
    for k, name in enumerate(names):
        solo_spec = _mixture([name] * len(names))
        sfinal, straj = rollout(eng.env(solo_spec, auto_reset=False),
                                None, 10)
        rows = slice(2 * k, 2 * k + 2)
        assert (np.asarray(traj.price[rows])
                == np.asarray(straj.price[rows])).all(), name
        assert (np.asarray(final.market.bid[rows])
                == np.asarray(sfinal.market.bid[rows])).all(), name


# ---------------------------------------------------------------------------
# Snapshot / restore through CheckpointManager.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy-pcg64", "jax-scan",
                                     "pallas-kinetic"])
def test_env_checkpoint_roundtrip(backend, tmp_path):
    env = _engine(backend).env(CFG, auto_reset=False,
                               obs=Composite((MarketFeatures(),
                                              StatsFeatures())))
    state, obs = env.reset()
    for t in range(4):
        state, obs, reward, done, info = env.step(state, _fixed_actions(t))
    manager = CheckpointManager(tmp_path, async_write=False)
    step = env.save_checkpoint(manager, state)
    assert step == 4
    restored = env.restore_checkpoint(manager)
    _states_equal(state.market, restored.market, backend)
    _states_equal(state.portfolio, restored.portfolio, backend)
    _states_equal(state.stats, restored.stats, backend)
    # both continuations advance identically (incl. the PCG64 stream)
    sa, sb = state, restored
    for t in range(4):
        sa, oa, ra, da, ia = env.step(sa, _fixed_actions(t))
        sb, ob, rb, db, ib = env.step(sb, _fixed_actions(t))
        assert (np.asarray(oa) == np.asarray(ob)).all(), (backend, t)
        assert (np.asarray(ra) == np.asarray(rb)).all(), (backend, t)


def test_env_restore_rejects_static_mismatch(tmp_path):
    env = _engine("jax-scan").env(CFG, auto_reset=False)
    state, _ = env.reset()
    snap = env.snapshot(state)
    other = _engine("jax-scan").env(dataclasses.replace(CFG, seed=9),
                                    auto_reset=False)
    with pytest.raises(ValueError, match="static_seed"):
        other.restore(snap)


# ---------------------------------------------------------------------------
# Eager action validation (both front doors).
# ---------------------------------------------------------------------------

_BAD_ACTIONS = [
    (ExternalOrders(True, CFG.num_levels, 1.0), "grid"),
    (ExternalOrders(True, -1, 1.0), "grid"),
    (ExternalOrders(True, 5, -2.0), "negative"),
    (ExternalOrders(np.ones(3, bool), 5, 1.0), "market mismatch"),
    (ExternalOrders(True, np.full(7, 5), 1.0), "market mismatch"),
    (ExternalOrders(True, 5.5, 1.0), "fractional"),
    ({"side_buy": True, "price": 5}, "missing key"),
    (object(), "must be an ExternalOrders"),
]


@pytest.mark.parametrize("bad,match", _BAD_ACTIONS,
                         ids=[m for _, m in _BAD_ACTIONS])
def test_env_step_validates_actions_eagerly(bad, match):
    env = _engine("numpy").env(CFG)
    state, _ = env.reset()
    with pytest.raises(ValueError, match=match):
        env.step(state, bad)


@pytest.mark.parametrize("bad,match", _BAD_ACTIONS,
                         ids=[m for _, m in _BAD_ACTIONS])
def test_session_step_validates_actions_eagerly(bad, match):
    sess = _engine("numpy").open(CFG)
    with pytest.raises(ValueError, match=match):
        sess.step(bad)


def test_validation_covers_concrete_jax_arrays():
    """Concrete device arrays get the same eager value checks as host
    arrays (only tracers skip them)."""
    import jax.numpy as jnp

    env = _engine("jax-scan").env(CFG)
    state, _ = env.reset()
    M = CFG.num_markets
    with pytest.raises(ValueError, match="grid"):
        env.step(state, ExternalOrders(jnp.ones(M, bool),
                                       jnp.full(M, CFG.num_levels),
                                       jnp.ones(M)))
    with pytest.raises(ValueError, match="negative"):
        env.step(state, ExternalOrders(jnp.ones(M, bool), jnp.full(M, 5),
                                       jnp.full(M, -1.0)))
    env.step(state, ExternalOrders(jnp.ones(M, bool), jnp.full(M, 5),
                                   jnp.ones(M)))  # in-grid still accepted


def _traced_neg_qty_policy(obs, t):
    z = obs[:, 0] * 0.0  # traced zeros: value checks cannot see these
    return ExternalOrders(side_buy=z == 0.0, price=z + 5.0, qty=z - 5.0)


def _traced_frac_price_policy(obs, t):
    z = obs[:, 0] * 0.0
    return ExternalOrders(side_buy=z == 0.0, price=z + 10.6, qty=z + 1.0)


def _tick11_policy(obs, t):
    return ExternalOrders(side_buy=True, price=11, qty=1.0)


def test_traced_negative_qty_clamps_to_noop():
    """In-graph policies can emit values validation cannot inspect; a
    traced negative quantity must clamp to a zero (no-op) order instead of
    injecting negative depth into the clearing."""
    env = _engine("jax-scan").env(CFG, auto_reset=False)
    f1, t1 = rollout(env, _traced_neg_qty_policy, 6)
    f2, t2 = rollout(env, None, 6)
    assert (np.asarray(t1.price) == np.asarray(t2.price)).all()
    assert (np.asarray(t1.volume) == np.asarray(t2.volume)).all()
    for leaf in f1.portfolio:
        assert (np.asarray(leaf) == 0.0).all()


def test_traced_fractional_price_rounds_to_nearest_tick():
    """Traced float prices quote the nearest tick (10.6 -> 11), matching
    the concrete path's semantics rather than truncating toward zero."""
    env = _engine("jax-scan").env(CFG, auto_reset=False)
    f1, t1 = rollout(env, _traced_frac_price_policy, 6)
    f2, t2 = rollout(env, _tick11_policy, 6)
    assert (np.asarray(t1.price) == np.asarray(t2.price)).all()
    assert (np.asarray(t1.fill_buy) == np.asarray(t2.fill_buy)).all()


def test_valid_action_shapes_accepted():
    env = _engine("numpy").env(CFG)
    state, _ = env.reset()
    M = CFG.num_markets
    for actions in (ExternalOrders(True, 5, 1.0),
                    ExternalOrders(np.ones(M, bool), np.full(M, 5),
                                   np.full(M, 2.0)),
                    ExternalOrders(np.ones((M, 1), bool),
                                   np.full((M, 1), 5), np.full((M, 1), 0.0)),
                    (True, 5, 1.0),
                    {"side_buy": True, "price": 5, "qty": 1.0}):
        env.step(state, actions)


# ---------------------------------------------------------------------------
# Observation / reward plumbing.
# ---------------------------------------------------------------------------

def test_observation_specs_shapes_and_composition():
    obs_spec = Composite((MarketFeatures(), BookWindow(depth=3),
                          PortfolioFeatures(), StatsFeatures()))
    env = _engine("jax-scan").env(CFG, obs=obs_spec)
    assert env.obs_size() == 5 + 12 + 3 + 6
    state, obs = env.reset()
    assert obs.shape == (CFG.num_markets, env.obs_size())
    assert state.stats is not None  # StatsFeatures forces the accumulators
    state, obs, reward, done, info = env.step(state)
    assert obs.shape == (CFG.num_markets, env.obs_size())
    # the stats features move once steps accumulate
    assert (np.asarray(state.stats.count) == 1.0).all()


def test_stats_not_carried_unless_needed():
    env = _engine("jax-scan").env(CFG, obs=MarketFeatures())
    state, _ = env.reset()
    assert state.stats is None


def test_fills_and_rewards_account_consistently():
    """Crossing buys fill at p*, cash flows match, and the reward surfaces
    decompose as documented."""
    env = _engine("numpy").env(
        CFG, auto_reset=False,
        reward=Sum((PnLReward(), SpreadCapture(), InventoryPenalty(0.5)),
                   (1.0, 0.0, 0.0)))
    state, obs = env.reset()
    # marketable buy at the top of the grid: fills whenever volume clears
    for t in range(6):
        state, obs, reward, done, info = env.step(
            state, ExternalOrders(True, CFG.num_levels - 1, 3.0))
    fb = np.asarray(state.portfolio.inventory)
    assert (fb >= 0).all() and fb.sum() > 0, "marketable buys never filled"
    port = state.portfolio
    # equity ≡ cash + inventory · mid at the marking mid of the last step
    mid = np.asarray(state.last_out.mid, np.float32)
    assert (np.asarray(port.equity)
            == np.asarray(port.cash) + fb * mid).all()


def test_stats_only_engine_rejected():
    eng = Engine("jax-scan", stats_only=True)
    with pytest.raises(ValueError, match="stats_only"):
        eng.env(CFG)


# ---------------------------------------------------------------------------
# Sharded composition (shard_map ensembles under the env).
# ---------------------------------------------------------------------------

_SHARDED_ENV_CODE = textwrap.dedent("""
    import numpy as np, jax
    from repro.core.config import MarketConfig
    from repro.core.session import Engine
    from repro.env import rollout
    assert len(jax.devices()) >= 2, jax.devices()
    cfg = MarketConfig(num_markets=6, num_agents=16, num_levels=16,
                       num_steps=10, seed=5)
    f1, t1 = rollout(Engine("pallas-kinetic").env(cfg, auto_reset=False),
                     None, 10)
    f2, t2 = rollout(
        Engine("pallas-kinetic", devices=2).env(cfg, auto_reset=False),
        None, 10)
    assert (np.asarray(t1.price) == np.asarray(t2.price)).all()
    assert (np.asarray(t1.obs) == np.asarray(t2.obs)).all()
    for a, b in zip(f1.market, f2.market):
        assert (np.asarray(a) == np.asarray(b)).all()
    print("OK")
""")


def test_sharded_env_rollout_parity_subprocess():
    """2-device sharded env rollout == single-device, bitwise (forced host
    devices in a child process, runnable anywhere)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", _SHARDED_ENV_CODE], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


@pytest.mark.distributed
def test_sharded_env_rollout_parity_in_process():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices in-process")
    cfg = MarketConfig(num_markets=6, num_agents=16, num_levels=16,
                       num_steps=10, seed=5)
    f1, t1 = rollout(Engine("pallas-kinetic").env(cfg, auto_reset=False),
                     None, 10)
    f2, t2 = rollout(
        Engine("pallas-kinetic", devices=2).env(cfg, auto_reset=False),
        None, 10)
    assert (np.asarray(t1.price) == np.asarray(t2.price)).all()
    for a, b in zip(f1.market, f2.market):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# vmap(seeds) × EnsembleSpec mixture × sharded path, composed in ONE trace.
# ---------------------------------------------------------------------------

_VMAP_MIX_SHARDED_CODE = textwrap.dedent("""
    import numpy as np, jax
    from repro.core.params import EnsembleSpec
    from repro.core.session import Engine
    from repro.env import rollout
    from repro.train.policies import make_market_maker
    assert len(jax.devices()) >= 2, jax.devices()
    mk = lambda seed: EnsembleSpec.from_scenarios(
        ["flash-crash", "high-vol"], num_markets=2, num_agents=16,
        num_levels=16, num_steps=10, seed=seed)
    policy = make_market_maker(16)
    eng = Engine("jax-scan")
    env = eng.env(mk(0), auto_reset=False)

    def roll(seed):
        state, obs = env.reset(seed)
        final, batch = rollout(env, policy, 10, state=state)
        return batch

    seeds = np.array([0, 9, 23], np.uint32)
    batches = jax.vmap(roll)(seeds)
    # the whole seeds-batch of mixture rollouts compiled exactly once
    assert eng.trace_count == 1, eng.trace_count
    # per-seed bitwise vs solo envs with the seed baked into the spec
    for i, s in enumerate(seeds):
        solo = Engine("jax-scan").env(mk(int(s)), auto_reset=False)
        _, ref = rollout(solo, policy, 10)
        assert (np.asarray(ref.obs) == np.asarray(batches.obs[i])).all(), s
        assert (np.asarray(ref.price)
                == np.asarray(batches.price[i])).all(), s
    # sharded composition: the 2-device shard_map rollout of the same
    # mixture is bitwise-identical to the vmapped seed-0 row (jax-scan and
    # pallas-kinetic share the counter-RNG stream)
    sharded = Engine("pallas-kinetic", devices=2).env(mk(0),
                                                      auto_reset=False)
    _, sb = rollout(sharded, policy, 10)
    assert (np.asarray(sb.obs) == np.asarray(batches.obs[0])).all()
    assert (np.asarray(sb.price) == np.asarray(batches.price[0])).all()
    print("OK")
""")


def test_vmap_seeds_mixture_sharded_composition_subprocess():
    """vmap over runtime seeds × a scenario mixture in one trace, with the
    seed-0 row bitwise-equal to a 2-device sharded rollout of the same
    mixture (PR-3 sharding × PR-4 ensembles × PR-5 env, finally composed)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", _VMAP_MIX_SHARDED_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
