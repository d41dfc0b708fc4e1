"""``stats_only`` mode: fused in-stream statistics vs full-path references.

The mode's claim is twofold: (a) the per-market running moments / extremes /
total volume computed *inside* the step loop match a NumPy reference derived
from the full recorded path to float32 tolerance on every backend that
supports the mode, and (b) for the persistent kernel the per-step paths
never reach HBM at all — the chunk executable's outputs are Θ(M), with no
chunk-width array anywhere.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.config import MarketConfig, scenario_config
from repro.core.session import Engine, StepBatch
from repro.core.stats import MarketStats, accumulate, init_stats

CFG = MarketConfig(num_markets=6, num_agents=16, num_levels=32,
                   num_steps=24, seed=13)

#: Every backend registered today supports the mode (host loops accumulate
#: through the same shared helper; the persistent kernel fuses it).
STATS_BACKENDS = ("numpy", "numpy-pcg64", "jax-scan", "jax-per-step",
                  "pallas-kinetic", "pallas-naive")


def _path_reference(backend: str, cfg: MarketConfig) -> StepBatch:
    """Full-path run of the *same* backend (same RNG stream) on host."""
    with Engine(backend).open(cfg) as sess:
        return sess.run(cfg.num_steps).to_numpy()


@pytest.mark.parametrize("backend", STATS_BACKENDS)
def test_stats_match_full_path_reference(backend):
    ref = _path_reference(backend, CFG)
    with Engine(backend, stats_only=True).open(CFG) as sess:
        batch = sess.run(CFG.num_steps)
        assert batch.num_steps == 0  # no paths in stats mode
        st = sess.stats
    mid = np.asarray(ref.mid, dtype=np.float64)
    assert (st.count[:, 0] == CFG.num_steps).all()
    np.testing.assert_allclose(st.mean_mid()[:, 0], mid.mean(axis=1),
                               rtol=1e-5)
    np.testing.assert_allclose(st.var_mid()[:, 0], mid.var(axis=1),
                               rtol=1e-3, atol=1e-3)
    # extremes and exact-integer volume sums are bitwise-representable
    assert (st.min_mid[:, 0] == ref.mid.min(axis=1)).all()
    assert (st.max_mid[:, 0] == ref.mid.max(axis=1)).all()
    np.testing.assert_allclose(st.sum_volume[:, 0],
                               np.asarray(ref.volume).sum(axis=1), rtol=1e-6)


@pytest.mark.parametrize("backend", ["numpy", "jax-scan", "pallas-kinetic"])
def test_stats_chunking_is_bitwise_invisible(backend):
    """Accumulators are carried through chunk calls, never merged after the
    fact — so any chunking equals the one-shot run *bitwise*."""
    def stats_with_chunk(chunk):
        with Engine(backend, stats_only=True,
                    chunk_size=chunk).open(CFG) as sess:
            sess.run(CFG.num_steps)
            return sess.stats

    want = stats_with_chunk(CFG.num_steps)
    for chunk in (1, 5, 7):
        got = stats_with_chunk(chunk)
        for field, a, b in zip(MarketStats._fields, got, want):
            assert (np.asarray(a) == np.asarray(b)).all(), (chunk, field)


def test_stats_scenario_shock(backend="pallas-kinetic"):
    cfg = scenario_config("flash-crash", num_markets=6, num_agents=16,
                          num_levels=32, num_steps=20, shock_step=9, seed=3)
    ref = _path_reference(backend, cfg)
    with Engine(backend, stats_only=True, chunk_size=6).open(cfg) as sess:
        sess.run(cfg.num_steps)  # chunk boundary straddles the shock step
        st = sess.stats
    np.testing.assert_allclose(
        st.mean_mid()[:, 0], np.asarray(ref.mid, np.float64).mean(axis=1),
        rtol=1e-5)
    assert (st.min_mid[:, 0] == ref.mid.min(axis=1)).all()


def test_stats_snapshot_restore_roundtrip(backend="pallas-kinetic"):
    eng = Engine(backend, stats_only=True, chunk_size=5)
    with eng.open(CFG) as sess:
        sess.run(12)
        snap = sess.snapshot()
        assert "stats" in snap
        sess.run(12)
        want = sess.stats
    with eng.open(CFG) as sess:
        sess.restore(snap)
        sess.run(12)
        got = sess.stats
    for field, a, b in zip(MarketStats._fields, got, want):
        assert (np.asarray(a) == np.asarray(b)).all(), field


def test_stats_checkpoint_manager_roundtrip(tmp_path, backend="numpy"):
    from repro.checkpoint.manager import CheckpointManager

    eng = Engine(backend, stats_only=True, chunk_size=5)
    mgr = CheckpointManager(tmp_path / "ckpt")
    with eng.open(CFG) as sess:
        sess.run(9)
        sess.save_checkpoint(mgr)
        sess.run(6)
        want = sess.stats
    with eng.open(CFG) as sess:
        sess.restore_checkpoint(mgr)
        assert sess.step_count == 9
        sess.run(6)
        got = sess.stats
    for field, a, b in zip(MarketStats._fields, got, want):
        assert (np.asarray(a) == np.asarray(b)).all(), field


def test_kinetic_stats_kernel_emits_no_chunk_width_outputs():
    """The Θ(M) HBM claim: the stats_only chunk executable's outputs are the
    books plus six [M, 1] accumulators — nothing with a chunk-width axis."""
    import jax
    import jax.numpy as jnp

    from repro.core.params import EnsembleSpec

    chunk = 16
    spec = EnsembleSpec.coerce(CFG)
    eng = Engine("pallas-kinetic", stats_only=True)
    runner = eng._runner(spec, chunk)
    state = runner.init_state(spec)
    params = runner.params_to_device(spec.params)
    stats = runner.init_stats(spec)
    step0 = jnp.zeros((1, 1), jnp.int32)
    nv = jnp.full((1, 1), chunk, jnp.int32)
    ext = jnp.zeros((CFG.num_markets, CFG.num_levels), jnp.float32)
    out = jax.eval_shape(runner._chunk_fn, state, stats, params, step0, nv,
                         ext, ext)
    shapes = [leaf.shape for leaf in jax.tree_util.tree_leaves(out)]
    assert shapes, "no outputs?"
    assert all(chunk not in shape for shape in shapes), shapes
    assert all(shape[-1] in (1, CFG.num_levels) for shape in shapes), shapes


def test_accumulate_inactive_is_bitwise_noop():
    st = init_stats(4, np)
    st = accumulate(st, np.full((4, 1), 3.5, np.float32),
                    np.ones((4, 1), np.float32), True, np)
    frozen = accumulate(st, np.full((4, 1), 9.9, np.float32),
                        np.ones((4, 1), np.float32), False, np)
    for field, a, b in zip(MarketStats._fields, frozen, st):
        assert (np.asarray(a) == np.asarray(b)).all(), field


def test_stats_only_rejected_by_oneshot_wrappers():
    """The one-shot simulate() wrappers have no stats channel — silent
    zero-width results must be a loud error instead."""
    from repro.core import engine

    with pytest.raises(ValueError, match="Session.stats"):
        engine.simulate(CFG, backend="numpy", stats_only=True)


def test_stats_only_rejected_without_accumulators():
    from repro.core.params import MarketParams
    from repro.kernels.kinetic_clearing import kinetic_clearing_chunk
    import jax.numpy as jnp

    z = jnp.zeros((8, 32), jnp.float32)
    s = jnp.zeros((8, 1), jnp.float32)
    i = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="stats_only"):
        kinetic_clearing_chunk(z, z, s, s, i, i, z, z, cfg=CFG, chunk=4,
                               params=MarketParams.zeros(8, jnp),
                               stats_only=True, interpret=True)
