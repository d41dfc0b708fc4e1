"""Distribution machinery: market-axis ensemble sharding, sharding rules,
HLO analyzer.

Two flavours of multi-device coverage:

  * subprocess probes (`_run_probe`) force N host devices in a child
    process, so the main pytest process stays single-device — these run in
    tier-1 on any machine;
  * `@pytest.mark.distributed` cases run *in-process* and skip unless the
    process already has >= 2 devices — the CI `distributed` tier runs them
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _device_count() -> int:
    import jax

    return len(jax.devices())


def _run_probe(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Sharded market ensembles (shard_map over the persistent chunk kernels).
# ---------------------------------------------------------------------------

# Odd M across 2 devices (pads on both layouts), flash-crash shock placed so
# a chunk boundary straddles it; chunk_size=6 -> chunks [0,6), [6,12)...
# straddle shock_step=9.
_SHARD_CFG = ("dict(num_markets=10, num_agents=16, num_levels=32, "
              "num_steps=20, shock_step=9, seed=7)")

_SHARD_PARITY_CODE = textwrap.dedent(f"""
    import numpy as np, jax
    from repro.core.config import scenario_config
    from repro.core.session import Engine
    assert len(jax.devices()) >= 2, jax.devices()
    cfg = scenario_config("flash-crash", **{_SHARD_CFG})

    def run(**opts):
        eng = Engine("pallas-kinetic", chunk_size=6, **opts)
        with eng.open(cfg) as s:
            batch = s.run(cfg.num_steps).to_numpy()
            snap = s.snapshot()
        return batch, snap

    single, ssnap = run()
    sharded, dsnap = run(devices=2)
    for f, a, b in zip(single._fields, single, sharded):
        assert (np.asarray(a) == np.asarray(b)).all(), f
    for f in ("bid", "ask", "last_price", "prev_mid"):
        assert (np.asarray(ssnap[f]) == np.asarray(dsnap[f])).all(), f
    print("OK")
""")


def test_sharded_ensemble_bitwise_parity_subprocess():
    """2-device shard_map run == single-device run, bitwise, including a
    shock-straddling chunk boundary (tier-1: runs in a forced-2-device
    subprocess on any machine)."""
    out = _run_probe(_SHARD_PARITY_CODE, devices=2)
    assert out.strip().splitlines()[-1] == "OK"


def test_sharded_snapshot_across_shard_boundary_subprocess():
    """A snapshot taken on a single-device session restores into a sharded
    session (and back) and continues the exact stream."""
    out = _run_probe(textwrap.dedent(f"""
        import numpy as np, jax
        from repro.core.config import scenario_config
        from repro.core.session import Engine
        cfg = scenario_config("flash-crash", **{_SHARD_CFG})
        eng1 = Engine("pallas-kinetic", chunk_size=6)
        eng2 = Engine("pallas-kinetic", chunk_size=6, devices=2)
        with eng1.open(cfg) as s:
            s.run(8)
            snap = s.snapshot()
            want = s.run(12).to_numpy()
        with eng2.open(cfg) as s:
            s.restore(snap)
            got = s.run(12).to_numpy()
            back = s.snapshot()
        for f, a, b in zip(want._fields, want, got):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        # ... and back across the boundary: sharded snapshot -> single device
        with eng1.open(cfg) as s:
            s.restore(back)
            assert s.step_count == 20
        print("OK")
    """), devices=2)
    assert out.strip().splitlines()[-1] == "OK"


def test_sharded_stats_only_subprocess():
    """devices=2 + stats_only compose: Θ(M) outputs, same statistics."""
    out = _run_probe(textwrap.dedent(f"""
        import numpy as np, jax
        from repro.core.config import scenario_config
        from repro.core.session import Engine
        from repro.core.stats import MarketStats
        cfg = scenario_config("flash-crash", **{_SHARD_CFG})

        def stats(**opts):
            with Engine("pallas-kinetic", stats_only=True, chunk_size=6,
                        **opts).open(cfg) as s:
                s.run(cfg.num_steps)
                return s.stats

        single, sharded = stats(), stats(devices=2)
        for f, a, b in zip(MarketStats._fields, single, sharded):
            assert (np.asarray(a) == np.asarray(b)).all(), f
        print("OK")
    """), devices=2)
    assert out.strip().splitlines()[-1] == "OK"


@pytest.mark.distributed
@pytest.mark.parametrize("backend", ["pallas-kinetic", "pallas-naive"])
def test_sharded_ensemble_bitwise_parity_inprocess(backend):
    """In-process variant for the CI `distributed` tier (XLA_FLAGS forces
    >= 2 host devices before pytest starts); skips on 1-device runs."""
    if _device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    from repro.core.config import scenario_config
    from repro.core.session import Engine

    cfg = scenario_config("flash-crash", num_markets=10, num_agents=16,
                          num_levels=32, num_steps=20, shock_step=9, seed=7)

    def run(**opts):
        with Engine(backend, chunk_size=6, **opts).open(cfg) as s:
            return s.run(cfg.num_steps).to_numpy()

    single, sharded = run(), run(devices=2)
    for f, a, b in zip(single._fields, single, sharded):
        assert (np.asarray(a) == np.asarray(b)).all(), (backend, f)


@pytest.mark.distributed
def test_sharded_session_no_warm_retrace_inprocess():
    if _device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    from repro.core.config import MarketConfig
    from repro.core.session import Engine

    cfg = MarketConfig(num_markets=10, num_agents=16, num_levels=32,
                       num_steps=18, seed=1)
    eng = Engine("pallas-kinetic", chunk_size=6, devices=2)
    with eng.open(cfg) as s:
        s.run(6)
        warm = eng.trace_count
        s.run(6)
        s.run(4)  # partial tail: n_valid gating, same trace
        assert eng.trace_count == warm


def test_markets_mesh_validation():
    from repro.launch.mesh import make_markets_mesh

    mesh = make_markets_mesh(1)
    assert mesh.axis_names == ("markets",)
    with pytest.raises(ValueError, match="devices"):
        make_markets_mesh(_device_count() + 1)


def test_hlo_analyzer_loop_accounting():
    out = _run_probe(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.hlo_analysis import summarize
        n = 128
        def f(x, w):
            def body(c, _):
                return c @ w, None
            out, _ = jax.lax.scan(body, x, None, length=16)
            return out
        c = jax.jit(f).lower(jax.ShapeDtypeStruct((n,n), jnp.float32),
                             jax.ShapeDtypeStruct((n,n), jnp.float32)).compile()
        s = summarize(c.as_text())
        print(s["flops"] / (16 * 2 * n**3))
    """))
    ratio = float(out.strip().splitlines()[-1])
    assert 0.95 < ratio < 1.10  # trip-count-aware (XLA's own reports ~1/16)


def test_sharded_matmul_collectives_detected():
    out = _run_probe(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import summarize
        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n = 128
        def g(x, w1, w2):
            return ((x @ w1) @ w2).sum()
        jf = jax.jit(g, in_shardings=(
            NamedSharding(mesh, P(None, None)),
            NamedSharding(mesh, P(None, "model")),
            NamedSharding(mesh, P("model", None))),
            out_shardings=NamedSharding(mesh, P()))
        cc = jf.lower(*[jax.ShapeDtypeStruct((n,n), jnp.float32)]*3).compile()
        s = summarize(cc.as_text())
        print(s["collective_breakdown"]["all-reduce"] > 0)
        print(abs(s["flops"] - 2*2*n**3/8) / (2*2*n**3/8) < 0.05)
    """))
    lines = out.strip().splitlines()
    assert lines[-2] == "True" and lines[-1] == "True"


def test_market_sharding_requires_markets_axis():
    import jax
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.launch.sharding import market_sharding, replicated_sharding

    mesh = jax.make_mesh((1,), ("markets",), axis_types=(AxisType.Auto,))
    assert market_sharding(mesh).spec == P("markets")
    assert replicated_sharding(mesh).spec == P()
    other = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="markets"):
        market_sharding(other)
