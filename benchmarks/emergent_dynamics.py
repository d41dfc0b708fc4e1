"""Paper Fig 7 / §IV-J: emergent market dynamics over the composition sweep.

Sweeps the momentum-agent fraction (alpha_mom 0.0 -> 0.70, step 0.05 at full
scale), fixes alpha_maker = 0.15, and reports the four stylized facts:
volatility escalation, fat tails (excess kurtosis), volume stimulation, and
volatility clustering (ACF of r_t vs |r_t|).

The per-configuration measurement grew into the scenario validation
subsystem and now lives in :mod:`repro.scenario.validate`; this module
re-exports :func:`stylized_facts` and the pinned smoke configuration so
existing imports (tests/test_emergent.py, downstream notebooks) keep
working. New code should import from ``repro.scenario.validate`` directly —
that module adds the typed :class:`~repro.scenario.validate.FactCheck` /
:class:`~repro.scenario.validate.ValidationReport` gate that CI runs via
``benchmarks/scenario_realism.py``.
"""
from __future__ import annotations

import argparse

from benchmarks.common import FULL, emit, time_call
from repro.core import engine
from repro.core.config import MarketConfig
from repro.scenario.validate import (  # noqa: F401  (re-exports)
    high_vol_momentum_config,
    stylized_facts,
)

SWEEP = ([round(x * 0.05, 2) for x in range(15)] if FULL
         else [0.0, 0.15, 0.30, 0.50, 0.70])
M = 64
S = 1000 if FULL else 200


def high_vol_smoke_config(num_steps: int = 500) -> MarketConfig:
    """The configuration the slow stylized-facts smoke pins.

    Alias of :func:`repro.scenario.validate.high_vol_momentum_config` —
    the same pinned mixture the CI realism gate validates.
    """
    return high_vol_momentum_config(num_steps)


def _sweep_config(amom: float) -> MarketConfig:
    # Calibrated dynamics parameterization (PERF.md, section 8: the
    # paper omits noise_delta / P_mkt; these values reproduce all four
    # stylized facts qualitatively).
    return MarketConfig(num_markets=M, num_agents=256, num_steps=S,
                        alpha_maker=0.15, alpha_momentum=amom, seed=1,
                        noise_delta=2.0, p_marketable=0.2)


def run(backend: str = "jax-scan") -> list:
    rows = []
    total_events = 0
    total_t = 0.0
    for amom in SWEEP:
        cfg = _sweep_config(amom)
        t, _ = time_call(engine.simulate, cfg, backend=backend,
                         trials=1, warmup=0)
        facts = stylized_facts(cfg, backend=backend)
        total_events += cfg.events()
        total_t += t
        rows.append((f"fig7/alpha_mom_{amom:.2f}", t * 1e6,
                     f"volatility={facts['volatility']:.3f};"
                     f"ex_kurtosis={facts['excess_kurtosis']:.2f};"
                     f"volume_per_step={facts['volume_per_step']:.1f};"
                     f"vv_corr={facts['volume_volatility_corr']:.3f}"))
    # volatility clustering at the momentum-heavy configuration
    facts = stylized_facts(MarketConfig(
        num_markets=M, num_agents=256, num_steps=S, alpha_momentum=0.40,
        seed=1, noise_delta=2.0, p_marketable=0.2), backend=backend)
    rows.append(("fig7/acf", 0.0,
                 f"r_lag1={facts['acf_r_lag1']:.3f};"
                 f"abs_lag1={facts['acf_abs_lag1']:.3f};"
                 f"abs_lag10={facts['acf_abs_lag10']:.3f}"))
    # high-vol preset: the configuration the smoke test pins (fat tails +
    # positive volume/volatility correlation)
    facts = stylized_facts(high_vol_smoke_config(), backend=backend)
    rows.append(("fig7/high_vol_preset", 0.0,
                 f"kurtosis={facts['kurtosis']:.2f};"
                 f"vv_corr={facts['volume_volatility_corr']:.3f}"))
    rows.append(("fig7/sweep_total", total_t * 1e6,
                 f"events={total_events};events_per_s="
                 f"{total_events / total_t:.4g}"))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="jax-scan")
    ap.add_argument("--json", default=None,
                    help="also write a BENCH_*.json artifact here")
    ns = ap.parse_args()
    emit(run(backend=ns.backend), json_path=ns.json,
         benchmark="emergent_dynamics")
