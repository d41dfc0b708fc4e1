#!/usr/bin/env python3
"""Smoke run of the KineticSim engine on a TPU: the main path, checked bitwise.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a four-chip host

With no option it drives ``Engine("pallas-kinetic")`` through
``open``/``stream``/``step`` on a heterogeneous ensemble at the paper's
Table IV width (M=8192 markets, A=256 agents, L=128 ticks): every agent
archetype populated, scenario overlays live, and a ring-coupled arbitrageur
block. It streams two full chunks and a partial tail, makes one
``Session.step`` with external orders, and runs a ``stats_only`` session.
Books, paths and statistics must be bitwise equal to ``jax-scan`` on the
same chip and to the ``numpy`` host reference, which runs on a host thread
meanwhile.

``--chips 4`` runs only the ring-coupled ensemble sharded over four chips
(``devices=4``: a ``ppermute`` ring halo per chunk) and compares it bitwise
with the same run on one chip.

Lines before the last are informational. The last line is one JSON object,
``{"ok": true, "device": {...}}``; any mismatch or error exits non-zero
without it. The script refuses to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

M, A, L = 8192, 256, 128
CHUNK = 64
TAIL = 9  # a partial last chunk: exercises the kernel's n_valid gate

#: (preset, overrides) blocks of equal size. Together they populate every
#: archetype; every block carries a few arbitrageurs and the last block is
#: arbitrage-heavy, all on one ring coupling over the whole ensemble.
BLOCKS = (
    ("baseline", dict(alpha_fundamentalist=0.30)),
    ("flash-crash", {}),
    ("high-vol", {}),
    ("whale", {}),
    ("hft", {}),
    ("informed", {}),
    ("thin-book", {}),
    ("baseline", dict(alpha_arbitrageur=0.30)),
)
ARB_SHARE = 0.05


class Mismatch(AssertionError):
    pass


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def build_spec(num_markets=M, num_agents=A, num_levels=L,
               num_steps=2 * CHUNK + TAIL, seed=0):
    """The heterogeneous ring-coupled ensemble (see ``BLOCKS``)."""
    from repro.core.config import scenario_config
    from repro.core.params import EnsembleSpec
    from repro.scenario import CouplingSpec

    per_block = num_markets // len(BLOCKS)
    cfgs = []
    for preset, overrides in BLOCKS:
        kw = dict(num_markets=per_block, num_agents=num_agents,
                  num_levels=num_levels, num_steps=num_steps, seed=seed,
                  alpha_arbitrageur=ARB_SHARE)
        kw.update(overrides)
        cfgs.append(scenario_config(preset, **kw))
    spec = EnsembleSpec.from_scenarios(cfgs)
    return CouplingSpec.ring(spec.num_markets).apply(spec)


def external_orders(spec, seed=0):
    """One random external limit order per market for ``Session.step``."""
    import numpy as np

    from repro.core.session import ExternalOrders

    rng = np.random.default_rng(seed)
    m = spec.num_markets
    return ExternalOrders(
        side_buy=rng.random(m) < 0.5,
        price=rng.integers(0, spec.num_levels, m).astype(np.int32),
        qty=rng.integers(0, 9, m).astype(np.float32))


def drive(engine, spec, orders, chunk, timings=None):
    """The main path: open, stream chunks (last one partial), one step.

    Returns the host copies of the final books and the concatenated paths.
    """
    import jax
    import numpy as np

    from repro.core.session import StepBatch

    batches = []
    t0 = time.perf_counter()
    with engine.open(spec, chunk_size=chunk) as sess:
        t1 = time.perf_counter()
        if timings is not None:
            timings.append(("open", t1 - t0))
        for batch in sess.stream(spec.num_steps):
            jax.block_until_ready(batch)
            t0 = time.perf_counter()
            if timings is not None:
                timings.append((f"{batch.num_steps} steps", t0 - t1))
            batches.append(batch.to_numpy())
            t1 = time.perf_counter()
        batches.append(sess.step(orders).to_numpy())
        state = {k: np.asarray(v) for k, v in sess.state._asdict().items()}
    paths = StepBatch.concatenate(batches)._asdict()
    return {**state, **{f"{k}_path": v for k, v in paths.items()}}


def run_stats(engine, spec, chunk):
    """A ``stats_only`` session over the spec's horizon."""
    with engine.open(spec, chunk_size=chunk) as sess:
        sess.run(spec.num_steps)
        return sess.stats._asdict()


def fold_stats(result, num_steps):
    """The statistics a ``stats_only`` session accumulates, folded on the
    host from a path run's first ``num_steps`` columns (same function, same
    step order)."""
    import numpy as np

    from repro.core import stats as stats_mod

    st = stats_mod.init_stats(result["mid_path"].shape[0], np)
    for s in range(num_steps):
        st = stats_mod.accumulate(st, result["mid_path"][:, s:s + 1],
                                  result["volume_path"][:, s:s + 1], True, np)
    return st._asdict()


def compare(got, want, what, spec):
    """Bitwise field-by-field equality of two results. Returns one line per
    differing field (empty when equal): the count, the scenario blocks of
    the rows, and the earliest differing step of a path."""
    from collections import Counter

    import numpy as np

    problems = []
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.dtype != b.dtype or a.shape != b.shape:
            problems.append(f"{what}: {k} is {a.dtype}{a.shape}, "
                            f"want {b.dtype}{b.shape}")
            continue
        bad = a.view(np.uint32) != b.view(np.uint32)
        if not bad.any():
            continue
        rows = np.flatnonzero(bad.any(axis=1))
        step = int(np.flatnonzero(bad.any(axis=0))[0])
        row = int(np.flatnonzero(bad[:, step])[0])
        blocks = Counter(spec.scenarios[r] for r in rows)
        problems.append(
            f"{what}: {k} differs at {int(bad.sum())} entries in "
            f"{rows.size} rows {dict(blocks)}; first column {step}, row "
            f"{row}: {a[row, step]!r} vs {b[row, step]!r}")
    info(f"{'bitwise equal' if not problems else 'DIFFERENT'}: {what}")
    return problems


def one_chip_phase(spec, chunk=CHUNK, seed=0):
    """``pallas-kinetic`` through Engine/Session, checked against
    ``jax-scan`` on the chip and the ``numpy`` reference on the host."""
    from repro.core.session import Engine

    orders = external_orders(spec, seed)
    with ThreadPoolExecutor(1) as pool:
        host_ref = pool.submit(drive, Engine("numpy"), spec, orders, chunk)

        eng = Engine("pallas-kinetic")
        timings = []
        kinetic = drive(eng, spec, orders, chunk, timings)
        runner = eng._runner(spec, chunk)
        info(f"pallas-kinetic tile: mb={runner.tile.mb} "
             f"agent_chunk={runner.tile.agent_chunk} "
             f"interpret={runner.interpret}")
        info(f"pallas-kinetic traces: {eng.trace_count} "
             f"(chunk={chunk} and step executables)")
        info("seconds (open includes the tile sweep, the first chunk its "
             "compile), informational: "
             + ", ".join(f"{n} {t:.6f}" for n, t in timings))
        check_mosaic(runner, spec)

        scan = drive(Engine("jax-scan"), spec, orders, chunk)
        stats = run_stats(Engine("pallas-kinetic", stats_only=True), spec,
                          chunk)
        scan_stats = run_stats(Engine("jax-scan", stats_only=True), spec,
                               chunk)
        from repro.kernels import autotune

        for rep in autotune.sweep_reports():
            info(f"tile sweep {rep.key[1:]}: mb={rep.winner.mb} "
                 f"agent_chunk={rep.winner.agent_chunk} of {len(rep.tried)}")
        t0 = time.perf_counter()
        reference = host_ref.result()
        info(f"numpy reference wait, informational: "
             f"{time.perf_counter() - t0:.6f} s")
    problems = (
        compare(kinetic, scan, "pallas-kinetic vs jax-scan (chip)", spec)
        + compare(stats, scan_stats, "pallas-kinetic stats_only vs jax-scan "
                  "stats_only (chip)", spec)
        + compare(kinetic, reference, "pallas-kinetic vs numpy (host)", spec)
        + compare(scan, reference, "jax-scan vs numpy (host)", spec)
        + compare(stats, fold_stats(reference, spec.num_steps),
                  "pallas-kinetic stats_only vs numpy paths folded (host)",
                  spec))
    if problems:
        raise Mismatch("\n".join(problems))


def check_mosaic(runner, spec):
    """The runner lowers its kernel through Mosaic, not the interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.core.step import MarketState

    if runner.interpret:
        raise Mismatch("pallas-kinetic runs in interpret mode on a TPU")
    sds = jax.ShapeDtypeStruct
    book = sds((spec.num_markets, spec.num_levels), jnp.float32)
    col = sds((spec.num_markets, 1), jnp.float32)
    scalar = sds((1, 1), jnp.int32)
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          spec.params.asarray(jnp))
    hlo = runner._chunk_fn.lower(MarketState(book, book, col, col), None,
                                 params, scalar, scalar, book,
                                 book).as_text()
    if "tpu_custom_call" not in hlo:
        raise Mismatch("no tpu_custom_call in the pallas-kinetic HLO")
    info("pallas-kinetic HLO contains tpu_custom_call")


def four_chip_phase(spec, chunk=CHUNK, seed=0, devices=4):
    """The ring-coupled ensemble sharded over ``devices`` chips against the
    same run on one chip."""
    from repro.core.session import Engine

    orders = external_orders(spec, seed)
    sharded_eng = Engine("pallas-kinetic", devices=devices)
    sharded = drive(sharded_eng, spec, orders, chunk)
    runner = sharded_eng._runner(spec, chunk)
    info(f"sharded over {runner._n_shards} devices: tile mb={runner.tile.mb} "
         f"agent_chunk={runner.tile.agent_chunk} "
         f"interpret={runner.interpret}")
    check_mosaic(runner, spec)
    single = drive(Engine("pallas-kinetic"), spec, orders, chunk)
    problems = compare(sharded, single,
                       f"pallas-kinetic on {devices} chips vs 1 chip", spec)
    if problems:
        raise Mismatch("\n".join(problems))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    info(f"device: {devices[0].device_kind} x{len(devices)}, "
         f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    spec = build_spec(seed=args.seed)
    info(f"ensemble: M={spec.num_markets} A={spec.num_agents} "
         f"L={spec.num_levels} steps={spec.num_steps}+1 chunk={CHUNK} "
         f"blocks={[b for b, _ in BLOCKS]}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(spec, seed=args.seed)
    else:
        one_chip_phase(spec, seed=args.seed)
    info(f"phase wall seconds, informational: {time.perf_counter() - t0:.6f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
