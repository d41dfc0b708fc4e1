"""One run of one cell: set-up, the measured window, the check, the metrics.

:func:`run_cell` is the whole run apart from the look for a chip, which
``kbench/run.py`` makes first; tests call it directly on the CPU at tiny
sizes.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

import numpy as np

from kbench import check, drive, peaks, registry, scenario, trace, work

BACKEND = "pallas-kinetic"


def info(msg: str) -> None:
    print(f"[kbench] {msg}", flush=True)


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def look_for_chip(chips: int):
    """The devices, or :class:`NoChip` where they are not TPUs enough."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts lowerings of new executables (each a compile or a cache load)
    while entered."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, name, _secs, **_):
        if name == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


class Context:
    """What a metric reader reads: the run's counts, window and trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def enable_cache() -> str:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    # Cache even the small tile-sweep candidates and slicing programs.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_entry: float, root: str = registry.ROOT,
             devices=None, control: Optional[str] = None,
             clock: Callable[[], float] = time.perf_counter) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    from repro.core.session import Engine

    cell = registry.cell(workload, root)
    cfg = cell.config
    devices = devices or jax.devices()
    kind = devices[0].device_kind
    chips = int(cfg.get("chips", 1))      # the engine shards over them
    if chips != cell.chips:
        raise ValueError(f"config {cfg['name']!r} runs on {chips} chips; "
                         f"cell {cell.name!r} asks for {cell.chips}")
    ens_seed, traffic_seed = scenario.seed_sequence(seed).spawn(2)
    ens = scenario.build(cfg, np.random.default_rng(ens_seed))
    opts = {"devices": chips} if chips > 1 else {}
    engine = Engine(BACKEND, **opts)
    traffic = drive.Traffic(cell.traffic, cfg, ens, traffic_seed,
                            devices=chips)
    traffic.warm(engine)
    setup_s = clock() - t_entry

    traces0 = engine.trace_count
    trace_dir = tempfile.mkdtemp(prefix="kbench-trace-") if traced else None
    with CompileCounter() as compiles:
        if traced:
            # Host spans and device operations only: the Python tracer
            # would slow the host loop it is meant to observe.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            win = traffic.window(engine, seconds, clock)
        finally:
            if traced:
                jax.profiler.stop_trace()
    traced_count = engine.trace_count - traces0

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    runner = next(iter(engine._runners.values()))
    info(f"device: {kind} x{len(devices)}; cell {cell.name} on {chips} "
         f"chip(s); backend {BACKEND}")
    info(f"tile: mb={runner.tile.mb} agent_chunk={runner.tile.agent_chunk} "
         f"(chunk {traffic.chunk})")
    info(f"set-up s {setup_s}")
    info(f"window: {win.seconds} s, {win.requests} requests, {win.steps} "
         f"valid steps, {len(win.episodes)} episodes")
    info(f"compiles in window: {compiles.count} lowerings, {traced_count} "
         f"engine traces")
    info(f"peak device memory bytes: {mem_peak}")
    if win.latencies:
        lat = np.asarray(win.latencies)
        p50, p95, p99 = np.percentile(lat, [50, 95, 99]) * 1e3
        info(f"latency samples: {lat.size}; above p95: "
             f"{int((lat * 1e3 > p95).sum())}; ms p50 {p50} p95 {p95} p99 "
             f"{p99} max {lat.max() * 1e3}")
    engine.clear_cache()
    del engine, runner
    gc.collect()

    reduced = None
    if traced:
        try:
            paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                     for f in fs if f.endswith(".xplane.pb")]
            reduced = trace.reduce(*trace.extract(paths[0]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    cfg_work = work.Work(0.0, 0.0)
    for n in win.launches:
        cfg_work = cfg_work + work.launch(
            cfg["num_markets"], cfg["num_agents"], cfg["num_levels"], n,
            orders=traffic.mode == "step")
    ctx = Context(cell=cell, config=cfg, window=win, setup_s=setup_s,
                  trace=reduced, work=cfg_work,
                  peak=peaks.peak(kind) if traced else None, chips=chips)

    t0 = clock()
    sample = cell.traffic["check"].get("episodes")
    verdict = check.run(cfg, ens, win.episodes, traffic.chunk, sample=sample,
                        rng=np.random.default_rng(traffic.pick_seed))
    info(f"check: {verdict['episodes']} episodes, {verdict['rows']} rows, "
         f"{verdict['entries']} path entries against the reference in "
         f"{clock() - t0} s")
    if control:
        ctl = check.run(cfg, ens, win.episodes, traffic.chunk,
                        sample=sample,
                        rng=np.random.default_rng(traffic.pick_seed),
                        control=control)
        info(f"control ({control} reference in the program's place): "
             f"correct {ctl['correct']}, {ctl['numbers']}")

    wanted = cell.per_layer if traced else cell.end_to_end
    metrics: Dict[str, dict] = {}
    for m in wanted:
        value = registry.reader(m["name"], root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if traced:
        least, bound = work.least_seconds(cfg_work, ctx.peak["flops"],
                                          ctx.peak["hbm_bytes_per_s"])
        info(f"roofline: the semantics' work in the traced window is "
             f"{cfg_work.ops} ops and {cfg_work.bytes} bytes, bound by "
             f"{bound}: least {least} chip-seconds; kernel time "
             f"{reduced.seconds_by_kind.get('kernel', 0.0)} s over "
             f"{reduced.events_by_kind.get('kernel', 0)} events")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if traced:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    out = {"correct": verdict["correct"], "attempted": win.requests,
           "failed": 0, "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = reduced.breakdown()
    if control:
        out["control"] = {"correct": ctl["correct"],
                          "check": ctl["numbers"]}
    out["check"] = verdict["numbers"]
    return out


def report(out: dict) -> None:
    """The result line, then the compared numbers as the last lines on
    standard error."""
    print(json.dumps(out), flush=True)
    for name, num in out["check"].items():
        print(f"check {name} {num['value']} limit {num['limit']}",
              file=sys.stderr, flush=True)
