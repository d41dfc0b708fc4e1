"""The general traffic generator: drives ``Engine``/``Session`` as a mix's
data file says.

A traffic file names a ``mode`` and its parameters:

* ``sweep``: back-to-back episodes of ``episode_steps`` steps, each a new
  ``Session`` on the warm engine with parameter values redrawn from the
  seed, streamed in chunks of ``chunk`` steps; each batch of paths is copied
  to the host as it completes (a closed loop with one caller).
* ``step``: one caller steps a session one step at a time, placing one
  limit order per market from the last observed mid (``orders``), and
  brings each observation to the host; a new session opens every
  ``episode_steps`` steps.

Both record, for every episode, what the check needs: the episode's
parameter values, samples of rows drawn from the seed (``check``) with
their coupling cones, those rows' paths as the host received them, and the
orders sent to the cones' rows. Host spans (``kbench.*``) mark what the
host is doing, for the traced run.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, NamedTuple

import numpy as np

from kbench import reference, scenario

MODES = ("sweep", "step")


def span(name: str):
    """A host span in the profiler's trace (a no-op while not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Sample:
    """A run of consecutive sampled rows of one episode, with its coupling
    cone (``rows``, the checked ``n_check`` first, and their ``depth``):
    what the host received for the checked rows, and the orders sent to the
    cone's rows."""

    def __init__(self, rows, depth, n_check):
        self.rows, self.depth, self.n_check = rows, depth, n_check
        self.paths: Dict[str, List[np.ndarray]] = {"price": [], "volume": [],
                                                   "mid": []}
        self.orders: Dict[int, tuple] = {}


class Episode:
    """What one episode leaves for the check: its parameter draws, its
    samples and the steps whose outputs reached the host."""

    def __init__(self, drawn, samples: List[Sample]):
        self.drawn = drawn
        self.samples = samples
        self.steps = 0

    def add(self, batch) -> None:
        for s in self.samples:
            for name in s.paths:
                s.paths[name].append(
                    np.asarray(getattr(batch, name))[s.rows[:s.n_check]])
        self.steps += int(batch.price.shape[-1])

    def add_orders(self, t: int, orders) -> None:
        for s in self.samples:
            s.orders[t] = tuple(np.asarray(x)[s.rows] for x in orders)


class Window(NamedTuple):
    seconds: float                 # wall time of the window
    steps: int                     # valid steps whose outputs reached host
    requests: int                  # chunk or step calls in the window
    launches: List[int]            # valid steps of each kernel launch
    latencies: List[float]         # seconds per request (step mode)
    episodes: List[Episode]


class Traffic:
    """One cell's traffic over one ensemble, from the run's seed."""

    def __init__(self, traffic: dict, config: dict, ens: scenario.Ensemble,
                 seeds: np.random.SeedSequence, devices: int = 1):
        if traffic.get("mode") not in MODES:
            raise KeyError(f"traffic mode {traffic.get('mode')!r} is not one "
                           f"of {MODES}")
        self.t = traffic
        self.cfg = config
        self.ens = ens
        self.devices = devices
        self.mode = traffic["mode"]
        self.S = int(traffic["episode_steps"])
        self.chunk = 1 if self.mode == "step" else int(traffic["chunk"])
        self.n_chunks = len(reference.chunk_plan(self.S, self.chunk))
        warm, window, sample, self.pick_seed = seeds.spawn(4)
        self.rng_warm = np.random.default_rng(warm)
        self.rng = np.random.default_rng(window)
        self.rng_sample = np.random.default_rng(sample)
        self.spec = scenario.program_spec(config, ens)

    # ---- the check's sample ----
    def _episode(self, drawn) -> Episode:
        """A new episode record with its sample of rows: ``segments`` runs
        of ``segment_rows`` consecutive markets, one at a place drawn from
        the seed in each of ``segments`` equal stretches of the market axis
        (so a fault in any half of the ensemble is sampled); on a sharded
        engine also one run ending at a row whose peer lives on another
        chip, for each chip."""
        M = self.cfg["num_markets"]
        chk = self.t["check"]
        k, n = int(chk["segment_rows"]), int(chk["segments"])
        ends = [i * M // n + int(self.rng_sample.integers(0, M // n)) + k - 1
                for i in range(n)]
        if self.devices > 1:
            peer = self.ens.params["coupling_peer"]
            per = -(-M // self.devices)
            own = np.arange(M)
            cross = own[(peer >= 0) & (peer // per != own // per)]
            if cross.size:
                ends += list(self.rng_sample.choice(
                    cross, min(self.devices, cross.size), replace=False))
        samples = []
        for e in ends:
            check = [int(r) % M for r in range(e - k + 1, e + 1)]
            rows, depth = reference.cone(
                check, self.ens.params["coupling_peer"], self.n_chunks)
            samples.append(Sample(rows, depth, len(check)))
        return Episode(drawn, samples)

    def _draw(self, rng):
        drawn = scenario.draw_episode(self.cfg, self.ens, rng)
        return drawn, self.spec.with_values(**drawn)

    def _orders(self, mid: np.ndarray, rng: np.random.Generator):
        """One limit order per market around the last observed mid."""
        from repro.core.session import ExternalOrders

        o = self.t["orders"]
        M, L = self.cfg["num_markets"], self.cfg["num_levels"]
        off = int(o["max_offset_ticks"])
        side = rng.random(M) < float(o["p_buy"])
        tick = np.clip(np.rint(mid).astype(np.int64)
                       + rng.integers(-off, off + 1, M), 0, L - 1)
        qty = rng.integers(0, int(o["max_qty"]) + 1, M).astype(np.float32)
        return ExternalOrders(side, tick.astype(np.int32), qty)

    # ---- set-up ----
    def warm(self, engine) -> None:
        """Compile and run once every shape the window uses, and one episode
        turnover, with draws of their own."""
        for _ in range(2):
            _, spec = self._draw(self.rng_warm)
            with engine.open(spec, chunk_size=self.chunk) as sess:
                if self.mode == "sweep":
                    tail = self.S % self.chunk
                    for n in (self.chunk, tail) if tail else (self.chunk,):
                        for batch in sess.stream(n):
                            batch.to_numpy()
                else:
                    mid = np.full(self.cfg["num_markets"],
                                  self.cfg["num_levels"] // 2)
                    for _ in range(2):
                        obs = sess.step(self._orders(mid, self.rng_warm))
                        mid = obs.to_numpy().mid[:, -1]

    # ---- the measured window ----
    def window(self, engine, seconds: float,
               clock: Callable[[], float] = time.perf_counter) -> Window:
        run = self._sweep if self.mode == "sweep" else self._step
        with span("kbench.window"):
            return run(engine, seconds, clock)

    def _sweep(self, engine, seconds, clock) -> Window:
        episodes, launches = [], []
        steps = 0
        t0 = clock()
        done = False
        while not done:
            with span("kbench.turnover"):
                drawn, spec = self._draw(self.rng)
                rec = self._episode(drawn)
            with span("kbench.open"):
                sess = engine.open(spec, chunk_size=self.chunk)
            with sess:
                it = sess.stream(self.S)
                while True:
                    with span("kbench.dispatch"):
                        batch = next(it, None)
                    if batch is None:
                        break
                    with span("kbench.host_copy"):
                        host = batch.to_numpy()
                    rec.add(host)
                    launches.append(host.num_steps)
                    steps += host.num_steps
                    if clock() - t0 >= seconds:
                        done = True
                        it.close()
                        break
            episodes.append(rec)
        return Window(clock() - t0, steps, len(launches), launches, [],
                      episodes)

    def _step(self, engine, seconds, clock) -> Window:
        episodes, lat = [], []
        M, L = self.cfg["num_markets"], self.cfg["num_levels"]
        t0 = clock()
        done = False
        while not done:
            with span("kbench.turnover"):
                drawn, spec = self._draw(self.rng)
                rec = self._episode(drawn)
            with span("kbench.open"):
                sess = engine.open(spec, chunk_size=1)
            with sess:
                mid = np.full(M, L // 2)
                for t in range(self.S):
                    with span("kbench.orders"):
                        orders = self._orders(mid, self.rng)
                    due = clock()
                    with span("kbench.step"):
                        batch = sess.step(orders)
                    with span("kbench.host_copy"):
                        host = batch.to_numpy()
                    lat.append(clock() - due)
                    rec.add_orders(t, orders)
                    rec.add(host)
                    mid = host.mid[:, -1]
                    if clock() - t0 >= seconds:
                        done = True
                        break
            episodes.append(rec)
        return Window(clock() - t0, len(lat), len(lat), [1] * len(lat), lat,
                      episodes)
