"""The program's own host spans in a profiler trace: count, total and self
time of each ``kinetic.*`` span inside the benchmark's window.

The program opens its spans through ``repro.ops.metrics.span`` (the list
is in that module's docstring); they land on the profiler's host plane,
on the clock of the device planes, with their arguments as the events'
stats. :func:`extract` reads them, with the ``kbench.window`` interval,
from the same ``.xplane.pb`` that :func:`kbench.trace.extract` reads;
:func:`reduce` keeps the events inside the window and sums each span's
duration and self time (its duration less what its child spans cover;
parentage is nesting on one host thread).

    python3 kbench/spans.py --workload a256.sweep --seed 7 --seconds 20

runs one cell traced, as ``kbench/run.py --trace 1`` does, and prints its
result line, then one JSON line with the span table and the span readers'
metrics (``kbench/metrics/*_ms*.py`` that read ``ctx.spans``).
``--config tableIV-a256 --traffic step`` runs a cell that
``BENCHMARK.json`` does not list, from a copy of the benchmark's files.

Temporary: :func:`run`, :func:`main` and :func:`adhoc_root` stand in for a
``ctx.spans`` that ``kbench/harness.py`` does not compute yet (it removes
the trace before any reader runs). Once the harness puts
``reduce(*extract(path))`` on its ``Context``, they go, and only
:func:`extract`, :func:`reduce` and their helpers stay.
"""
from __future__ import annotations

import time

T_ENTRY = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, NamedTuple, Optional, Tuple  # noqa: E402

PREFIX = "kinetic."
WINDOW = "kbench.window"
#: The readers of ``ctx.spans``, run by :func:`main`.
READERS = ("open_ms.sweep", "dispatch_ms_per_chunk.sweep",
           "to_host_ms_per_chunk.sweep")


class Event(NamedTuple):
    name: str
    thread: Tuple[str, int]        # (host plane, line index): a host thread
    start_ns: float
    dur_ns: float
    args: Dict[str, Any]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Stat(NamedTuple):
    count: int
    total_s: float
    self_s: float                  # total less what child spans cover


def extract(path: str) -> Tuple[List[Event], Optional[Tuple[float, float]]]:
    """The ``kinetic.*`` host events of an xplane file, with their
    arguments, and the ``kbench.window`` interval (``None`` if absent)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    events, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    events.append(Event(ev.name, (plane.name, k),
                                        float(ev.start_ns),
                                        float(ev.duration_ns),
                                        {k: v for k, v in ev.stats}))
                elif ev.name == WINDOW:
                    window = (float(ev.start_ns), float(ev.end_ns))
    return events, window


def parents(events: List[Event]) -> List[Optional[int]]:
    """For each event, the index of the innermost event on its thread that
    encloses it, or ``None``."""
    order = sorted(range(len(events)), key=lambda i: (
        events[i].thread, events[i].start_ns, -events[i].dur_ns))
    out: List[Optional[int]] = [None] * len(events)
    stack: List[int] = []
    for i in order:
        ev = events[i]
        while stack and (events[stack[-1]].thread != ev.thread
                         or events[stack[-1]].end_ns < ev.end_ns):
            stack.pop()
        out[i] = stack[-1] if stack else None
        stack.append(i)
    return out


def window_events(events: List[Event],
                  window: Optional[Tuple[float, float]]) -> List[Event]:
    """The events that lie wholly inside ``window`` (all where it is
    ``None``)."""
    if window is None:
        return list(events)
    w0, w1 = window
    return [e for e in events if e.start_ns >= w0 and e.end_ns <= w1]


def own_pieces(events: List[Event]) -> List[Tuple[str, float, float]]:
    """``(name, start_ns, dur_ns)`` pieces of each event's interval that
    none of its child spans covers: at every instant, the innermost span."""
    kids: Dict[int, List[Event]] = {}
    for i, p in enumerate(parents(events)):
        if p is not None:
            kids.setdefault(p, []).append(events[i])
    out = []
    for i, ev in enumerate(events):
        t = ev.start_ns
        for child in sorted(kids.get(i, ()), key=lambda c: c.start_ns):
            if child.start_ns > t:
                out.append((ev.name, t, child.start_ns - t))
            t = max(t, child.end_ns)
        if ev.end_ns > t:
            out.append((ev.name, t, ev.end_ns - t))
    return out


def reduce(events: List[Event],
           window: Optional[Tuple[float, float]] = None) -> Dict[str, Stat]:
    """Count, total and self seconds of each span name inside ``window``."""
    events = window_events(events, window)
    count: Dict[str, int] = {}
    total: Dict[str, float] = {}
    own: Dict[str, float] = {}
    for ev in events:
        count[ev.name] = count.get(ev.name, 0) + 1
        total[ev.name] = total.get(ev.name, 0.0) + ev.dur_ns * 1e-9
    for name, _, dur in own_pieces(events):
        own[name] = own.get(name, 0.0) + dur * 1e-9
    return {n: Stat(count[n], total[n], own.get(n, 0.0)) for n in count}


# ---- one traced run of a cell ----

def adhoc_root(config: str, traffic: str, tmp: str) -> Tuple[str, str]:
    """A copy of the benchmark's files under ``tmp`` whose
    ``BENCHMARK.json`` also lists the cell ``<config>.<traffic>``; it
    reports each ``.sweep`` per-layer metric whose reader has a
    ``.<traffic>`` twin, under that name (a traced run reads no end-to-end
    metric). Returns (root, cell name)."""
    from kbench import registry

    shutil.copytree(os.path.join(registry.ROOT, "kbench"),
                    os.path.join(tmp, "kbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = registry.benchmark()
    name = f"{config}.{traffic}"
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[config]
    with open(os.path.join(registry.ROOT, cfg_file)) as f:
        chips = int(json.load(f).get("chips", 1))
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "not in BENCHMARK.json"})
    for m in list(bench["per_layer"]):
        twin = m["name"].rsplit(".", 1)[0] + "." + traffic
        if (m["name"].endswith(".sweep") and twin != m["name"]
                and os.path.isfile(os.path.join(tmp, "kbench", "metrics",
                                                twin + ".py"))):
            bench["per_layer"].append(dict(m, name=twin, workloads=[name]))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp, name


def run(workload: str, seed: int, seconds: float, root: str,
        devices=None, t_entry: float = T_ENTRY) -> Tuple[dict, dict]:
    """One traced run of ``workload``: the harness's result object, and the
    spans of its window with the readers' metrics and, per span, the
    median and 95th percentile of its durations in ms and the device idle
    time (mean over chips) while it was the innermost span."""
    import numpy as np

    from kbench import harness, registry, trace

    got: Dict[str, Any] = {}
    extract_devices = trace.extract

    def extract_both(path):
        # The harness removes its trace directory once reduced.
        got["events"], got["window"] = extract(path)
        got["device"] = extract_devices(path)
        return got["device"]

    trace.extract = extract_both
    try:
        out = harness.run_cell(workload, seed, seconds, True, t_entry,
                               root=root, devices=devices)
    finally:
        trace.extract = extract_devices
    events = window_events(got["events"], got["window"])
    stats = reduce(events)
    ctx = harness.Context(spans=stats)
    metrics = {}
    for name in READERS:
        value = registry.reader(name, root)(ctx)
        if value is not None:
            metrics[name] = value
    ms: Dict[str, List[float]] = {}
    for ev in events:
        ms.setdefault(ev.name, []).append(ev.dur_ns * 1e-6)
    ops, host = got["device"]
    idle = trace.reduce(ops, [h for h in host if h.name == WINDOW] + [
        trace.Span(*piece) for piece in own_pieces(events)]).idle_by_span
    table = {name: {"count": s.count, "total_s": s.total_s,
                    "self_s": s.self_s, "idle_s": idle.get(name, 0.0),
                    "p50_ms": float(np.percentile(ms[name], 50)),
                    "p95_ms": float(np.percentile(ms[name], 95))}
             for name, s in sorted(stats.items())}
    return out, {"spans": table, "metrics": metrics,
                 "idle_outside_spans_s": idle.get("(no span)", 0.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.config and args.traffic):
        ap.error("give --workload, or --config and --traffic")

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [here, os.path.join(here, "src")]
    from kbench import harness, registry

    with tempfile.TemporaryDirectory(prefix="kbench-spans-") as tmp:
        root, workload = registry.ROOT, args.workload
        if not workload:
            root, workload = adhoc_root(args.config, args.traffic, tmp)
        try:
            cell = registry.cell(workload, root)
            devices = harness.look_for_chip(cell.chips)
        except (KeyError, harness.NoChip) as exc:
            print(f"kbench: {exc}", file=sys.stderr)
            return 3
        harness.info(f"compile cache: {harness.enable_cache()}")
        out, spans = run(workload, args.seed, args.seconds, root,
                         devices=devices)
    print(json.dumps(out), flush=True)
    print(json.dumps(spans), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
