"""Reduce a profiler trace to device busy and idle time, time per device
operation, and idle gaps by what the host was doing.

:func:`extract` reads a trace (``.xplane.pb``) into plain events: each
device's operations (the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane) and the benchmark's host spans (``kbench.*`` annotations).
:func:`reduce` then works on those events alone, inside the
``kbench.window`` span:

* busy time is the union of a device's operation intervals, averaged over
  the devices; idle share is one minus busy over the window;
* each operation is classed, from its HLO text, as the clearing kernel (a
  Mosaic custom call, ``custom_call_target="tpu_custom_call"``), a
  collective, or other device work (the runner's glue);
* each idle interval is charged to the host spans it overlaps; what no
  span covers is charged to ``(no span)``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Tuple

WINDOW = "kbench.window"
_COLLECTIVE = re.compile(r"^%?(collective-permute|all-gather|all-reduce|"
                         r"reduce-scatter|all-to-all|send|recv)", re.I)
_KERNEL = 'custom_call_target="tpu_custom_call"'   # a Mosaic kernel
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


class Op(NamedTuple):
    device: int
    name: str
    kind: str          # "kernel", "collective" or "other"
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                  # mean over devices
    devices: int
    seconds_by_kind: Dict[str, float]   # summed over devices
    events_by_kind: Dict[str, int]
    op_seconds: Dict[str, float]        # per op name, mean over devices
    idle_by_span: Dict[str, float]      # mean over devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv:
                                               -kv[1])[:top]]
        return {"device_ops": head(self.op_seconds),
                "idle_gaps": head(self.idle_by_span)}


def op_name(text: str) -> str:
    """An XLA op's name from its event text: ``%name = <hlo>`` -> ``%name``."""
    return text.split(" = ", 1)[0]


def classify(text: str) -> str:
    """``kernel``, ``collective`` or ``other``, from an op's HLO text."""
    if _KERNEL in text:
        return "kernel"
    if _COLLECTIVE.match(op_name(text)):
        return "collective"
    return "other"


def extract(path: str) -> Tuple[List[Op], List[Span]]:
    """Device operations and ``kbench.*`` host spans from an xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(dev, op_name(ev.name), classify(ev.name),
                                  float(ev.start_ns), float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("kbench."):
                        spans.append(Span(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
    return ops, spans


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(ops: List[Op], spans: List[Span]) -> Reduced:
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0 = windows[0].start_ns
    w1 = w0 + windows[0].dur_ns
    inner = sorted((s for s in spans if s.name != WINDOW),
                   key=lambda s: s.start_ns)
    starts = [s.start_ns for s in inner]

    devices = sorted({op.device for op in ops})
    by_kind: Dict[str, float] = defaultdict(float)
    n_kind: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for dev in devices:
        mine = []
        for op in ops:
            if op.device != dev:
                continue
            s, e = max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)
            if e <= s:
                continue
            mine.append((s, e))
            by_kind[op.kind] += (e - s) * 1e-9
            n_kind[op.kind] += 1
            op_s[op.name] += (e - s) * 1e-9 / len(devices)
        merged = _union(mine)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                _charge(g0, g1, inner, starts, idle, len(devices))
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy / max(1, len(devices)),
                   devices=len(devices), seconds_by_kind=dict(by_kind),
                   events_by_kind=dict(n_kind), op_seconds=dict(op_s),
                   idle_by_span=dict(idle))


def _charge(g0, g1, inner, starts, idle, n_dev) -> None:
    """Charge the idle interval [g0, g1) to the host spans it overlaps."""
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, g0) - 1)
    while i < len(inner) and inner[i].start_ns < g1:
        s = inner[i]
        ov = min(g1, s.start_ns + s.dur_ns) - max(g0, s.start_ns)
        if ov > 0:
            idle[s.name] += ov * 1e-9 / n_dev
            covered += ov
        i += 1
    rest = (g1 - g0) - covered
    if rest > 0:
        idle["(no span)"] += rest * 1e-9 / n_dev
