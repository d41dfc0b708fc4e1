"""The program's ``kinetic.*`` spans: a traced CPU session shows every span
with its arguments, nested as ``repro.ops.metrics`` lists them and once
per chunk; the reduction's self times and window; the span readers on
hand-made spans; the ad hoc cell of ``kbench/spans.py``."""
from __future__ import annotations

import glob
import gzip
import os

import numpy as np
import pytest

from kbench import harness, registry, spans, trace
from kbench.spans import Event, Stat

M, A, L, S, CHUNK = 4, 16, 16, 12, 4
DATA = os.path.join(os.path.dirname(__file__), "data")


def _trace_session(tmp_path, backend, **opts):
    """Spans of: open, a 12-step stream in 4-step chunks with each batch
    copied to the host, then one ``Session.step`` with orders."""
    import jax

    from repro.core.config import MarketConfig
    from repro.core.session import Engine, ExternalOrders

    cfg = MarketConfig(num_markets=M, num_agents=A, num_levels=L,
                       num_steps=S, seed=3)
    eng = Engine(backend, **opts)
    orders = ExternalOrders(np.ones(M, bool), np.full(M, L // 2, np.int32),
                            np.ones(M, np.float32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng.open(cfg, chunk_size=CHUNK) as sess:
            for batch in sess.stream(S):
                batch.to_numpy()
            sess.step(orders).to_numpy()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events, window = spans.extract(path)
    assert window is None      # no benchmark window around this session
    return events


def _named(events, name):
    return [e for e in events if e.name == name]


def _parent_names(events):
    return [None if p is None else events[p].name
            for p in spans.parents(events)]


@pytest.mark.parametrize("backend", ["jax-scan", "pallas-kinetic"])
def test_session_spans_nest_with_their_arguments(backend, tmp_path,
                                                 monkeypatch):
    from repro.kernels import autotune as tune

    pallas = backend.startswith("pallas")
    opts = {}
    if pallas:   # a sweep of its own, whatever other tests cached
        monkeypatch.setattr(tune, "_TUNE_CACHE", {})
        monkeypatch.setattr(tune, "_SWEEP_REPORTS", [])
        opts["autotune"] = True
    events = _trace_session(tmp_path, backend, **opts)
    parent = {id(e): n for e, n in zip(events, _parent_names(events))}

    opened, = _named(events, "kinetic.open")
    assert opened.args == {"session": 0, "markets": M}
    assert parent[id(opened)] is None
    for child in ("kinetic.open.runner", "kinetic.open.place"):
        first = _named(events, child)[0]
        assert parent[id(first)] == "kinetic.open"
    place, = _named(events, "kinetic.open.place")
    assert place.args["bytes"] >= 2 * M * L * 4      # the books at least

    chunks = S // CHUNK
    dispatch = _named(events, "kinetic.dispatch")
    assert [(e.args["kind"], e.args["step0"], e.args["n"]) for e in dispatch
            ] == [("chunk", t, CHUNK) for t in range(0, S, CHUNK)] + [
                ("step", S, 1)]
    assert all(e.args["session"] == 0 for e in dispatch)
    assert [parent[id(e)] for e in dispatch] == [None] * chunks + [
        "kinetic.step"]

    step, = _named(events, "kinetic.step")
    orders, = _named(events, "kinetic.step.orders")
    assert parent[id(orders)] == "kinetic.step"
    assert orders.args == {"bytes": 2 * M * L * 4}

    to_host = _named(events, "kinetic.to_host")
    assert len(to_host) == chunks + 1
    assert [e.args["bytes"] for e in to_host] == [
        3 * M * CHUNK * 4] * chunks + [3 * M * 4]
    for child in ("kinetic.to_host.wait", "kinetic.to_host.copy"):
        got = _named(events, child)
        assert len(got) == chunks + 1
        assert {parent[id(e)] for e in got} == {"kinetic.to_host"}

    runner_children = ("kinetic.dispatch.operands", "kinetic.dispatch.launch",
                       "kinetic.dispatch.slice")
    for child in runner_children:
        got = _named(events, child)
        assert len(got) == (chunks + 1 if pallas else 0)
        assert {parent[id(e)] for e in got} <= {"kinetic.dispatch"}
    if pallas:
        operands = _named(events, "kinetic.dispatch.operands")
        assert [e.args["bytes"] for e in operands] == [8] * chunks + [
            8 + 2 * M * L * 4]
        sweeps = _named(events, "kinetic.open.autotune")
        assert sweeps and sweeps[0].args["candidates"] >= 1
        assert parent[id(sweeps[0])] == "kinetic.open.runner"
    else:
        assert not _named(events, "kinetic.open.autotune")

    stats = spans.reduce(events)
    assert stats["kinetic.dispatch"].count == chunks + 1
    for name, s in stats.items():
        assert 0 <= s.self_s <= s.total_s + 1e-12, name
    assert step.dur_ns >= orders.dur_ns + dispatch[-1].dur_ns


def _ev(name, start, dur, thread=0, **args):
    return Event(name, ("/host:CPU", thread), float(start), float(dur), args)


def test_reduce_self_time_nesting_and_window():
    events = [
        _ev("kinetic.open", 0, 100),
        _ev("kinetic.open.runner", 10, 20),
        _ev("kinetic.open.autotune", 12, 10),
        _ev("kinetic.open.place", 40, 30),
        # another thread, overlapping in time: not a child of open
        _ev("kinetic.dispatch", 20, 50, thread=1),
        _ev("kinetic.dispatch.launch", 30, 5, thread=1),
        _ev("kinetic.dispatch", 300, 10),      # outside the window
    ]
    assert _parent_names(events) == [
        None, "kinetic.open", "kinetic.open.runner", "kinetic.open",
        None, "kinetic.dispatch", None]
    got = spans.reduce(events, window=(0.0, 200.0))
    assert got["kinetic.open"] == pytest.approx(Stat(1, 100e-9, 50e-9))
    assert got["kinetic.open.runner"] == pytest.approx(Stat(1, 20e-9, 10e-9))
    assert got["kinetic.open.autotune"] == pytest.approx(Stat(1, 10e-9,
                                                              10e-9))
    assert got["kinetic.dispatch"] == pytest.approx(Stat(1, 50e-9, 45e-9))
    assert spans.reduce(events)["kinetic.dispatch"].count == 2
    assert sorted(spans.own_pieces(events[:4])) == [
        ("kinetic.open", 0, 10), ("kinetic.open", 30, 10),
        ("kinetic.open", 70, 30), ("kinetic.open.autotune", 12, 10),
        ("kinetic.open.place", 40, 30), ("kinetic.open.runner", 10, 2),
        ("kinetic.open.runner", 22, 8)]


def _read(name, table):
    return registry.reader(name)(harness.Context(spans=table))


@pytest.mark.parametrize("name,want", [
    ("open_ms.sweep", 1e3 * 0.06 / 3),
    ("dispatch_ms_per_chunk.sweep", 1e3 * 0.02 / 10),
    ("to_host_ms_per_chunk.sweep", 1e3 * 0.05 / 10),
])
def test_span_readers_on_hand_made_spans(name, want):
    table = {"kinetic.open": Stat(3, 0.06, 0.01),
             "kinetic.dispatch": Stat(10, 0.02, 0.001),
             "kinetic.to_host": Stat(10, 0.9, 0.0),
             "kinetic.to_host.copy": Stat(10, 0.05, 0.05)}
    assert _read(name, table) == pytest.approx(want)


@pytest.mark.parametrize("name", spans.READERS)
def test_span_readers_read_nothing_without_spans(name):
    # The harness's context has no spans, nor does a parent program's run.
    assert registry.reader(name)(harness.Context(trace=None)) is None
    assert _read(name, {}) is None


def test_adhoc_cell_lists_the_step_traffic(tmp_path):
    root, name = spans.adhoc_root("tableIV-a256", "step", str(tmp_path))
    assert name == "tableIV-a256.step"
    assert name not in {w["name"] for w in registry.benchmark()["workloads"]}
    cell = registry.cell(name, root)
    assert cell.traffic["mode"] == "step" and cell.chips == 1
    # No metric or bound is made up for it: of the end-to-end metrics it
    # has only those that BENCHMARK.json gives every cell.
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share.step", "kinetic_clearing_roofline.step",
        "step_mfu.step"}


def _unzip(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(os.path.join(DATA, name)) as f:
        path.write_bytes(f.read())
    return str(path)


def test_first_recorded_trace_reduces_as_before(tmp_path):
    """The device-side reduction is unchanged by the program's spans: the
    trace recorded before them reduces to the numbers it always gave."""
    r = trace.reduce(*trace.extract(_unzip("tiny.xplane.pb.gz", tmp_path)))
    assert (r.devices, r.events_by_kind) == (1, {"other": 4125, "kernel": 43})
    assert r.window_s == pytest.approx(0.301933873, rel=1e-12)
    assert r.busy_s == pytest.approx(0.004571988, rel=1e-12)
    assert r.seconds_by_kind == pytest.approx(
        {"other": 0.000402524, "kernel": 0.004169464}, rel=1e-9)
    assert r.idle_by_span == pytest.approx({
        "kbench.turnover": 0.007815659, "kbench.open": 0.104129537,
        "kbench.dispatch": 0.117753762, "(no span)": 0.004198376,
        "kbench.host_copy": 0.063464551}, rel=1e-9)


def test_recorded_chip_trace_with_program_spans(tmp_path):
    """``data/tiny_spans.xplane.pb.gz``: the harness's traced window of a
    tiny sweep cell on a TPU v5e (M=64, A=32, L=128, 0.3 s, 14 episodes of
    20 steps in 8-step chunks), cut to the device's ``XLA Ops`` line and the
    ``kbench.*`` and ``kinetic.*`` host spans."""
    path = _unzip("tiny_spans.xplane.pb.gz", tmp_path)
    ops, host = trace.extract(path)
    assert {op.name for op in ops if op.kind == "kernel"} == {
        "%kinetic_clearing_chunk.1"}
    r = trace.reduce(ops, host)

    events, window = spans.extract(path)
    events = spans.window_events(events, window)
    caller = {"kinetic.open": "kbench.open",
              "kinetic.dispatch": "kbench.dispatch",
              "kinetic.to_host": "kbench.host_copy"}
    for ev, p in zip(events, spans.parents(events)):
        if p is None:
            inside = [h for h in host if h.name == caller[ev.name]
                      and h.start_ns <= ev.start_ns
                      and ev.end_ns <= h.start_ns + h.dur_ns]
            assert len(inside) == 1, ev
    stats = spans.reduce(events)
    assert stats["kinetic.dispatch"].count == r.events_by_kind["kernel"] == 40
    assert stats["kinetic.to_host.copy"].count == 40
    assert stats["kinetic.open"].count == 14
    assert all(e.args["kind"] == "chunk" for e in events
               if e.name == "kinetic.dispatch")

    # The device idles through the whole session open of this tiny cell:
    # the idle charged to the innermost kinetic.open* span at each instant
    # is the benchmark's kbench.open idle gap, within 1%.
    win = [h for h in host if h.name == trace.WINDOW]
    idle = trace.reduce(ops, win + [trace.Span(*p) for p in
                                    spans.own_pieces(events)]).idle_by_span
    assert sum(v for k, v in idle.items() if k.startswith("kinetic.open")
               ) == pytest.approx(r.idle_by_span["kbench.open"], rel=0.01)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s,
                                               rel=1e-6)
