"""The trace reduction: busy union, classification of device operations,
and idle gaps charged to host spans; on hand-made events and on a small
trace recorded on a TPU v5e (``data/tiny.xplane.pb.gz``: the harness's
traced window of a tiny sweep cell, M=64, A=32, L=128, 0.3 s, cut to the
device's ``XLA Ops`` line and the ``kbench.*`` host spans)."""
from __future__ import annotations

import gzip
import os

import pytest

from kbench import trace
from kbench.trace import Op, Span

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tiny.xplane.pb.gz")
KERNEL = ('%chunk_fn.1 = (f32[8,128]) custom-call(s32[1,1] %a), '
          'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("text,kind", [
    (KERNEL, "kernel"),
    ('%custom-call.2 = f32[64,128] custom-call(f32[16,128] %s), '
     'custom_call_target="ConcatBitcast"', "other"),
    ("%collective-permute-start.1 = (f32[8,1]) collective-permute-start("
     "f32[8,1] %x)", "collective"),
    ("%all-gather.3 = f32[32,1] all-gather(f32[8,1] %y)", "collective"),
    ("%copy.5 = f32[8,1] copy(f32[8,1] %collective-permute-done)", "other"),
    ("%fusion = f32[8,128] fusion(f32[8,128] %p)", "other"),
])
def test_classify(text, kind):
    assert trace.classify(text) == kind
    assert trace.op_name(text) == text.split(" = ")[0]


def test_reduce_by_hand():
    # Window [0, 100) ns; device 0 busy [10, 30) u [20, 40) and [60, 70);
    # device 1 busy [0, 50). Host spans cover [0, 50) and [55, 100).
    ops = [Op(0, "%k", "kernel", 10, 20), Op(0, "%g", "other", 20, 20),
           Op(0, "%k", "kernel", 60, 10), Op(1, "%k", "kernel", 0, 50),
           Op(1, "%c", "collective", 120, 5)]      # outside the window
    spans = [Span("kbench.window", 0, 100), Span("kbench.dispatch", 0, 50),
             Span("kbench.host_copy", 55, 45)]
    r = trace.reduce(ops, spans)
    assert r.devices == 2 and r.window_s == pytest.approx(100e-9)
    # busy: device 0 = 30 + 10 = 40 ns, device 1 = 50 ns; mean 45 ns.
    assert r.busy_s == pytest.approx(45e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.seconds_by_kind == pytest.approx({"kernel": 80e-9,
                                               "other": 20e-9})
    assert "collective" not in r.events_by_kind
    # Idle: device 0 [0,10) [40,60) [70,100); device 1 [50,100).
    assert r.idle_by_span == pytest.approx({
        "kbench.dispatch": (10 + 10) / 2 * 1e-9,
        "(no span)": (5 + 5) / 2 * 1e-9,
        "kbench.host_copy": (5 + 30 + 45) / 2 * 1e-9})
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "%k"
    assert len(bd["idle_gaps"]) == 3


def test_one_window_span_required():
    with pytest.raises(ValueError):
        trace.reduce([], [Span("kbench.open", 0, 5)])


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    ops, spans = trace.extract(str(path))
    assert ops and spans
    r = trace.reduce(ops, spans)
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s
    # 43 launches of the clearing kernel, as the run reported.
    assert r.events_by_kind["kernel"] == 43
    assert r.seconds_by_kind["kernel"] > r.seconds_by_kind.get("other", 0.0)
    names = {s.name for s in spans}
    assert {"kbench.window", "kbench.dispatch", "kbench.host_copy"} <= names
    assert set(r.idle_by_span) <= names | {"(no span)"}
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
