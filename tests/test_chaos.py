"""Chaos tier (`-m chaos`): every fault class recovers bitwise.

Each test drives :func:`repro.ops.chaos.run_plan` through a fault and
asserts the recovered trajectory is **bitwise-identical** to a fault-free
run — replayed chunks equal the originally streamed ones, the concatenated
stream equals the baseline, and the typed corruption errors actually fired
(damaged checkpoints must never load silently). Single-device cases run
in-process; the sharded cases re-run the same plans in a forced-2-device
subprocess (the `_run_probe` pattern from test_distributed.py), so both
paths are covered on any machine.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.config import scenario_config
from repro.core.session import Engine
from repro.ops import (AutotuneOOM, CheckpointCorruption, DeviceLoss,
                       FaultPlan, run_plan)

pytestmark = pytest.mark.chaos

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Flash-crash so the recovery window replays a shock (shock_step=11 sits
# between the step-6 checkpoint and the step-18 faults); chunk=6 makes
# 12/18 chunk-boundary fault coordinates.
CFG_KW = dict(num_markets=6, num_agents=16, num_levels=32, num_steps=24,
              shock_step=11, seed=7)
CHUNK = 6

BACKENDS = ["pallas-kinetic", "numpy-pcg64"]


def _cfg():
    return scenario_config("flash-crash", **CFG_KW)


def _baseline(backend):
    with Engine(backend, chunk_size=CHUNK).open(_cfg()) as s:
        return s.run(CFG_KW["num_steps"]).to_numpy()


def _assert_bitwise(report, want, ctx):
    assert report.replay_matched, f"{ctx}: replayed chunks diverged"
    got = report.batch
    for f, a, b in zip(want._fields, want, got):
        assert (np.asarray(a) == np.asarray(b)).all(), \
            f"{ctx}: stream field {f} differs after recovery"


# ---------------------------------------------------------------------------
# single-device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_device_loss_restores_from_last_snapshot(backend, tmp_path):
    """Plain restart: rebuild the engine, restore the newest checkpoint,
    replay — bitwise."""
    want = _baseline(backend)
    plan = FaultPlan([DeviceLoss(at_step=18)], checkpoint_every=CHUNK)
    rep = run_plan(plan, _cfg(), backend=backend, ckpt_dir=tmp_path,
                   chunk_size=CHUNK)
    _assert_bitwise(rep, want, f"{backend} device-loss")
    ev = rep.events[0]
    assert ev.at_step == 18 and ev.recovered_from == 18
    assert not ev.errors


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind,target", [("truncate", "shard"),
                                         ("bitflip", "shard"),
                                         ("truncate", "manifest"),
                                         ("bitflip", "manifest")])
def test_checkpoint_corruption_falls_back_typed(backend, kind, target,
                                                tmp_path):
    """A damaged newest checkpoint raises a typed CheckpointCorruptError —
    never loads silently — and recovery falls back to the previous intact
    step, still bitwise."""
    want = _baseline(backend)
    plan = FaultPlan([CheckpointCorruption(at_step=18, kind=kind,
                                           target=target)],
                     checkpoint_every=CHUNK)
    rep = run_plan(plan, _cfg(), backend=backend, ckpt_dir=tmp_path,
                   chunk_size=CHUNK)
    _assert_bitwise(rep, want, f"{backend} corruption {kind}/{target}")
    ev = rep.events[0]
    # the corrupt step-18 checkpoint was rejected; step 12 loaded
    assert ev.recovered_from == 12
    assert any("CheckpointCorruptError" in e or "CheckpointError" in e
               for e in ev.errors), ev.errors


def test_autotune_oom_falls_back_to_conservative_tile(tmp_path):
    """Restarting with an OOM-shaped autotune sweep degrades to the
    heuristic tile (never crashes); the recovered stream stays bitwise."""
    from repro.kernels import autotune as tune

    want = _baseline("pallas-kinetic")
    plan = FaultPlan([AutotuneOOM(at_step=12)], checkpoint_every=CHUNK)
    rep = run_plan(plan, _cfg(), backend="pallas-kinetic", ckpt_dir=tmp_path,
                   chunk_size=CHUNK)
    _assert_bitwise(rep, want, "pallas-kinetic autotune-oom")
    ev = rep.events[0]
    assert "fell_back=True" in ev.detail
    assert ev.errors and all("RESOURCE_EXHAUSTED" in e for e in ev.errors)
    report = tune.last_sweep_report()
    assert report is not None and report.fell_back
    tune.clear_tune_cache()


def test_multiple_faults_in_one_plan(tmp_path):
    """Faults compose: a corruption at 12 then a device loss at 18."""
    want = _baseline("pallas-kinetic")
    plan = FaultPlan([CheckpointCorruption(at_step=12, kind="bitflip"),
                      DeviceLoss(at_step=18)], checkpoint_every=CHUNK)
    rep = run_plan(plan, _cfg(), backend="pallas-kinetic", ckpt_dir=tmp_path,
                   chunk_size=CHUNK)
    _assert_bitwise(rep, want, "pallas-kinetic multi-fault")
    assert [e.at_step for e in rep.events] == [12, 18]
    assert rep.events[0].recovered_from == 6   # step-12 ckpt was corrupted
    assert rep.events[1].recovered_from == 18  # rewritten intact on replay


def test_torn_checkpoint_write_every_offset(tmp_path):
    """Crash the checkpoint commit at EVERY durable-write offset: the
    restart must restore a committed checkpoint — the previous one, or the
    new one when the crash landed after the COMMIT rename — and replay
    bitwise. A torn write never yields loadable-but-wrong state."""
    from repro.checkpoint.manager import CheckpointManager, session_tree
    from repro.ops import TornCheckpointWrite, count_write_ops

    want = _baseline("numpy-pcg64")
    with Engine("numpy-pcg64", chunk_size=CHUNK).open(_cfg()) as s:
        s.run(12)
        tree = session_tree(s.snapshot())
    ops = count_write_ops(
        CheckpointManager(tmp_path / "probe", async_write=False), 12, tree)
    assert ops >= 15
    for k in range(ops):
        plan = FaultPlan([TornCheckpointWrite(at_step=12, crash_at_op=k)],
                         checkpoint_every=CHUNK)
        rep = run_plan(plan, _cfg(), backend="numpy-pcg64",
                       ckpt_dir=tmp_path / f"op{k}", chunk_size=CHUNK)
        _assert_bitwise(rep, want, f"torn write at op {k}")
        ev = rep.events[0]
        assert any("SimulatedCrash" in e for e in ev.errors), (k, ev.errors)
        # the step-12 rewrite was uncommitted first, so a crash mid-commit
        # falls back to 6; a crash after the COMMIT rename keeps 12
        assert ev.recovered_from in (6, 12), (k, ev)


def test_plan_validates_chunk_alignment():
    with pytest.raises(ValueError, match="chunk boundary"):
        run_plan(FaultPlan([DeviceLoss(at_step=7)]), _cfg(),
                 backend="numpy", ckpt_dir="/tmp/unused", chunk_size=6)
    with pytest.raises(ValueError, match="checkpoint_every"):
        run_plan(FaultPlan([DeviceLoss(at_step=6)], checkpoint_every=5),
                 _cfg(), backend="numpy", ckpt_dir="/tmp/unused",
                 chunk_size=6)
    with pytest.raises(ValueError, match="window"):
        run_plan(FaultPlan([DeviceLoss(at_step=600)]), _cfg(),
                 backend="numpy", ckpt_dir="/tmp/unused", chunk_size=6)


# ---------------------------------------------------------------------------
# sharded (forced-2-device subprocess, as in test_distributed.py)
# ---------------------------------------------------------------------------

def _run_probe(code: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_SHARDED_PROLOGUE = textwrap.dedent(f"""
    import tempfile, numpy as np, jax
    assert len(jax.devices()) == 2, jax.devices()
    from repro.core.config import scenario_config
    from repro.core.session import Engine
    from repro.ops import (AutotuneOOM, CheckpointCorruption, DeviceLoss,
                           FaultPlan, run_plan)
    cfg = scenario_config("flash-crash", **{CFG_KW!r})
    with Engine("pallas-kinetic", chunk_size={CHUNK}).open(cfg) as s:
        want = s.run(cfg.num_steps).to_numpy()

    def check(fault, expect_recovered, expect_errors=0):
        with tempfile.TemporaryDirectory() as d:
            rep = run_plan(FaultPlan([fault], checkpoint_every={CHUNK}),
                           cfg, backend="pallas-kinetic", ckpt_dir=d,
                           chunk_size={CHUNK}, engine_opts={{"devices": 2}})
        ev = rep.events[0]
        assert rep.replay_matched, fault
        for f, a, b in zip(want._fields, want, rep.batch):
            assert (np.asarray(a) == np.asarray(b)).all(), (fault, f)
        assert ev.recovered_from == expect_recovered, ev
        assert len(ev.errors) >= expect_errors, ev
        return ev
""")


def test_sharded_device_loss_shrinks_mesh_subprocess():
    """Drop one of two devices mid-run: the snapshot restores onto the
    1-device topology (layout-portable) and the stream stays bitwise equal
    to the single-device baseline."""
    out = _run_probe(_SHARDED_PROLOGUE + textwrap.dedent("""
        ev = check(DeviceLoss(at_step=18, devices_after=1), 18)
        assert "devices=1" in ev.detail, ev.detail
        ev = check(DeviceLoss(at_step=18, lost_device=0), 18)
        assert "1 survivors" in ev.detail, ev.detail
        print("OK")
    """))
    assert out.strip().splitlines()[-1] == "OK"


def test_sharded_checkpoint_corruption_subprocess():
    out = _run_probe(_SHARDED_PROLOGUE + textwrap.dedent("""
        for kind in ("truncate", "bitflip"):
            ev = check(CheckpointCorruption(at_step=18, kind=kind), 12,
                       expect_errors=1)
            assert any("CheckpointCorruptError" in e for e in ev.errors), ev
        print("OK")
    """))
    assert out.strip().splitlines()[-1] == "OK"


def test_sharded_autotune_oom_subprocess():
    out = _run_probe(_SHARDED_PROLOGUE + textwrap.dedent("""
        ev = check(AutotuneOOM(at_step=12), 12, expect_errors=1)
        assert "fell_back=True" in ev.detail, ev.detail
        print("OK")
    """))
    assert out.strip().splitlines()[-1] == "OK"


# ---------------------------------------------------------------------------
# serving gateway: device loss under concurrent client load
# ---------------------------------------------------------------------------

SCENARIOS = ["baseline", "flash-crash", "high-vol"]


def _assert_serve_bitwise(rep, want, ctx):
    assert set(rep.frames) == set(want.frames), ctx
    for client in want.frames:
        fs0, fs1 = want.frames[client], rep.frames[client]
        assert len(fs0) == len(fs1), \
            f"{ctx}: client {client} got {len(fs1)} frames, want {len(fs0)}"
        for f0, f1 in zip(fs0, fs1):
            assert f0.step0 == f1.step0 and f0.seq == f1.seq, \
                f"{ctx}: client {client} frame misaligned at seq {f0.seq}"
            for field in ("mid", "price", "volume"):
                assert (np.asarray(getattr(f0, field))
                        == np.asarray(getattr(f1, field))).all(), \
                    f"{ctx}: client {client} {field} diverged at {f0.step0}"


def test_serve_device_loss_under_client_load(tmp_path):
    """Kill the engine under concurrent streaming clients (one attached
    after the newest checkpoint, so recovery must replay the splice
    journal): every client observes a ``reconnect`` event and its stream
    continues bitwise-identical to a fault-free run."""
    from repro.ops import run_serve_plan

    kw = dict(scenarios=SCENARIOS, backend="jax-scan", chunk_size=8,
              chunks=10, checkpoint_every=2, late_attach="thin-book",
              late_after=5)
    want = run_serve_plan(ckpt_dir=tmp_path / "ff", **kw)
    rep = run_serve_plan(ckpt_dir=tmp_path / "f1",
                         fault=DeviceLoss(at_step=0), fault_after=3, **kw)
    assert want.reconnects == 0 and rep.reconnects == 1
    for client, events in rep.events.items():
        # every client (including "late", attached before the fault fires)
        # observes the recovery
        assert any(e.kind == "reconnect" for e in events), \
            f"client {client} never saw the reconnect event"
    _assert_serve_bitwise(rep, want, "serve device-loss")
    assert rep.traces_delta == 0, \
        f"{rep.traces_delta} retraces after recovery re-warm"


def test_serve_fault_storm_coalesces_into_one_recovery(tmp_path):
    """A reconnect storm: 16 concurrent clients, four back-to-back device
    losses. The supervisor must coalesce the storm into ONE recovery pass
    — every client sees exactly one ``reconnect`` broadcast — and every
    stream resumes bitwise."""
    from repro.ops import run_serve_plan

    scen = (SCENARIOS * 6)[:16]
    kw = dict(scenarios=scen, backend="numpy-pcg64", chunk_size=8,
              chunks=10, checkpoint_every=2, slots=16)
    want = run_serve_plan(ckpt_dir=tmp_path / "ff", **kw)
    storm = [DeviceLoss(at_step=0)] * 4
    rep = run_serve_plan(ckpt_dir=tmp_path / "f1", fault=storm,
                         fault_after=3, **kw)
    assert rep.recoveries == 1, rep.recoveries   # 4 faults -> ONE pass
    assert rep.reconnects == 1
    for client, events in rep.events.items():
        recs = [e for e in events if e.kind == "reconnect"]
        assert len(recs) == 1, (client, [e.kind for e in events])
        assert recs[0].payload["faults_coalesced"] == 4, recs[0].payload
    _assert_serve_bitwise(rep, want, "serve fault-storm")
    assert rep.traces_delta == 0
    assert rep.health is not None and rep.health["state"] == "serving"


def test_serve_journal_compaction_never_breaks_replay(tmp_path):
    """A 2-deep checkpoint ladder under checkpoint_every=1 forces GC —
    and therefore splice-journal compaction — repeatedly mid-run; a late
    fault must still recover bitwise from what remains."""
    from repro.ops import run_serve_plan
    from repro.serve import SpliceJournal

    # fault_after is in kw for BOTH runs: it also sets how many frames are
    # consumed before the late attach, which fixes the attach boundary
    kw = dict(scenarios=SCENARIOS, backend="numpy-pcg64", chunk_size=8,
              chunks=12, checkpoint_every=1, ckpt_keep=2,
              late_attach="thin-book", late_after=3, fault_after=8)
    want = run_serve_plan(ckpt_dir=tmp_path / "ff", **kw)
    rep = run_serve_plan(ckpt_dir=tmp_path / "f1",
                         fault=DeviceLoss(at_step=0), **kw)
    assert rep.recoveries == 1
    _assert_serve_bitwise(rep, want, "serve compaction")
    assert rep.traces_delta == 0
    # compaction really fired: the t=0 admission splice predates every
    # retained checkpoint and must be gone from the durable journal
    entries = SpliceJournal(tmp_path / "f1").entries()
    assert all(e.t > 0 for e in entries), [e.t for e in entries]


# ---------------------------------------------------------------------------
# full process crash (kill -9) + restart: the durable-restart guarantee
# ---------------------------------------------------------------------------

_CRASH_PHASE1 = textwrap.dedent("""
    import asyncio, json, os, sys
    import numpy as np
    from repro.serve import Gateway, parked_template

    d, out = sys.argv[1], sys.argv[2]
    tpl = parked_template(slots=3, num_agents=16, num_levels=32,
                          num_steps=4096)

    async def main():
        gw = Gateway(tpl, backend="numpy-pcg64", chunk_size=8,
                     queue_maxsize=64, ckpt_dir=d, checkpoint_every=1)
        await gw.start(chunks=10)
        scen = ["baseline", "flash-crash", "high-vol"]
        clients = [gw.open_session(s, client=f"c{i}")
                   for i, s in enumerate(scen)]
        f = open(out, "a")
        written = 0

        async def pump(cs):
            nonlocal written
            while True:
                fr = await cs.next_frame()
                if fr is None:
                    return
                f.write(json.dumps({
                    "client": cs.client, "step0": fr.step0,
                    "mid": np.asarray(fr.mid).tolist(),
                    "price": np.asarray(fr.price).tolist()}) + "\\n")
                f.flush()
                os.fsync(f.fileno())
                written += 1
                if written >= 9:
                    os.kill(os.getpid(), 9)   # kill -9, mid-stream

        await asyncio.gather(*(pump(c) for c in clients))

    asyncio.run(main())
""")

_CRASH_PHASE2 = textwrap.dedent("""
    import asyncio, json, sys
    import numpy as np
    from repro.serve import Gateway, parked_template

    d, out = sys.argv[1], sys.argv[2]
    tpl = parked_template(slots=3, num_agents=16, num_levels=32,
                          num_steps=4096)

    async def main():
        gw = Gateway(tpl, backend="numpy-pcg64", chunk_size=8,
                     queue_maxsize=64, ckpt_dir=d, checkpoint_every=1)
        await gw.start(chunks=12)          # restart path: committed ladder
        assert gw.resumed_from is not None, "no committed ladder found"
        assert not gw.restart_errors, gw.restart_errors
        slots = sorted(gw.scheduler.attached)
        assert slots == [0, 1, 2], slots   # attachments rebuilt from disk
        clients = [gw.resume_session(s, client=f"r{s}") for s in slots]
        rest = await asyncio.gather(*(c.frames(12) for c in clients))
        with open(out, "w") as f:
            for slot, frames in zip(slots, rest):
                for fr in frames:
                    f.write(json.dumps({
                        "client": f"c{slot}", "step0": fr.step0,
                        "mid": np.asarray(fr.mid).tolist(),
                        "price": np.asarray(fr.price).tolist()}) + "\\n")
        for c in clients:
            att = [e for e in c.events if e.kind == "attached"]
            assert att and att[0].payload.get("resumed") is True, c.events
        assert gw.traces_delta == 0, gw.traces_delta
        await gw.stop()
        print("RESUMED", gw.resumed_from)

    asyncio.run(main())
""")


def test_serve_crash_restart_resumes_bitwise(tmp_path):
    """kill -9 a streaming gateway process mid-delivery, restart a fresh
    process over the same ckpt_dir: the newest committed checkpoint is
    restored, journaled splices replay from disk, clients re-subscribe via
    resume_session, and every frame either phase produced bitwise-matches
    a crash-free reference at the same step coordinate."""
    import json as _json

    from repro.ops import run_serve_plan

    want = run_serve_plan(scenarios=SCENARIOS, backend="numpy-pcg64",
                          chunk_size=8, chunks=18, checkpoint_every=1,
                          ckpt_dir=tmp_path / "ref")
    ref = {}
    for client, frames in want.frames.items():
        for fr in frames:
            ref[(client, fr.step0)] = (np.asarray(fr.mid).tolist(),
                                       np.asarray(fr.price).tolist())
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"  # host devices only
    d = str(tmp_path / "crash")
    out1, out2 = str(tmp_path / "phase1.jsonl"), str(tmp_path / "p2.jsonl")
    p1 = subprocess.run([sys.executable, "-c", _CRASH_PHASE1, d, out1],
                        env=env, capture_output=True, text=True, timeout=300)
    assert p1.returncode == -9, (p1.returncode, p1.stderr[-3000:])
    p2 = subprocess.run([sys.executable, "-c", _CRASH_PHASE2, d, out2],
                        env=env, capture_output=True, text=True, timeout=300)
    assert p2.returncode == 0, p2.stderr[-3000:]
    resumed = int(p2.stdout.split("RESUMED")[1].split()[0])
    assert resumed % 8 == 0
    with open(out1) as f:
        phase1 = [_json.loads(ln) for ln in f]
    with open(out2) as f:
        phase2 = [_json.loads(ln) for ln in f]
    assert len(phase1) == 9            # the fsync'd pre-crash deliveries
    matched = 0
    for r in phase1 + phase2:
        key = (r["client"], r["step0"])
        if key not in ref:             # past the reference horizon
            continue
        m, p = ref[key]
        assert r["mid"] == m and r["price"] == p, \
            f"frame {key} diverged from the crash-free reference"
        matched += 1
    assert matched >= 18, matched      # pre-crash + post-restart overlap
    # phase 2 streamed contiguously from the restored cursor
    steps2 = sorted({r["step0"] for r in phase2})
    assert steps2[0] == resumed
    assert steps2 == list(range(resumed, resumed + 8 * len(steps2), 8))


def test_serve_sharded_device_loss_subprocess():
    """Drop one of two devices under live client load: the gateway rebuilds
    on the survivor, clients reconnect, and post-recovery trajectories
    bitwise-match the fault-free sharded run."""
    out = _run_probe(textwrap.dedent("""
        import tempfile, numpy as np, jax
        assert len(jax.devices()) == 2, jax.devices()
        from repro.ops import DeviceLoss, run_serve_plan
        kw = dict(scenarios=["baseline", "flash-crash", "high-vol"],
                  backend="pallas-kinetic", chunk_size=8, chunks=8,
                  checkpoint_every=2, slots=4, num_agents=16, num_levels=32,
                  engine_opts={"devices": 2})
        with tempfile.TemporaryDirectory() as d:
            want = run_serve_plan(ckpt_dir=d, **kw)
        with tempfile.TemporaryDirectory() as d:
            rep = run_serve_plan(ckpt_dir=d, fault_after=3,
                                 fault=DeviceLoss(at_step=0,
                                                  devices_after=1), **kw)
        assert rep.reconnects == 1, rep.events
        for client in want.frames:
            fs0, fs1 = want.frames[client], rep.frames[client]
            assert len(fs0) == len(fs1), (client, len(fs0), len(fs1))
            for f0, f1 in zip(fs0, fs1):
                assert f0.step0 == f1.step0, (client, f0.step0, f1.step0)
                assert (f0.mid == f1.mid).all(), (client, f0.step0)
                assert (f0.price == f1.price).all(), (client, f0.step0)
        assert rep.traces_delta == 0, rep.traces_delta
        print("OK")
    """))
    assert out.strip().splitlines()[-1] == "OK"
