"""Fleet aggregation tier integration (repro.telemetry) — PR 10 tentpole.

The acceptance tests ISSUE 10 names:

* 3 subprocess "hosts" over localhost sockets → 1 aggregator → head:
  fleet counter sums exactly equal the sum of per-host drained deltas
  (int lanes exact, float lanes at f64 tolerance against the agents' own
  f64 shipped-sum oracles), percentiles match a merged-reservoir oracle,
  and the straggler host is flagged.
* A killed host degrades gracefully — no hang, accounting intact.
* The agent NEVER dispatches device work: raising sys.modules guard
  around emit/flush/close (same technique as the token-drain tests).
* Double close never double-sends the shutdown frame; the runtime's
  graceful-shutdown path emits it exactly once.
* Drop accounting is uniform: bounded-buffer drops, reconnects, sink
  errors all surface through ``TelemetryPlane.stats()``.
"""
import json
import os
import socket
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from repro.telemetry import wire
from repro.telemetry.agent import FleetAgent
from repro.telemetry.aggregator import Aggregator
from repro.telemetry.head import FleetHead

FP = "ab" * 20


def _env():
    env = dict(os.environ)
    # simulated hosts run on the CPU: never contend for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return env


def _fake_snap(step=4, n_scopes=3, total=12, seed=0, fingerprint=FP):
    """A TelemetrySnapshot stand-in (compact delta, host numpy)."""
    rng = np.random.default_rng(seed)
    delta = types.SimpleNamespace(
        calls=rng.integers(0, 50, n_scopes).astype(np.int32),
        values=rng.normal(size=total).astype(np.float32),
        samples=rng.integers(0, 20, total).astype(np.int32),
    )
    spec = types.SimpleNamespace(fingerprint=fingerprint, contexts=())
    return types.SimpleNamespace(step=step, seq=0, delta=delta, spec=spec)


def _wait(pred, timeout=10.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return False


# ---------------------------------------------------------------------------
# the multi-process acceptance test
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_three_subprocess_hosts_exact_sums_percentiles_straggler(tmp_path):
    from repro.core import plan as plan_lib
    from repro.telemetry.simhost import build_spec

    agg = Aggregator(("127.0.0.1", 0), node_id="root", reservoir_k=256,
                     seed=7).serve()
    _, port = agg.address
    procs = []
    for i in range(3):
        cmd = [sys.executable, "-m", "repro.telemetry.simhost",
               "--host-id", f"h{i}", "--port", str(port),
               "--steps", "20", "--cadence", "2", "--seed", str(i),
               "--pace-s", "0.004"]
        if i == 2:
            cmd += ["--straggle-s", "0.06"]   # ~15x slower than its peers
        procs.append(subprocess.Popen(cmd, env=_env(),
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    oracles = {}
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-3000:]
        line = [ln for ln in out.splitlines()
                if ln.startswith("FLEET-ORACLE: ")][-1]
        o = json.loads(line[len("FLEET-ORACLE: "):])
        oracles[o["host_id"]] = o

    assert _wait(lambda: all(r.shutdown
                             for r in agg.merged().hosts.values())
                 and len(agg.merged().hosts) == 3)
    spec = build_spec()
    head = FleetHead(agg, spec=spec, jsonl_path=str(tmp_path / "fleet.jsonl"))
    snap = head.write_report()

    # every host compiled the same plans — and the wire agrees
    fps = {o["fingerprint"] for o in oracles.values()}
    assert fps == {spec.fingerprint} == {snap["fingerprint"]}
    assert snap["n_hosts"] == 3
    assert snap["dropped"] == 0

    # exact fleet sums == sum of per-host drained deltas (agent oracles)
    oracle_calls = np.sum([o["shipped_calls"] for o in oracles.values()],
                          axis=0)
    assert snap["calls"] == [int(c) for c in oracle_calls]
    oracle_vals = np.sum([o["shipped_values"] for o in oracles.values()],
                         axis=0)
    fleet_vals = np.array([ln["sum"] for ln in snap["lanes"]])
    np.testing.assert_allclose(fleet_vals, oracle_vals, rtol=1e-9)
    oracle_samp = np.sum([o["shipped_samples"] for o in oracles.values()],
                         axis=0)
    assert [ln["samples"] for ln in snap["lanes"]] == \
        [int(s) for s in oracle_samp]

    # percentiles match the merged-reservoir oracle (all interval means fit
    # in k=256, so the reservoir is exhaustive — only f32 wire rounding)
    labels = plan_lib.lane_slot_ids(spec)
    checked = 0
    for i, lane in enumerate(snap["lanes"]):
        merged = np.concatenate([
            np.asarray(o["lane_means"][i], np.float64)
            for o in oracles.values() if o["lane_means"]])
        if not lane["reservoir_n"] or not len(merged):
            continue
        assert lane["reservoir_seen"] == len(merged), labels[i]
        got = [lane["p50"], lane["p95"], lane["p99"]]
        want = np.percentile(merged, [50, 95, 99])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6,
                                   err_msg=str(labels[i]))
        checked += 1
    assert checked >= 8        # 12 lanes; NaN/Inf lanes may be all-zero

    # the straggler is flagged — and only the straggler
    assert snap["stragglers"] == ["h2"], snap["hosts"]
    assert oracles["h2"]["straggler_fired"]

    # per-host frame accounting agrees end to end
    for hid, o in oracles.items():
        assert snap["hosts"][hid]["frames"] == o["agent"]["frames_sent"]
        assert snap["hosts"][hid]["lost_frames"] == 0
        assert snap["hosts"][hid]["shutdown"] is True

    # the JSONL fleet report parses back
    lines = (tmp_path / "fleet.jsonl").read_text().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["n_hosts"] == 3
    agg.close()


@pytest.mark.slow
def test_killed_host_degrades_gracefully():
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    _, port = agg.address
    victim = subprocess.Popen(
        [sys.executable, "-m", "repro.telemetry.simhost",
         "--host-id", "victim", "--port", str(port),
         "--steps", "100000", "--cadence", "1", "--pace-s", "0.02"],
        env=_env(), stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    survivor = subprocess.Popen(
        [sys.executable, "-m", "repro.telemetry.simhost",
         "--host-id", "survivor", "--port", str(port),
         "--steps", "20", "--cadence", "2", "--pace-s", "0.004"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        # wait until the victim has shipped at least a few frames, then
        # kill it mid-run — no shutdown frame, connection drops hard
        assert _wait(lambda: agg.merged().hosts.get("victim") is not None
                     and agg.merged().hosts["victim"].frames >= 3,
                     timeout=180)
        victim.kill()
        victim.wait(timeout=30)
        out, err = survivor.communicate(timeout=300)
        assert survivor.returncode == 0, err[-3000:]

        # no hang: the head still answers, the survivor completed cleanly
        head = FleetHead(agg)
        snap = head.snapshot()
        assert snap["hosts"]["survivor"]["shutdown"] is True
        assert snap["hosts"]["victim"]["shutdown"] is False   # died silently
        assert snap["hosts"]["victim"]["frames"] >= 3
        assert snap["n_hosts"] == 2
        # counters remain a consistent prefix — everything that arrived
        assert sum(snap["calls"]) > 0
    finally:
        victim.kill()
        survivor.kill()
        agg.close()


# ---------------------------------------------------------------------------
# device-freedom attestation (runtime half; static half in test_wire.py)
# ---------------------------------------------------------------------------

class _NoDeviceOps:
    """Raising guard: ANY attribute access means device work was attempted."""

    def __getattr__(self, name):
        raise AssertionError(
            f"fleet agent touched jax.{name} on the drain path")


def test_agent_emit_never_dispatches_device_work(monkeypatch):
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    agent = FleetAgent("h0", agg.address, fingerprint=FP)
    guard = _NoDeviceOps()
    for mod in ("jax", "jax.numpy", "jaxlib"):
        monkeypatch.setitem(sys.modules, mod, guard)
    # emit / flush / close all run with jax unusable — pure host numpy
    for i in range(5):
        agent.emit(_fake_snap(step=2 * i + 2, seed=i))
    agent.flush(2.0)
    agent.close()
    assert agent.frames_encoded == 5
    assert _wait(lambda: agg.merged().frames_in == 6)   # 5 deltas + shutdown
    agg.close()


# ---------------------------------------------------------------------------
# shutdown semantics
# ---------------------------------------------------------------------------

def test_double_close_never_double_sends():
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    agent = FleetAgent("h0", agg.address, fingerprint=FP)
    agent.emit(_fake_snap())
    agent.close()
    sent = agent.stats()["frames_sent"]
    assert sent == 2                       # one delta + one shutdown frame
    agent.close()                          # second close: no-op
    agent.close()
    assert agent.stats()["frames_sent"] == sent
    assert _wait(lambda: agg.merged().hosts["h0"].shutdown)
    rec = agg.merged().hosts["h0"]
    assert rec.frames == 2 and rec.lost_frames == 0
    # emits after close are dropped with accounting, never sent
    agent.emit(_fake_snap(step=99))
    assert agent.stats()["frames_sent"] == sent
    agg.close()


def test_runtime_graceful_shutdown_flushes_and_sends_final_frame(capsys):
    from repro import core as scalpel
    from repro.telemetry.simhost import build_spec

    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    spec = build_spec()
    rt = scalpel.ScalpelRuntime(spec, hook_every=1, graceful_shutdown=True)
    agent = rt.attach_fleet_agent("h0", agg.address)
    assert rt.fleet_agent is agent
    state = scalpel.CounterState.zeros(spec)
    for _ in range(3):
        rt.on_step(state)
    rt.flush()
    rt.shutdown()                          # report + close: flush + final
    sent = agent.stats()["frames_sent"]
    rt.shutdown()                          # idempotent with close()
    rt.close()
    assert agent.stats()["frames_sent"] == sent
    assert _wait(lambda: agg.merged().hosts.get("h0") is not None
                 and agg.merged().hosts["h0"].shutdown)
    assert agg.merged().hosts["h0"].frames == sent
    # the shutdown report carries the telemetry-health footer
    out = capsys.readouterr().out
    assert "telemetry:" in out and "fleet[sent=" in out
    agg.close()


# ---------------------------------------------------------------------------
# drop accounting: bounded buffer, reconnects, plane surface
# ---------------------------------------------------------------------------

def test_bounded_buffer_drops_oldest_with_accounting():
    # no listener on this port: every frame queues; the buffer bounds it
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()                           # nothing listens here now
    agent = FleetAgent("h0", ("127.0.0.1", port), fingerprint=FP,
                       max_buffer=2, connect_timeout=0.1, backoff_s=0.01,
                       backoff_max_s=0.05)
    for i in range(10):
        agent.emit(_fake_snap(step=i + 1, seed=i))
    assert agent.frames_encoded == 10
    assert agent.dropped_frames >= 7       # bounded at 2 (+1 in flight)
    agent.close(flush_timeout=0.2)
    st = agent.stats()
    # everything encoded was either sent (it can't be) or accounted dropped
    assert st["frames_sent"] == 0
    assert st["dropped_frames"] == 11      # 10 deltas + the shutdown frame
    assert st["connected"] is False


def test_seq_gaps_from_buffer_drops_visible_at_aggregator():
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    agent = FleetAgent("h0", agg.address, fingerprint=FP)
    # simulate loss: encode seqs 0..5 but only deliver 0, 3, 5
    frames = []
    orig_send = agent._link.send
    agent._link.send = lambda b, force=False: frames.append(b)
    for i in range(6):
        agent.emit(_fake_snap(step=i + 1, seed=i))
    agent._link.send = orig_send
    for i in (0, 3, 5):
        agent._link.send(frames[i])
    agent._link.flush(5.0)
    assert _wait(lambda: agg.merged().hosts.get("h0") is not None
                 and agg.merged().hosts["h0"].frames == 3)
    assert agg.merged().hosts["h0"].lost_frames == 3
    assert agg.merged().dropped == 3
    agent._link.close(1.0)
    agg.close()


def test_plane_stats_surfaces_sink_and_agent_accounting():
    from repro import core as scalpel
    from repro.testing.faults import FailingSink
    from repro.telemetry.simhost import build_spec

    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    spec = build_spec()
    rt = scalpel.ScalpelRuntime(spec, hook_every=1)
    rt.attach_fleet_agent("h0", agg.address)
    failing = rt.telemetry.add_sink(FailingSink(fail_first=1))
    state = scalpel.CounterState.zeros(spec)
    rt.on_step(state)
    rt.flush()
    st = rt.telemetry.stats()
    # uniform surface: drain counters, per-sink errors, agent extras
    assert st["drain_count"] >= 1
    assert any(v >= 1 for v in st["sink_errors"].values()), st
    agent_entries = [v for v in st["sinks"].values()
                     if v.get("host_id") == "h0"]
    assert len(agent_entries) == 1
    a = agent_entries[0]
    assert {"frames_sent", "dropped_frames", "reconnects"} <= set(a)
    assert failing.attempts >= 1
    footer = rt._telemetry_footer()
    assert "sink_errors=" in footer and "fleet[" in footer
    rt.close()
    agg.close()


def test_reconnect_backoff_recovers_and_counts():
    # an aggregator that appears only after the agent started sending
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    agent = FleetAgent("h0", ("127.0.0.1", port), fingerprint=FP,
                       connect_timeout=0.2, backoff_s=0.02,
                       backoff_max_s=0.1)
    agent.emit(_fake_snap(step=2))
    time.sleep(0.3)                        # a few failed connect rounds
    agg = Aggregator(("127.0.0.1", port), node_id="late").serve()
    assert _wait(lambda: agent.stats()["frames_sent"] == 1)
    agent.close()
    assert _wait(lambda: agg.merged().hosts.get("h0") is not None
                 and agg.merged().hosts["h0"].shutdown)
    assert agg.merged().hosts["h0"].lost_frames == 0   # nothing was lost
    agg.close()


# ---------------------------------------------------------------------------
# tree composition + hints
# ---------------------------------------------------------------------------

def test_tree_child_push_is_cumulative_not_double_counted():
    root = Aggregator(("127.0.0.1", 0), node_id="root", seed=1).serve()
    child = Aggregator(("127.0.0.1", 0), node_id="child0",
                       parent=root.address, seed=2).serve()
    a0 = FleetAgent("h0", child.address, fingerprint=FP)
    a1 = FleetAgent("h1", root.address, fingerprint=FP)
    for i in range(4):
        a0.emit(_fake_snap(step=i + 1, seed=i))
        a1.emit(_fake_snap(step=i + 1, seed=100 + i))
    a0.flush(5.0)
    a1.flush(5.0)
    assert _wait(lambda: child.merged().frames_in == 4
                 and len(root.merged().hosts) >= 1)
    child.push()
    assert _wait(lambda: root.merged().n_hosts == 2)
    want_calls = sum(
        np.asarray(_fake_snap(seed=s).delta.calls, np.int64)
        for s in [0, 1, 2, 3, 100, 101, 102, 103])
    view = root.merged()
    np.testing.assert_array_equal(view.calls, want_calls)
    assert view.frames_in == 8
    # cumulative re-push: totals must NOT change
    child.push()
    child.push()
    time.sleep(0.3)
    np.testing.assert_array_equal(root.merged().calls, want_calls)
    # reservoirs carried through the tree, weighted by seen
    assert view.reservoirs[0].seen == sum(
        1 for s in [0, 1, 2, 3, 100, 101, 102, 103]
        if _fake_snap(seed=s).delta.samples[0] > 0)
    a0.close()
    a1.close()
    child.close()
    root.close()


def test_hint_downlink_reaches_controller_through_tree():
    from repro.core.adaptive import SENTINEL, AdaptiveConfig, \
        AdaptiveController
    from repro.core.telemetry import TelemetryPlane
    from repro.telemetry.simhost import build_spec

    root = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    child = Aggregator(("127.0.0.1", 0), node_id="child0",
                       parent=root.address).serve()
    spec = build_spec()
    plane = TelemetryPlane(spec, cadence=1)
    ctl = AdaptiveController(spec=spec, telemetry=plane,
                             config=AdaptiveConfig()).install()
    agent = FleetAgent("h0", child.address, fingerprint=spec.fingerprint,
                       controller=ctl)
    agent.emit(_fake_snap(step=2, fingerprint=spec.fingerprint))
    agent.flush(5.0)
    assert _wait(lambda: child.merged().frames_in == 1)
    child.push()       # opens the child→root uplink (hints ride it back)
    assert _wait(lambda: len(root.merged().hosts) == 1)

    head = FleetHead(root, spec=spec)
    head.broadcast_hint("layer/mlp", "fleet:nan_count", tripwire=True)
    assert _wait(lambda: ctl.stats["fleet_hints"] >= 1), ctl.stats
    assert ctl.levels["layer/mlp"] == "wide"

    # a global hint wakes sentinel scopes (the step-time-wake move)
    ctl._level[0] = SENTINEL
    head.broadcast_hint("", "fleet:step_time", tripwire=True)
    assert _wait(lambda: ctl.stats["fleet_hints"] >= 2), ctl.stats
    assert ctl.levels[spec.scopes[0]] == "configured"
    agent.close()
    child.close()
    root.close()
    plane.close()


def test_apply_fleet_hint_gating():
    from repro.core.adaptive import AdaptiveConfig, AdaptiveController
    from repro.core.telemetry import TelemetryPlane
    from repro.telemetry.simhost import build_spec

    spec = build_spec()
    plane = TelemetryPlane(spec, cadence=1)
    ctl = AdaptiveController(
        spec=spec, telemetry=plane,
        config=AdaptiveConfig(accept_fleet_hints=False))
    assert not ctl.apply_fleet_hint("layer/mlp", reason="x", tripwire=True)
    assert ctl.stats["fleet_hints"] == 0
    assert ctl.stats["fleet_hints_ignored"] == 1
    assert ctl.levels["layer/mlp"] == "configured"    # unchanged

    ctl2 = AdaptiveController(spec=spec, telemetry=plane,
                              config=AdaptiveConfig())
    # a scope this process doesn't monitor: ignored, not an error
    assert not ctl2.apply_fleet_hint("no/such/scope", reason="x")
    assert ctl2.stats["fleet_hints_ignored"] == 1
    assert ctl2.apply_fleet_hint("layer/attn", reason="y", tripwire=True)
    assert ctl2.levels["layer/attn"] == "wide"
    plane.close()


def test_auto_hints_fire_once_per_tripwire_tick():
    from repro.telemetry.simhost import build_spec

    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    spec = build_spec()
    agent = FleetAgent("h0", agg.address, fingerprint=spec.fingerprint)
    snap = _fake_snap(step=2, fingerprint=spec.fingerprint)
    # lane 2 of scope 0 is layer/attn NAN_COUNT (EVENTS order in simhost)
    snap.delta.values[:] = 0.0
    snap.delta.samples[:] = 1
    snap.delta.values[2] = 3.0             # 3 NaN ticks this interval
    agent.emit(snap)
    agent.flush(5.0)
    assert _wait(lambda: agg.merged().frames_in == 1)
    head = FleetHead(agg, spec=spec)
    sent = head.auto_hints()
    assert sent == [("layer/attn", "fleet:nan_count")]
    assert head.auto_hints() == []         # same tick: no re-broadcast
    agent.close()
    agg.close()


# ---------------------------------------------------------------------------
# socket-level rejection accounting
# ---------------------------------------------------------------------------

def test_version_skew_on_stream_accounted_and_connection_dropped():
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    buf = bytearray(wire.encode_delta(
        [1], [1.0], [1], host_id="h9", seq=0, fingerprint=FP,
        step_lo=-1, step_hi=1))
    buf[2] = wire.WIRE_VERSION + 1         # a sender from the future
    with socket.create_connection(agg.address, timeout=5) as s:
        s.sendall(wire.pack_frame(bytes(buf)))
        assert _wait(lambda: agg.stats()["rejected_version"] == 1)
        assert s.recv(1) == b""            # aggregator dropped the conn
    assert agg.merged().frames_in == 0
    assert agg.dropped == 1
    agg.close()


def test_corrupt_stream_accounted():
    agg = Aggregator(("127.0.0.1", 0), node_id="root").serve()
    good = wire.encode_delta([1], [1.0], [1], host_id="h9", seq=0,
                             fingerprint=FP, step_lo=-1, step_hi=1)
    bad = bytearray(good)
    bad[-6] ^= 0x55                        # payload tamper: CRC fails
    with socket.create_connection(agg.address, timeout=5) as s:
        s.sendall(wire.pack_frame(bytes(bad)))
        assert _wait(lambda: agg.stats()["rejected_corrupt"] == 1)
    assert agg.dropped == 1
    agg.close()
