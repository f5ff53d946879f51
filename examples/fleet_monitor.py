"""Fleet telemetry, end to end (ROADMAP item 2): five simulated hosts ship
compact binary frames over localhost sockets into one ``Aggregator``; a
``FleetHead`` on the tree root reports fleet percentiles, exact fleet
counter sums, and straggler flags, then rebroadcasts a tripwire hint back
DOWN the tree so a lingering host's ``AdaptiveController`` escalates.

The moving parts, in ship order:

    simhost x5  --frames-->  Aggregator (root)  --merged-->  FleetHead
        ^                         |
        '------- KIND_HINT -------'          (fleet-wide escalation)

* every host runs ``repro.telemetry.simhost`` — the same monitored
  workload behind ``tests/test_fleet_agg.py`` — so each prints a
  ``FLEET-ORACLE:`` JSON line with its agent's own shipped-frame sums;
* host ``h2`` carries a ``StragglerDelay`` (~15x slower steps): the head
  must flag it, and ONLY it, from EWMA+MAD step rates — the three healthy
  hosts agree tightly, so the MAD collapses and the relative floor sets
  the outlier threshold;
* host ``h0`` gets a NaN spliced into one probed tensor and lingers with
  an attached controller: the head's ``auto_hints`` sees the fleet-level
  NAN_COUNT tick and pushes a hint down the wire — ``h0``'s controller
  escalates without ever seeing its neighbours' telemetry.

The smoke assertions are the acceptance criteria: fleet sums equal the
sum of per-host oracles exactly (int lanes) / to f64 tolerance (float
lanes), fleet percentiles match a merged-stream oracle, the straggler is
flagged, and the downlink hint lands.

    PYTHONPATH=src python examples/fleet_monitor.py
"""
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import plan as plan_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.telemetry.aggregator import Aggregator
from repro.telemetry.head import FleetHead
from repro.telemetry.simhost import build_spec

N_HOSTS = 5
STEPS = 20
CADENCE = 2
STRAGGLER = "h2"          # gets the per-step StragglerDelay
STRAGGLE_S = 0.06         # ~15x the healthy 4ms pace
NAN_HOST = "h0"           # gets the TensorFault + lingering controller
NAN_STEP = 6
LINGER_S = 8.0            # h0 waits this long for the downlink hint


def _env():
    env = dict(os.environ)
    # simulated hosts run on the CPU: never contend for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return env


def main():
    enable_compile_cache()
    spec = build_spec()
    agg = Aggregator(("127.0.0.1", 0), node_id="root", reservoir_k=256,
                     seed=7).serve()
    _, port = agg.address
    report_path = os.path.join(tempfile.mkdtemp(prefix="fleet_"),
                               "fleet.jsonl")
    head = FleetHead(agg, spec=spec, jsonl_path=report_path)
    print(f"aggregator root listening on 127.0.0.1:{port}")

    procs = []
    for i in range(N_HOSTS):
        hid = f"h{i}"
        cmd = [sys.executable, "-m", "repro.telemetry.simhost",
               "--host-id", hid, "--port", str(port),
               "--steps", str(STEPS), "--cadence", str(CADENCE),
               "--seed", str(i), "--pace-s", "0.004"]
        if hid == STRAGGLER:
            cmd += ["--straggle-s", str(STRAGGLE_S)]
        if hid == NAN_HOST:
            cmd += ["--nan-step", str(NAN_STEP), "--adaptive",
                    "--linger-s", str(LINGER_S)]
        procs.append(subprocess.Popen(cmd, env=_env(),
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    print(f"spawned {N_HOSTS} hosts: {STRAGGLER} straggles "
          f"({STRAGGLE_S * 1000:.0f}ms/step), {NAN_HOST} hits a NaN at "
          f"step {NAN_STEP} and lingers for the hint")

    # while the hosts run, the head scans tripwire lanes: the first
    # fleet-level NAN_COUNT tick becomes a KIND_HINT pushed down every
    # connected agent link (h0's controller is waiting for exactly that)
    hints = []
    while any(p.poll() is None for p in procs):
        hints.extend(head.auto_hints())
        time.sleep(0.05)

    oracles = {}
    for p in procs:
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, err[-3000:]
        line = [ln for ln in out.splitlines()
                if ln.startswith("FLEET-ORACLE: ")][-1]
        o = json.loads(line[len("FLEET-ORACLE: "):])
        oracles[o["host_id"]] = o

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        view = agg.merged()
        if (len(view.hosts) == N_HOSTS
                and all(r.shutdown for r in view.hosts.values())):
            break
        time.sleep(0.02)

    snap = head.write_report()
    labels = list(plan_lib.lane_slot_ids(spec))

    # -- fleet report ------------------------------------------------------
    print(f"\nfleet report  (hosts={snap['n_hosts']} "
          f"frames={snap['frames_in']} dropped={snap['dropped']} "
          f"fingerprint={snap['fingerprint'][:12]}...)")
    print(f"{'scope':<12} {'slot':<22} {'samples':>7} "
          f"{'p50':>9} {'p95':>9} {'p99':>9}")
    for lane in snap["lanes"]:
        if not lane["reservoir_n"]:
            continue
        print(f"{lane['scope']:<12} {lane['slot']:<22} "
              f"{lane['samples']:>7} {lane['p50']:>9.4f} "
              f"{lane['p95']:>9.4f} {lane['p99']:>9.4f}")
    print(f"\n{'host':<6} {'frames':>6} {'rate/s':>8} {'shutdown':>8} "
          f"{'straggler':>9}")
    for hid in sorted(snap["hosts"]):
        h = snap["hosts"][hid]
        rate = h["rate_smoothed"]
        print(f"{hid:<6} {h['frames']:>6} "
              f"{('-' if rate is None else f'{rate:.1f}'):>8} "
              f"{str(h['shutdown']):>8} {str(h['straggler']):>9}")
    print(f"hints broadcast: {hints}")
    print(f"report line appended to {report_path}")

    # -- smoke assertions (the acceptance criteria) ------------------------
    # 1. every host compiled the same plans, and the wire agrees
    fps = {o["fingerprint"] for o in oracles.values()}
    assert fps == {spec.fingerprint} == {snap["fingerprint"]}, fps
    assert snap["n_hosts"] == N_HOSTS and snap["dropped"] == 0

    # 2. fleet sums == sum of per-host shipped-frame oracles
    oracle_calls = np.sum([o["shipped_calls"] for o in oracles.values()],
                          axis=0)
    assert snap["calls"] == [int(c) for c in oracle_calls]
    oracle_vals = np.sum([o["shipped_values"] for o in oracles.values()],
                         axis=0)
    np.testing.assert_allclose([ln["sum"] for ln in snap["lanes"]],
                               oracle_vals, rtol=1e-9)
    oracle_samp = np.sum([o["shipped_samples"] for o in oracles.values()],
                         axis=0)
    assert [ln["samples"] for ln in snap["lanes"]] == \
        [int(s) for s in oracle_samp]

    # 3. fleet percentiles match the merged per-host interval-mean streams
    checked = 0
    for i, lane in enumerate(snap["lanes"]):
        merged = np.concatenate([
            np.asarray(o["lane_means"][i], np.float64)
            for o in oracles.values() if o["lane_means"]])
        if (not lane["reservoir_n"] or not len(merged)
                or not np.all(np.isfinite(merged))):
            continue
        got = [lane["p50"], lane["p95"], lane["p99"]]
        want = np.percentile(merged, [50, 95, 99])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6,
                                   err_msg=str(labels[i]))
        checked += 1
    assert checked >= 6, checked

    # 4. the straggler is flagged — and only the straggler
    assert snap["stragglers"] == [STRAGGLER], snap["hosts"]
    assert oracles[STRAGGLER]["straggler_fired"]

    # 5. the NaN tripwire round-tripped: head saw the fleet-level tick,
    #    broadcast a hint, and h0's controller applied it from the downlink
    assert any(r == "fleet:nan_count" for _, r in hints), hints
    assert head.hints_broadcast >= 1
    assert oracles[NAN_HOST]["fleet_hints"] >= 1, oracles[NAN_HOST]

    # 6. per-host frame accounting agrees end to end, report parses back
    for hid, o in oracles.items():
        assert snap["hosts"][hid]["frames"] == o["agent"]["frames_sent"]
        assert snap["hosts"][hid]["shutdown"] is True
    with open(report_path) as f:
        lines = f.read().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["n_hosts"] == N_HOSTS

    agg.close()
    print("FLEET-SMOKE: PASS")


if __name__ == "__main__":
    main()
