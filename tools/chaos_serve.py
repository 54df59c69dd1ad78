#!/usr/bin/env python
"""Chaos harness for the serving fleet (doc/fault_tolerance.md).

Runs a seeded randomized fault schedule against a 2-process serve
fleet (A and B, ``--peers`` pointed at each other) while a client
pumps distinct farmer requests at both — then verifies the fleet
invariant the whole migration subsystem exists for:

    every admitted request reaches a terminal state with correct
    results, and zero are lost.

Faults come from two layers, both seeded:

- driver-side process faults: SIGTERM (the preemption notice — with a
  live peer the donor migrates its wheels out before exiting) and
  SIGKILL (no notice at all — the restarted process recovers from its
  durable request store, resolving interrupted migrations against the
  peer), fired at random times; the driver is also the supervisor and
  restarts whatever died so every request can terminate;
- in-process serve fault plans (testing/faults ``"serve"`` key,
  injected via MPISPPY_TPU_FAULT_PLAN at process start): torn bundle
  transfers, refused/stalled peer offers, wedged wheels.

Verification walks BOTH durable request stores (the json files are the
ground truth — counters die with a SIGKILL, records don't): every
admitted id must settle ``done``/``failed`` somewhere, ``migrated``
records must have their result on the peer, and a sample of
migrated-and-done requests is re-solved on a clean solo service to
check the objectives match at solver tolerance. The per-process
``serve.migrate.*`` ledger must reconcile on the final ``/metrics``
scrape: offered == handed_off + sum(aborted.*) — every offer settles
exactly one way.

jax-free (PURE001: tools/): the serve processes do the solving; this
is a stdlib HTTP client + process supervisor.

Usage:
  python tools/chaos_serve.py --requests 12 --seed 7
  python tools/chaos_serve.py --requests 20 --faults 6 --out chaos.json
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA_VERSION = 1
_TOL = 1e-4


# ------------------------------------------------------------- client


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _post(url, obj, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _payload(i, num_scens=3, max_iterations=20):
    """Distinct data-only farmer instances of ONE shape bucket — the
    per-request cost patch makes every objective unique, so a migrated
    result can be checked against a solo re-solve of the same data."""
    return {"model": "farmer", "num_scens": num_scens,
            "algo": {"max_iterations": max_iterations},
            "patch": {"c": {"DevotedAcreage":
                            [150.0 + i, 230.0 + i, 260.0 + i]}}}


def _free_port() -> int:
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------- the fleet


class Host:
    """One supervised serve process: fixed pre-picked port (survives
    restarts — the peer registry address must stay valid), its own
    state dir, an optional per-incarnation fault plan."""

    def __init__(self, name, port, peer_port, state, num_scens,
                 migrate_deadline=15.0):
        self.name = name
        self.port = port
        self.peer_port = peer_port
        self.state = state
        self.num_scens = num_scens
        self.migrate_deadline = migrate_deadline
        self.proc = None
        self.restarts = 0

    @property
    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def start(self, fault_plan=None):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        # a native crash (XLA abort, segfault) leaves no Python
        # traceback — faulthandler's dump in serve.log is the only
        # post-mortem a SIGKILL-free abrupt death gets
        env.setdefault("PYTHONFAULTHANDLER", "1")
        env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
        env.pop("MPISPPY_TPU_FAULT_PLAN", None)
        if fault_plan:
            env["MPISPPY_TPU_FAULT_PLAN"] = json.dumps(fault_plan)
        os.makedirs(self.state, exist_ok=True)
        with open(os.path.join(self.state, "serve.log"), "ab") as log:
            log.write(f"\n--- host {self.name} incarnation "
                      f"{self.restarts + 1} "
                      f"(plan={json.dumps(fault_plan)}) ---\n"
                      .encode())
            log.flush()
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "mpisppy_tpu", "serve",
                 "--port", str(self.port), "--state-dir", self.state,
                 "--peers", f"127.0.0.1:{self.peer_port}",
                 "--batch-window", "0.1",
                 "--checkpoint-interval", "0.2",
                 "--migrate-deadline", str(self.migrate_deadline),
                 "--telemetry-dir",
                 os.path.join(self.state, "telemetry")],
                cwd=REPO, env=env,
                stdout=log, stderr=subprocess.STDOUT)
        return self

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def healthy(self) -> bool:
        try:
            return bool(json.loads(
                _get(f"{self.base}/healthz", timeout=3)).get("ok"))
        except (OSError, ValueError):
            return False

    def wait_healthy(self, budget=180) -> bool:
        end = time.time() + budget
        while time.time() < end:
            if not self.alive():
                return False
            if self.healthy():
                return True
            time.sleep(0.3)
        return False

    def kill(self, sig):
        if self.alive():
            self.proc.send_signal(sig)

    def reap(self, timeout=60):
        if self.proc is not None:
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)

    def records(self) -> dict:
        """The durable request store — ground truth that survives any
        kill (doc/serving.md request lifecycle)."""
        out = {}
        rdir = os.path.join(self.state, "requests")
        if not os.path.isdir(rdir):
            return out
        for fn in sorted(os.listdir(rdir)):
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(rdir, fn),
                          encoding="utf-8") as f:
                    rec = json.load(f)
                out[rec["id"]] = rec
            except (OSError, ValueError, KeyError):
                pass
        return out

    def metrics(self) -> dict:
        """Parse the Prometheus exposition into {name: value}."""
        out = {}
        try:
            text = _get(f"{self.base}/metrics", timeout=5)
        except OSError:
            return out
        for line in text.splitlines():
            if line.startswith("#") or " " not in line:
                continue
            name, _, val = line.rpartition(" ")
            try:
                out[name.strip()] = float(val)
            except ValueError:
                pass
        return out


def _random_plan(rng) -> dict | None:
    """A per-incarnation in-process fault plan: torn transfers,
    refused/stalled offers, short wheel wedges — the faults a driver
    can't deliver from outside the process."""
    specs = []
    if rng.random() < 0.5:
        specs.append({"action": "tear_transfer",
                      "at_transfer": rng.randint(1, 2)})
    if rng.random() < 0.35:
        specs.append({"action": "refuse_peer",
                      "at_offer": rng.randint(1, 2)})
    if rng.random() < 0.25:
        specs.append({"action": "wedge_wheel",
                      "at_wheel": rng.randint(1, 3),
                      "seconds": rng.uniform(2.0, 5.0)})
    return {"seed": rng.randint(0, 2 ** 30), "serve": specs} \
        if specs else None


# ---------------------------------------------------------- the drive


def submit_all(hosts, n, num_scens, max_iterations, rng,
               budget=120) -> dict:
    """Pump ``n`` distinct requests at the fleet, honoring
    ``Retry-After`` + peer hints and failing over on connection
    errors. Returns {request_id: payload_index}."""
    admitted = {}
    for i in range(n):
        target = hosts[rng.randint(0, len(hosts) - 1)]
        end = time.time() + budget
        while True:
            if time.time() > end:
                raise RuntimeError(f"request {i} never admitted")
            try:
                rec = _post(f"{target.base}/solve",
                            _payload(i, num_scens, max_iterations))
                admitted[rec["request_id"]] = i
                break
            except urllib.error.HTTPError as e:
                retry = float(e.headers.get("Retry-After") or 1.0)
                try:
                    peer = json.loads(e.read().decode()).get("peer")
                except (ValueError, OSError):
                    peer = None
                if peer:     # draining host told us who will take it
                    for h in hosts:
                        if peer.endswith(str(h.port)):
                            target = h
                time.sleep(retry * (0.5 + rng.random()))
            except (urllib.error.URLError, OSError):
                target = hosts[(hosts.index(target) + 1) % len(hosts)]
                time.sleep(0.5 + rng.random())
    return admitted


def follow(hosts, rid) -> dict | None:
    """The terminal record for one id, following ``migrated`` hops
    across the fleet's durable stores."""
    recs = [h.records().get(rid) for h in hosts]
    recs = [r for r in recs if r is not None]
    for r in recs:
        if r["status"] in ("done", "failed"):
            return r
    return recs[0] if recs else None


def wait_all_terminal(hosts, admitted, budget) -> dict:
    """Poll both durable stores until every admitted id settles.

    The driver stays the SUPERVISOR here too: a host that dies during
    the settle wait (a crash just after the last scheduled fault, an
    abrupt native abort) is restarted — with no fresh fault plan, the
    schedule is over — so its queued/running requests recover instead
    of sitting stranded in a dead process until the budget expires and
    indicts the fleet for work nobody resupervised."""
    end = time.time() + budget
    final = {}
    while time.time() < end:
        for h in hosts:
            if not h.alive():
                rc = h.proc.returncode if h.proc is not None else None
                print(f"chaos_serve: host {h.name} died (exit {rc}) "
                      f"during settle; restarting", flush=True)
                h.reap(timeout=45)
                h.restarts += 1
                h.start()
                h.wait_healthy(budget=120)
        final = {rid: follow(hosts, rid) for rid in admitted}
        if all(r is not None and r["status"] in ("done", "failed")
               for r in final.values()):
            break
        time.sleep(1.0)
    return final


def solo_baseline(payloads, work, budget=300) -> dict:
    """Re-solve payloads on a clean solo service -> {index: objective}
    — the unmigrated truth migrated results must match."""
    host = Host("solo", _free_port(), _free_port(),
                os.path.join(work, "solo"), 0)
    host.start()
    out = {}
    try:
        if not host.wait_healthy():
            raise RuntimeError("baseline service never came up")
        rids = {}
        for i, payload in payloads.items():
            rids[i] = _post(f"{host.base}/solve",
                            payload)["request_id"]
        end = time.time() + budget
        for i, rid in rids.items():
            while time.time() < end:
                rec = json.loads(_get(f"{host.base}/result/{rid}"))
                if rec["status"] in ("done", "failed"):
                    if rec["status"] == "done":
                        out[i] = rec["result"]["objective"]
                    break
                time.sleep(0.2)
    finally:
        host.kill(signal.SIGTERM)
        host.reap()
    return out


def run_chaos(requests=12, faults=4, seed=7, num_scens=3,
              max_iterations=20, budget=900, baseline_sample=3,
              work=None) -> dict:
    rng = random.Random(seed)
    work = work or tempfile.mkdtemp(prefix="chaos_serve_")
    pa, pb = _free_port(), _free_port()
    hosts = [
        Host("A", pa, pb, os.path.join(work, "stateA"), num_scens),
        Host("B", pb, pa, os.path.join(work, "stateB"), num_scens),
    ]
    for h in hosts:
        h.start(fault_plan=_random_plan(rng))
        if not h.wait_healthy():
            raise RuntimeError(f"host {h.name} never became healthy")
    faults_fired = []
    try:
        admitted = submit_all(hosts, requests, num_scens,
                              max_iterations, rng)
        print(f"chaos_serve: {len(admitted)} requests admitted "
              f"across {len(hosts)} hosts", flush=True)

        # the fault schedule: random kill/SIGTERM interleaved with
        # supervision (restart whatever died so work can finish)
        end_faults = time.time() + min(budget * 0.5, faults * 12.0)
        fired = 0
        while fired < faults and time.time() < end_faults:
            time.sleep(rng.uniform(2.0, 6.0))
            victim = hosts[rng.randint(0, 1)]
            sig = signal.SIGKILL if rng.random() < 0.5 \
                else signal.SIGTERM
            if victim.alive():
                faults_fired.append({"host": victim.name,
                                     "signal": sig.name,
                                     "t": time.time()})
                print(f"chaos_serve: {sig.name} -> host "
                      f"{victim.name}", flush=True)
                victim.kill(sig)
                fired += 1
            # supervise: restart anything dead (the fleet must keep
            # capacity or nothing terminates)
            for h in hosts:
                if not h.alive():
                    rc = h.proc.returncode \
                        if h.proc is not None else None
                    print(f"chaos_serve: host {h.name} down "
                          f"(exit {rc}); restarting", flush=True)
                    h.reap(timeout=45)
                    h.restarts += 1
                    h.start(fault_plan=_random_plan(rng))
                    h.wait_healthy(budget=120)
        # quiet period: everything up, no more faults
        for h in hosts:
            if not h.alive():
                h.reap(timeout=45)
                h.restarts += 1
                h.start()
                h.wait_healthy(budget=120)
            elif not h.healthy():
                h.wait_healthy(budget=120)

        final = wait_all_terminal(hosts, admitted, budget)

        # ---- the invariants ----
        lost = [rid for rid, r in final.items()
                if r is None or r["status"] not in ("done", "failed")]
        migrated_done = []
        for rid, r in final.items():
            if r is not None and r["status"] == "done" \
                    and (r.get("migrated_from")
                         or any((h.records().get(rid) or {})
                                .get("status") == "migrated"
                                for h in hosts)):
                migrated_done.append(rid)
        # correctness: sampled migrated results vs a solo re-solve
        sample = migrated_done[:baseline_sample]
        mismatches = []
        if sample:
            payloads = {admitted[rid]: _payload(admitted[rid],
                                                num_scens,
                                                max_iterations)
                        for rid in sample}
            base_objs = solo_baseline(payloads, work)
            for rid in sample:
                i = admitted[rid]
                got = final[rid]["result"]["objective"]
                want = base_objs.get(i)
                if want is None or got is None \
                        or abs(got - want) > _TOL * max(
                            1.0, abs(want)):
                    mismatches.append({"id": rid, "index": i,
                                       "got": got, "want": want})
        # ledger: each live process's migrate counters must reconcile
        # (counters are per-process — the durable stores above are the
        # cross-kill truth)
        ledgers = {}
        for h in hosts:
            m = h.metrics()
            offered = m.get("mpisppy_tpu_serve_migrate_offered", 0)
            handed = m.get("mpisppy_tpu_serve_migrate_handed_off", 0)
            aborted = sum(v for k, v in m.items()
                          if "serve_migrate_aborted" in k)
            ledgers[h.name] = {
                "offered": offered, "handed_off": handed,
                "aborted": aborted,
                "committed": m.get(
                    "mpisppy_tpu_serve_migrate_committed", 0),
                "completed": m.get(
                    "mpisppy_tpu_serve_migrate_completed", 0),
                "reconciled": offered == handed + aborted}
        statuses = {}
        for r in final.values():
            key = r["status"] if r is not None else "missing"
            statuses[key] = statuses.get(key, 0) + 1
        ok = not lost and not mismatches \
            and all(v["reconciled"] for v in ledgers.values())
        return {"metric": "chaos_serve", "schema_version":
                SCHEMA_VERSION, "ok": ok, "requests": len(admitted),
                "statuses": statuses, "lost": lost,
                "migrated_done": len(migrated_done),
                "baseline_checked": len(sample),
                "result_mismatches": mismatches,
                "faults": faults_fired,
                "restarts": {h.name: h.restarts for h in hosts},
                "ledgers": ledgers, "seed": seed, "work": work}
    finally:
        for h in hosts:
            h.kill(signal.SIGTERM)
        for h in hosts:
            h.reap()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="randomized fault schedule against a 2-process "
                    "serve fleet; verifies zero requests are lost. "
                    "A CPU tool: the fleet it starts runs with "
                    "JAX_PLATFORMS=cpu unless the caller's environment "
                    "says otherwise (two server processes cannot share "
                    "one chip).")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--faults", type=int, default=4,
                   help="process faults (SIGTERM/SIGKILL) to fire")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--num-scens", type=int, default=3)
    p.add_argument("--max-iterations", type=int, default=20)
    p.add_argument("--budget", type=float, default=900.0,
                   help="overall settle budget (seconds)")
    p.add_argument("--baseline-sample", type=int, default=3,
                   help="migrated results to re-solve solo and "
                        "compare (0 disables)")
    p.add_argument("--out", default=None,
                   help="write the verdict JSON here")
    args = p.parse_args(argv)
    row = run_chaos(requests=args.requests, faults=args.faults,
                    seed=args.seed, num_scens=args.num_scens,
                    max_iterations=args.max_iterations,
                    budget=args.budget,
                    baseline_sample=args.baseline_sample)
    out = json.dumps(row, indent=2)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(out + "\n")
    print(out)
    print(f"chaos_serve: {'OK' if row['ok'] else 'FAILED'} — "
          f"{row['requests']} requests, statuses {row['statuses']}, "
          f"{len(row['lost'])} lost, "
          f"{row['migrated_done']} migrated-and-done", flush=True)
    return 0 if row["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
