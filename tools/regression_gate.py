#!/usr/bin/env python
"""Tier-1 perf regression gate: graft-lint + farmer bench vs committed
golden run.

The ISSUE 8 CI satellite: perf regressions used to surface only on the
driver (a BENCH re-run on real hardware, days later). This gate runs
the SMALL farmer bench wheel with telemetry on and diffs it against a
COMMITTED golden telemetry directory with ``analyze --compare``, so a
per-iteration time or counter regression (gate syncs per solve call,
total compile count, phase s/call) fails in-repo, at tier-1 speed.

Since ISSUE 12 the gate runs ``python -m tools.lint`` FIRST: a new
blocking-sync / read-after-donate / unlocked-ledger / purity / catalog
violation fails statically in seconds, before any bench cycles, and
the JSON report lands in the fresh telemetry dir as ``lint.json`` so
``analyze`` stamps the compared run with its lint status.

Since ISSUE 13 a serve smoke stage rides last (``--skip-serve-smoke``
opts out): the serving layer on an ephemeral port, the same farmer
shape POSTed twice — the second request must hit the warm cache with
an XLA compile delta of 0 and ``serve.cache.hit`` ≥ 1 on /metrics
(the compile-once contract, doc/serving.md).

Since ISSUE 14 the compare stage also renders the
per-iteration-time-vs-active-set verdict row (``shrink[A/B]: bucket
... s/iter — active-set verdict``) for any side whose wheel ran
progressive shrinking (ops/shrink): a run whose post-compaction
buckets iterate SLOWER than bucket 0 by more than the time threshold
books a regression like any other compare row. The golden farmer
bench runs shrink-free, so the row is absent there by construction.

Since ISSUE 15 a streamed-farmer smoke rides after the compare stage
(``--skip-stream-smoke`` opts out): a small SYNTHESIZED-source farmer
wheel (``--scenario-source synthesized``, doc/streaming.md) whose
telemetry must show stream activity AND flat steady-state
``xfer.device_put_bytes`` — analyze's streaming section is the judge,
so a staging leak or a source regression trips the gate in-repo.

Since ISSUE 19 a forensics smoke rides after the compare stage
(``--skip-forensics-smoke`` opts out): the fresh bench dir must carry
forensic samples and judge HEALTHY through analyze's forensics
section, and a deliberately rho-starved farmer wheel (rho 1e-9 — the
outer bound freezes over a real gap) must judge non-HEALTHY with an
evidence-carrying verdict (doc/forensics.md) — the diagnosis engine
is gated from both the false-positive and the false-negative side.

Since ISSUE 20 a migration smoke rides last (``--skip-migrate-smoke``
opts out): two serve processes peered at each other, one in-flight
farmer request, SIGTERM on the donor mid-wheel — the request must
complete on the RECEIVER with ``resumed_from_iter > 0`` and
``serve.migrate.completed == 1`` on its /metrics (the live-handoff
contract, doc/serving.md), so a protocol or bundle-transfer regression
fails in CI instead of during a real eviction.

Exit codes (analyze's own): 0 PASS, 2 usage / schema refusal,
3 REGRESSION.

Usage:
  python tools/regression_gate.py                 # gate against golden
  python tools/regression_gate.py --threshold 2   # stricter time gate
  python tools/regression_gate.py --update-golden # re-baseline (after
                                                  # a LEGITIMATE change
                                                  # to compile counts /
                                                  # phase anatomy)

The default time gate is deliberately loose (3x ratio over a 20 ms
absolute floor): the golden dir was recorded on one machine and CI
runs on another — the gate exists to catch structural regressions
(a 2x phase blowup, extra gate syncs, a retrace per iteration), not
±20% machine jitter or scheduler noise on the bench's sub-ms
micro-phases. Count metrics use analyze's fixed 1.25x gate, which IS
machine-independent.
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "ci", "golden_farmer_telemetry")

# the golden run's exact recipe — regeneration and the fresh side must
# match, or the compare diffs configuration instead of code. --with-dive
# (ISSUE 9) keeps the device incumbent-pool path inside the gate so a
# regression in its counters/compiles fails here at tier-1 speed.
BENCH_ARGS = ["farmer", "--num-scens", "3", "--max-iterations", "5",
              "--convthresh", "-1", "--subproblem-max-iter", "1500",
              "--with-lagrangian", "--with-xhatshuffle", "--with-dive",
              "--rel-gap", "1e-6"]


def run_lint(out_path=None) -> int:
    """The ISSUE 12 CI step: ``python -m tools.lint`` over the package
    + tools BEFORE any bench cycles are spent — a new sync/donation/
    lock/purity/catalog violation fails the gate statically, at parse
    speed. ``out_path`` lands the JSON report in the fresh telemetry
    dir so ``analyze`` stamps the run with its lint status."""
    cmd = [sys.executable, "-m", "tools.lint", "mpisppy_tpu", "tools"]
    if out_path:
        cmd += ["--out", out_path]
    r = subprocess.run(cmd, cwd=REPO, timeout=300)
    return r.returncode


def run_bench(out_dir: str, extra_args=()) -> int:
    """One small farmer wheel with telemetry into ``out_dir`` — a
    subprocess so the gate script itself never imports jax and every
    invocation pays the same cold-start shape the golden did."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)   # ours, explicitly
    cmd = [sys.executable, "-m", "mpisppy_tpu", *BENCH_ARGS,
           *extra_args, "--telemetry-dir", out_dir]
    r = subprocess.run(cmd, cwd=REPO, env=env, timeout=600)
    return r.returncode


def check_checkpoints(ckpt_dir: str) -> int:
    """The ISSUE 10 acceptance rider: the gated bench ran with
    ``--checkpoint-dir``, so checkpoint capture is INSIDE the compared
    run — any gate-sync or steady-state device_put it added fails the
    ``analyze --compare`` gate below (the PR 6 acceptance contract).
    Here we assert the capture itself worked: a LATEST-pointed bundle
    exists and passes load-side validation."""
    from mpisppy_tpu.ckpt.bundle import CheckpointError, load_bundle
    try:
        manifest, arrays, _ = load_bundle(ckpt_dir)
    except CheckpointError as e:
        print(f"regression_gate: checkpoint capture broken: {e}")
        return 1
    print(f"regression_gate: checkpoint bundle ok (iter "
          f"{manifest.get('iter')}, {len(manifest.get('files') or {})} "
          "members)")
    return 0


def run_serve_smoke(work_dir: str) -> int:
    """The ISSUE 13 CI rider: the compile-once serving contract,
    gated. Starts the serving layer (``python -m mpisppy_tpu serve``)
    on an ephemeral port with telemetry on, POSTs the same farmer
    shape twice (different data), and asserts the second wheel hit the
    warm cache with an XLA compile delta of 0 and ``serve.cache.hit``
    ≥ 1 on /metrics — the serve twin of the compile-count gate the
    compare stage applies to the batch wheel."""
    import json
    import signal
    import time
    import urllib.request

    state = os.path.join(work_dir, "serve_state")
    tdir = os.path.join(work_dir, "serve_telemetry")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "mpisppy_tpu", "serve", "--port", "0",
         "--state-dir", state, "--telemetry-dir", tdir,
         "--batch-window", "0.05"],
        cwd=REPO, env=env)

    def _get(url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.read().decode()

    def _post(url, obj):
        req = urllib.request.Request(
            url, data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read().decode())

    try:
        ep = os.path.join(state, "serve.json")
        deadline = time.time() + 180
        port = None
        while time.time() < deadline:
            if proc.poll() is not None:
                print("regression_gate: serve process died at startup")
                return 1
            if os.path.isfile(ep):
                port = json.load(open(ep, encoding="utf-8"))["port"]
                break
            time.sleep(0.2)
        if port is None:
            print("regression_gate: serve endpoint file never appeared")
            return 1
        base = f"http://127.0.0.1:{port}"
        payload = {"model": "farmer", "num_scens": 3,
                   "algo": {"max_iterations": 10}}
        stamps = []
        for patch in (None, {"c": {"DevotedAcreage":
                                   [160.0, 235.0, 250.0]}}):
            body = dict(payload)
            if patch:
                body["patch"] = patch
            rid = _post(f"{base}/solve", body)["request_id"]
            # per-request poll budget (not the shared startup
            # deadline): a slow first compile must not leave the
            # second request judged on a stale — or unbound — record
            rec = None
            poll_end = time.time() + 180
            while time.time() < poll_end:
                rec = json.loads(_get(f"{base}/result/{rid}"))
                if rec["status"] in ("done", "failed"):
                    break
                time.sleep(0.25)
            if rec is None or rec["status"] != "done":
                print(f"regression_gate: serve request {rid} ended "
                      f"{(rec or {}).get('status', 'timeout')}: "
                      f"{(rec or {}).get('error')}")
                return 1
            stamps.append(rec["result"]["wheel"])
        metrics = _get(f"{base}/metrics")
        if not stamps[1]["cache_hit"]:
            print("regression_gate: second same-shape request MISSED "
                  "the warm cache")
            return 3
        if stamps[1]["xla_compiles_delta"] != 0:
            print("regression_gate: COMPILE-ONCE REGRESSION — second "
                  "same-shape request recompiled "
                  f"({stamps[1]['xla_compiles_delta']} new XLA "
                  f"compiles; first request paid "
                  f"{stamps[0]['xla_compiles_delta']})")
            return 3
        hit_line = next((ln for ln in metrics.splitlines()
                         if ln.startswith("mpisppy_tpu_serve_cache_hit ")),
                        None)
        if hit_line is None or float(hit_line.split()[1]) < 1:
            print("regression_gate: serve.cache.hit missing from "
                  "/metrics (expected >= 1)")
            return 3
        print("regression_gate: serve smoke ok (second request: "
              "cache hit, compile delta 0)")
        return 0
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_migrate_smoke(work_dir: str) -> int:
    """The ISSUE 20 CI rider: the live-migration handoff contract,
    gated end to end. Two serve processes on ephemeral pre-picked
    ports, ``--peers`` pointed at each other; one slow farmer request
    lands on the donor, and once its wheel has checkpointed, the donor
    gets SIGTERM — with a live peer that escalates from bundle-and-
    exit to migrate-then-exit (doc/serving.md). The request must
    complete ON THE RECEIVER with ``resumed_from_iter > 0`` (the
    bundle actually resumed, not a cold re-run) and
    ``serve.migrate.completed == 1`` on the receiver's /metrics."""
    import json
    import signal
    import socket
    import time
    import urllib.request

    def _free_port():
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def _get(url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.read().decode()

    def _post(url, obj):
        req = urllib.request.Request(
            url, data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read().decode())

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    ports = (_free_port(), _free_port())
    states = [os.path.join(work_dir, f"migrate_{n}")
              for n in ("donor", "receiver")]
    procs = []
    try:
        for i, (port, state) in enumerate(zip(ports, states)):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "mpisppy_tpu", "serve",
                 "--port", str(port), "--state-dir", state,
                 "--peers", f"127.0.0.1:{ports[1 - i]}",
                 "--batch-window", "0.05",
                 "--checkpoint-interval", "0.2",
                 "--migrate-deadline", "30",
                 "--telemetry-dir",
                 os.path.join(state, "telemetry")],
                cwd=REPO, env=env))
        bases = [f"http://127.0.0.1:{p}" for p in ports]
        deadline = time.time() + 180
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                print("regression_gate: a migrate-smoke serve process "
                      "died at startup")
                return 1
            try:
                if all(json.loads(_get(f"{b}/healthz")).get("ok")
                       for b in bases):
                    break
            except OSError:
                pass
            time.sleep(0.3)
        else:
            print("regression_gate: migrate-smoke fleet never became "
                  "healthy")
            return 1
        # a deliberately long wheel: enough iterations that the donor
        # is still mid-flight when the SIGTERM lands
        rid = _post(f"{bases[0]}/solve",
                    {"model": "farmer", "num_scens": 3,
                     "algo": {"max_iterations": 120,
                              "convthresh": -1.0}})["request_id"]
        # wait for the donor's wheel to have a bundle to hand off —
        # the LATEST pointer under the request's ckpt namespace is the
        # deterministic signal
        latest = os.path.join(states[0], "ckpt", rid, "LATEST")
        bundle_end = time.time() + 120
        while time.time() < bundle_end and not os.path.exists(latest):
            time.sleep(0.1)
        if not os.path.exists(latest):
            print("regression_gate: donor wheel never checkpointed")
            return 3
        procs[0].send_signal(signal.SIGTERM)
        rec = None
        poll_end = time.time() + 300
        while time.time() < poll_end:
            try:
                rec = json.loads(_get(f"{bases[1]}/result/{rid}"))
                if rec.get("status") in ("done", "failed"):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.3)
        if rec is None or rec.get("status") != "done":
            print("regression_gate: MIGRATION SMOKE FAILURE — the "
                  "SIGTERM'd donor's request never completed on the "
                  f"receiver (last record: {rec})")
            return 3
        resumed = (rec["result"].get("wheel") or {}).get(
            "resumed_from_iter")
        if not resumed or resumed <= 0:
            print("regression_gate: MIGRATION SMOKE REGRESSION — the "
                  "receiver re-ran the request cold "
                  f"(resumed_from_iter={resumed!r}); the handed-off "
                  "bundle must resume through load_bundle")
            return 3
        metrics = _get(f"{bases[1]}/metrics")
        line = next((ln for ln in metrics.splitlines() if ln.startswith(
            "mpisppy_tpu_serve_migrate_completed ")), None)
        if line is None or float(line.split()[1]) != 1:
            print("regression_gate: MIGRATION SMOKE REGRESSION — "
                  "receiver /metrics shows serve.migrate.completed "
                  f"{line!r}, expected exactly 1")
            return 3
        print(f"regression_gate: migrate smoke ok (request completed "
              f"on the receiver, resumed from iteration {resumed})")
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()


def run_stream_smoke(work_dir: str) -> int:
    """The ISSUE 15 CI rider: the streaming acceptance contract,
    gated. Runs a small synthesized-source farmer wheel (hub-only —
    the v1 streaming scope) with telemetry on and asserts, through
    analyze's streaming section, that (a) the scenario source actually
    ran (synth chunks > 0) and (b) the per-iteration
    ``xfer.device_put_bytes`` deltas stayed FLAT across steady-state
    iterations — the doc/sharding.md transfer contract extended to
    streamed wheels (doc/streaming.md)."""
    tdir = os.path.join(work_dir, "stream_telemetry")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    cmd = [sys.executable, "-m", "mpisppy_tpu", "farmer",
           "--num-scens", "64", "--scenario-source", "synthesized",
           "--subproblem-chunk", "16", "--max-iterations", "4",
           "--convthresh", "-1", "--subproblem-max-iter", "1200",
           "--telemetry-dir", tdir]
    r = subprocess.run(cmd, cwd=REPO, env=env, timeout=600)
    if r.returncode != 0:
        print(f"regression_gate: streamed farmer wheel failed "
              f"(rc {r.returncode})")
        return r.returncode or 1
    from mpisppy_tpu.obs.analyze import load_run, streaming_summary
    sm = streaming_summary(load_run(tdir))
    if sm is None or not sm.get("synth_chunks"):
        print("regression_gate: STREAM SMOKE FAILURE — the synthesized "
              "source never staged a chunk (streaming section empty)")
        return 3
    if sm.get("device_put_flat_steady_state") is False:
        print("regression_gate: STREAM SMOKE REGRESSION — steady-state "
              "xfer.device_put_bytes deltas are not flat (per-iteration "
              f"trajectory: {[r_['device_put_bytes'] for r_ in sm['per_iteration']]})")
        return 3
    print(f"regression_gate: stream smoke ok (synth chunks "
          f"{sm['synth_chunks']}, steady-state device_put flat)")
    # shrink×stream rider (ISSUE 17, doc/streaming.md): a
    # compacted+STREAMED integer-UC wheel — one bucket transition must
    # re-block the host store at the compacted width, after which the
    # per-iteration shipped bytes drop strictly and go flat, the
    # restage books out-of-band, and the transition's warm transplant
    # lands without a cold fallback. Analyze's shrink + stream
    # summaries are the judge, same as the flat contract above.
    tdir2 = os.path.join(work_dir, "stream_shrink_telemetry")
    cmd = [sys.executable, "-m", "mpisppy_tpu", "uc",
           "--num-scens", "6", "--model-kwargs",
           '{"num_gens":3,"num_hours":6,"relax_integrality":false}',
           "--scenario-source", "streamed",
           "--subproblem-chunk", "2", "--max-iterations", "10",
           "--convthresh", "-1", "--default-rho", "50",
           "--subproblem-max-iter", "4000",
           "--subproblem-eps", "1e-6",
           "--shrink-fix", "--shrink-fix-iters", "2",
           "--shrink-fix-tol", "1e-2", "--shrink-compact",
           "--shrink-buckets", "0.1", "--telemetry-dir", tdir2]
    r = subprocess.run(cmd, cwd=REPO, env=env, timeout=600)
    if r.returncode != 0:
        print(f"regression_gate: compacted streamed UC wheel failed "
              f"(rc {r.returncode})")
        return r.returncode or 1
    from mpisppy_tpu.obs.analyze import shrink_summary
    run2 = load_run(tdir2)
    sm2, sh2 = streaming_summary(run2), shrink_summary(run2)
    if sm2 is None or sh2 is None or not sh2.get("compactions") \
            or not sm2.get("compacted_transitions"):
        print("regression_gate: STREAM SMOKE FAILURE — the compacted "
              "streamed wheel never re-blocked (compactions "
              f"{None if sh2 is None else sh2.get('compactions')}, "
              "transitions "
              f"{None if sm2 is None else sm2.get('compacted_transitions')})")
        return 3
    ship = [r_["bytes_shipped"] for r_ in sm2["per_iteration"]]
    trans_i = max(i for i, r_ in enumerate(sm2["per_iteration"])
                  if r_["compacted_transitions"])
    pre = [b for b in ship[:trans_i] if b]
    post = [b for b in ship[trans_i + 1:] if b]
    if not pre or not post or max(post) >= min(pre):
        print("regression_gate: STREAM SMOKE REGRESSION — shipped "
              "bytes did not drop across the compaction "
              f"(per-iteration: {ship})")
        return 3
    if sm2.get("device_put_flat_steady_state") is False:
        print("regression_gate: STREAM SMOKE REGRESSION — post-"
              "transition device_put deltas are not flat "
              f"(per-iteration: "
              f"{[r_['device_put_bytes'] for r_ in sm2['per_iteration']]})")
        return 3
    if sh2.get("transplant_cold_fallbacks"):
        print("regression_gate: STREAM SMOKE REGRESSION — the bucket "
              "transition fell back to a cold restart "
              f"({sh2['transplant_cold_fallbacks']} fallbacks)")
        return 3
    print(f"regression_gate: shrink-stream smoke ok (shipped/iter "
          f"{min(pre)} -> {max(post)}, restage "
          f"{sm2['compacted_restage_bytes']}B out-of-band, "
          f"transplants {sh2['transplants']})")
    return 0


def run_forensics_smoke(fresh: str) -> int:
    """The ISSUE 19 CI rider: the diagnosis engine's verdict contract,
    gated from BOTH sides. The fresh golden-recipe bench (the dir the
    compare stage just judged) must carry forensic samples AND judge
    HEALTHY — a threshold drift that starts flagging a converging
    wheel fails here. Then a deliberately rho-starved farmer wheel
    (rho 1e-9: W barely moves, the Lagrangian outer bound freezes
    while a real gap remains) must judge non-HEALTHY with
    evidence-carrying verdicts — a rule that stops firing on a
    genuinely stuck wheel also fails here."""
    from mpisppy_tpu.obs.analyze import load_run, forensics_summary
    fz = forensics_summary(load_run(fresh))
    if fz is None or not fz.get("samples"):
        print("regression_gate: FORENSICS SMOKE FAILURE — the fresh "
              "bench produced no forensic samples (ops/forensics -> "
              "iteration_record wiring broken)")
        return 3
    if fz["verdict"] != "HEALTHY":
        why = fz["verdicts"][0]["summary"] if fz["verdicts"] else "?"
        print("regression_gate: FORENSICS SMOKE REGRESSION — the "
              f"golden-recipe bench judged {fz['verdict']} ({why}); "
              "a converging wheel must judge HEALTHY (rule threshold "
              "drift, doc/forensics.md)")
        return 3
    starved = os.path.join(fresh, "forensics_starved")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    cmd = [sys.executable, "-m", "mpisppy_tpu", "farmer",
           "--num-scens", "3", "--max-iterations", "14",
           "--convthresh", "-1", "--subproblem-max-iter", "1500",
           "--with-lagrangian", "--with-xhatshuffle",
           "--rel-gap", "1e-6", "--default-rho", "1e-9",
           "--forensics-interval", "1", "--telemetry-dir", starved]
    r = subprocess.run(cmd, cwd=REPO, env=env, timeout=600)
    if r.returncode != 0:
        print("regression_gate: FORENSICS SMOKE FAILURE — the "
              f"rho-starved wheel itself failed (rc {r.returncode})")
        return 3
    sz = forensics_summary(load_run(starved))
    if sz is None or sz["verdict"] == "HEALTHY":
        print("regression_gate: FORENSICS SMOKE REGRESSION — the "
              "rho-starved wheel judged "
              f"{sz['verdict'] if sz else 'no-data'}; a frozen outer "
              "bound over a 7% gap must produce a non-HEALTHY verdict "
              "(diagnosis rules went blind, doc/forensics.md)")
        return 3
    top = sz["verdicts"][0]
    if not top.get("evidence"):
        print("regression_gate: FORENSICS SMOKE REGRESSION — verdict "
              f"{top['verdict']} carries no evidence dict (the "
              "diagnosis contract is named AND evidenced)")
        return 3
    print(f"regression_gate: forensics smoke ok (golden recipe "
          f"HEALTHY over {fz['samples']} samples; starved wheel "
          f"{sz['verdict']}: {top['summary']})")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="tier-1 perf regression gate "
                    "(bench + analyze --compare vs committed golden). "
                    "A CPU tool: every stage's child runs with "
                    "JAX_PLATFORMS=cpu unless the caller's environment "
                    "says otherwise, and the golden it compares against "
                    "is CPU-tier telemetry; it never times a chip "
                    "(chip_smoke.py is the chip's entry point).")
    p.add_argument("--golden", default=GOLDEN,
                   help=f"golden telemetry dir (default {GOLDEN})")
    p.add_argument("--threshold", type=float, default=3.0,
                   help="time-metric regression ratio passed to "
                        "analyze --compare (default 3.0 — loose on "
                        "purpose, cross-machine)")
    p.add_argument("--abs-floor-ms", type=float, default=20.0,
                   help="ignore time deltas below this many ms per "
                        "call (default 20 — the bench's real phases "
                        "run 0.1-2 s/call, so a structural 2x blowup "
                        "still clears it, while its sub-ms "
                        "micro-phases ride scheduler noise that a "
                        "ratio gate alone would flag)")
    p.add_argument("--keep", default=None,
                   help="keep the fresh telemetry dir here (default: "
                        "a deleted tempdir)")
    p.add_argument("--update-golden", action="store_true",
                   help="re-record the golden dir instead of gating "
                        "(commit the result)")
    p.add_argument("--skip-serve-smoke", action="store_true",
                   help="skip the serving-layer compile-once smoke "
                        "stage (doc/serving.md); the bench + compare "
                        "gate still runs")
    p.add_argument("--skip-migrate-smoke", action="store_true",
                   help="skip the live-migration handoff smoke stage "
                        "(doc/serving.md); the bench + compare gate "
                        "still runs")
    p.add_argument("--skip-stream-smoke", action="store_true",
                   help="skip the streamed-farmer flat-transfer smoke "
                        "stage (doc/streaming.md); the bench + compare "
                        "gate still runs")
    p.add_argument("--skip-forensics-smoke", action="store_true",
                   help="skip the diagnosis-engine smoke stage "
                        "(doc/forensics.md: golden recipe HEALTHY, "
                        "rho-starved wheel non-HEALTHY with "
                        "evidence); the bench + compare gate still "
                        "runs")
    args = p.parse_args(argv)

    if args.update_golden:
        rc = run_lint()
        if rc != 0:
            print("regression_gate: lint failed — fix or suppress "
                  "(doc/lint.md) before re-baselining")
            return rc
        os.makedirs(os.path.dirname(args.golden), exist_ok=True)
        shutil.rmtree(args.golden, ignore_errors=True)
        rc = run_bench(args.golden)
        if rc != 0:
            print(f"regression_gate: bench run failed (rc {rc})")
            return rc or 1
        # live.json is a moving in-run snapshot, not a comparison
        # artifact — keep the committed golden minimal
        lj = os.path.join(args.golden, "live.json")
        if os.path.exists(lj):
            os.remove(lj)
        print(f"regression_gate: golden re-recorded at {args.golden} "
              "— commit it")
        return 0

    if not os.path.isdir(args.golden):
        print(f"regression_gate: no golden dir at {args.golden} — "
              "record one with --update-golden and commit it")
        return 2

    fresh = args.keep or tempfile.mkdtemp(prefix="regression_gate_")
    try:
        # lint gate first (static, seconds): new contract violations
        # fail before the bench spends minutes; the report rides the
        # fresh telemetry dir so analyze stamps the compared run
        os.makedirs(fresh, exist_ok=True)
        rc = run_lint(out_path=os.path.join(fresh, "lint.json"))
        if rc != 0:
            print("regression_gate: LINT FAILURE — `python -m "
                  "tools.lint` found unsuppressed findings (fix the "
                  "violation or suppress with a reason, doc/lint.md)")
            return rc
        # the fresh side runs WITH checkpoint capture armed (the
        # golden stays minimal): checkpoint writes ride the compared
        # run, so a capture-induced gate sync / device_put / phase
        # blowup trips the same compare gate as any other regression
        ckpt_dir = os.path.join(fresh, "ckpt")
        rc = run_bench(fresh, extra_args=["--checkpoint-dir", ckpt_dir,
                                          "--checkpoint-interval", "1"])
        if rc != 0:
            print(f"regression_gate: bench run failed (rc {rc})")
            return rc or 1
        # analyze is jax-free — import it here, after the bench
        # subprocess did the heavy lifting
        sys.path.insert(0, REPO)
        rc = check_checkpoints(ckpt_dir)
        if rc != 0:
            return rc
        from mpisppy_tpu.obs.analyze import main as analyze_main
        rc = analyze_main(["--compare", args.golden, fresh,
                           "--threshold", str(args.threshold),
                           "--abs-floor-ms", str(args.abs_floor_ms)])
        if rc == 3:
            print("regression_gate: REGRESSION vs committed golden "
                  f"({args.golden}). If the change is intentional "
                  "(new compile, reshaped phases), re-baseline with "
                  "--update-golden and commit the new golden dir.")
        if rc != 0:
            return rc
        if not args.skip_forensics_smoke:
            # forensics smoke (ISSUE 19): the diagnosis-engine verdict
            # contract — the fresh dir must judge HEALTHY, a
            # rho-starved wheel must judge non-HEALTHY with evidence
            rc = run_forensics_smoke(fresh)
            if rc != 0:
                return rc
        if not args.skip_stream_smoke:
            # stream smoke (ISSUE 15): the flat-transfer streaming
            # contract on a synthesized farmer wheel
            rc = run_stream_smoke(fresh)
            if rc != 0:
                return rc
        if not args.skip_serve_smoke:
            # serve smoke (ISSUE 13): the compile-once contract on
            # the serving layer — same lint-first -> bench -> compare
            # pipeline, one more stage
            rc = run_serve_smoke(fresh)
            if rc != 0:
                return rc
        if args.skip_migrate_smoke:
            return rc
        # migration smoke last (ISSUE 20): SIGTERM the donor of a
        # 2-process fleet mid-wheel; the receiver must finish the
        # request from the handed-off bundle
        return run_migrate_smoke(fresh)
    finally:
        if args.keep is None:
            shutil.rmtree(fresh, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
