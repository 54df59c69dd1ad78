#!/usr/bin/env python
"""Serve load benchmark: requests/s vs wheel width (ROADMAP item 2
remainder).

Sizes the serving layer's ``--max-wheels`` / ``--batch-max`` defaults
with measurements instead of guesses: for each (max_wheels, batch_max)
point of a small grid, the tool starts a FRESH ``python -m mpisppy_tpu
serve`` process on an ephemeral port, warms the shape bucket with one
request (compile cost must not pollute the throughput window), then
fires ``--requests`` data-only farmer requests (batchable — the
scenario-axis batcher is exactly what the sweep measures) and clocks
first-POST -> last-done. Results land as bench-style JSON rows
(``{"metric": "serve_load", ...}``, same ``schema_version`` discipline
as bench.py) in ``--out`` plus a recommended-defaults row, so the
evidence rides the repo like every other bench artifact.

jax-free by design (PURE001: tools/): the serve process does the
solving; this is a stdlib HTTP client.

Usage:
  python tools/serve_loadbench.py --out serve_load.json
  python tools/serve_loadbench.py --wheels 1,2 --batch 1,8 \\
      --requests 12 --num-scens 3
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA_VERSION = 1


def _pid_alive(pid) -> bool:
    try:
        os.kill(int(pid), 0)
    except (ProcessLookupError, ValueError, TypeError):
        return False
    except OSError:
        return True
    return True


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _post(url, obj, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _payload(num_scens, max_iterations, i=None):
    """A farmer request; ``i`` varies the planting-cost vector so every
    request is a DISTINCT data-only instance of one shape bucket (the
    batcher's eligibility surface, doc/serving.md)."""
    body = {"model": "farmer", "num_scens": num_scens,
            "algo": {"max_iterations": max_iterations}}
    if i is not None:
        body["patch"] = {"c": {"DevotedAcreage":
                               [150.0 + i, 230.0 + i, 260.0 + i]}}
    return body


def _wait_done(base, rid, budget):
    end = time.time() + budget
    while time.time() < end:
        rec = json.loads(_get(f"{base}/result/{rid}"))
        if rec["status"] in ("done", "failed"):
            return rec
        time.sleep(0.1)
    return None


def measure_point(max_wheels, batch_max, requests, num_scens,
                  max_iterations, budget=600):
    """One grid point: fresh serve process, warm the bucket, then the
    timed request burst. Returns the bench row dict."""
    work = tempfile.mkdtemp(prefix="serve_loadbench_")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "mpisppy_tpu", "serve", "--port", "0",
         "--state-dir", os.path.join(work, "state"),
         "--max-wheels", str(max_wheels),
         "--batch-max", str(batch_max),
         "--batch-window", "0.1"],
        cwd=REPO, env=env)
    try:
        ep = os.path.join(work, "state", "serve.json")
        deadline = time.time() + 180
        port = None
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError("serve process died at startup")
            if os.path.isfile(ep):
                d = json.load(open(ep, encoding="utf-8"))
                # staleness gate: a serve.json whose recorded pid is
                # dead is a leftover from a killed process — keep
                # waiting for OUR service to write, never connect to
                # nothing
                if _pid_alive(d.get("pid")):
                    port = d["port"]
                    break
            time.sleep(0.2)
        if port is None:
            raise RuntimeError("serve endpoint file never appeared")
        base = f"http://127.0.0.1:{port}"
        # warm the bucket: the first request pays the compiles; the
        # throughput window must measure the warm serving path
        rid = _post(f"{base}/solve",
                    _payload(num_scens, max_iterations))["request_id"]
        rec = _wait_done(base, rid, budget)
        if rec is None or rec["status"] != "done":
            raise RuntimeError(f"warmup request ended "
                               f"{(rec or {}).get('status', 'timeout')}")
        t0 = time.time()
        # the burst deliberately outruns admission at aggressive grid
        # points. A 429/503 carries Retry-After (doc/serving.md) — the
        # client backs off with jitter and retries instead of
        # hammering; a point that only completed via backoff is
        # reported separately (retried_ok) from first-try admissions.
        rng = random.Random(0)
        rids, retried, failed = [], set(), 0
        for i in range(requests):
            rid, was_retried = None, False
            for _attempt in range(4):
                try:
                    rid = _post(
                        f"{base}/solve",
                        _payload(num_scens, max_iterations, i))[
                        "request_id"]
                    break
                except urllib.error.HTTPError as e:
                    if e.code not in (429, 503):
                        break
                    was_retried = True
                    retry = float(e.headers.get("Retry-After") or 1.0)
                    time.sleep(retry * (0.5 + rng.random()))
                except urllib.error.URLError:
                    break
            if rid is None:
                failed += 1
            else:
                rids.append(rid)
                if was_retried:
                    retried.add(rid)
        done = retried_ok = 0
        for r in rids:
            rec = _wait_done(base, r, budget)
            if rec is not None and rec["status"] == "done":
                done += 1
                if r in retried:
                    retried_ok += 1
            else:
                failed += 1
        elapsed = time.time() - t0
        return {"metric": "serve_load", "schema_version": SCHEMA_VERSION,
                "max_wheels": max_wheels, "batch_max": batch_max,
                "requests": requests, "done": done, "failed": failed,
                "retried_ok": retried_ok,
                "num_scens": num_scens,
                "max_iterations": max_iterations,
                "elapsed_s": elapsed,
                "requests_per_s": (done / elapsed) if elapsed > 0
                else None}
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


def recommend(rows) -> dict:
    """The sizing row: the (max_wheels, batch_max) point with the best
    all-done throughput — what ``--max-wheels``/``--batch-max`` should
    default to on hardware shaped like the bench host."""
    ok = [r for r in rows if r["done"] == r["requests"]
          and r["requests_per_s"]]
    if not ok:
        return {"metric": "serve_load_recommendation",
                "schema_version": SCHEMA_VERSION, "recommended": None,
                "reason": "no grid point completed every request"}
    best = max(ok, key=lambda r: r["requests_per_s"])
    return {"metric": "serve_load_recommendation",
            "schema_version": SCHEMA_VERSION,
            "recommended": {"max_wheels": best["max_wheels"],
                            "batch_max": best["batch_max"]},
            "requests_per_s": best["requests_per_s"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="serve load benchmark: requests/s vs wheel width. "
                    "A CPU tool: the servers it starts run with "
                    "JAX_PLATFORMS=cpu unless the caller's environment "
                    "says otherwise; it never times a chip "
                    "(chip_smoke.py is the chip's entry point).")
    p.add_argument("--wheels", default="1,2",
                   help="comma-separated --max-wheels grid")
    p.add_argument("--batch", default="1,8",
                   help="comma-separated --batch-max grid")
    p.add_argument("--requests", type=int, default=8,
                   help="timed requests per grid point")
    p.add_argument("--num-scens", type=int, default=3)
    p.add_argument("--max-iterations", type=int, default=10)
    p.add_argument("--out", default=None,
                   help="write the JSON rows here (default: stdout "
                        "only)")
    args = p.parse_args(argv)

    rows = []
    for w in (int(x) for x in args.wheels.split(",") if x.strip()):
        for bm in (int(x) for x in args.batch.split(",") if x.strip()):
            print(f"serve_loadbench: max_wheels={w} batch_max={bm} "
                  f"({args.requests} requests)...", flush=True)
            row = measure_point(w, bm, args.requests, args.num_scens,
                                args.max_iterations)
            print(f"  -> {row['requests_per_s']:.2f} req/s "
                  f"({row['done']}/{row['requests']} done, "
                  f"{row['elapsed_s']:.1f}s)", flush=True)
            rows.append(row)
    rows.append(recommend(rows))
    out = json.dumps(rows, indent=2)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(out + "\n")
        print(f"serve_loadbench: rows written to {args.out}")
    else:
        print(out)
    rec = rows[-1].get("recommended")
    if rec:
        print(f"serve_loadbench: recommended defaults "
              f"--max-wheels {rec['max_wheels']} "
              f"--batch-max {rec['batch_max']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
