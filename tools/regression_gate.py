#!/usr/bin/env python
"""Tier-1 regression gate: graft-lint, then the small farmer wheel's
COUNTS against a committed golden run. A CPU tool that reads no clock.

Three stages:

1. ``python -m tools.lint`` over the package + tools FIRST: a new
   blocking-sync / read-after-donate / unlocked-ledger / purity /
   catalog violation fails statically in seconds, before any wheel
   runs, and the JSON report lands in the fresh telemetry dir as
   ``lint.json`` so ``analyze`` stamps the compared run with its lint
   status (doc/lint.md).
2. The SMALL farmer wheel (hub + Lagrangian + x̂ + dive spokes) with
   telemetry on and checkpoint capture armed; the bundle it leaves
   must load.
3. ``analyze --compare`` of the fresh telemetry against the COMMITTED
   golden directory, on the COUNT rows only: gate syncs per solve
   call, total XLA compiles, fused kernel iterations per solve call,
   the sharded and streamed transfer rows where a side carries them
   (analyze's fixed 1.25x count gate, machine-independent), and the
   verdict rows that read no clock (forensics: a fresh wheel that
   judges non-HEALTHY against a HEALTHY golden is a regression). The
   time rows are still printed; the time threshold is infinite, so
   they cannot fail the gate: a CPU second is not a measurement of
   this program (PERF.md), and a ratio of two machines' loads is not
   a regression. Times are the benchmark's, on the chip
   (``benchmarks/run.py --workload <cell>``, PERF_LEDGER.jsonl).

Exit codes (analyze's own): 0 PASS, 2 usage / schema refusal,
3 REGRESSION.

Usage:
  python tools/regression_gate.py                 # gate against golden
  python tools/regression_gate.py --update-golden # re-baseline (after
                                                  # a LEGITIMATE change
                                                  # to compile counts /
                                                  # phase anatomy)
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
    run — any gate sync or compile it added fails ``compare_counts``
    below (the PR 6 acceptance contract).
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


def compare_counts(golden: str, fresh: str) -> int:
    """Stage 3: analyze's ``--compare`` with the time threshold at
    infinity. Every row is printed; only the count rows and the
    clock-free verdict rows can return 3."""
    from mpisppy_tpu.obs.analyze import main as analyze_main
    rc = analyze_main(["--compare", golden, fresh,
                       "--threshold", "inf"])
    if rc == 3:
        print("regression_gate: REGRESSION vs committed golden "
              f"({golden}). If the change is intentional "
              "(new compile, reshaped phases), re-baseline with "
              "--update-golden and commit the new golden dir.")
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="tier-1 regression gate (lint, the small farmer "
                    "wheel, analyze --compare of its COUNTS vs the "
                    "committed golden). A CPU tool: every stage's child "
                    "runs with JAX_PLATFORMS=cpu unless the caller's "
                    "environment says otherwise; it times nothing "
                    "(benchmarks/run.py measures, on the chip; "
                    "chip_smoke.py is the chip's bring-up check).")
    p.add_argument("--golden", default=GOLDEN,
                   help=f"golden telemetry dir (default {GOLDEN})")
    p.add_argument("--keep", default=None,
                   help="keep the fresh telemetry dir here (default: "
                        "a deleted tempdir)")
    p.add_argument("--update-golden", action="store_true",
                   help="re-record the golden dir instead of gating "
                        "(commit the result)")
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
        # run, so a capture-induced gate sync or compile trips the
        # same count gate as any other regression
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
        return compare_counts(args.golden, fresh)
    finally:
        if args.keep is None:
            shutil.rmtree(fresh, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
