"""One cell through ``harness.run_cell`` with a telemetry session ON,
for what tracing costs when it is on (PERF.md section 6, PR 26):

    MPISPPY_TPU_TELEMETRY_DIR=chiprun_out/obs chiprun -- python \
        benchmarks/tests/session_on.py --workload <cell> --seed <n> \
        --seconds 51

The session is configured from the environment before the run, the way
``python -m mpisppy_tpu`` and ``serve`` configure theirs; the cell, its
window and its contract line (the LAST line of standard output) are
``run.py``'s. The line before it sums the session's own ``trace.json``:
seconds by span name and, for the served wheel, the median seconds of
each ``serve.wheel.*`` step by stack size.
"""

import time

_T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import statistics    # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def summarize(trace_path):
    """``{"seconds_by_span": {name: [count, seconds]}, "wheel_steps":
    {stack: {step: median seconds}}}`` of one session's trace."""
    with open(trace_path, encoding="utf-8") as f:
        evs = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in evs:
        ent = by_name.setdefault(e["name"], [0, 0.0])
        ent[0] += 1
        ent[1] += e["dur"] / 1e6
    steps = {}
    for w in (e for e in evs if e["name"] == "serve.wheel"):
        acc = steps.setdefault(int(w["args"]["stack"]), {})
        acc.setdefault("serve.wheel", []).append(w["dur"] / 1e6)
        inside = [e for e in evs if e["tid"] == w["tid"] and e is not w
                  and w["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= w["ts"] + w["dur"]]
        tot = {}
        for e in inside:
            if e["name"].startswith(("serve.wheel.", "ph.", "qp.")):
                tot[e["name"]] = tot.get(e["name"], 0.0) + e["dur"] / 1e6
        for name, v in tot.items():
            acc.setdefault(name, []).append(v)
    return {"seconds_by_span": {n: [c, round(v, 4)] for n, (c, v)
                                in sorted(by_name.items())},
            "wheel_steps": {k: {n: round(statistics.median(v), 4)
                                for n, v in sorted(acc.items())}
                            for k, acc in sorted(steps.items())}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out_dir = os.environ.get("MPISPPY_TPU_TELEMETRY_DIR")
    if not out_dir:
        ap.error("set MPISPPY_TPU_TELEMETRY_DIR: this run is the one "
                 "WITH a session")
    import harness
    from mpisppy_tpu import obs
    obs.maybe_configure_from_env(role=args.workload)
    line = harness.run_cell(args.workload, args.seed, args.seconds, False,
                            t_process=_T_PROCESS)
    obs.shutdown()
    print(json.dumps(summarize(os.path.join(
        out_dir, f"trace-{args.workload}.json"))), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
