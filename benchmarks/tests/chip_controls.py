"""Read, on the chip and in ONE process, the numbers a cell's limits
are set from (PERF.md section 2): every compared number of sound runs
over many seeds, then of the control over a few.

    chiprun -- python benchmarks/tests/chip_controls.py <cell> \
        --sound 1,2,3 --control 4,5,6 --seconds 1 --out chiprun_out/ctl

The control of the UC cells is the recipe below its stated precision,
and nothing else changed: the split-f32 refinement tail off
(``subproblem_tail_iter`` 0), so every solve is the f32 bulk phase
alone, under the same float64 outer arithmetic; the serve cell's control breaks the batching guarantee (a
tenant of a stacked wheel gets a result that is not its own). Each run
goes through ``harness.run_cell`` - the entry ``run.py`` uses - at the
cell's own size; one JSON line per run lands in ``<out>/<cell>.jsonl``.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

UC_CONTROL = {"recipe": {"subproblem_tail_iter": 0}}


def break_batching():
    from mpisppy_tpu.serve import manager
    real = manager.consensus_results

    def altered(engine, blocks, *a, **kw):
        out = real(engine, blocks, *a, **kw)
        for k, res in enumerate(out):
            if res["objective"] is not None:
                res["objective"] *= 1.0 + 0.01 * k
        return out

    manager.consensus_results = altered


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control-seconds", type=float, default=None,
                    help="window of the control runs (default: --seconds)")
    ap.add_argument("--override", default="{}",
                    help="JSON of traffic parameters for every run")
    ap.add_argument("--out", default="chiprun_out/controls")
    args = ap.parse_args(argv)
    import harness
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    over = json.loads(args.override)
    is_uc = harness.load_json(
        "traffic", harness.load_json(
            "workloads", f"{args.cell}.json")["traffic"] + ".json"
    )["driver"] == "ph_hot"
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.cell}.jsonl"), "a") as f:
        for kind, ss in (("sound", seeds(args.sound)),
                         ("control", seeds(args.control))):
            if kind == "control" and ss and not is_uc:
                break_batching()
            for seed in ss:
                t0 = time.perf_counter()
                try:
                    line = harness.run_cell(
                        args.cell, seed,
                        args.control_seconds
                        if kind == "control" and args.control_seconds
                        else args.seconds, False,
                        overrides=over,
                        variant=UC_CONTROL if kind == "control" and is_uc
                        else None)
                except Exception as e:      # a control may crash: report
                    line = {"error": repr(e)[:400]}
                gc.collect()    # the engine's device arrays, before the next
                row = {"kind": kind, "seed": seed,
                       "wall_s": time.perf_counter() - t0, **line}
                f.write(json.dumps(row) + "\n")
                f.flush()
                print(json.dumps(row)[:600], flush=True)


if __name__ == "__main__":
    main()
