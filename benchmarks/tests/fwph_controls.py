"""Read, on the chip and in ONE process, the numbers the FWPH cell's
limits are set from (PERF.md section 2): every compared number of sound
runs, then of the control, through ``harness.run_cell`` - the entry
``run.py`` uses - at the cell's own size, with ``run.variant`` changed
underneath the driver and nothing else.

    chiprun -- python benchmarks/tests/fwph_controls.py \
        --sound 1,2 --control 3 --seconds 51 --out chiprun_out/ctl

The control is the UC cells' (``chip_controls.UC_CONTROL``): the recipe
below its stated precision, the split-f32 refinement tail off
(``subproblem_tail_iter`` 0), so every linearized solve is the f32 bulk
phase alone under the same float64 outer arithmetic and the same
float64 QP. One JSON line per run lands in
``<out>/uc_s256_fwph_hot.jsonl``.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "uc_s256_fwph_hot"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--override", default="{}",
                    help="JSON of traffic parameters for every run")
    ap.add_argument("--out", default="chiprun_out/controls")
    args = ap.parse_args(argv)
    import harness
    from chip_controls import UC_CONTROL
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{CELL}.jsonl"), "a") as f:
        for kind, ss in (("sound", seeds(args.sound)),
                         ("control", seeds(args.control))):
            for seed in ss:
                t0 = time.perf_counter()
                line = harness.run_cell(
                    CELL, seed, args.seconds, bool(args.trace),
                    overrides=json.loads(args.override),
                    variant=UC_CONTROL if kind == "control" else None)
                gc.collect()    # the engine's device arrays, before the next
                row = {"kind": kind, "seed": seed,
                       "wall_s": time.perf_counter() - t0, **line}
                f.write(json.dumps(row) + "\n")
                f.flush()
                print(json.dumps(
                    {k: row[k] for k in ("kind", "seed", "correct",
                                         "metrics")}
                    | {"failed_checks": [c["name"] for c in row["checks"]
                                         if not c["ok"]]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
