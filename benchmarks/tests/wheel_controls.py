"""Read, on the chip, ONE control run of the wheel cell (PERF.md
section 2), in ``chip_controls.py``'s manner: the cell through
``harness.run_cell`` - the entry ``run.py`` uses - at its own size, with
``run.variant`` changed underneath the driver and nothing else.

    chiprun -- python benchmarks/tests/wheel_controls.py \
        --control below_df32 --seed 5 --seconds 51 --out chiprun_out/ctl

Controls (each must come out not ``correct``):
  below_df32            the recipe below its stated precision: the
                        split-f32 refinement tail off in all three
                        engines (``subproblem_tail_iter`` 0), the UC
                        cells' control
  uncertified_bound     the outer spoke publishes its primal objective
  unverified_incumbent  the x-hat spoke publishes the pool screen's
                        verdict with no verification solve
One process per control: three engines fill the chip's memory, and a
second wheel in the same process would be built beside the first's.
One JSON line lands in ``<out>/uc_s256_wheel.jsonl``. ``--control sound``
is the cell itself, as ``run.py --workload uc_s256_wheel`` runs it.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "uc_s256_wheel"
CONTROLS = {"below_df32": {"recipe": {"subproblem_tail_iter": 0}},
            "uncertified_bound": {"control": "uncertified_bound"},
            "unverified_incumbent": {"control": "unverified_incumbent"},
            "sound": {}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/controls")
    args = ap.parse_args(argv)
    import harness
    t0 = time.perf_counter()
    try:
        line = harness.run_cell(CELL, args.seed, args.seconds,
                                bool(args.trace),
                                variant=CONTROLS[args.control])
    except Exception as e:      # a control may crash: report
        line = {"error": repr(e)[:400]}
    row = {"kind": args.control, "seed": args.seed,
           "wall_s": time.perf_counter() - t0, **line}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{CELL}.jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row)[:1500], flush=True)
    return 0 if "error" not in line and not line["correct"] \
        or args.control == "sound" else 1


if __name__ == "__main__":
    sys.exit(main())
