"""Read, on the chip, ONE control run of the per-scenario stack cell
(PERF.md section 2), in ``wheel_controls.py``'s manner: the cell through
``harness.run_cell`` - the entry ``run.py`` uses - at its own size, with
``run.variant`` changed underneath the driver and nothing else.

    chiprun -- python benchmarks/tests/stack_controls.py \
        --control float32 --seed 5 --seconds 51 --out chiprun_out/ctl

Controls (each must come out not ``correct``, by a named limit):
  float32         the engine's dtype float32: matrices, factors,
                  iterates and the outer PH arithmetic one precision
                  below the configuration's "float64 end to end"
  mixed_no_tail   ``subproblem_precision`` mixed with
                  ``subproblem_tail_iter`` 0: every solve the f32 bulk
                  alone under float64 outer arithmetic (the UC cells'
                  control, on this cell's recipe)
One process per control: a stack fills most of the chip's memory. One
JSON line lands in ``<out>/farmer_cm32_s1024_hub_hot.jsonl``.
``--control sound`` is the cell itself.
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

CELL = "farmer_cm32_s1024_hub_hot"
CONTROLS = {"float32": {"outer_dtype": "float32"},
            "mixed_no_tail": {"recipe": {"subproblem_precision": "mixed",
                                         "subproblem_tail_iter": 0}},
            "sound": {}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--override", default="{}",
                    help="JSON of traffic parameters")
    ap.add_argument("--out", default="chiprun_out/controls")
    args = ap.parse_args(argv)
    import harness
    t0 = time.perf_counter()
    line = harness.run_cell(CELL, args.seed, args.seconds, False,
                            overrides=json.loads(args.override),
                            variant=CONTROLS[args.control] or None)
    row = {"kind": args.control, "seed": args.seed,
           "wall_s": time.perf_counter() - t0, **line}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{CELL}.jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps({k: row[k] for k in ("kind", "seed", "correct")}
                     | {"failed_checks": [c for c in row["checks"]
                                          if not c["ok"]]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
