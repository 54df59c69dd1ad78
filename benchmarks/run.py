"""One cell of the benchmark, in one process, on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic parameters, driver and per-layer readers are files under
``benchmarks/`` (see ``benchmarks/README.md``). Earlier lines of
standard output are free; the LAST line is the contract's JSON object.
Without the TPU chips the cell asks for the process ends with a
non-zero code and prints no result: there is no CPU fallback.
"""

import time

_T_PROCESS = time.perf_counter()    # set-up is counted from here

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "mpisppy_tpu")):
        print("benchmark: the system under test (mpisppy_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 3
    import harness
    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_process=_T_PROCESS)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
