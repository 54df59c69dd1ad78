"""One run of a wheel cell with what PR 39's third round read beside it
(PERF.md section 6, section 7 row 2: the turn that runs long):

  <out>_turns.jsonl   every admitted turn of the wheel's arbiter: cylinder,
                      rows, seconds, and the device allocator's numbers at
                      its admission and its end (``memory_stats()``)
  <out>_iters.jsonl   every booked solve's ADMM counts (total, bulk,
                      refactorizations), by thread, when booked
  <out>_stacks.txt    every thread's stack when a warm turn has lasted
                      longer than its cylinder's limit (LIMIT)

    chiprun -- env TPU_LOG_DIR=/root/repo/chiprun_out/diag/tpulog \\
        TPU_VMODULE=tpu_pjrt_client=1 python wheel_diag.py \\
        chiprun_out/diag/w --workload uc_s256_wheel --seed 7 \\
        --seconds 250 --trace 0

With that vlog libtpu writes one ``ExecutablesStart`` / ``Complete``
line per launch (a long turn's time on the device's side of the queue)
and, at INFO anyway, every defragmentation. The arguments after the
first are ``benchmarks/run.py``'s. It patches the program from outside
(``WheelArbiter._turn``, ``core.ph._book_admm_iters``) and is no part of
the benchmark: a run under it is a diagnosis, not a measurement."""

import contextlib
import faulthandler
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# seconds after which a warm turn of a cylinder counts as long (UC cell:
# hub 0.2 - 0.6, Lagrangian 0.6 - 1.03, pool 1.41)
LIMIT = {"hub": 0.9, "spoke0": 1.5, "spoke1": 1.9}
KEYS = ("bytes_in_use", "largest_free_block_bytes", "peak_bytes_in_use",
        "num_allocs")


def main(argv):
    out_base, argv = argv[0], argv[1:]
    for p in (os.path.join(ROOT, "benchmarks"), ROOT):
        sys.path.insert(0, p)
    import jax
    from mpisppy_tpu.core import ph
    from mpisppy_tpu.utils import runtime

    os.makedirs(os.path.dirname(out_base) or ".", exist_ok=True)
    turns = open(f"{out_base}_turns.jsonl", "w")
    iters = open(f"{out_base}_iters.jsonl", "w")
    stacks = open(f"{out_base}_stacks.txt", "w")
    t_zero = time.perf_counter()
    current = [None]            # (cylinder, admitted at, watched)

    def stats():
        ms = jax.local_devices()[0].memory_stats() or {}
        return {k: ms[k] for k in KEYS if k in ms}

    def watchdog():
        dumped = None
        while True:
            time.sleep(0.05)
            c = current[0]
            if c is None or c is dumped or not c[2]:
                continue
            lasted = time.perf_counter() - c[1]
            if lasted > LIMIT.get(c[0], 2.0):
                dumped = c
                stacks.write(f"\n==== {c[0]} turn admitted at "
                             f"{c[1] - t_zero:.3f} has lasted {lasted:.3f} "
                             f"s; {stats()}\n")
                stacks.flush()
                faulthandler.dump_traceback(stacks, all_threads=True)
                stacks.flush()

    real_turn = runtime.WheelArbiter._turn

    @contextlib.contextmanager
    def turn(self, i, rows):
        with real_turn(self, i, rows):
            t0 = time.perf_counter()
            adm = stats()
            # warm turns only: a cold first solve compiles for minutes
            current[0] = (self.names[i], t0, t0 - t_zero > 200 and rows > 0)
            try:
                yield
            finally:
                current[0] = None
                turns.write(json.dumps({
                    "cyl": self.names[i], "rows": rows,
                    "t": round(t0 - t_zero, 4),
                    "s": round(time.perf_counter() - t0, 4),
                    "adm": adm, "done": stats()}) + "\n")
                turns.flush()

    real_book = ph._book_admm_iters

    def book(admm, states, fused, *a, **k):
        r = real_book(admm, states, fused, *a, **k)
        got = jax.device_get([(st.iters, st.iters_lo, st.refactors)
                              for st in states])
        iters.write(json.dumps({
            "thread": threading.current_thread().name,
            "t": round(time.perf_counter() - t_zero, 4),
            "solves": [[int(v) for v in g] for g in got]}) + "\n")
        iters.flush()
        return r

    runtime.WheelArbiter._turn = turn
    ph._book_admm_iters = book
    threading.Thread(target=watchdog, daemon=True).start()
    import run as bench_run
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
