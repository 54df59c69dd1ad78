"""ADMM iterations of the split-f32 refinement tail per chunk solve
(``admm_iters_per_call["tail"]`` / chunk solves per iteration): see
``solve.bulk_iters``, whose reader this is. The recipe caps it at
``subproblem_tail_iter``; PERF.md section 5 has every hot solve spend
the cap. Moves ``ph_iter_s``."""

import harness

_bulk = harness.load_module("metrics", "solve.bulk_iters")


def read(obs):
    return _bulk.read(obs, phase="tail")
