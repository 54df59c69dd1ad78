"""ADMM iterations of the f32 bulk phase per chunk solve, a mean over
ALL the window's pass-1 chunk solves: ``PHBase.phase_timing(True)``
``admm_iters_per_call["bulk"]`` / chunk solves per iteration. The
program counts them where they happen (``QPState.iters_lo``), for the
same solve passes, under the same reset, as the seconds ``solve.chunk_s``
reads: the work beside the time. Like every ``solve.*`` reader it
reports from the chip only (``benchmarks/tests`` holds a rehearsal to no
``solve.*`` metric). Moves ``ph_iter_s``."""


def read(obs, phase="bulk"):
    admm = (obs.get("phase") or {}).get("admm_iters_per_call")
    if not admm or obs.get("platform") != "tpu":
        return None         # a program without the counter: nothing to read
    return admm[phase] / obs["chunk_solves_per_iteration"]
