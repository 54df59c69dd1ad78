"""Rebuilds of the factor per hot solve call, a mean over ALL the
window's solve calls: ``PHBase.phase_timing(True)``
``admm_iters_per_call["refactors"]`` / chunk solves per iteration
(``QPState.refactors``: how often the rho adaptation moved a stepsize by
more than 5x and the solve program rebuilt its factor, counted where it
happens, for the same solves and under the same reset as the seconds
``solve.chunk_s`` reads). On a per-scenario float64 stack each rebuild
is a whole (S, n, n) inverse, a thousand ADMM iterations' worth of
device time at n = 384; 0 once rho has settled. ``None`` off the TPU or
on a program without the counter. Moves ``ph_iter_s``."""


def read(obs):
    admm = (obs.get("phase") or {}).get("admm_iters_per_call")
    if not admm or "refactors" not in admm \
            or obs.get("platform") != "tpu":
        return None
    return admm["refactors"] / obs["chunk_solves_per_iteration"]
