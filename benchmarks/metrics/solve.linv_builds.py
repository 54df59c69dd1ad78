"""Explicit inverses of the shared f32 factor (``qp_solver.LInv``) built
per solve call, a mean over ALL the window's hot solve calls:
``PHBase.phase_timing(True)["admm_iters_per_call"]["linv_builds"]`` /
chunk solves per iteration. Derived by the program on the host, beside
``refactors`` and under the same reset as the solve seconds: the eager
wrap of a run's cold state, plus the call's refactorizations (each
leaves the inverse to be built anew: at the handoff after a bulk phase
that moved rho, in the tail at once). 0 where the
explicit inverse is off; ``None`` off the TPU or where the program has
no such counter. Moves ``ph_iter_s``."""


def read(obs):
    admm = (obs.get("phase") or {}).get("admm_iters_per_call")
    if not admm or "linv_builds" not in admm \
            or obs.get("platform") != "tpu":
        return None
    return admm["linv_builds"] / obs["chunk_solves_per_iteration"]
