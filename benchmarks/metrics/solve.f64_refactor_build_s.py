"""Seconds of ONE eager build of a per-scenario float64 KKT inverse
stack (a mode's cold state: ``qp_solver.qp_cold_state``): the program's
span ``qp.f64_refactor_build``, which waits for the inverse, its seconds
added up by the timed mode's kernel plan and handed on as
``PHBase.phase_timing(True)["f64_refactor_build"]`` = {builds, seconds,
rows, n}; this is seconds / builds. The plan outlives
``reset_phase_timing``, so the build made in set-up is still told after
the window. The rebuilds INSIDE a solve program (rho moved) are
``solve.refactors``. ``None`` off the TPU, where the factor is no such
stack, or on a program with no such span. Moves ``setup_s``."""


def entry(obs):
    """The timed mode's build record where it holds a build, else None
    (shared with ``solve.f64_refactor_roofline``)."""
    rec = (obs.get("phase") or {}).get("f64_refactor_build")
    if not rec or not rec.get("builds") or obs.get("platform") != "tpu":
        return None
    return rec


def read(obs):
    rec = entry(obs)
    return None if rec is None else rec["seconds"] / rec["builds"]
