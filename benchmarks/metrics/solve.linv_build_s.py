"""Seconds of ONE eager build of the explicit inverse of the shared f32
factor (``qp_solver.make_l_inv``: the wrap of a mode's cold state at
the entry of its first fused solve): the program's span
``qp.l_inv_build``, which waits for the inverse, its seconds added up
by the timed mode's kernel plan and handed on as
``PHBase.phase_timing(True)["linv_build"]`` = {builds, seconds, n,
panels}; this is seconds / builds. The plan outlives
``reset_phase_timing``, so a build made in set-up (the UC cells: the
hot mode's cold state is wrapped in the warm-up) is still told after
the window. ``None`` off the TPU, where the plan built none (the
explicit inverse off: the rule's choice is ``solve.linv_applies``'s to
tell), or on a program with no such span. Moves ``setup_s``."""


def entry(obs):
    """The timed mode's build record where it holds a build, else None
    (shared with ``solve.linv_build_roofline``)."""
    rec = (obs.get("phase") or {}).get("linv_build")
    if not rec or not rec.get("builds") or obs.get("platform") != "tpu":
        return None
    return rec


def read(obs):
    rec = entry(obs)
    return None if rec is None else rec["seconds"] / rec["builds"]
