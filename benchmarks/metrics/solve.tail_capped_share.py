"""Share of the window's pass-1 chunk solves whose accurate tail ran
its WHOLE budget: 100 x ``tail_capped`` / ``solves`` of
``PHBase.phase_timing(True)["exits"]`` (PR 37; booked beside the solve
seconds and the ADMM counts, under the same reset, with no session).
The recipe checks convergence every 25 iterations, so a UC tail is 25,
50, 75 or the cap of 100 and ``solve.tail_iters`` is a mean over that
distribution: this is the share of solves at its far end (33.6 =
25 + share x 75 where no solve ends at 50 or 75). Counted by the
program; like every ``solve.*`` reader it reports from the chip only
(``benchmarks/tests`` holds a rehearsal to no ``solve.*`` metric).
``None`` on a program without the entry. Moves ``ph_iter_s``."""


def entry(obs):
    """The ``exits`` entry the six readers read, or None: a program
    without it (the parent), a window with no solve, a rehearsal."""
    ex = (obs.get("phase") or {}).get("exits")
    if not ex or not ex.get("solves") or obs.get("platform") != "tpu":
        return None
    return ex


def read(obs, phase="tail"):
    ex = entry(obs)
    return ex and 100.0 * ex[f"{phase}_capped"] / ex["solves"]
