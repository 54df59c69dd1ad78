"""Share of the window's pass-1 chunk solves whose f32 bulk phase ran
its whole budget (``subproblem_max_iter``): 100 x ``bulk_capped`` /
``solves`` of ``phase_timing(True)["exits"]``; see
``solve.tail_capped_share``, whose reader this is. 0 where the bulk
converges to its clamped tolerances (the UC cells), 100 where the
budget ends every solve (the sslp cell). Moves ``ph_iter_s``."""

import harness

_tail = harness.load_module("metrics", "solve.tail_capped_share")


def read(obs):
    return _tail.read(obs, phase="bulk")
