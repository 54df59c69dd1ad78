"""Solve-phase host seconds of a ``solve_loop`` call that held at least
one tail-capped chunk solve, a mean over exactly those calls of the
window: ``capped_solve_seconds`` / ``capped_calls`` of
``phase_timing(True)["exits"]`` (the ``ph.solve`` span's seconds, the
ones ``solve.chunk_s`` divides over ALL calls). Seconds per CALL, not
per chunk solve: beside ``solve.chunk_s`` x chunk solves per iteration
it says what a capped call costs over the mean one. Never from a
rehearsal; ``None`` without the entry, 0 in a window with no capped
call. Moves ``ph_iter_s``."""

import harness

_tail = harness.load_module("metrics", "solve.tail_capped_share")


def read(obs):
    ex = _tail.entry(obs)
    if not ex:
        return None
    return ex["capped_solve_seconds"] / ex["capped_calls"] \
        if ex["capped_calls"] else 0.0
