"""Of the rows still over tolerance at tail-capped exits, the share
held there by the DUAL test alone: 100 x ``rows_over_dua_only`` /
``rows_over`` of ``phase_timing(True)["exits"]`` (the hot recipe tests
the primal residual against 1e-4 and the dual against 1e-2; the rest
fail the primal test, alone or with the dual). Near 100 a looser dual
test would end those solves; near 0 it would change nothing. From the
chip only; ``None`` without the entry, 0 in a window with no row over.
Moves ``ph_iter_s``."""

import harness

_tail = harness.load_module("metrics", "solve.tail_capped_share")


def read(obs):
    ex = _tail.entry(obs)
    if not ex:
        return None
    return 100.0 * ex["rows_over_dua_only"] / ex["rows_over"] \
        if ex["rows_over"] else 0.0
