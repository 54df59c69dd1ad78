"""Share of all (scenario, tail-capped exit) incidences carried by the
eight scenarios over at the most capped exits: 100 x the exits of
``top``'s eight / ``rows_over`` of ``phase_timing(True)["exits"]``.
Near 100: a few hard scenarios hold the solves at the cap (a hospital
for one row, a cap by row); near 8 x 100 / S: anybody does. From the
chip only; ``None`` without the entry, 0 in a window with no row over.
Moves ``ph_iter_s``."""

import harness

_tail = harness.load_module("metrics", "solve.tail_capped_share")


def read(obs):
    ex = _tail.entry(obs)
    if not ex:
        return None
    return 100.0 * sum(n for _g, n in ex["top"]) / ex["rows_over"] \
        if ex["rows_over"] else 0.0
