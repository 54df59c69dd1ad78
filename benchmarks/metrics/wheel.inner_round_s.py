"""``wheel.inner_round_s``: median seconds of one candidate-pool round
of the x-hat spoke that ENDED inside the window (span
``incumbent.round``: the batched screen and, where a candidate won,
its verification solve; ``Spoke.wheel_totals()["rounds"]["round_s"]``).
``None`` where no round ended in the window, or where the program
books none. Moves ``solves_per_s``."""

import statistics

import harness

_outer = harness.load_module("metrics", "wheel.outer_period_s")


def read(obs):
    sp = _outer.stamps(obs, "D")
    secs = (((sp or {}).get("own") or {}).get("rounds") or {}).get("round_s")
    return statistics.median(secs) if secs else None
