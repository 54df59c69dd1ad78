"""``wheel.outer_period_s``: median seconds between two outer bounds
the hub accepted inside the window (the stamps of
``Hub.wheel_timing()["spokes"]`` for the outer-bound spoke). ``None``
with fewer than two, or where the program keeps no stamps. Moves
``solves_per_s``."""

import statistics


def stamps(obs, char):
    """``wheel_timing()``'s entry of the spoke whose bounds carry
    ``char`` (``L`` the Lagrangian, ``D`` the pool), or ``None``."""
    for sp in ((obs.get("wheel") or {}).get("spokes") or {}).values():
        if sp.get("char") == char:
            return sp
    return None


def read(obs):
    sp = stamps(obs, "L")
    at = (sp or {}).get("accepted_at") or []
    if len(at) < 2:
        return None
    return statistics.median(b - a for a, b in zip(at, at[1:]))
