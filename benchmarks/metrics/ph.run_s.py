"""Seconds of one whole PH run (the program's span ``ph.run``: reset,
iter-0, the hot iterations), a mean over the window's runs
(``PHBase.phase_timing()["runs"]``: seconds / count, booked with no
telemetry session and reset with the phases' seconds). ``None`` where
the program books no runs (a program from before the span). Moves
``solves_per_s``."""


def read(obs, key="seconds"):
    runs = (obs.get("phase") or {}).get("runs")
    if not runs or not runs.get("count"):
        return None
    return runs[key] / runs["count"]
