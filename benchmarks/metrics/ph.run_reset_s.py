"""Seconds of one ``PHBase.reset_run()`` (the program's span
``ph.run.reset``, under ``ph.run``): what re-arming a warm engine for
the next run from a cold W costs, a mean over the window's runs
(``PHBase.phase_timing()["runs"]``: reset_seconds / count). ``None``
where the program books no runs. Moves ``solves_per_s``."""

import harness


def read(obs):
    return harness.load_module("metrics", "ph.run_s").read(
        obs, "reset_seconds")
