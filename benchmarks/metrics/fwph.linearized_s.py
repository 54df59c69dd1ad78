"""Host seconds of one SDM pass's linearized solve (span
``fwph.linearized`` around the prox-off ``solve_loop``: staging, the
chunk solves, the gate read, the objectives), a mean over the window's
passes, from ``FWPH.phase_timing()["fwph"]``. Moves ``ph_iter_s``."""

import harness

_passes = harness.load_module("metrics", "fwph.passes_per_iter")


def read(obs, key="linearized_seconds"):
    fw = _passes.entry(obs)
    return fw and fw[key] / fw["passes"]
