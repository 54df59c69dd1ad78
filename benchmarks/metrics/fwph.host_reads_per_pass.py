"""Device values the FWPH engine read back to the host, per SDM pass:
(``host_reads`` - ``iterations``) / ``passes`` of
``FWPH.phase_timing()["fwph"]``: the conv read of each outer iteration
taken off, what is left is the passes' own (1: Γ, its test's scale, the
bound and the manifold's error come back as one row). The linearized
solve's gate read is the chunked loop's (``ph.gate_s``). Moves
``ph_iter_s``."""

import harness

_passes = harness.load_module("metrics", "fwph.passes_per_iter")


def read(obs):
    fw = _passes.entry(obs)
    return fw and (fw["host_reads"] - fw["iterations"]) / fw["passes"]
