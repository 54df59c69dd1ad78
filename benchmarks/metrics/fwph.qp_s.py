"""Host seconds of one SDM pass's weight QP (span ``fwph.simplex_qp``:
the launch of ``simplex_qp_solve`` and the wait for its result), a mean
over the window's passes. Moves ``ph_iter_s``."""

import harness

_lin = harness.load_module("metrics", "fwph.linearized_s")


def read(obs):
    return _lin.read(obs, key="qp_seconds")
