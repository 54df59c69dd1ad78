"""Host seconds of one SDM pass's column step (span ``fwph.column``:
the launch of the ONE program that makes Γ, the bound and the
manifold's error and writes the new column into its slot of the pool,
and the wait for it), a mean over the window's passes. Moves
``ph_iter_s``."""

import harness

_lin = harness.load_module("metrics", "fwph.linearized_s")


def read(obs):
    return _lin.read(obs, key="column_seconds")
