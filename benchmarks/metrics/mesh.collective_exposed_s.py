"""Seconds of the traced slice in which a collective op ran on a chip
with no compute op beside it (``trace_reduce``'s
``collective_exposed_s``: the union of the collectives' intervals minus
the union of the other leaf ops', per chip, averaged over the chips):
the part of ``mesh.collective_s`` the chip waits for. ``None`` with no
device trace (off the chip) and on a one-chip cell.

The slice holds ``ph.assemble`` and the first ~0.15 s of the first
chunk solve: the in-solve all-reduces and any collective of the
staging; the consensus psum at the iteration's end is outside it (see
``mesh.collective_s``). Moves ``ph_iter_s``."""


def read(obs):
    tr = obs.get("trace")
    if not tr or tr.get("n_device_planes", 1) < 2:
        return None
    return tr.get("collective_exposed_s")
