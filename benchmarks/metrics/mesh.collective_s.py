"""Seconds of the traced slice in which a collective op (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) ran on a
chip: the union of their intervals per chip, averaged over the chips
(``trace_reduce``'s ``collective_s``). A mesh cell's number: ``None``
with no device trace (off the chip) and on a one-chip cell.

What the slice holds: ``ph.assemble`` and the first ~0.15 s of the
iteration's first chunk solve. So the collectives it sees are the
in-solve all-reduces of the fused program (termination tests, the
chunk-pooled rho adaptation) and any the staging needs. The consensus
psum lies at the iteration's end, outside the slice: ``reduce.host_s``
reads its host seconds and ``reduce.collective_bytes`` its payload.
Moves ``ph_iter_s``."""


def read(obs):
    tr = obs.get("trace")
    if not tr or tr.get("n_device_planes", 1) < 2:
        return None
    return tr.get("collective_s")
