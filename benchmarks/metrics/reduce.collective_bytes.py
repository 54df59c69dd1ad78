"""Bytes the consensus reduce moves between the chips per hot
``solve_loop`` call, as the program books them
(``PHBase.phase_timing(True)["collective"]["bytes"]``: the payload of
the cross-chip psums of x-bar, the squared mean and conv, by
``parallel/mesh.combine_collective_bytes``; counted with no telemetry
session and reset with the seconds). This is the psum the traced slice
does not reach (it ends inside the first chunk solve). ``None`` where
the program books none: a one-chip engine, or a program from before the
counter. Moves ``ph_iter_s``."""


def read(obs):
    coll = (obs.get("phase") or {}).get("collective")
    if not coll or not coll.get("combines"):
        return None
    return coll["bytes"]
