"""Host seconds a dispatch-masked pass spends moving warm states
between the full-width row store and its chunks, a mean over the
window's passes: the gathers on the way in (span ``ph.dispatch.gather``,
nine a chunk) plus the scatter-back (span ``ph.dispatch.scatter``: the
concatenations, nine store scatters, the engine's x / yA / yB and the
three objective vectors), from ``PHBase.phase_timing()["dispatch"]``
(no telemetry session needed, reset with the phases' seconds). The
gather's seconds are also inside ``ph.assemble_s``, the scatter's
inside ``reduce.host_s``. ``None`` where the program books none or the
window made no such pass. Moves ``ph_iter_s``."""


def read(obs):
    d = (obs.get("phase") or {}).get("dispatch")
    if not d or not d.get("passes"):
        return None
    return (d["gather_seconds"] + d["scatter_seconds"]) / d["passes"]
