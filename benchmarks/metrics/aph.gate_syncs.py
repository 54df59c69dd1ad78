"""Device-to-host reads of the APH loop per iteration, a mean over the
window's iterations (``APH.phase_timing()["aph"]``: gate_syncs /
iterations): the stacked gate's contract is exactly 1.0 (tau, phi,
theta, conv, the phi stats and the dispatch mask ride one row). A
count, so a rehearsal reports it too. ``None`` where the program books
none. Moves ``ph_iter_s``."""


def read(obs):
    aph = (obs.get("phase") or {}).get("aph")
    if not aph or not aph.get("iterations"):
        return None
    return aph["gate_syncs"] / aph["iterations"]
