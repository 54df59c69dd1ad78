"""Host seconds from an APH iteration's start to its dispatch mask on
the host, a mean over the window's iterations: the projective step's
launches (span ``aph.project``) plus the ONE gate transfer (span
``aph.gate``, which waits for the step and the selection on the
device), from ``APH.phase_timing()["aph"]`` (booked with no telemetry
session, reset with the phases' seconds). ``None`` where the program
books none (a program from before the spans). Moves ``ph_iter_s``."""


def read(obs):
    aph = (obs.get("phase") or {}).get("aph")
    if not aph or not aph.get("iterations"):
        return None
    return (aph["project_seconds"] + aph["gate_seconds"]) \
        / aph["iterations"]
