"""SDM passes an outer iteration of the FWPH engine, a mean over the
window: ``FWPH.phase_timing()["fwph"]`` ``passes`` / ``iterations``
(booked with no telemetry session, reset with the phases' seconds).
``FW_iter_limit`` where no Γ test ended a loop early. ``None`` where
the program books none. Moves ``ph_iter_s``."""


def entry(obs):
    fw = (obs.get("phase") or {}).get("fwph")
    return fw if fw and fw.get("passes") and fw.get("iterations") else None


def read(obs):
    fw = entry(obs)
    return fw and fw["passes"] / fw["iterations"]
