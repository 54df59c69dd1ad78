"""``wheel.hub_queue_wait_s``: seconds a hub iteration of the window
spent waiting for its turn on the device queue (span ``wheel.queue_wait``
of the hub cylinder; ``Hub.wheel_timing()["cylinders"]["hub"]
["queue_wait_s"]`` / hub iterations). What the spokes' turns cost the
hub's pace. ``None`` where the program has no arbiter. Moves
``ph_iter_s``."""


def read(obs):
    cyl = ((obs.get("wheel") or {}).get("cylinders") or {}).get("hub")
    n = obs.get("hub_iterations")
    return cyl["queue_wait_s"] / n if cyl and n else None
