"""``wheel.spoke_turn_share``: % of the window's admitted chunk-solve
seconds that were the spokes' (``Hub.wheel_timing()["cylinders"]``:
``device_s`` of every cylinder but the hub / of all). Equal turns among
three busy cylinders read 67 where their solves cost alike. ``None``
where the program has no arbiter. Moves ``ph_iter_s``."""


def read(obs):
    cyl = (obs.get("wheel") or {}).get("cylinders")
    if not cyl:
        return None
    dev = {n: v["device_s"] for n, v in cyl.items()}
    total = sum(dev.values())
    return 100.0 * (total - dev.get("hub", 0.0)) / total if total else None
