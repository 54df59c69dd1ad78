"""``wheel.exchange_s``: host seconds of one hub exchange of the window
(span ``hub.sync``: the two read-backs of W and the nonants from the
device, the puts into the spokes' windows, the bound ingest;
``Hub.wheel_timing()["sync"]``). ``None`` where the program books none.
Moves ``ph_iter_s``."""


def read(obs):
    sync = (obs.get("wheel") or {}).get("sync")
    return sync["seconds"] / sync["syncs"] if sync and sync["syncs"] \
        else None
