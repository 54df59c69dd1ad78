"""Share of the traced window in which no op ran on the chip
(1 - union of device op intervals / window, averaged over the chips)."""


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
