"""``wheel.bound_lag_iters``: hub exchanges between the payload a bound
was made from and the exchange that consumed the bound, the median over
every fresh publish of both spokes inside the window
(``Hub.wheel_timing()["spokes"][*]["lag_iters"]``: the hub window's
write-id counts the exchanges, the spoke notes the id beside its
publish seq). ``None`` with no publish, or where the program keeps
none. Moves ``solves_per_s``."""

import statistics


def read(obs):
    lags = [v for sp in ((obs.get("wheel") or {}).get("spokes")
                         or {}).values()
            for v in (sp.get("lag_iters") or [])]
    return statistics.median(lags) if lags else None
