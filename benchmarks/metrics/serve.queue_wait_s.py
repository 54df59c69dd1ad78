"""Median over the window's requests of (client latency - the seconds
of the wheel that answered it): what a request waited outside its
wheel - queue, batch window, result evaluation, HTTP. Moves
``latency_p95_s``."""

import statistics


def read(obs):
    r = obs.get("requests")
    if not r:
        return None
    return statistics.median(x["latency"] - x["wheel_seconds"] for x in r)
