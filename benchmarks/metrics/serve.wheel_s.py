"""Median seconds of one wheel (the response stamp's ``wheel.seconds``,
one value per distinct wheel of the window). Moves ``req_per_s``."""

import statistics


def read(obs):
    w = obs.get("wheels")
    return statistics.median(x["seconds"] for x in w) if w else None
