"""Median over the window's wheels of the solve-phase seconds the engine
booked DURING the wheel (``ph`` of the wheel record: ``phase_booked()``
by difference over the wheel, all of its solve modes): the host's wait
on the solve programs, an upper bound of their device seconds. Reader:
``serve.wheel_engine_s``. Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "ph", "solve")
