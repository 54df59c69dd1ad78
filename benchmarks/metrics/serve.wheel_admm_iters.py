"""Median over the window's wheels of the ADMM iterations the engine
booked during the wheel (``ph`` of the wheel record: every solve of
iter-0, the hot iterations and the results' evaluation). Reader:
``serve.wheel_engine_s``. Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "ph", "admm_iters")
