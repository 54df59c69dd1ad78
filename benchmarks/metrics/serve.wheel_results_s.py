"""Median seconds of span ``serve.wheel.results`` (the consensus fixed and
evaluated: one prox-off solve, the objectives demultiplexed per
request) over the window's wheels, from the serving layer's own record
(``serve.wheel_engine_s`` has the reader). Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "steps", "results")
