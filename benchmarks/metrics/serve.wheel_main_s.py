"""Median seconds of span ``serve.wheel.main`` (``hub.main()``: iter-0 and
the hot PH iterations; ``serve.wheel_solve_s`` is the part of it, and
of ``.results``, that waits on the solve programs) over the window's
wheels, from the serving layer's own record (``serve.wheel_engine_s``
has the reader). Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "steps", "main")
