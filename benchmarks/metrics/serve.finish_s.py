"""Median seconds from a wheel's end to its last member's result persisted
and status flipped (``t_done - t_wheel1``; span ``serve.finish``) over
the window's wheels. Reader: ``serve.wheel_engine_s``. Moves
``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "parts", "finish_s")
