"""Median seconds a free worker waited for a first request (``t_first -
t_pop0``; spans ``serve.queue.idle``) before each of the window's
wheels. Reader: ``serve.wheel_engine_s``. Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "parts", "queue_idle_s")
