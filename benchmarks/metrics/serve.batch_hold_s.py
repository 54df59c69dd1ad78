"""Median seconds the batcher held a group open for stragglers (``t_group
- t_first``; spans ``serve.batch.window``) before each of the window's
wheels. Reader: ``serve.wheel_engine_s``. Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "parts", "batch_hold_s")
