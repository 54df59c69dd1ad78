"""Median seconds between two wheels of one worker: ``t_wheel0`` of the
worker's next wheel minus ``t_wheel1`` of this one, over the window's
wheels (consecutive ``seq`` of one worker; its parts are
``serve.finish_s`` of this wheel and ``serve.queue_idle_s`` +
``serve.batch_hold_s`` + ``serve.prepare_s`` of the next). Reader:
``serve.wheel_engine_s``. Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "parts", "between_s")
