"""Median seconds of span ``serve.wheel.hub_setup`` (the hub-only
cylinder over the leased engine: ``PHHub``, its windows, ``setup_hub``)
over the window's wheels, from the serving layer's own record
(``serve.wheel_engine_s`` has the reader). Moves ``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "steps", "hub_setup")
