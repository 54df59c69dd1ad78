"""Median seconds from the closed group to its wheel's start (``t_wheel0 -
t_group``; span ``serve.group.prepare`` with ``serve.stack`` inside it:
expiry check, base batch, group file, the members' records, stacking)
over the window's wheels. Reader: ``serve.wheel_engine_s``. Moves
``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    return _rec.median(obs, "parts", "prepare_s")
