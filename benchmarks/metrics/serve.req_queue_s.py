"""Median seconds a request of the window's wheels spent in the
admission queue (``queue_s`` of its ``timing.timeline``: ``t_pop -
t_submit``, admitted -> taken by a worker): the inside twin of
``serve.queue_wait_s``. Reader: ``serve.wheel_engine_s``. Moves
``req_per_s``."""

import harness

_rec = harness.load_module("metrics", "serve.wheel_engine_s")


def read(obs):
    found = _rec.window(obs)
    if found is None:
        return None
    timing, kept, snap = found
    seqs = {w["seq"] for w in kept}
    return timing.median(timing.timeline(r)["queue_s"]
                         for r in snap["requests"]
                         if r["wheel_seq"] in seqs)
