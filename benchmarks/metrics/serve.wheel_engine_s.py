"""Median seconds of a wheel's first step, span ``serve.wheel.engine``
(the warm engine's check-out and ``install_batch``, or build + admit),
over the window's wheels. Moves ``req_per_s``.

This file holds the ONE reader of the serving layer's own record
(``mpisppy_tpu.serve.timing``: always on, no session); the other
``serve.*`` files of PR 50 are one line over it. The record is read
from ``serve.timing.latest().snapshot()``, the service the driver
started in this process, and what is derived from its marks is the
program's own derivation (``timing.cycle_parts``, ``timing.timeline``,
``timing.median``: what ``GET /status`` shows). The window's wheels are
the wheel records whose ``seconds`` is the ``seconds`` of one of
``obs["wheels"]`` (the stamps the driver collected: the very float, so
``==`` finds it); warm-up's wheels and the reference's solo re-sends
fall out. ``None`` where the program has no such record (a parent of
PR 50) or fewer than half of the window's wheels are found in it."""


def window(obs):
    """``(timing, kept, snapshot)``: the program's module, the window's
    wheel records (each with its ``cycle_parts`` under ``"parts"``,
    paired over the WHOLE record: a wheel's next one may lie outside
    the window) and the snapshot; ``None`` if there is nothing to
    read."""
    want = {w["seconds"] for w in obs.get("wheels") or ()}
    try:
        from mpisppy_tpu.serve import timing
    except ImportError:
        return None
    rec = timing.latest()
    if rec is None or not want:
        return None
    snap = rec.snapshot()
    kept = [dict(w, parts=p) for w, p
            in zip(snap["wheels"], timing.cycle_parts(snap["wheels"]))
            if w["seconds"] in want]
    if 2 * len(kept) < len(want):
        return None
    return timing, kept, snap


def median(obs, group, key):
    """Median of ``record[group][key]`` (``group``: ``"steps"``,
    ``"ph"`` or ``"parts"``) over the window's wheels; a record that
    has none is left out."""
    found = window(obs)
    if found is None:
        return None
    timing, kept, _ = found
    return timing.median((w[group] or {}).get(key) for w in kept)


def read(obs):
    return median(obs, "steps", "engine")
