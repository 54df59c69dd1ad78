"""The serving layer's own account of a request's life and a worker's
cycle: always on, no telemetry session needed (the serve-level twin of
``PHBase.phase_timing()`` and ``Hub.wheel_timing()``).

One :class:`ServeTiming` per :class:`~.manager.ServeService`
(``service.timing``): lock-protected totals and two bounded rings of
plain dicts, every stamp a ``time.perf_counter()`` read.

A WHEEL record (everything that passes ``ServeService._run_wheel``:
solo, stacked, chain step) holds the marks of its worker's cycle ::

    t_pop0    the worker became free and went to the queue
    t_first   it holds a first request: the batch window opens
    t_group   the group is closed
    t_wheel0  the wheel starts (the stamp's ``seconds`` counts from here)
    t_wheel1  ... and ends: ``seconds == t_wheel1 - t_wheel0``
    t_done    the last member's result is persisted, its status flipped

with ``seq``, ``worker``, ``stack``, ``cache_hit``, ``seconds`` (the
very float the response stamp carries), ``steps`` (seconds of the spans
``serve.stack`` and ``serve.wheel.engine`` / ``.hub_setup`` / ``.main``
/ ``.finalize`` / ``.results``: the spans' own marks, one clock read)
and ``ph`` (what the engine booked during THIS wheel, by difference of
its ``phase_booked()`` over the wheel). A REQUEST record holds
``t_submit``, ``t_pop``, ``t_wheel0``, ``t_wheel1``, ``t_finish`` and
``wheel_seq``. A mark the path does not have (a recovered group never
waited in the queue; a request loaded from disk was submitted to
another process) is ``None``.

jax-free (PURE001): stdlib only.
"""

from __future__ import annotations

import statistics
import threading
import time
from collections import deque

RING = 4096            # records kept per ring
STATUS_WHEELS = 256    # wheels behind the medians of ``GET /status``
STEPS = ("stack", "engine", "hub_setup", "main", "finalize", "results")
PH_FIELDS = ("calls", "assemble", "solve", "gate", "reduce",
             "admm_iters", "refactors", "capped")

_LATEST = None


def latest():
    """The :class:`ServeTiming` of the service most recently started in
    this process (kept after ``stop()``; ``None`` before any start): for
    an embedder that does not hold the service."""
    return _LATEST


def new_request_marks(req_id):
    return {"id": req_id, "t_submit": None, "t_pop": None,
            "t_wheel0": None, "t_wheel1": None, "t_finish": None,
            "wheel_seq": None}


def _diff(a, b):
    return None if a is None or b is None else a - b


def timeline(marks):
    """Where one request waited: ``queue_s`` (admitted -> taken by a
    worker), ``hold_s`` (-> its wheel starts: the batch window and the
    group's preparation), ``wheel_s``, ``finish_s`` (wheel end -> its
    result persisted). They sum to ``t_finish - t_submit``."""
    return {"queue_s": _diff(marks["t_pop"], marks["t_submit"]),
            "hold_s": _diff(marks["t_wheel0"], marks["t_pop"]),
            "wheel_s": _diff(marks["t_wheel1"], marks["t_wheel0"]),
            "finish_s": _diff(marks["t_finish"], marks["t_wheel1"])}


def cycle_parts(wheels):
    """Per wheel record (in ``wheels``' order) the worker's seconds
    around it: ``finish_s`` (``t_done - t_wheel1``), ``queue_idle_s``
    (``t_first - t_pop0``), ``batch_hold_s`` (``t_group - t_first``),
    ``prepare_s`` (``t_wheel0 - t_group``) and ``between_s``
    (``t_wheel0`` of the SAME worker's next wheel in ``wheels`` minus
    this ``t_wheel1``; ``None`` for a worker's last)."""
    nxt, last = {}, {}
    for w in sorted(wheels, key=lambda w: w["seq"]):
        prev = last.get(w["worker"])
        if prev is not None:
            nxt[prev["seq"]] = w
        last[w["worker"]] = w
    return [{"finish_s": _diff(w["t_done"], w["t_wheel1"]),
             "queue_idle_s": _diff(w["t_first"], w["t_pop0"]),
             "batch_hold_s": _diff(w["t_group"], w["t_first"]),
             "prepare_s": _diff(w["t_wheel0"], w["t_group"]),
             "between_s": _diff(nxt[w["seq"]]["t_wheel0"], w["t_wheel1"])
             if w["seq"] in nxt else None}
            for w in wheels]


def median(values):
    """Median of the values that are not ``None`` (``None`` of none)."""
    vals = [v for v in values if v is not None]
    return statistics.median(vals) if vals else None


class ServeTiming:
    def __init__(self):
        self._lock = threading.Lock()
        self._wheels = deque(maxlen=RING)
        self._requests = deque(maxlen=RING)
        self._seq = 0
        self._totals = {"wheels": 0, "requests": 0,
                        "wheel_seconds": 0.0,
                        "steps": dict.fromkeys(STEPS, 0.0),
                        "ph": dict.fromkeys(PH_FIELDS, 0)}

    def start(self):
        global _LATEST
        _LATEST = self
        return self

    # ---- wheels ----
    def open_wheel(self, cycle=None):
        """A wheel record at ``t_wheel0``, with the queue's marks of the
        worker's cycle (``AdmissionQueue.pop_group(cycle=...)``)."""
        cycle = cycle or {}
        with self._lock:
            self._seq += 1
            seq = self._seq
        rec = {"seq": seq, "worker": threading.current_thread().name,
               "stack": None, "cache_hit": None, "seconds": None,
               "t_pop0": cycle.get("t_pop0"),
               "t_first": cycle.get("t_first"),
               "t_group": cycle.get("t_group"),
               "t_wheel0": None, "t_wheel1": None, "t_done": None,
               "steps": dict.fromkeys(STEPS), "ph": None}
        rec["t_wheel0"] = time.perf_counter()
        return rec

    def close_wheel(self, rec):
        """``t_done``: the wheel's members are settled; the record goes
        into the ring whole."""
        rec["t_done"] = time.perf_counter()
        with self._lock:
            self._wheels.append(rec)
            tot = self._totals
            tot["wheels"] += 1
            tot["wheel_seconds"] += rec["seconds"]
            for k, v in rec["steps"].items():
                tot["steps"][k] += v or 0.0
            for k, v in (rec["ph"] or {}).items():
                tot["ph"][k] += v

    # ---- requests ----
    def close_request(self, marks):
        """``t_finish``: the request's record goes into the ring.
        Returns its :func:`timeline`."""
        with self._lock:
            marks["t_finish"] = time.perf_counter()
            self._requests.append(marks)
            self._totals["requests"] += 1
            return timeline(marks)

    # ---- readers ----
    def snapshot(self, last=None):
        """``{"totals", "wheels", "requests"}`` as plain dicts (copies:
        oldest record first). ``last``: only the newest ``last`` wheels
        and their requests."""
        with self._lock:
            totals = {**self._totals,
                      "steps": dict(self._totals["steps"]),
                      "ph": dict(self._totals["ph"])}
            wheels = list(self._wheels)
            requests = [dict(r) for r in self._requests]
        if last is not None:
            wheels = wheels[-last:]
            seqs = {w["seq"] for w in wheels}
            requests = [r for r in requests if r["wheel_seq"] in seqs]
        # a wheel record is whole, and final, once it is in the ring
        return {"totals": totals,
                "wheels": [{**w, "steps": dict(w["steps"]),
                            "ph": dict(w["ph"]) if w["ph"] else None}
                           for w in wheels],
                "requests": requests}

    def summary(self, last=STATUS_WHEELS):
        """The ``timing`` block of ``GET /status``: the totals and the
        medians over the last ``last`` wheels (the steps, the seconds
        between two wheels and their four parts) and over those wheels'
        requests (``req_queue_s``)."""
        snap = self.snapshot(last)
        wheels = snap["wheels"]
        parts = cycle_parts(wheels)
        med = {"wheel_s": median(w["seconds"] for w in wheels),
               "ph_solve_s": median((w["ph"] or {}).get("solve")
                                     for w in wheels),
               "ph_admm_iters": median((w["ph"] or {}).get("admm_iters")
                                        for w in wheels),
               "req_queue_s": median(timeline(r)["queue_s"]
                                      for r in snap["requests"])}
        for k in STEPS:
            med[f"{k}_s"] = median(w["steps"][k] for w in wheels)
        for k in ("between_s", "finish_s", "queue_idle_s",
                  "batch_hold_s", "prepare_s"):
            med[k] = median(p[k] for p in parts)
        return {"totals": snap["totals"], "last_wheels": len(wheels),
                "median": med}
