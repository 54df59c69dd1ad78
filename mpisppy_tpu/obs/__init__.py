"""Unified telemetry for mpisppy_tpu: metrics, events, traces.

One process-wide telemetry session replaces the historical scatter of
per-module sinks (spoke ``trace_prefix`` CSVs, hub ``bound_events``
screen rows, PH hospital prints, ``MPISPPY_TPU_SOLVE_TRACE`` stderr
stamps, bench one-off JSON) with three coherent artifacts:

 - ``events.jsonl`` — structured event stream (monotonic stamps, run
   id, config snapshot in the ``run_header`` line),
 - ``trace.json``  — Chrome trace-event spans of the PH pipeline
   phases (load into Perfetto / chrome://tracing),
 - ``metrics.json``— counters / gauges / histograms snapshot.

This module is the FACADE the rest of the codebase calls: module-level
functions that forward to the process-wide :class:`Recorder` when one
is configured and do (almost) nothing when not. The disabled path is a
single global read + ``is None`` test per call and allocates nothing,
so instrumentation can live permanently on the PH hot loop (the <2%
disabled-overhead budget in ISSUE 3's acceptance criteria). The one
exception is ``span(...)``: a span ALWAYS enters the profiler's
``TraceMe`` (obs/trace.py), so any ``jax.profiler`` capture shows the
program's phases with or without a session; with no capture running
that is a flag test, and the span object dies at its exit.

Usage::

    from mpisppy_tpu import obs
    obs.configure(out_dir="runs/t1")        # or None for in-memory
    obs.counter_add("ph.gate_syncs")
    obs.event("hub.bound", kind="outer", value=-1.5)
    with obs.span("ph.iteration", args={"iter": 3}):
        ...
    obs.shutdown()

Environment: ``MPISPPY_TPU_TELEMETRY_DIR`` — when set, the first call
to :func:`maybe_configure_from_env` (the CLI, serve, children) enables
telemetry into that directory without code changes.
"""

from __future__ import annotations

import atexit
import math
import os

from .metrics import Histogram, MetricsRegistry        # noqa: F401
from .events import EventStream, SCHEMA_VERSION        # noqa: F401
from .trace import Span, TraceBuffer                   # noqa: F401
from .recorder import Recorder                         # noqa: F401

_REC: Recorder | None = None


def configure(out_dir=None, run_id=None, config=None,
              role=None) -> Recorder:
    """Start (or replace) the process-wide telemetry session. The old
    session, if any, is closed first. ``out_dir=None`` records
    in-memory only (events tail + metrics; no files) — useful in tests
    and interactive sessions. ``role`` suffixes the artifact filenames
    (``events-<role>.jsonl`` …) so multi-process cylinder runs can
    share one directory (utils/multiproc.py sets it for spoke
    children)."""
    global _REC
    if _REC is not None:
        _REC.close()
    _REC = Recorder(out_dir=out_dir, run_id=run_id, config=config,
                    role=role)
    return _REC


def maybe_configure_from_env(role=None) -> Recorder | None:
    """Enable telemetry when MPISPPY_TPU_TELEMETRY_DIR is set (no-op
    when unset or when a session is already active)."""
    d = os.environ.get("MPISPPY_TPU_TELEMETRY_DIR")
    if d and _REC is None:
        return configure(out_dir=d, role=role)
    return _REC


def shutdown():
    """Close the process-wide session (flushes all artifacts)."""
    global _REC
    if _REC is not None:
        _REC.close()
        _REC = None


@atexit.register
def _atexit_close():
    # a crash-free exit persists trace.json/metrics.json even when the
    # driver never called shutdown(); events streamed incrementally
    shutdown()


def active() -> Recorder | None:
    return _REC


def enabled() -> bool:
    return _REC is not None


# ---- hot-path forwarding (each: one global read + None test) ----
def event(etype, fields=None, t=None):
    r = _REC
    if r is not None:
        r.event(etype, fields, t=t)


def counter_add(name, n=1):
    r = _REC
    if r is not None:
        r.metrics.counter_add(name, n)


def gauge_set(name, value):
    r = _REC
    if r is not None:
        r.metrics.gauge_set(name, value)


def histogram_observe(name, value):
    r = _REC
    if r is not None:
        r.metrics.histogram_observe(name, value)


def span(name, cat="host", args=None, lane=None):
    """THE span primitive (obs/trace.Span): always a ``TraceMe`` on the
    profiler's clock, and a record in the session's trace when one is
    configured. ``args`` reach the session only."""
    r = _REC
    return Span(r.trace if r is not None else None, name, cat, args, lane)


def counters_snapshot() -> dict:
    """Copy of the counter map ({} when telemetry is disabled). Taken
    under the registry lock — spoke cylinder threads may be
    inserting new keys concurrently."""
    r = _REC
    return r.metrics.counters_snapshot() if r is not None else {}


def counter_value(name) -> float:
    r = _REC
    return r.metrics.counter_get(name) if r is not None else 0


def histogram_snapshot(name) -> dict | None:
    """Snapshot of one histogram (None when disabled or never
    observed) — the hub's bound-flow status reads staleness tails
    through this."""
    r = _REC
    return r.metrics.histogram_get(name) if r is not None else None


def flush(nonblocking=False):
    """Persist artifacts. ``nonblocking=True`` is for signal handlers:
    skips any sink whose lock the interrupted frame holds."""
    r = _REC
    if r is not None:
        r.flush(nonblocking=nonblocking)


def finite_or_none(v):
    """THE sanitizer for bound/gap fields in telemetry events: None for
    absent or non-finite values (never-established bounds are ±inf,
    which strict-JSON consumers reject), a plain float otherwise."""
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None
    return v if math.isfinite(v) else None
