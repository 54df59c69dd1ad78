"""Span recording + Chrome trace-event export.

A span is ONE primitive with two sinks:

 - the profiler's own clock, always: :class:`Span` enters a
   ``jax.profiler.TraceAnnotation`` (a ``TraceMe``) by NAME when it
   opens, so any ``jax.profiler`` capture — an operator's
   ``jax.profiler.trace``, the benchmark's ``--trace 1`` — holds the
   program's phases in the same xplane, on the same clock, as the
   device ops. No switch: with no capture running a ``TraceMe`` costs
   a flag test.
 - the session's :class:`TraceBuffer`, when a telemetry session is
   configured: the span's own ``perf_counter`` marks buffered as a
   Chrome trace-event "X" (complete) record and written as one
   ``trace.json`` loadable in Perfetto / chrome://tracing. Lanes map to
   Chrome ``tid`` so concurrent work renders as parallel tracks.

The marks are public (``t0``/``t1``/``seconds``): PH's phase accounting
adds ``span.seconds`` to the accumulators ``PHBase.phase_timing``
reads, so the seconds have one source and span totals equal
``phase_timing`` totals exactly. Span ``args`` go to the session's
sink only — the ``TraceMe`` carries the bare name, which is what
``benchmarks/trace_reduce`` matches. ``TraceBuffer.complete`` is the
sink's record method (also used for durations the runtime reports
after the fact, obs/resource.py's ``jax.compile``); it cannot annotate.
"""

from __future__ import annotations

import json
import os
import threading
import time

_TRACE_ME = None      # jax.profiler.TraceAnnotation, bound at the first span


class _NoTraceMe:
    """Stand-in where jax cannot be imported (the declared jax-free
    service plane): the span then records into the session only."""

    __slots__ = ()

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _trace_me(name):
    global _TRACE_ME
    if _TRACE_ME is None:
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ME = TraceAnnotation
        except ImportError:
            _TRACE_ME = _NoTraceMe
    return _TRACE_ME(name)


class Span:
    """Context-manager span: a ``TraceMe`` for its whole extent, and a
    complete event in ``buf`` (the session's TraceBuffer, or None) on
    exit. A span abandoned by an exception is closed by the
    ``TraceMe``'s destructor and never reaches the session."""

    __slots__ = ("_buf", "name", "cat", "args", "lane", "t0", "t1", "_ann")

    def __init__(self, buf, name, cat="host", args=None, lane=None):
        self._buf = buf
        self.name = name
        self.cat = cat
        self.args = args
        self.lane = lane
        self.t0 = self.t1 = None
        self._ann = _trace_me(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._buf is not None:
            self._buf.complete(self.name, self.t0, self.t1, cat=self.cat,
                               args=self.args, lane=self.lane)
        return False

    @property
    def seconds(self):
        return self.t1 - self.t0


class TraceBuffer:
    """In-memory Chrome trace-event buffer, flushed to one JSON file."""

    def __init__(self, path=None, run_id=None, role=None):
        self.path = path
        self.run_id = run_id
        self.role = role
        self._lock = threading.Lock()
        self._events = []
        self._pid = os.getpid()
        # (wall clock, perf_counter) pair read back-to-back: the only
        # sanctioned way to put this process's monotonic span stamps on
        # a cross-process timeline (obs/merge.py aligns role traces
        # from exactly this anchor)
        self.anchor = {"wall_time_unix": time.time(),
                       "perf_counter": time.perf_counter()}
        self._lanes = {}          # lane name -> tid + emitted metadata
        name = f"mpisppy_tpu:{run_id or self._pid}"
        if role:
            name += f":{role}"
        self._meta(self._pid, 0, "process_name", {"name": name})

    def _meta(self, pid, tid, name, args):
        self._events.append({"name": name, "ph": "M", "pid": pid,
                             "tid": tid, "args": args})

    def _tid(self, lane):
        """Map a logical lane (None = host thread, str = named track
        like ``dev0``) to a stable Chrome tid, emitting thread_name
        metadata on first use."""
        if lane is None:
            return threading.get_ident() % 2 ** 31
        tid = self._lanes.get(lane)
        if tid is None:
            tid = self._lanes[lane] = 1 + len(self._lanes)
            self._meta(self._pid, tid, "thread_name", {"name": str(lane)})
        return tid

    def complete(self, name, t0, t1, cat="host", args=None, lane=None):
        """Record a complete ("X") span from explicit perf_counter
        marks; timestamps convert to the microseconds Chrome expects."""
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": t0 * 1e6, "dur": max(t1 - t0, 0.0) * 1e6,
              "pid": self._pid}
        with self._lock:
            ev["tid"] = self._tid(lane)
            if args:
                ev["args"] = args
            self._events.append(ev)

    def span(self, name, cat="host", args=None, lane=None):
        return Span(self, name, cat, args, lane)

    def to_json(self, nonblocking=False):
        """Trace dict, or None when ``nonblocking`` and the lock is
        held (signal-handler context: the interrupted frame underneath
        may own it — blocking there would self-deadlock)."""
        if nonblocking:
            if not self._lock.acquire(blocking=False):
                return None
        else:
            self._lock.acquire()
        try:
            return {"traceEvents": list(self._events),
                    "displayTimeUnit": "ms",
                    "metadata": {"run_id": self.run_id,
                                 "role": self.role,
                                 "clock": "perf_counter_us",
                                 **self.anchor}}
        finally:
            self._lock.release()

    def flush(self, nonblocking=False):
        """Atomically (re)write the whole trace file. Nonblocking mode
        skips (returns) when the buffer lock is unavailable."""
        if self.path is None:
            return
        data = self.to_json(nonblocking=nonblocking)
        if data is None:
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, self.path)
