"""Resource accounting: XLA compiles, device memory, transfer bytes.

The two costs that dominate a TPU stack are invisible in wall-clock
phase spans: an unexpected *retrace* of a hot-loop jitted entry (a
shape or static-arg drift recompiling a ~200-340 s reference-scale
program mid-run) and *device memory* creeping toward the OOM cliff.
This module makes both first-class metrics, plus explicit byte
counters for the host<->device transfers the chunked PH loop performs
at its `device_put` / stacked-residual sites.

Three surfaces:

 - **Compile hooks** (:func:`install`, process-global, installed once
   by the first :class:`~mpisppy_tpu.obs.recorder.Recorder`): a
   ``jax.monitoring`` duration listener counts backend compiles /
   traces / lowerings into counters + latency histograms and books
   each backend compile as a ``jax.compile`` trace span, and a DEBUG
   handler on the ``jax._src.dispatch`` logger attributes each compile
   to its *jitted entry by name* (``jax.compile.entry.<name>``
   counters + a ``jax.compile`` event) — an unexpected retrace in the
   PH hot loop shows up as a counter, not a mystery slowdown. Both
   forward to whatever recorder is active and no-op when none is.
 - **Memory watermarks** (:func:`sample_memory`): per-device
   ``device.memory_stats()`` gauges (bytes in use + peak) where the
   backend supports it; a guarded no-op on backends that don't (CPU
   returns None) — sampled once per PH iteration.
 - **Transfer byte helpers** (:func:`tree_nbytes`): the instrumented
   sites (core/ph.py gate reads,
   core/spbase.py batch shipping, ops/qp_solver.py host rho
   refactors) guard with ``obs.enabled()`` and add to
   ``xfer.h2d_bytes`` / ``xfer.d2h_bytes`` / ``xfer.device_put_bytes``
   so the disabled path never computes a byte count.
"""

from __future__ import annotations

import logging
import re
import time

_installed = False
# device keys observed without memory_stats support (the CPU backend
# returns None): probed once, then skipped forever — sample_memory sits
# on the per-iteration path and must not re-raise per device per iter
_mem_unsupported: set = set()


def _active():
    from . import active
    return active()


# ---- jax.monitoring duration events -> counters + histograms ----
# name -> (counter, histogram). backend_compile is the expensive one
# (the actual XLA compile); trace/lowering counts reveal *why* (a
# retrace re-traces AND re-lowers AND re-compiles; a python-level
# cache hit does none).
_DUR_EVENTS = {
    "/jax/core/compile/backend_compile_duration":
        ("jax.compiles", "jax.compile_seconds"),
    "/jax/core/compile/jaxpr_trace_duration":
        ("jax.traces", "jax.trace_seconds"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax.lowerings", "jax.lowering_seconds"),
}


def _on_duration(name, secs, **kw):
    r = _active()
    if r is None:
        return
    ent = _DUR_EVENTS.get(name)
    if ent is None:
        return
    counter, hist = ent
    r.metrics.counter_add(counter)
    r.metrics.histogram_observe(hist, secs)
    if counter == "jax.compiles":
        # book the compile as a span ending now: retraces render as
        # fat blocks interrupting the phase timeline in Perfetto
        now = time.perf_counter()
        r.trace.complete("jax.compile", now - secs, now, cat="resource")


class _CompileLogHandler(logging.Handler):
    """Per-jitted-entry compile attribution. ``jax.monitoring`` events
    carry no function name, but ``jax._src.dispatch`` logs every
    backend compile as ``Finished XLA compilation of jit(<name>) in
    <secs> sec`` at DEBUG — the one place the entry name and its
    compile wall-clock meet."""

    _RE = re.compile(
        r"Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec")

    def emit(self, record):
        r = _active()
        if r is None:
            return
        try:
            m = self._RE.match(record.getMessage())
        except Exception:
            return
        if not m:
            return
        entry = m.group(1)
        if entry.startswith("jit(") and entry.endswith(")"):
            entry = entry[4:-1]
        try:
            secs = float(m.group(2))
        except ValueError:
            return
        r.metrics.counter_add(f"jax.compile.entry.{entry}")
        r.event("jax.compile", {"entry": entry, "seconds": secs})


class _RootPassthrough(logging.Handler):
    """Re-deliver WARNING+ records to the root handlers. Lowering the
    ``jax._src.dispatch`` logger to DEBUG forces ``propagate=False``
    (absl and friends hang level-0 handlers on root, which would spam
    every compile line to stderr); this preserves the ONE flow the
    original configuration allowed — records at/above root's WARNING
    threshold — so jax warnings still reach the user."""

    def emit(self, record):
        if record.levelno >= logging.WARNING:
            logging.getLogger().handle(record)


def install():
    """Install the process-global compile hooks (idempotent). JAX's
    listener registry has no unregister, so hooks are installed once
    and forward to the *currently active* recorder — reconfiguring or
    disabling telemetry needs no teardown."""
    global _installed
    if _installed:
        return
    _installed = True
    try:
        from jax import monitoring
    except Exception:       # jax absent/ancient: resource hooks off
        return
    monitoring.register_event_duration_secs_listener(_on_duration)
    lg = logging.getLogger("jax._src.dispatch")
    lg.addHandler(_CompileLogHandler(level=logging.DEBUG))
    lg.addHandler(_RootPassthrough(level=logging.WARNING))
    lg.propagate = False
    # the compile lines are DEBUG; enable them for our handler without
    # touching jax_log_compiles (which would promote them to WARNING
    # on the user's screen)
    if lg.level == logging.NOTSET or lg.level > logging.DEBUG:
        lg.setLevel(logging.DEBUG)


# ---- device memory watermarks ----
def sample_memory():
    """Sample ``memory_stats()`` of every device into gauges
    (``mem.<dev>.bytes_in_use`` + ``.peak_bytes_in_use``). Returns the
    sampled {dev: stats} map ({} when unsupported/disabled)."""
    r = _active()
    if r is None:
        return {}
    import jax

    out = {}
    for d in jax.devices():
        key = f"{d.platform}{d.id}"
        if key in _mem_unsupported:
            continue
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            # CPU (and some backends) have no allocator stats — probe
            # once, then no-op forever on this device
            _mem_unsupported.add(key)
            continue
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        if in_use is not None:
            r.metrics.gauge_set(f"mem.{key}.bytes_in_use", in_use)
        if peak is not None:
            r.metrics.gauge_set(f"mem.{key}.peak_bytes_in_use", peak)
        out[key] = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}
    return out


# ---- transfer byte accounting ----
def tree_nbytes(tree) -> int:
    """Total array bytes across a pytree's leaves (0 for leaves with
    no ``nbytes``). Callers guard with ``obs.enabled()`` — the byte
    walk must never run on the disabled path."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb:
            total += int(nb)
    return total


def put_nbytes(tree, target_of) -> int:
    """Bytes a ``device_put`` will actually MOVE: leaves already
    committed to their target are free passthroughs and don't count —
    the chunked loop re-pins resident warm-start states every
    iteration, and counting those would overstate traffic by orders of
    magnitude. ``target_of(leaf)`` returns the leaf's destination (a
    Device or a Sharding). Callers guard with ``obs.enabled()``."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if not nb:
            continue
        target = target_of(leaf)
        try:
            if hasattr(target, "is_fully_replicated") \
                    or hasattr(target, "device_set"):   # a Sharding
                if leaf.sharding == target:
                    continue
            elif leaf.devices() == {target}:            # a Device
                continue
        except Exception:
            pass        # host arrays etc.: everything moves
        total += int(nb)
    return total
