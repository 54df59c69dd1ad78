"""Measured roofline: XLA cost-model capture and the compile ledger.

Every jitted entry point the engine runs (``qp_solve`` variants, the
fused ADMM block, the Pallas plan, shrink/dispatch ops) routes through
:func:`call` when telemetry is enabled. On the FIRST sighting of a new
argument-shape bucket the lowered computation's XLA cost model is
captured — ``Lowered.cost_analysis()`` FLOPs and bytes-accessed, plus
argument bytes from the live operands — keyed by the same
``config_fingerprint`` the serve cache and shrink registry use for
their shape buckets. Per-call, the capture books cumulative
``profile.flops`` / ``profile.hbm_bytes`` counters whose
PER-ITERATION deltas ``core/ph.py`` records into each ``ph.iteration``
event; ``analyze`` joins those deltas against the span timeline to
report measured MFU and HBM-bandwidth utilization per phase, per
bucket, per engine mode (doc/roofline.md's measured column).

Capture cost discipline: ``fn.lower(...)`` is a trace+lower only — it
fires NO backend compile (verified: the ``jax.compiles`` monitoring
event stays silent), so a new bucket costs one extra trace
(milliseconds), never a compile. ``memory_analysis()`` needs the
compiled executable and the AOT path does NOT share the executable
cache with the normal call path, so it would pay one full extra
backend compile per bucket — it is therefore opt-in via
``MPISPPY_TPU_PROFILE_MEMORY=1``. Capture happens BEFORE the call:
donated operands' buffers are deleted afterwards.

The compile ledger: a thread-local entry context is pushed around
every instrumented call; ``resource._on_duration`` reports each
backend compile here, which books ``profile.ledger.compiles.<key>`` /
``profile.ledger.seconds.<key>`` to the entry|fingerprint in flight
(``(unattributed)`` otherwise — ph-level jits, warmup). Every compile
books exactly once, so the ledger column-sums to ``jax.compiles`` by
construction.

Failures never propagate: any cost-model/capture error books a
``profile.unavailable`` counter with a reasoned event (once per
entry/reason) and the call proceeds uninstrumented.

jax is imported lazily inside capture paths only — importing this
module stays jax-free (the hub status plane and bench signal handler
read :func:`last_iteration` / :func:`peaks` as plain dict lookups).
"""

from __future__ import annotations

import os
import threading
import time

from . import active as _active
from . import counter_add, event, gauge_set

UNATTRIBUTED = "(unattributed)"

# Peak device throughput by the EXACT ``device_kind`` string jax
# reports (lower-cased): (peak FLOP/s, peak HBM GB/s). One row per kind
# a machine has actually reported: the attached v5e says ``TPU v5
# lite`` (published bf16 peak and HBM bandwidth, Google Cloud TPU v5e
# documentation; bench.py's V5E_PEAK_BF16 is the same number). Any
# other accelerator is an ERROR, never a default — add its row when a
# machine reports its name, or set BOTH MPISPPY_TPU_PEAK_FLOPS and
# MPISPPY_TPU_PEAK_HBM_GBPS. The CPU row is a documented NOMINAL
# placeholder so CPU-tier MFU is finite (doc/roofline.md: CPU-tier
# rows are not absolute utilization).
_PEAKS_BY_KIND = {
    "tpu v5 lite": (197e12, 819.0),
    "cpu": (1e11, 50.0),
}


def peaks_for_kind(kind: str, platform: str = ""):
    """(peak_flops, peak_hbm_gbps) of one device, by its exact
    ``device_kind`` (case-insensitive). ``platform == "cpu"`` resolves
    to the CPU-tier nominal row whatever the host CPU calls itself;
    anything else outside the table raises."""
    if platform == "cpu":
        return _PEAKS_BY_KIND["cpu"]
    row = _PEAKS_BY_KIND.get(str(kind).strip().lower())
    if row is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth row for device_kind "
            f"{kind!r} (platform {platform!r}) in obs/profile."
            f"_PEAKS_BY_KIND; known: {sorted(_PEAKS_BY_KIND)}. Add the "
            "published peaks there, or set BOTH MPISPPY_TPU_PEAK_FLOPS "
            "and MPISPPY_TPU_PEAK_HBM_GBPS")
    return row


class _State:
    """Per-telemetry-session capture state. Reset whenever the
    process-wide Recorder changes (tests reconfigure sessions
    freely)."""

    __slots__ = ("rec", "lock", "costs", "failed", "seconds",
                 "compile_seconds", "device_emitted", "peaks",
                 "last_iter")

    def __init__(self, rec):
        self.rec = rec
        self.lock = threading.Lock()
        # (entry, shape_key) -> _Cost | None (None = capture failed;
        # the call still runs, just uninstrumented)
        self.costs = {}
        self.failed = set()          # (entry, reason) emitted once
        self.seconds = {}            # ledger key -> cumulative call s
        self.compile_seconds = {}    # ledger key -> cumulative compile s
        self.device_emitted = False
        self.peaks = None            # (flops, gbps, source, kind)
        self.last_iter = {}          # plain dict: the signal-safe view


class _Cost:
    __slots__ = ("entry", "fingerprint", "key", "flops", "bytes",
                 "arg_bytes", "memory")

    def __init__(self, entry, fingerprint, key, flops, nbytes,
                 arg_bytes, memory):
        self.entry = entry
        self.fingerprint = fingerprint
        self.key = key               # ledger key: "entry|fp"
        self.flops = flops
        self.bytes = nbytes
        self.arg_bytes = arg_bytes
        self.memory = memory


_STATE: _State | None = None
_STATE_LOCK = threading.Lock()
_TLS = threading.local()


def _state() -> _State | None:
    """The capture state bound to the CURRENT telemetry session (None
    when telemetry is off). Identity-checked per call so a
    reconfigured session never inherits a prior session's buckets."""
    global _STATE
    rec = _active()
    if rec is None:
        return None
    s = _STATE
    if s is None or s.rec is not rec:
        with _STATE_LOCK:
            s = _STATE
            if s is None or s.rec is not rec:
                s = _STATE = _State(rec)
    return s


# ---------------- peaks ----------------

def _resolve_peaks(s: _State):
    """(peak_flops, peak_hbm_gbps, source, device_kind) — the env pair
    (both or neither) > the exact-``device_kind`` table; an accelerator
    outside the table with no override raises (peaks_for_kind). Emits
    the one-shot ``profile.device`` event so jax-free consumers
    (analyze) read the resolved peaks from the stream."""
    if s.peaks is not None:
        return s.peaks
    import jax

    dev = jax.devices()[0]
    kind, platform = str(dev.device_kind), str(dev.platform)
    env_f = os.environ.get("MPISPPY_TPU_PEAK_FLOPS")
    env_g = os.environ.get("MPISPPY_TPU_PEAK_HBM_GBPS")
    if bool(env_f) != bool(env_g):
        raise ValueError(
            "MPISPPY_TPU_PEAK_FLOPS and MPISPPY_TPU_PEAK_HBM_GBPS "
            "override the peaks table TOGETHER; only one of them is set")
    if env_f:
        flops, gbps, source = float(env_f), float(env_g), "env"
    else:
        flops, gbps = peaks_for_kind(kind, platform)
        source = "table"
    s.peaks = (flops, gbps, source, kind)
    if not s.device_emitted:
        s.device_emitted = True
        event("profile.device", {
            "device_kind": kind, "peak_flops": flops,
            "peak_hbm_gbps": gbps, "source": source,
            "cpu_tier": platform == "cpu"})
    return s.peaks


def peaks():
    """(peak_flops, peak_hbm_gbps, source, device_kind) for the active
    session, or None when telemetry is off."""
    s = _state()
    return _resolve_peaks(s) if s is not None else None


# ---------------- the shape bucket key ----------------

def _shape_key(args, kwargs):
    """Cheap hashable bucket key over the call operands: arrays key by
    (shape, dtype); ints/bools/strings key by VALUE (they are jit
    statics here — a different value is a different executable);
    floats key by presence only (traced weak-typed scalars like eps
    knobs vary per call without retracing — keying their value would
    mint a bucket per tolerance)."""
    import jax

    key = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            key.append((tuple(shape), str(getattr(leaf, "dtype", "?"))))
        elif isinstance(leaf, bool) or isinstance(leaf, int) \
                or isinstance(leaf, str) or leaf is None:
            key.append(leaf)
        elif isinstance(leaf, float):
            key.append("f")
        else:
            key.append(type(leaf).__name__)
    return tuple(key)


def _fingerprint(entry, key):
    """The shape bucket's fingerprint — THE SAME
    ``config_fingerprint`` the serve compile cache and the shrink
    bucket registry key by, so one id joins the three planes."""
    from ..ckpt.bundle import config_fingerprint
    return config_fingerprint({"entry": entry,
                               "key": [str(k) for k in key]})


# ---------------- capture ----------------

def _unavailable(s, entry, reason):
    counter_add("profile.unavailable")
    if (entry, reason) not in s.failed:
        s.failed.add((entry, reason))
        event("profile.unavailable", {"entry": entry,
                                       "reason": reason})


def _capture(s, entry, fn, key, args, kwargs) -> _Cost | None:
    """First sighting of (entry, shape bucket): lower and read the XLA
    cost model. Trace+lower only — no backend compile (unless the
    opt-in memory capture asks for the executable)."""
    _resolve_peaks(s)
    try:
        fp = _fingerprint(entry, key)
    except Exception:
        fp = "nofp"
    ledger_key = f"{entry}|{fp}"
    try:
        lower = getattr(fn, "lower", None)
        if lower is None:
            # a plain callable (e.g. the pallas_call wrapper): a
            # throwaway jit gives the lowering — traced, never
            # executed, so still no backend compile
            import jax
            lower = jax.jit(fn).lower
        lowered = lower(*args, **kwargs)
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            raise TypeError(f"cost_analysis returned {type(ca).__name__}")
        flops = float(ca.get("flops") or 0.0)
        nbytes = float(ca.get("bytes accessed") or 0.0)
    except Exception as e:
        _unavailable(s, entry, f"cost_analysis: {type(e).__name__}: {e}")
        return None
    arg_bytes = 0
    try:
        from .resource import tree_nbytes
        arg_bytes = tree_nbytes((args, kwargs))
    except Exception:
        pass
    if nbytes <= 0.0:
        # backends without a bytes-accessed model: fall back to the
        # operand footprint (one read of every argument) so HBM
        # attribution degrades to a floor instead of zero
        nbytes = float(arg_bytes)
    memory = None
    if os.environ.get("MPISPPY_TPU_PROFILE_MEMORY") == "1":
        # opt-in: pays one EXTRA backend compile per bucket (the AOT
        # executable cache is disjoint from the call path's); the
        # ledger context is already pushed, so that compile books to
        # this key and the ledger still sums to jax.compiles
        try:
            ma = lowered.compile().memory_analysis()
            memory = {
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(
                    getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(
                    getattr(ma, "temp_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
                "alias_bytes": int(
                    getattr(ma, "alias_size_in_bytes", 0)),
            }
        except Exception as e:
            _unavailable(s, entry,
                         f"memory_analysis: {type(e).__name__}: {e}")
    cost = _Cost(entry, fp, ledger_key, flops, nbytes, arg_bytes,
                 memory)
    counter_add("profile.captures")
    fields = {"entry": entry, "fingerprint": fp, "flops": flops,
              "bytes_accessed": nbytes, "arg_bytes": arg_bytes}
    if memory:
        fields["memory"] = memory
    event("profile.entry", fields)
    return cost


# ---------------- the ledger context ----------------

def _push(key):
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(key)


def _pop():
    stack = getattr(_TLS, "stack", None)
    if stack:
        stack.pop()


def current_key():
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


def note_compile(secs):
    """Called by ``resource._on_duration`` for EVERY backend compile
    while a session is active: attribute it to the instrumented entry
    in flight on this thread (or the unattributed bucket). One booking
    per compile — the ledger sums to ``jax.compiles`` exactly."""
    s = _state()
    if s is None:
        return
    key = current_key() or UNATTRIBUTED
    counter_add(f"profile.ledger.compiles.{key}")
    counter_add(f"profile.ledger.seconds.{key}", secs)
    with s.lock:
        tot = s.compile_seconds.get(key, 0.0) + secs
        s.compile_seconds[key] = tot
    if key != UNATTRIBUTED:
        fp = key.rsplit("|", 1)[-1]
        gauge_set(f"profile.bucket.compile_seconds.{fp}", tot)


# ---------------- the instrumented call ----------------

def call(entry, fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` with cost capture + ledger
    attribution. Call sites guard with ``obs.enabled()`` — the
    disabled path never reaches here (the zero-cost-when-off
    contract). Any capture failure degrades to the plain call."""
    s = _state()
    if s is None:
        return fn(*args, **kwargs)
    try:
        key = _shape_key(args, kwargs)
    except Exception as e:
        _unavailable(s, entry, f"shape_key: {type(e).__name__}: {e}")
        return fn(*args, **kwargs)
    ck = (entry, key)
    cost = s.costs.get(ck, False)
    if cost is False:
        # push BEFORE capture: the first real call's backend compile
        # (and the opt-in AOT memory compile) book to this key
        _push(f"{entry}|?")
        try:
            cost = _capture(s, entry, fn, key, args, kwargs)
        finally:
            _pop()
        with s.lock:
            s.costs[ck] = cost
    if cost is None:
        return fn(*args, **kwargs)
    _push(cost.key)
    t0 = time.perf_counter()
    try:
        return fn(*args, **kwargs)
    finally:
        dt = time.perf_counter() - t0
        _pop()
        counter_add("profile.flops", cost.flops)
        counter_add("profile.hbm_bytes", cost.bytes)
        with s.lock:
            tot = s.seconds.get(cost.key, 0.0) + dt
            s.seconds[cost.key] = tot
        # host-side elapsed around the dispatched call: on the async
        # path this undercounts the device tail (the iteration gate
        # absorbs it) — MFU math uses the span timeline, this gauge
        # is the /metrics-plane per-bucket attribution
        gauge_set(f"profile.bucket.device_seconds.{cost.fingerprint}",
                   tot)


# ---------------- the per-iteration plane ----------------

def note_iteration(it, seconds, flops_delta, hbm_delta):
    """Called by ``core/ph.py`` once per iteration with that
    iteration's counter deltas: computes the measured-roofline figures,
    sets the ``profile.iter.*`` gauges, and refreshes the plain-dict
    view :func:`last_iteration` (the hub live plane and bench's
    signal-handler gap rows read THAT — no locks). Returns the figures
    dict (JSON-ready) or None when nothing was instrumented."""
    s = _state()
    if s is None:
        return None
    if not flops_delta and not hbm_delta:
        return None
    peak_f, peak_g, _src, _kind = _resolve_peaks(s)
    secs = float(seconds) if seconds else 0.0
    mfu = hbm_gbps = hbm_util = None
    if secs > 0.0:
        mfu = float(flops_delta) / secs / peak_f
        hbm_gbps = float(hbm_delta) / secs / 1e9
        hbm_util = hbm_gbps / peak_g if peak_g else None
    fig = {"iter": int(it), "seconds": secs,
           "flops_per_iter": float(flops_delta),
           "hbm_bytes_per_iter": float(hbm_delta),
           "mfu": mfu, "hbm_gbps": hbm_gbps, "hbm_util": hbm_util}
    if mfu is not None:
        gauge_set("profile.iter.mfu", mfu)
        gauge_set("profile.iter.hbm_gbps", hbm_gbps)
        if hbm_util is not None:
            gauge_set("profile.iter.hbm_util", hbm_util)
    # rebind, don't mutate: signal-handler readers see either the old
    # complete dict or the new complete dict, never a half-update
    s.last_iter = fig
    return fig


def last_iteration():
    """The most recent iteration's roofline figures as a plain dict
    (None before the first instrumented iteration or when telemetry is
    off). Safe from signal handlers: one attribute read, no locks."""
    s = _STATE
    rec = _active()
    if s is None or rec is None or s.rec is not rec:
        return None
    return s.last_iter or None
