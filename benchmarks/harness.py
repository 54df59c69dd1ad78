"""The benchmark's harness: everything a cell's run shares.

One run = one process = one cell. ``run_cell`` finds the cell's files by
the names in ``BENCHMARK.json`` (configuration, traffic parameters,
driver, per-layer readers), checks the device, hands the driver a
:class:`Run`, and builds the contract line from what the driver
returns. Nothing here knows a cell, a configuration or a metric by
name: a later PR adds files and entries, never edits this one.

The driver calls back into :class:`Run` for the clocks that define the
end-to-end metrics (``open_window`` ends set-up), for the compile
counter (a compile or a cache load inside the window makes the run
incorrect), for the profiler (``--trace 1``) and to record each number
it compares with its limit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# drivers and readers import the yardstick's modules by name
for _p in (HERE, os.path.join(HERE, "reference")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots,
    so this is a load by path, not an import)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def metrics_of(bench, group, cell_name):
    """The cell's metrics of one group (``end_to_end`` / ``per_layer``):
    those with no ``workloads`` key, or that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks_for(device_kind):
    """The peaks table's row for exactly this ``device_kind``; a device
    that is not in the table is an error, not a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not "
                         f"in benchmarks/peaks.json ({sorted(table)})")
    return table[device_kind]


class CompileLog:
    """Every backend compile OR persistent-cache load of this process,
    with the host time it ended: jax fires one duration event per
    program it makes executable, whichever way."""

    def __init__(self):
        self.events = []            # (perf_counter at end, seconds)
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == _COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(secs)))

    def between(self, t0, t1):
        return [(t, s) for t, s in self.events if t0 <= t <= t1]


class Run:
    """What a driver gets: the cell's data, the clocks and the probes."""

    def __init__(self, *, cell, config, params, limits, seed, seconds,
                 trace, devices, t_process, on_chip=True, variant=None):
        self.cell = cell
        self.config = config
        self.params = params        # the traffic mix's parameters
        self.limits = limits        # limit of each compared number
        # True in every run of run.py: the driver then holds the
        # configuration to its stated shape
        self.on_chip = bool(on_chip)
        # what a TEST or a control run changes underneath the driver
        # (a toy instance, a recipe below its precision): given to
        # run_cell by its caller, never read from a data file
        self.variant = dict(variant or {})
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_process = t_process
        self.compiles = CompileLog()
        self.checks = []            # {"name", "value", "limit", "ok"}
        self.spans = {}             # name -> seconds (the harness's own)
        self.t_open = self.t_close = None
        self._trace_stopped = False
        self._trace_dir = None
        self._trace_lock = threading.Lock()
        self.trace_path = None

    # ---- clocks ----
    def span(self, name, t0):
        """Book ``now - t0`` seconds under ``name`` (a span recorded
        from the benchmark's own files, around a call into a layer)."""
        self.spans[name] = self.spans.get(name, 0.0) \
            + time.perf_counter() - t0

    def open_window(self):
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self):
        self.t_close = time.perf_counter()
        return self.t_close

    @property
    def setup_s(self):
        return self.t_open - self.t_process

    def window_compiles(self):
        return len(self.compiles.between(self.t_open, self.t_close))

    # ---- checks ----
    def check(self, name, value, limit, ok=None, how="<="):
        """Record one compared number beside its limit and print it.
        ``how``: "<=" (value must not exceed the limit), ">=" or "=="."""
        if ok is None:
            v = float("nan") if value is None else float(value)
            ok = {"<=": v <= limit, ">=": v >= limit,
                  "==": v == limit}[how]
        ok = bool(ok)
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "how": how, "ok": ok})
        print(f"check {name}: value={value!r} {how} limit={limit!r} "
              f"-> {'ok' if ok else 'FAILED'}", flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    # ---- profiler (--trace 1 only) ----
    def trace_start(self):
        if not self.trace or self._trace_dir is not None:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host TraceMe spans, no python
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def trace_stop(self):
        """Idempotent, and callable from a timer thread."""
        import jax
        with self._trace_lock:
            if self._trace_dir is None or self._trace_stopped:
                return
            jax.profiler.stop_trace()
            self._trace_stopped = True
        for dirpath, _dirs, files in os.walk(self._trace_dir):
            for fn in files:
                if fn.endswith(".xplane.pb"):
                    self.trace_path = os.path.join(dirpath, fn)

    def annotate(self, name):
        """A host span in the profiler's own trace (no-op cost when no
        trace is running)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def cleanup(self):
        if self._trace_dir is not None:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def device_stamp(devices):
    import jax
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use") or 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def find_devices(chips, require_chip):
    """The devices the cell runs on. With ``require_chip`` (every run
    of ``run.py``) anything but ``chips`` TPU devices ends the process
    with no result line: no number of this benchmark comes from a CPU."""
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: this cell needs {chips} TPU chip(s); jax found "
              f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind}). "
              "No result is reported without the chip.", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        raise SystemExit(f"benchmark: {chips} devices needed, "
                         f"{len(devs)} present")
    return devs[:chips]


def run_cell(workload, seed, seconds, trace, *, t_process=None,
             require_chip=True, overrides=None, limits=None,
             variant=None, chips=None):
    """Run one cell; returns the contract line as a dict.

    ``run.py`` passes none of the keyword arguments below. They exist
    for ``benchmarks/tests``: ``require_chip=False``, ``overrides``
    (traffic parameters at toy counts), ``limits`` (those of the toy
    size) and ``chips`` (the mesh path on virtual devices) for the CPU
    rehearsal; ``variant`` (see :class:`Run`) for the rehearsal's toy
    instance and for the control runs on the chip."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = load_benchmark()
    # the cell's own file says what it runs; BENCHMARK.json lists it
    # for the driver (the two are checked against each other in tests)
    try:
        cell = load_json("workloads", f"{workload}.json")
    except FileNotFoundError:
        raise SystemExit(f"benchmark: no benchmarks/workloads/"
                         f"{workload}.json") from None
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    params = dict(traffic["parameters"])
    params.update(overrides or {})
    devices = find_devices(int(chips or cell["chips"]), require_chip)

    # the program's one owner of the process-level jax settings: x64,
    # honest f32 matmuls and the persistent compile cache
    # (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache)
    import jax
    from mpisppy_tpu.utils.runtime import setup_jax_runtime
    setup_jax_runtime()
    if devices[0].platform == "tpu":
        # set-up is paid by every run of every later check: cache every
        # program, not only those that took over a second to compile.
        # Never on the CPU: a rehearsal that fills <checkout>/.jax_cache
        # with sub-second XLA:CPU programs makes the serve tests' child
        # servers hang on reloading them (seen in PR 25)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)

    run = Run(cell=cell, config=config, params=params,
              limits=dict(cell.get("limits") or {}, **(limits or {})),
              seed=seed,
              seconds=seconds, trace=trace, devices=devices,
              t_process=t_process, on_chip=require_chip, variant=variant)
    driver = load_module("drivers", traffic["driver"])
    try:
        out = driver.run(run)
        run.trace_stop()
        if run.t_open is None or run.t_close is None:
            raise RuntimeError("the driver never opened/closed its window")
        run.check("window_compiles", run.window_compiles(), 0, how="==")
        line = {"correct": run.correct,
                "attempted": int(out["attempted"]),
                "failed": int(out["failed"]),
                "metrics": {}, "device": device_stamp(devices),
                "checks": run.checks}
        group = "per_layer" if trace else "end_to_end"
        wanted = metrics_of(bench, group, workload)
        if trace:
            obs = dict(out.get("observations") or {})
            obs["setup_compile_s"] = sum(
                s for _t, s in run.compiles.between(run.t_process,
                                                    run.t_open))
            obs["device_kind"] = devices[0].device_kind
            obs["platform"] = devices[0].platform
            obs["memory_peak_bytes"] = line["device"]["memory_peak_bytes"]
            obs["trace"] = None
            if run.trace_path:
                import trace_reduce
                obs["trace"] = trace_reduce.reduce_file(run.trace_path)
            t = obs["trace"]
            if t:
                line["device"]["busy_s"] = t["busy_s"]
                line["device"]["window_s"] = t["window_s"]
                line["breakdown"] = {"device_ops": t["top_ops"][:10],
                                     "idle_gaps": t["idle_gaps"][:10]}
            for m in wanted:
                val = load_module("metrics", m["name"]).read(obs)
                if val is not None:
                    line["metrics"][m["name"]] = {"value": float(val),
                                                  "unit": m["unit"]}
        else:
            e2e = dict(out["end_to_end"], setup_s=run.setup_s)
            for m in wanted:
                line["metrics"][m["name"]] = {
                    "value": float(e2e[m["name"]]), "unit": m["unit"]}
        return line
    finally:
        run.cleanup()
