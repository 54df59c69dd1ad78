"""Unified telemetry subsystem (mpisppy_tpu/obs — ISSUE 3): metrics
registry, JSONL event stream, Chrome-trace span export, and the PH /
cylinder wiring.

Coverage demanded by the issue's acceptance criteria:
 - a farmer PH run with --telemetry-dir produces events.jsonl +
   trace.json whose phase-span totals match PHBase.phase_timing,
 - the ``ph.gate_syncs`` counter evidences O(1) D2H syncs per PH
   iteration in pipelined chunked mode (read the counter, no
   monkeypatching of engine internals),
 - counters survive reset_phase_timing,
 - disabled mode allocates nothing on the hot-path calls,
 - the solve-trace env flag is re-read lazily and emits through the
   telemetry layer,
 - recovery/hospital notes are quiet on screen by default but always
   land in the event stream.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.cylinders.hub import Hub
from mpisppy_tpu.cylinders.spoke import OuterBoundSpoke
from mpisppy_tpu.cylinders.spcommunicator import Window
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc


# same shapes as tests/test_pipeline.py so the UC programs compile once
# per suite run
def _uc_batch(S, G=3, T=6, **kw):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T, **kw},
                       vector_patch=uc.scenario_vector_patch)


_OPTS = {"defaultPHrho": 50.0, "subproblem_max_iter": 1200,
         "subproblem_eps": 1e-6, "subproblem_chunk": 3}


@pytest.fixture
def telemetry(tmp_path):
    """A process-wide telemetry session into tmp_path, torn down after
    the test so the rest of the suite runs with telemetry disabled."""
    rec = obs.configure(out_dir=str(tmp_path))
    yield rec, tmp_path
    obs.shutdown()


class _DummyOpt:
    options = {}

    class batch:        # window sizing (Spoke.local_window_length)
        S, K = 1, 1


# ---------------- core registry / stream / trace ----------------

def test_histogram_buckets_and_quantiles():
    """The ISSUE-4 satellite: fixed-edge buckets report tails
    (p50/p95/p99), not just means — a 5% population of 1 s outliers
    must own the p99 while the mean sits near the bulk."""
    from mpisppy_tpu.obs.metrics import Histogram

    h = Histogram()
    for v in [0.001] * 50 + [0.01] * 45 + [1.0] * 5:
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 100 and s["min"] == 0.001 and s["max"] == 1.0
    assert s["p50"] is not None and s["p50"] < 0.004
    assert 0.005 < s["p95"] < 0.05
    assert s["p99"] > 0.5          # the outlier tail, invisible in mean
    assert s["mean"] < 0.06
    assert sum(s["buckets_upper_edge"].values()) == 100
    assert len(s["buckets_upper_edge"]) == 3  # three value classes
    # exact-edge values land in the bucket whose UPPER edge they equal
    assert s["buckets_upper_edge"]["1"] == 5
    # single observation: quantiles clamp to the observed value
    h1 = Histogram()
    h1.observe(0.42)
    s1 = h1.snapshot()
    assert s1["p50"] == s1["p99"] == 0.42


def test_metrics_registry_kinds():
    from mpisppy_tpu.obs.metrics import MetricsRegistry

    m = MetricsRegistry()
    m.counter_add("a.b")
    m.counter_add("a.b", 4)
    m.gauge_set("g", 2.5)
    for v in (1.0, 3.0, 2.0):
        m.histogram_observe("h", v)
    snap = m.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    assert (h["count"], h["min"], h["max"], h["sum"]) == (3, 1.0, 3.0, 6.0)


def test_event_stream_header_and_artifacts(telemetry):
    rec, path = telemetry
    obs.event("custom.thing", {"x": 1})
    obs.counter_add("c.n", 2)
    with obs.span("s.outer", cat="test"):
        pass
    obs.shutdown()
    lines = [json.loads(ln)
             for ln in open(path / "events.jsonl", encoding="utf-8")]
    assert lines[0]["type"] == "run_header"
    assert {"run_id", "wall_time_unix", "t", "clock"} <= set(lines[0])
    assert lines[-1]["type"] == "run_footer"
    assert lines[-1]["metrics"]["counters"]["c.n"] == 2
    assert any(e["type"] == "custom.thing" and e["x"] == 1 for e in lines)
    tr = json.load(open(path / "trace.json"))
    assert any(e.get("name") == "s.outer" and e.get("ph") == "X"
               for e in tr["traceEvents"])
    mx = json.load(open(path / "metrics.json"))
    assert mx["counters"]["c.n"] == 2


def test_disabled_mode_allocates_nothing():
    """With no session, every hot-path call is a global read + None
    test. A span still enters the profiler's TraceMe (a flag test when
    no capture runs) and times itself, but keeps nothing once it has
    exited: tracemalloc sees no allocation that outlives the call
    attributed to the obs package."""
    import tracemalloc

    assert not obs.enabled()
    with obs.span("ph.x") as sp:
        pass
    assert sp.seconds >= 0.0             # the marks exist with no session
    # warm up any lazy interning, then measure
    obs.counter_add("w")
    obs.event("w")
    obs_dir = os.path.dirname(obs.__file__)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(500):
        obs.counter_add("ph.gate_syncs")
        obs.event("ph.iteration")
        obs.gauge_set("g", 1.0)
        with obs.span("ph.x"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    leaked = sum(s.size_diff
                 for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0
                 and any(obs_dir in str(fr.filename)
                         for fr in s.traceback))
    # a genuine per-call allocation over 500 iterations x 4 calls
    # would read tens of KB; anything under ~1 B/iteration is
    # tracemalloc/interpreter bookkeeping noise, not hot-path cost
    assert leaked < 500, \
        f"disabled-mode obs calls allocated {leaked} B over 500 iters"


# ---------------- PH wiring ----------------

def test_gate_syncs_counter_O1_per_iteration_pipelined(telemetry):
    """THE acceptance invariant, via the counter: pipelined chunked PH
    pays ONE gate D2H per iteration regardless of chunk count."""
    ph = PHBase(_uc_batch(8), dict(_OPTS), dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    n_chunks = len(ph._chunk_index(3))
    assert n_chunks == 3
    base = obs.counter_value("ph.gate_syncs")
    iters = 3
    for _ in range(iters):
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
    delta = obs.counter_value("ph.gate_syncs") - base
    assert delta == iters, \
        f"expected O(1)={iters} gate syncs, counter says {delta}"
    # the sequential opt-out pays one blocking read per chunk
    ph_seq = PHBase(_uc_batch(8), {**_OPTS, "subproblem_pipeline": 0},
                    dtype=jnp.float64)
    ph_seq.solve_loop(w_on=False, prox_on=False)
    ph_seq.W = ph_seq.W_new
    base = obs.counter_value("ph.gate_syncs")
    for _ in range(iters):
        ph_seq.solve_loop(w_on=True, prox_on=True)
        ph_seq.W = ph_seq.W_new
    assert obs.counter_value("ph.gate_syncs") - base \
        == iters * n_chunks
    # donation engaged after the first completed pipelined pass
    assert obs.counter_value("qp.donated_passes") >= 1


def test_span_totals_match_phase_timing(telemetry):
    """Chrome-trace phase spans are recorded from the very marks
    phase_timing accumulates, so per-mode totals agree to roundoff
    (the 5% acceptance tolerance is generous)."""
    rec, path = telemetry
    ph = PHBase(_uc_batch(8), dict(_OPTS), dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    for _ in range(2):
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
    obs.flush()
    tr = json.load(open(path / "trace.json"))
    tot = {}
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and e["name"].startswith("ph.") \
                and e.get("args", {}).get("mode") == "prox":
            tot[e["name"]] = tot.get(e["name"], 0.0) + e["dur"] / 1e6
    acc = ph._phase_times[True]["acc"]
    for phase in ("assemble", "solve", "gate", "reduce"):
        assert tot[f"ph.{phase}"] == pytest.approx(
            acc[phase], rel=0.05, abs=1e-6), phase
    # per-chunk solve spans exist (mode-tagged) and nest inside the
    # prox-mode solve-phase total
    chunk_total = sum(e["dur"] / 1e6 for e in tr["traceEvents"]
                      if e.get("name") == "ph.solve.chunk"
                      and e.get("args", {}).get("mode") == "prox")
    assert chunk_total > 0.0
    assert chunk_total <= tot["ph.solve"] * 1.05 + 1e-3


def test_farmer_fused_span_totals_match_phase_timing(telemetry):
    """The acceptance criterion on the farmer shape: the FUSED path
    (farmer's per-scenario A cannot chunk) books the same assemble/
    solve/reduce anatomy, and its span totals match phase_timing
    within 5% (gate stays 0 — no recovery gate on the fused path)."""
    rec, path = telemetry
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PHBase(batch, {"subproblem_max_iter": 1500})
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    for _ in range(2):
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
    obs.flush()
    tr = json.load(open(path / "trace.json"))
    tot = {}
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" \
                and e.get("args", {}).get("mode") == "prox":
            tot[e["name"]] = tot.get(e["name"], 0.0) + e["dur"] / 1e6
    acc = ph._phase_times[True]["acc"]
    for phase in ("assemble", "solve", "reduce"):
        assert tot[f"ph.{phase}"] == pytest.approx(
            acc[phase], rel=0.05, abs=1e-6), phase
    assert acc["gate"] == 0.0 and "ph.gate" not in tot


@pytest.mark.parametrize("shape", ["chunked", "fused"])
def test_span_totals_equal_phase_timing_exactly(telemetry, shape):
    """The seconds have ONE source: phase_timing accumulates the spans'
    own perf_counter marks, so in a session the trace.json totals equal
    the accumulators to roundoff, not to a tolerance."""
    rec, path = telemetry
    if shape == "chunked":
        ph = PHBase(_uc_batch(8), dict(_OPTS), dtype=jnp.float64)
    else:
        ph = PHBase(build_batch(farmer.scenario_creator,
                                farmer.make_tree(3)),
                    {"subproblem_max_iter": 1500})
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    obs.flush()
    tr = json.load(open(path / "trace.json"))
    for key, mode in ((False, "noprox"), (True, "prox")):
        acc = ph._phase_times[key]["acc"]
        for phase, want in acc.items():
            got = sum(e["dur"] / 1e6 for e in tr["traceEvents"]
                      if e.get("name") == f"ph.{phase}"
                      and e.get("args", {}).get("mode") == mode)
            assert got == pytest.approx(want, rel=1e-9, abs=1e-9), \
                (mode, phase)


def _trace_reduce_pattern():
    """``benchmarks/trace_reduce``'s own pattern for "an annotated
    span": the ledger's idle-gap labels are cut with it."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import trace_reduce
    return trace_reduce._ANNOTATED


def test_profiler_capture_holds_ph_spans_without_session(profiler_capture):
    """A jax.profiler capture of one hot iteration, NO telemetry
    session: the phases, the per-chunk solves and the segmented
    driver's segments are in it, bare-named, nested, on one thread."""
    assert not obs.enabled()
    ph = PHBase(_uc_batch(8),
                {**_OPTS, "subproblem_kernel_mode": "segmented"},
                dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    with profiler_capture as cap:
        with obs.span("ph.iteration", args={"iter": 1}):
            ph.solve_loop(w_on=True, prox_on=True)
    names = {e[0] for e in cap.spans()}
    assert {"ph.iteration", "ph.assemble", "ph.solve", "ph.solve.chunk",
            "ph.gate", "ph.reduce", "qp.segment",
            "qp.polish_call"} <= names, names
    pat = _trace_reduce_pattern()
    assert all(pat.match(n) for n in names), names     # bare: no #k=v#
    assert len({e[1] for e in cap.spans()}) == 1       # one thread
    assert sum(e[0] == "ph.solve.chunk" for e in cap.spans()) == 3
    for child, parent in (("ph.assemble", "ph.iteration"),
                          ("ph.solve", "ph.iteration"),
                          ("ph.gate", "ph.iteration"),
                          ("ph.reduce", "ph.iteration"),
                          ("ph.solve.chunk", "ph.solve"),
                          ("qp.segment", "ph.solve.chunk"),
                          ("qp.polish_call", "ph.solve.chunk")):
        assert cap.inside(child, parent), (child, parent)


def test_counters_survive_reset_phase_timing(telemetry):
    ph = PHBase(_uc_batch(8), dict(_OPTS), dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    c = obs.counter_value("ph.gate_syncs")
    assert c > 0
    assert ph.phase_timing(True) is not None
    ph.reset_phase_timing()
    assert ph.phase_timing(True) is None          # wall-clock: zeroed
    assert obs.counter_value("ph.gate_syncs") == c  # counters: kept


def test_recovery_notes_quiet_on_screen_but_in_stream(telemetry, capsys):
    rec, _ = telemetry
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PHBase(batch, {})
    ph._trace_note("ph.test_note", "a hospital-style note", rows=7)
    out = capsys.readouterr().out
    assert "hospital-style" not in out           # quiet by default
    ev = [e for e in rec.events.tail if e["type"] == "ph.test_note"]
    assert ev and ev[0]["rows"] == 7             # but always in stream
    ph_loud = PHBase(batch, {"hospital_trace": True})
    ph_loud._trace_note("ph.test_note", "a hospital-style note")
    assert "hospital-style" in capsys.readouterr().out


def test_solve_trace_env_reread_lazily(telemetry, monkeypatch):
    """The MPISPPY_TPU_SOLVE_TRACE freeze-at-import bug: the flag is
    re-read per segment, so toggling it mid-process works, and the
    stamps emit through the telemetry layer."""
    from mpisppy_tpu.ops import qp_solver

    monkeypatch.delenv("MPISPPY_TPU_SOLVE_TRACE", raising=False)
    assert not qp_solver._trace_enabled()
    monkeypatch.setenv("MPISPPY_TPU_SOLVE_TRACE", "1")
    assert qp_solver._trace_enabled()
    rec, _ = telemetry
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PHBase(batch, {"subproblem_max_iter": 600})
    ph.solve_loop(w_on=False, prox_on=False)
    segs = [e for e in rec.events.tail if e["type"] == "qp.solve_segment"]
    assert segs, "no qp.solve_segment events with the trace enabled"
    assert {"tag", "seconds", "iters", "pri_rel_max"} <= set(segs[0])
    assert obs.counter_value("qp.solve_segments") >= len(segs)


# ---------------- resource accounting (ISSUE 4 tentpole) ----------

def test_resource_compile_accounting(telemetry):
    """XLA compiles land as counters, a latency histogram, AND
    per-jitted-entry attribution — the retrace-visibility contract."""
    import jax

    rec, _ = telemetry
    base = obs.counter_value("jax.compiles")

    def _telemetry_probe_fn(x):
        return (x * 3.0 + 1.0).sum()

    jax.jit(_telemetry_probe_fn)(jnp.arange(7.0)).block_until_ready()
    assert obs.counter_value("jax.compiles") > base
    assert obs.counter_value(
        "jax.compile.entry._telemetry_probe_fn") >= 1
    ev = [e for e in rec.events.tail if e["type"] == "jax.compile"
          and e.get("entry") == "_telemetry_probe_fn"]
    assert ev and ev[0]["seconds"] > 0
    snap = rec.metrics.snapshot()
    h = snap["histograms"]["jax.compile_seconds"]
    assert h["count"] >= 1 and h["p99"] is not None
    # and the compile books a span on the trace timeline
    spans = [e for e in rec.trace.to_json()["traceEvents"]
             if e.get("name") == "jax.compile"]
    assert spans


def test_memory_sampling_guarded_on_cpu(telemetry):
    """The acceptance guard: resource sampling must be a no-op, not an
    error, where the backend lacks allocator stats (CPU tier-1)."""
    from mpisppy_tpu.obs import resource

    assert resource.sample_memory() == {}
    assert resource.sample_memory() == {}    # and again


def test_transfer_byte_counters(telemetry):
    """H2D bytes book at batch-shipping sites and D2H bytes at the
    chunked loop's fused residual gate."""
    h2d0 = obs.counter_value("xfer.h2d_bytes")
    ph = PHBase(_uc_batch(8), dict(_OPTS), dtype=jnp.float64)
    assert obs.counter_value("xfer.h2d_bytes") > h2d0
    d2h0 = obs.counter_value("xfer.d2h_bytes")
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    assert obs.counter_value("xfer.d2h_bytes") > d2h0


def test_iteration_record_schema(telemetry):
    """The per-iteration convergence record (the device-resident
    Diagnoser analog): residual summary, phase anatomy that sums to
    roughly the iteration wall-clock, and counter deltas."""
    from mpisppy_tpu.core.ph import PH
    from mpisppy_tpu.ir.batch import build_batch

    rec, _ = telemetry
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PH(batch, {"PHIterLimit": 2, "convthresh": -1.0,
                    "subproblem_max_iter": 1500})
    ph.ph_main()
    its = [e for e in rec.events.tail if e["type"] == "ph.iteration"]
    assert [e["iter"] for e in its] == [1, 2]
    for e in its:
        assert {"conv", "seconds", "best_outer", "pri_rel_max",
                "pri_rel_mean", "dua_rel_max", "phase_seconds",
                "counter_deltas"} <= set(e)
        assert e["conv"] is not None and e["seconds"] > 0
        ps = e["phase_seconds"]
        assert set(ps) == {"assemble", "solve", "gate", "reduce"}
        # phase anatomy is measured inside solve_loop; it must not
        # exceed the iteration wall-clock that wraps it
        assert sum(ps.values()) <= e["seconds"] * 1.05 + 1e-3
    # iteration latency histogram feeds the tail metrics
    snap = rec.metrics.snapshot()
    assert snap["histograms"]["ph.iteration_seconds"]["count"] == 2


# ---------------- a session changes no call path (ISSUE 30) ----------

def _tiny_qp(seed=1):
    """Small well-posed box-constrained QP over one shared dense A."""
    from mpisppy_tpu.ops.qp_solver import QPData, qp_cold_state, qp_setup
    rng = np.random.default_rng(seed)
    S, m, n = 3, 6, 4
    mid = rng.normal(size=(S, m))
    d = QPData(P_diag=jnp.asarray(np.abs(rng.normal(size=n)) + 0.5),
               A=jnp.asarray(rng.normal(size=(m, n))),
               l=jnp.asarray(mid - 3.0), u=jnp.asarray(mid + 3.0),
               lb=jnp.full((S, n), -5.0), ub=jnp.full((S, n), 5.0))
    q = jnp.asarray(rng.normal(size=(S, n)))
    fac = qp_setup(d, q_ref=q)
    return fac, d, q, qp_cold_state(fac, d)


def _entry_call(entry):
    """A zero-argument call of one jitted entry point of the engine:
    the seven that PR 18 routed through a cost-model wrapper while a
    session was on."""
    from mpisppy_tpu.ops import dispatch, kernels, qp_solver, shrink
    from mpisppy_tpu.ops.kernels.reference import fused_mixed_solve
    if entry.startswith(("qp.", "kernel.")):
        fac, d, q, st = _tiny_qp()
        if entry == "qp.solve":
            return lambda: qp_solver.qp_solve(
                fac, d, q, st, max_iter=20, check_every=10,
                eps_abs=1e-6, eps_rel=1e-6, polish=False)
        if entry == "qp.solve_lo":
            return lambda: qp_solver.qp_solve_mixed(
                fac, d, q, st, max_iter=50, tail_iter=50, segment=50,
                eps_abs=1e-9, eps_rel=1e-9, polish=True)
        plan = kernels.prepare(fac, mode="fused", precision="mixed")
        return lambda: fused_mixed_solve(
            fac, plan.A_lo, d, q, st, bulk_iter=50, tail_iter=50,
            check_every=25, eps_abs=1e-9, eps_rel=1e-9, eps_abs_dua=1e-9,
            eps_rel_dua=1e-9, polish=True, polish_iters=12,
            polish_chunk=0, stall_rel=0.0, ir_sweeps=1, l_inv=False)
    S, K = 4, 3
    xbar = jnp.zeros((S, K))
    if entry == "shrink.fixer_update":
        cnt = jnp.zeros((K,), jnp.int32)
        return lambda: shrink.fixer_update(
            cnt, cnt, cnt, jnp.zeros((S, K), bool), xbar, xbar, xbar,
            xbar, xbar - 1.0, xbar + 1.0, 1e-4, 1e-6, 2, 2, 2,
            jnp.zeros((K,), bool))
    if entry == "shrink.rho_update":
        return lambda: shrink.per_slot_rho_update(
            jnp.full((S, K), 2.0), jnp.full((S,), 0.25),
            xbar.at[:, 0].add(8.0), xbar, xbar.at[:, 1].add(-10.0),
            2.0, 3.0)
    phis = jnp.asarray([-2.0, 0.5, -1.0, 3.0, 0.0, 0.0])
    if entry == "aph.dispatch_gate":
        last = jnp.asarray([5, 1, 2, 3, 0, 0])
        return lambda: dispatch.dispatch_gate(
            1.5, -0.25, 0.75, 2.0, phis, last, scnt=2, S_real=4)
    assert entry == "aph.scalar_gate"
    return lambda: dispatch.scalar_gate(1.5, -0.25, 0.75, 2.0, phis,
                                        S_real=4)


@pytest.mark.parametrize("entry", [
    "qp.solve", "qp.solve_lo", "kernel.fused_mixed",
    "shrink.fixer_update", "shrink.rho_update", "aph.dispatch_gate",
    "aph.scalar_gate"])
def test_session_adds_no_lowering(tmp_path, entry):
    """A telemetry session wraps nothing around a jitted entry point:
    once a call has compiled with the session off, the same call with a
    session on traces, lowers and compiles nothing. (The cost-model
    capture re-lowered every shape bucket once per session to read a
    cost model the chip does not have: +0.8 s of the UC cell's set-up,
    +37 s of the serve cell's, PERF.md section 6 PR 26.)"""
    import jax
    call = _entry_call(entry)
    assert not obs.enabled()
    jax.block_until_ready(call())
    obs.configure(out_dir=str(tmp_path))
    try:
        before = obs.counters_snapshot()
        for _ in range(3):
            jax.block_until_ready(call())
        after = obs.counters_snapshot()
    finally:
        obs.shutdown()
    for k in ("jax.traces", "jax.lowerings", "jax.compiles"):
        assert after.get(k, 0) == before.get(k, 0), k


# ---------------- event-stream rotation (ISSUE 18 satellite) ----------

def test_event_stream_rotation_mid_run(tmp_path, monkeypatch):
    """A tiny byte cap forces mid-run rotation; analyze reads the
    chain back as ONE logical stream (no phantom earlier_runs), the
    newest file leads with a continuation header, and the merge
    anchor survives."""
    monkeypatch.setenv("MPISPPY_TPU_TELEMETRY_ROTATE_BYTES", "4096")
    monkeypatch.setenv("MPISPPY_TPU_TELEMETRY_ROTATE_FILES", "4")
    obs.configure(out_dir=str(tmp_path))
    try:
        for i in range(200):
            obs.event("test.tick", {"i": i, "pad": "x" * 64})
    finally:
        obs.shutdown()
    base = tmp_path / "events.jsonl"
    assert (tmp_path / "events.jsonl.1").exists()
    with open(base, encoding="utf-8") as fh:
        first = json.loads(fh.readline())
    assert first["type"] == "run_header" and first["rotated"] >= 1
    from mpisppy_tpu.obs.analyze import load_run, truncated
    run = load_run(str(tmp_path))
    assert run.earlier_runs == 0
    ticks = run.of("test.tick")
    # the oldest generations may have dropped off the 4-file cap, but
    # the retained chain must be contiguous and ordered
    idx = [e["i"] for e in ticks]
    assert idx == sorted(idx) and idx[-1] == 199
    assert len(idx) == len(set(idx))
    assert run.of("telemetry.rotated")
    assert not truncated(run)          # footer in the newest file
    from mpisppy_tpu.obs.merge import _anchor_from_events
    anchor = _anchor_from_events(str(tmp_path), role="")
    assert anchor is not None and anchor["wall_time_unix"] > 0


def test_rotation_disabled_by_default(telemetry):
    from mpisppy_tpu.obs.analyze import load_run
    rec, path = telemetry
    for i in range(50):
        obs.event("test.tick", {"i": i})
    obs.shutdown()
    assert not os.path.exists(os.path.join(str(path),
                                           "events.jsonl.1"))
    assert len(load_run(str(path)).of("test.tick")) == 50


# ---------------- cylinder wiring ----------------

def test_hub_bound_events_monotonic_with_wall_anchor(telemetry):
    rec, _ = telemetry
    hub = Hub(_DummyOpt())
    assert {"wall_time_unix", "perf_counter"} == set(hub.clock_anchor)
    assert hub.OuterBoundUpdate(-100.0, "T")
    assert hub.InnerBoundUpdate(50.0, "I")
    bound_ev = [e for e in rec.events.tail if e["type"] == "hub.bound"]
    assert len(bound_ev) == 2
    # the stream re-emits the SAME monotonic stamps bound_events holds
    assert bound_ev[0]["t"] == hub.bound_events[0][0]
    assert bound_ev[0]["kind"] == "outer" and bound_ev[0]["char"] == "T"
    start_ev = [e for e in rec.events.tail if e["type"] == "hub.start"]
    assert start_ev and start_ev[0]["wall_time_unix"] \
        == hub.clock_anchor["wall_time_unix"]
    assert obs.counter_value("hub.bound_updates") == 2
    # the hub half of the per-iteration record: bounds + gap on every
    # termination check
    hub.determine_termination()
    it_ev = [e for e in rec.events.tail if e["type"] == "hub.iteration"]
    assert it_ev and it_ev[-1]["outer"] == -100.0 \
        and it_ev[-1]["inner"] == 50.0
    assert it_ev[-1]["abs_gap"] == 150.0


def test_spoke_bound_update_emits_event(telemetry):
    rec, _ = telemetry
    sp = OuterBoundSpoke(_DummyOpt())
    sp.my_window = Window(sp.local_window_length())
    sp.update_bound(-42.5)
    ev = [e for e in rec.events.tail if e["type"] == "spoke.bound"]
    assert ev and ev[0]["value"] == -42.5
    assert ev[0]["spoke"] == "OuterBoundSpoke" and ev[0]["char"] == "O"
    assert obs.counter_value("spoke.bound_updates") == 1


# ---------------- CLI end-to-end smoke (CI/tooling satellite) --------

def test_cli_farmer_ph_smoke_with_telemetry_dir(tmp_path):
    """Tier-1 guard against schema drift: a farmer PH run through the
    CLI with --telemetry-dir must produce JSONL + Chrome-trace + metric
    artifacts that PARSE and carry the expected structure."""
    from mpisppy_tpu.__main__ import config_from_args, make_parser, run

    tdir = tmp_path / "telemetry"
    args = make_parser().parse_args(
        ["farmer", "--num-scens", "3", "--max-iterations", "3",
         "--convthresh", "-1", "--subproblem-max-iter", "1500",
         "--telemetry-dir", str(tdir)])
    result = run(config_from_args(args))
    assert np.isfinite(result["outer_bound"] or np.nan) \
        or result["outer_bound"] is None
    assert not obs.enabled()        # run() closed the session
    # events.jsonl: every line parses; header carries the config
    lines = [json.loads(ln)
             for ln in open(tdir / "events.jsonl", encoding="utf-8")]
    assert lines[0]["type"] == "run_header"
    assert lines[0]["config"]["model"] == "farmer"
    types = {e["type"] for e in lines}
    assert {"wheel.build", "batch.build", "hub.start", "ph.iter0",
            "ph.iteration", "run.result", "run_footer"} <= types
    # trace.json: valid Chrome trace with the expected span names,
    # and phase spans nest inside their iteration span
    tr = json.load(open(tdir / "trace.json"))
    spans = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    assert {"ph.assemble", "ph.solve", "ph.reduce",
            "ph.iteration"} <= names
    iters = [(e["ts"], e["ts"] + e["dur"]) for e in spans
             if e["name"] == "ph.iteration"]
    assert iters
    for t0, t1 in iters:
        assert any(e["name"] == "ph.solve"
                   and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1
                   for e in spans), "no ph.solve span nested in iteration"
    # metrics.json: the counter catalog's PH counters are present
    mx = json.load(open(tdir / "metrics.json"))
    assert mx["counters"]["ph.solve_loop_calls"] >= 4   # iter0 + 3
    assert mx["gauges"].get("ph.conv") is not None
