"""The run-diagnostics layer (ISSUE 4): the ``analyze`` subcommand,
the multi-process trace merge, and the counter-catalog drift guard.

Coverage demanded by the issue's acceptance criteria:
 - ``python -m mpisppy_tpu analyze`` on a farmer ``--telemetry-dir``
   run renders a report with phase breakdown, convergence trajectory,
   compile/retrace counts, and invariant checks,
 - ``analyze --compare`` flags an injected 2x phase-time regression
   (exit 3), passes an identical-run diff (exit 0), and REFUSES a
   schema_version mismatch (exit 2),
 - the merged multi-process trace parses in the Chrome trace-event
   schema with one aligned process track per role,
 - every metric name emitted in the source tree appears in the
   doc/observability.md catalog (CI drift guard).
"""

import json
import os
import re

import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.obs import analyze
from mpisppy_tpu.obs.merge import merge_traces

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def farmer_run_dir(tmp_path_factory):
    """ONE CLI farmer run with --telemetry-dir, shared by every
    analyze test in this module (the run is the expensive part; the
    analyze passes are pure JSON work)."""
    from mpisppy_tpu.__main__ import config_from_args, make_parser, run

    tdir = tmp_path_factory.mktemp("analyze") / "run"
    args = make_parser().parse_args(
        ["farmer", "--num-scens", "3", "--max-iterations", "3",
         "--convthresh", "-1", "--subproblem-max-iter", "1500",
         "--telemetry-dir", str(tdir)])
    run(config_from_args(args))
    assert not obs.enabled()
    return str(tdir)


def _tampered_copy(src, dst, factor=2.0, schema=None, counters=None):
    """Copy a telemetry dir, scaling every per-iteration/phase time by
    ``factor`` (the injected regression) and optionally rewriting the
    header schema version or (``counters``: a function of the recorded
    counters) the final counter values."""
    import shutil

    shutil.copytree(src, dst)
    ev = os.path.join(dst, "events.jsonl")
    out = []
    for ln in open(ev, encoding="utf-8"):
        e = json.loads(ln)
        if e.get("type") == "ph.iteration" and "seconds" in e:
            e["seconds"] *= factor
            e["phase_seconds"] = {k: v * factor for k, v in
                                  e.get("phase_seconds", {}).items()}
        if schema is not None and e.get("type") == "run_header":
            e["schema"] = schema
        out.append(json.dumps(e))
    open(ev, "w").write("\n".join(out) + "\n")
    tr_path = os.path.join(dst, "trace.json")
    tr = json.load(open(tr_path))
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and e.get("name", "").startswith("ph."):
            e["dur"] *= factor
    json.dump(tr, open(tr_path, "w"))
    mx_path = os.path.join(dst, "metrics.json")
    mx = json.load(open(mx_path))
    for name, h in mx.get("histograms", {}).items():
        if name.endswith("seconds"):
            for k in ("sum", "min", "max", "last", "mean",
                      "p50", "p95", "p99"):
                if isinstance(h.get(k), (int, float)):
                    h[k] *= factor
    if counters is not None:
        mx["counters"].update(counters(mx["counters"]))
    json.dump(mx, open(mx_path, "w"))
    return dst


# ---------------- report ----------------

def test_report_sections_on_farmer_run(farmer_run_dir, capsys):
    """The golden-ish smoke: the report must carry every section the
    acceptance criteria name, with real content."""
    rc = analyze.main([farmer_run_dir])
    assert rc == 0
    out = capsys.readouterr().out
    for section in ("== run ==", "== phase breakdown ==",
                    "== convergence trajectory ==", "== bounds ==",
                    "== resources ==", "== faults ==",
                    "== invariant checks =="):
        assert section in out, f"missing section {section}"
    # phase breakdown with per-mode rows and occupancy
    assert "[prox]" in out and "occupancy" in out
    # convergence rows for each iteration
    assert re.search(r"^\s+1\s", out, re.M) and "conv" in out
    # compile accounting (the retrace-visibility tentpole). The count
    # is 0 when an earlier test in the same process already compiled
    # the farmer programs (python-level jit cache), so per-entry rows
    # are asserted only when compiles actually happened — the hook
    # itself is covered order-independently in
    # test_telemetry.py::test_resource_compile_accounting.
    m = re.search(r"XLA compiles (\d+)", out)
    assert m
    if int(m.group(1)) > 0:
        assert "compile x" in out
    # invariant checks all pass on a healthy run
    assert "[FAIL]" not in out
    assert "gate_syncs_per_solve_call_O1" in out
    assert "no_late_retraces" in out


def test_main_dispatches_analyze_subcommand(farmer_run_dir, capsys):
    """``python -m mpisppy_tpu analyze <dir>`` routes to the
    diagnostics path (and never touches the jax runtime setup)."""
    from mpisppy_tpu.__main__ import main

    rc = main(["analyze", farmer_run_dir])
    assert rc == 0
    assert "== invariant checks ==" in capsys.readouterr().out


def test_report_json_mode(farmer_run_dir, capsys):
    rc = analyze.main([farmer_run_dir, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == obs.SCHEMA_VERSION
    assert doc["iterations"] and doc["iterations"][-1]["iter"] == 3
    it = doc["iterations"][-1]
    # the per-iteration convergence record schema (the Diagnoser
    # analog): residual summary + phase anatomy + counter deltas
    assert {"conv", "seconds", "pri_rel_max", "dua_rel_max",
            "phase_seconds", "counter_deltas"} <= set(it)
    assert {"assemble", "solve", "gate", "reduce"} \
        == set(it["phase_seconds"])
    assert all(c["name"] and c["severity"] in ("fail", "warn")
               for c in doc["invariants"])
    assert doc["compile"]["compiles"] >= 0     # 0 when jit-cache-warm
    assert "late_retrace_iters" in doc["compile"]


def test_reused_dir_keeps_only_last_run(farmer_run_dir, tmp_path,
                                        capsys):
    """events.jsonl APPENDS across sessions while trace/metrics
    overwrite — re-running into the same --telemetry-dir must not
    garble the report: analyze keeps the last session only (matching
    the overwritten artifacts) and flags the reuse as a WARN."""
    import shutil

    d = str(tmp_path / "reused")
    shutil.copytree(farmer_run_dir, d)
    ev = os.path.join(d, "events.jsonl")
    first = open(ev, encoding="utf-8").read()
    # simulate a second CLI run appending to the same stream, whose
    # first outer bound sits BELOW run 1's best (the case that falsely
    # failed the monotone invariant when runs were mixed)
    second = []
    for ln in first.splitlines():
        e = json.loads(ln)
        if e.get("type") == "hub.bound" and e.get("kind") == "outer":
            e["value"] -= 1000.0
        second.append(json.dumps(e))
    open(ev, "a").write("\n".join(second) + "\n")
    run = analyze.load_run(d)
    assert run.earlier_runs == 1
    its = analyze.iteration_rows(run)
    assert [e["iter"] for e in its] == sorted({e["iter"] for e in its})
    rc = analyze.main([d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[WARN] single_run_in_dir" in out
    assert "[FAIL]" not in out          # no spurious monotone failure


def test_report_on_missing_dir_is_an_error(tmp_path, capsys):
    rc = analyze.main([str(tmp_path / "nope")])
    assert rc == 2
    assert "events" in capsys.readouterr().out


# ---------------- compare ----------------

def test_compare_identical_run_passes(farmer_run_dir, capsys):
    rc = analyze.main(["--compare", farmer_run_dir, farmer_run_dir])
    assert rc == 0
    out = capsys.readouterr().out
    assert "VERDICT: PASS" in out
    assert "REGRESSION" not in out


def test_compare_flags_injected_2x_regression(farmer_run_dir, tmp_path,
                                              capsys):
    bad = _tampered_copy(farmer_run_dir, str(tmp_path / "regressed"),
                         factor=2.0)
    rc = analyze.main(["--compare", farmer_run_dir, bad])
    assert rc == 3
    out = capsys.readouterr().out
    assert "VERDICT: REGRESSION" in out
    assert "ph_seconds_per_iteration" in out
    # and the improved direction does NOT read as a regression
    rc = analyze.main(["--compare", bad, farmer_run_dir])
    assert rc == 0
    assert "improved" in capsys.readouterr().out


@pytest.mark.parametrize("tamper, rc, row", [
    # every time field tenfold: printed, never a verdict
    (dict(factor=10.0), 0, None),
    # the committed golden books NO gate sync; one a solve call is the
    # regression the gate exists for
    (dict(factor=1.0, counters=lambda c: {
        "ph.gate_syncs": c["ph.solve_loop_calls"]}), 3,
     "gate_syncs_per_solve_call"),
    (dict(factor=1.0, counters=lambda c: {
        "jax.compiles": 2 * c["jax.compiles"]}), 3,
     "xla_compiles_total"),
], ids=["tenfold_slower_passes", "gate_syncs_fail",
        "doubled_compiles_fail"])
def test_regression_gate_compares_counts_not_clocks(tamper, rc, row,
                                                    tmp_path, capsys):
    """tools/regression_gate.py's comparison stage, in-process, on
    copies of the committed golden: a CPU clock cannot fail it, a
    count can (exit 3)."""
    from tools import regression_gate as rg
    fresh = _tampered_copy(rg.GOLDEN, str(tmp_path / "fresh"), **tamper)
    assert rg.compare_counts(rg.GOLDEN, fresh) == rc
    out = capsys.readouterr().out
    assert ("VERDICT: PASS" in out) == (rc == 0)
    if row is None:
        assert "REGRESSION" not in out
        assert "ph_seconds_per_iteration" in out    # still printed
    else:
        assert f"VERDICT: REGRESSION ({row})" in out


def test_compare_refuses_schema_mismatch(farmer_run_dir, tmp_path,
                                         capsys):
    old = _tampered_copy(farmer_run_dir, str(tmp_path / "oldschema"),
                         factor=1.0, schema=1)
    rc = analyze.main(["--compare", farmer_run_dir, old])
    assert rc == 2
    assert "schema mismatch" in capsys.readouterr().out


# ---------------- faults section (supervised-wheel satellite) --------

def test_faults_section_clean_run_all_pass(farmer_run_dir, capsys):
    """A clean run: the faults section reads empty, the degraded-run
    invariant is PASS, and the fault summary is all zeros."""
    rc = analyze.main([farmer_run_dir])
    assert rc == 0
    out = capsys.readouterr().out
    assert "(none — no spoke downs" in out
    assert "DEGRADED RUN" not in out
    assert "[PASS] no_quarantines_or_corruption: clean" in out
    run = analyze.load_run(farmer_run_dir)
    f = analyze.fault_summary(run)
    assert not f["degraded"] and f["downs"] == 0 \
        and f["rejected_payloads"] == 0 and not f["watchdog_fired"]


def _degraded_dir(tmp_path):
    """Synthesize a degraded run's artifacts: one spoke died, was
    respawned, then quarantined; one crossed-bound payload rejected."""
    d = str(tmp_path / "degraded")
    os.makedirs(d)
    events = [
        {"type": "run_header", "schema": obs.SCHEMA_VERSION, "t": 0.0,
         "run_id": "deg", "wall_time_unix": 0.0},
        {"type": "hub.spoke_down", "t": 1.0, "spoke": 0,
         "kind": "lagrangian", "reason": "died", "exitcode": -9,
         "crashes": 1},
        {"type": "hub.spoke_respawn", "t": 2.0, "spoke": 0,
         "kind": "lagrangian", "gen": 1, "crashes": 1},
        {"type": "hub.bound_rejected", "t": 3.0, "spoke": 0,
         "kind": "outer", "char": "L", "value": None,
         "reason": "crossed"},
        {"type": "hub.spoke_quarantined", "t": 4.0, "spoke": 0,
         "kind": "lagrangian", "cause": "crashes", "crashes": 3,
         "rejections": 1},
        {"type": "run_footer", "t": 5.0},
    ]
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(e) for e in events) + "\n")
    with open(os.path.join(d, "metrics.json"), "w") as f:
        json.dump({"counters": {"hub.spoke_down": 1,
                                "hub.spoke_respawn": 1,
                                "hub.spoke_quarantined": 1,
                                "hub.bound_rejected": 1,
                                "hub.bound_crossed": 1}}, f)
    return d


def test_degraded_run_renders_faults_and_warns(tmp_path, capsys):
    d = _degraded_dir(tmp_path)
    rc = analyze.main([d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "DEGRADED RUN: 1 down(s), 1 respawn(s), 1 quarantined" in out
    assert "spoke0-lagrangian" in out and "died" in out
    assert "[WARN] no_quarantines_or_corruption" in out
    assert "[FAIL]" not in out          # degradation is WARN, not FAIL
    # --json carries the same summary for CI consumers
    rc = analyze.main([d, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["faults"]["degraded"] is True
    assert doc["faults"]["quarantined"] == 1
    assert doc["faults"]["crossed_rejections"] == 1
    # ONE row per spoke: the crash events (spoke kind "lagrangian")
    # and the rejection event (bound kind "outer") aggregate together
    row = doc["faults"]["per_spoke"]["spoke0-lagrangian"]
    assert row["downs"] == 1 and row["rejected"] == 1
    assert list(doc["faults"]["per_spoke"]) == ["spoke0-lagrangian"]


def test_fault_summary_falls_back_to_events(tmp_path):
    """A killed run without metrics.json still reports faults from the
    streamed events."""
    d = _degraded_dir(tmp_path)
    os.remove(os.path.join(d, "metrics.json"))
    f = analyze.fault_summary(analyze.load_run(d))
    assert f["downs"] == 1 and f["quarantined"] == 1 and f["degraded"]


# ---------------- multi-process trace merge ----------------

def test_merged_trace_parses_chrome_schema(tmp_path):
    """Synthetic 3-process capture (hub + two role recorders writing
    into ONE run dir, as utils/multiproc.py arranges): the merge must
    produce a single Chrome-schema trace with one labelled process
    track per role and wall-clock-aligned stamps."""
    d = str(tmp_path)
    for role, span in ((None, "ph.solve"),
                       ("spoke0-lagrangian", "spoke.work"),
                       ("spoke1-xhatshuffle", "spoke.work")):
        rec = obs.Recorder(out_dir=d, role=role)
        with rec.span(span, cat="test"):
            pass
        rec.close()
    out = merge_traces(d)
    assert out == os.path.join(d, "trace_merged.json")
    m = json.load(open(out))
    assert set(m) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert m["metadata"]["unaligned_roles"] == []
    assert set(m["metadata"]["roles"]) \
        == {"hub", "spoke0-lagrangian", "spoke1-xhatshuffle"}
    evs = m["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert len(names) == 3 and any("spoke0-lagrangian" in n
                                   for n in names)
    spans = [e for e in evs if e.get("ph") == "X"]
    assert len(spans) == 3
    for e in spans:
        # the Chrome trace-event schema for complete events
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # distinct pids per source (in-process recorders share one OS pid;
    # the merge must still keep three tracks)
    assert len({e["pid"] for e in spans}) == 3
    # aligned to a shared small-origin timeline, not raw perf_counter
    assert all(0 <= e["ts"] < 60e6 for e in spans)
    # merging is idempotent against its own output (trace_merged is
    # not re-consumed as an input)
    m2 = json.load(open(merge_traces(d)))
    assert len(m2["traceEvents"]) == len(m["traceEvents"])


def test_merge_skips_anchorless_gracefully(tmp_path):
    d = str(tmp_path)
    rec = obs.Recorder(out_dir=d)
    with rec.span("x"):
        pass
    rec.close()
    # a pre-anchor (schema-1 style) role trace: no metadata anchor and
    # no events file to recover one from
    with open(os.path.join(d, "trace-old.json"), "w") as f:
        json.dump({"traceEvents": [{"name": "y", "ph": "X", "ts": 1.0,
                                    "dur": 2.0, "pid": 7, "tid": 1}],
                   "metadata": {"role": "old"}}, f)
    m = json.load(open(merge_traces(d)))
    assert m["metadata"]["unaligned_roles"] == ["old"]
    assert any(e.get("name") == "y" for e in m["traceEvents"])


# ---------------- telemetry propagation (multiproc satellite) --------

def test_multiproc_telemetry_dir_resolution(tmp_path, monkeypatch):
    """The spoke-bootstrap propagation source: explicit RunConfig dir
    wins; a PROGRAMMATICALLY configured parent session (the path that
    used to be silently dropped) comes next; the inherited env var is
    the fallback."""
    from mpisppy_tpu.utils.config import RunConfig
    from mpisppy_tpu.utils.multiproc import _telemetry_out_dir

    monkeypatch.delenv("MPISPPY_TPU_TELEMETRY_DIR", raising=False)
    assert _telemetry_out_dir(RunConfig(telemetry_dir="/x/y")) == "/x/y"
    assert _telemetry_out_dir(RunConfig()) is None
    obs.configure(out_dir=str(tmp_path / "prog"))
    try:
        assert _telemetry_out_dir(RunConfig()) \
            == str(tmp_path / "prog")
    finally:
        obs.shutdown()
    monkeypatch.setenv("MPISPPY_TPU_TELEMETRY_DIR", "/from/env")
    assert _telemetry_out_dir(RunConfig()) == "/from/env"


# ---------------- counter-catalog drift guard (CI satellite) ---------
# One source of truth with the linter (ISSUE 12): the extractor IS
# graft-lint's OBS001 rule, so this guard, ``python -m tools.lint``
# and the regression gate can never disagree about what counts as an
# emitted name.

from tools.lint.rules.obscat import extract_names  # noqa: E402


def _emitted_names(kinds=("metric", "event")):
    """Every statically resolvable metric/event name (or family
    prefix) emitted across the source tree, via the OBS001 AST
    extractor — literal, f-string, ``"x" + var`` and ``.format``
    spellings all resolve to the catalogued prefix."""
    names = set()
    pkg = os.path.join(REPO, "mpisppy_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn),
                       encoding="utf-8").read()
            names |= extract_names(src, kinds=kinds)
    return names


def test_counter_catalog_documents_every_metric():
    """CI drift guard: a metric or event name emitted anywhere in the
    source tree must appear in the doc/observability.md catalog —
    otherwise the catalog silently rots and analyze users chase
    undocumented names. (The same check runs as lint rule OBS001 per
    call site; this is the doc-side aggregate.)"""
    doc = open(os.path.join(REPO, "doc", "observability.md"),
               encoding="utf-8").read()
    names = _emitted_names()
    assert len(names) >= 15, f"extractor broke? found {sorted(names)}"
    missing = sorted(n for n in names if n not in doc)
    assert not missing, \
        f"names emitted but not in doc/observability.md: {missing}"


def test_obs001_extractor_agrees_with_legacy_grep():
    """The ISSUE 12 swap contract: before replacing the historical
    regex guard, the old grep and the new AST extractor must agree on
    the current tree (counter/gauge/histogram subset — events are the
    extractor's extension). One sanctioned difference: the extractor
    sees BOTH arms of a conditional-name emission, the regex only the
    first."""
    legacy_re = re.compile(
        r"\b(?:counter_add|gauge_set|histogram_observe)\(\s*"
        r"(f?)\"([^\"]+)\"")
    legacy = set()
    pkg = os.path.join(REPO, "mpisppy_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn),
                       encoding="utf-8").read()
            for m in legacy_re.finditer(src):
                name = m.group(2)
                if m.group(1):
                    name = name.split("{", 1)[0]
                legacy.add(name)
    new = _emitted_names(kinds=("metric",))
    assert legacy - new == set(), \
        f"legacy grep found names the extractor missed: {legacy - new}"
    extras = new - legacy
    assert all("accepted" in n or "rejected" in n for n in extras), \
        f"unexplained extractor-only names: {extras}"


# ---------------- lint stamp (ISSUE 12 satellite) ----------------

def _mini_run_dir(tmp_path):
    d = tmp_path / "run"
    d.mkdir()
    (d / "events.jsonl").write_text(json.dumps(
        {"type": "run_header", "t": 0.0, "schema": 2,
         "run_id": "lintstamp"}) + "\n")
    return d


def test_analyze_lint_stamp(tmp_path):
    """A ``lint.json`` report in the telemetry dir (written by
    ``python -m tools.lint --out`` / the regression gate) adds a
    one-line lint-status stamp to the report and a ``lint`` block to
    ``--json``; absent file, no stamp."""
    d = _mini_run_dir(tmp_path)
    r = analyze.load_run(str(d))
    assert analyze.lint_summary(r) is None
    assert "lint:" not in analyze.render_report(r)

    (d / "lint.json").write_text(json.dumps(
        {"schema_version": 1, "files_checked": 102, "findings": [],
         "suppressed": [{"rule": "SYNC001"}] * 17}))
    r = analyze.load_run(str(d))
    ls = analyze.lint_summary(r)
    assert ls == {"status": "clean", "findings": 0, "suppressed": 17,
                  "files_checked": 102}
    rep = analyze.render_report(r)
    assert "lint: clean" in rep and "17 suppressed" in rep

    (d / "lint.json").write_text(json.dumps(
        {"schema_version": 1, "files_checked": 102,
         "findings": [{"rule": "OBS001", "path": "x.py", "line": 1,
                       "col": 0, "message": "m"}],
         "suppressed": []}))
    rep = analyze.render_report(analyze.load_run(str(d)))
    assert "1 FINDING(S)" in rep

    # torn/odd payloads must stamp "unreadable", never crash the
    # whole run report
    for payload in ("{truncated", "null", "[]"):
        (d / "lint.json").write_text(payload)
        r = analyze.load_run(str(d))
        assert analyze.lint_summary(r)["status"] == "unreadable"
        assert "unreadable" in analyze.render_report(r)


# ---------------- sharding section (ISSUE 6) ----------------

def test_analyze_sharding_section_and_compare_counters(tmp_path):
    """A sharded run's telemetry renders the sharding section (devices,
    shard size, collective bytes/iter, zero device_put) and feeds the
    collective/device_put per-call counters into --compare metrics."""
    from mpisppy_tpu.__main__ import config_from_args, make_parser, run

    tdir = tmp_path / "sharded"
    args = make_parser().parse_args(
        ["farmer", "--num-scens", "4", "--max-iterations", "3",
         "--convthresh", "-1", "--subproblem-max-iter", "1500",
         "--mesh-devices", "2", "--telemetry-dir", str(tdir)])
    run(config_from_args(args))
    r = analyze.load_run(str(tdir))
    sh = analyze.sharding_summary(r)
    assert sh is not None
    assert sh["mode"] == "sharded" and sh["n_devices"] == 2
    assert sh["shard_scenarios"] == 2
    assert sh["collective_bytes_total"] > 0
    assert sh.get("collective_bytes_per_iter", 0) > 0
    # acceptance evidence as analyze reads it: the one-time initial
    # shard placement is booked, and the steady-state iterations add
    # NOTHING on top of it
    assert sh["device_put_bytes_total"] > 0
    assert sh["device_put_bytes_iterations"] == 0
    rep = analyze.render_report(r)
    assert "== sharding ==" in rep
    assert "devices 2" in rep and "psum operand estimate" in rep
    m = analyze.comparison_metrics(r)
    assert ("collective_kbytes_per_solve_call", "count") in m
    assert m[("device_put_kbytes_across_iterations", "count")] == 0.0
    # unsharded runs carry no section and no sharded counters
    # (compare() then skips the keys instead of mis-diffing)


def test_analyze_no_sharding_section_on_unsharded_run(farmer_run_dir):
    r = analyze.load_run(farmer_run_dir)
    assert analyze.sharding_summary(r) is None
    assert "== sharding ==" not in analyze.render_report(r)
    assert ("collective_kbytes_per_solve_call", "count") \
        not in analyze.comparison_metrics(r)


# ---------------- truncated runs (ISSUE 18 satellite) ----------------

def _synth_run(path, run_id="synth", footer=True):
    """Hand-written telemetry dir: three iterations, with or without
    the ``run_footer`` a run killed before shutdown never writes."""
    os.makedirs(path, exist_ok=True)
    evs = [{"t": 0.0, "type": "run_header", "schema": 2,
            "run_id": run_id, "role": None, "pid": 1,
            "wall_time_unix": 1000.0, "clock": "perf_counter",
            "config": {}}]
    for it in range(1, 4):
        evs.append({"t": it * 10.0, "type": "ph.iteration", "iter": it,
                    "conv": 1e-3, "seconds": 2.0,
                    "phase_seconds": {"solve": 1.6},
                    "counter_deltas": {}})
    counters = {"jax.compiles": 2, "ph.solve_loop_calls": 3}
    if footer:
        evs.append({"t": 40.0, "type": "run_footer",
                    "metrics": {"counters": counters}})
    with open(os.path.join(path, "events.jsonl"), "w",
              encoding="utf-8") as fh:
        for e in evs:
            fh.write(json.dumps(e) + "\n")
    with open(os.path.join(path, "metrics.json"), "w",
              encoding="utf-8") as fh:
        json.dump({"counters": counters, "gauges": {},
                   "histograms": {}}, fh)


def test_truncated_run_stamps_every_section(tmp_path):
    """A run killed before run_footer renders EVERY section header with
    the TRUNCATED RUN stamp plus one explicit notice — uniform
    handling, not section-dependent silence — in the report and in
    ``--compare``; a run with its footer carries no stamp."""
    whole, cut = str(tmp_path / "a"), str(tmp_path / "c")
    _synth_run(whole, run_id="a")
    _synth_run(cut, run_id="c", footer=False)
    ra, rc = analyze.load_run(whole), analyze.load_run(cut)
    assert analyze.truncated(rc) and not analyze.truncated(ra)
    assert "TRUNCATED" not in analyze.render_report(ra)
    text = analyze.render_report(rc)
    assert "TRUNCATED RUN: no run_footer" in text
    heads = [ln for ln in text.splitlines() if ln.startswith("== ")]
    assert heads and all("[TRUNCATED RUN]" in ln for ln in heads)
    text, _ = analyze.compare(ra, rc)
    assert "TRUNCATED RUN (B)" in text
    assert "== compare ==  [TRUNCATED RUN]" in text
