"""graft-lint (tools/lint): fixture-verified rules, suppression
parsing, CLI schema/exit codes, and the tier-1 zero-findings gate over
the real tree (ISSUE 12).

Everything here is jax-free and fast: the linter is stdlib ast, and
the fixtures under tests/lint_fixtures/ are parsed, never executed.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import (LintConfig, lint_paths,  # noqa: E402
                        parse_suppressions, registry)

FIX = os.path.join(REPO, "tests", "lint_fixtures")

RULES = ("SYNC001", "DONATE001", "TRACE001", "LOCK001", "PURE001",
         "OBS001")

# fixture file stem per rule (``<stem>_tp.py`` / ``_suppressed.py`` /
# ``_clean.py``); PURE001's true-positive corpus spans both halves of
# the rule, listed explicitly below
_STEM = {"SYNC001": "sync", "DONATE001": "donate", "TRACE001": "trace",
         "LOCK001": "lock", "OBS001": "obs", "PURE001": "pure_jaxfree"}


def fixture_cfg():
    return LintConfig(
        repo_root=FIX,
        hot_loop=("sync_tp.py", "sync_suppressed.py", "sync_clean.py"),
        jax_free=("pure_jaxfree_tp.py", "pure_jaxfree_suppressed.py",
                  "pure_jaxfree_clean.py"),
        catalog_paths=("doc/obs_catalog.md",))


def run_one(path, rule):
    return lint_paths([path], fixture_cfg(), rules=[rule])


# ---------------- per-rule fixture corpus ----------------

def test_registry_has_every_rule():
    names = set(registry())
    assert set(RULES) <= names


@pytest.mark.parametrize("rule", RULES)
def test_rule_true_positive(rule):
    rep = run_one(f"{_STEM[rule]}_tp.py", rule)
    found = [f for f in rep["findings"] if f["rule"] == rule]
    assert found, f"{rule}: true-positive fixture produced no findings"
    assert all(f["line"] > 0 and f["message"] for f in found)


@pytest.mark.parametrize("rule", RULES)
def test_rule_suppressed(rule):
    rep = run_one(f"{_STEM[rule]}_suppressed.py", rule)
    assert [f for f in rep["findings"] if f["rule"] == rule] == []
    sup = [f for f in rep["suppressed"] if f["rule"] == rule]
    assert sup, f"{rule}: suppressed fixture settled nothing"
    assert all(f["reason"] for f in sup)


@pytest.mark.parametrize("rule", RULES)
def test_rule_clean_negative(rule):
    rep = run_one(f"{_STEM[rule]}_clean.py", rule)
    assert [f for f in rep["findings"] if f["rule"] == rule] == []
    assert [f for f in rep["suppressed"] if f["rule"] == rule] == []


def test_sync_tp_catches_every_readback_shape():
    """The TP fixture enumerates all five readback shapes; each line
    must be caught (a silent miss in ONE shape is how a real
    violation ships)."""
    rep = run_one("sync_tp.py", "SYNC001")
    msgs = "\n".join(f["message"] for f in rep["findings"])
    for shape in ("float()", ".item()", "block_until_ready",
                  "np.asarray", "np.array", "bool()"):
        assert shape in msgs, f"SYNC001 missed {shape}"


def test_donate_tp_catches_wrapper_and_alias():
    rep = run_one("donate_tp.py", "DONATE001")
    lines = {f["line"] for f in rep["findings"]}
    assert len(lines) == 3      # raw twin, donate= wrapper, alias


def test_pure_testing_half():
    """The clean-path half of PURE001: mpisppy_tpu.testing imports
    (absolute and relative) flagged outside mpisppy_tpu/testing,
    never inside it."""
    cfg = fixture_cfg()
    tp = lint_paths(["mpisppy_tpu/pure_testing_tp.py"], cfg,
                    rules=["PURE001"])
    assert len(tp["findings"]) == 2     # absolute + relative import
    ok = lint_paths(["mpisppy_tpu/testing/inside_ok.py"], cfg,
                    rules=["PURE001"])
    assert ok["findings"] == []


def test_lock001_flags_each_mutation_shape():
    rep = run_one("lock_tp.py", "LOCK001")
    msgs = [f["message"] for f in rep["findings"]]
    assert len(msgs) == 4
    assert any("_watchdog_fired" in m for m in msgs)
    assert any(".append()" in m for m in msgs)


def test_lock001_rebind_kills_alias(tmp_path):
    """A local once bound to the ledger then rebound to a plain value
    is no longer an alias — mutating it needs no lock."""
    p = tmp_path / "rebind.py"
    p.write_text(
        "class Hub:\n"
        "    def f(self):\n"
        "        with self._flow_lock:\n"
        "            flow = self._spoke_flow[0]\n"
        "            flow['x'] = 1\n"
        "        flow = {'y': 2}\n"
        "        flow['y'] = 3\n")
    rep = lint_paths([str(p)], LintConfig(), rules=["LOCK001"])
    assert rep["findings"] == [], rep["findings"]


def test_obs001_sees_recorder_instance_events(tmp_path):
    """Dotted event names emitted through a Recorder instance
    (``r.event(\"jax.compile\", ...)`` — the obs/resource.py spelling)
    are extracted too; non-dotted `.event()` calls of unrelated APIs
    stay out of scope."""
    src = ('def f(r, w):\n'
           '    r.event("rogue.recorder_event", {})\n'
           '    w.event("plainword")\n')
    from tools.lint.rules.obscat import extract_names
    assert extract_names(src, kinds=("event",)) \
        == {"rogue.recorder_event"}
    p = tmp_path / "rec.py"
    p.write_text(src)
    rep = lint_paths([str(p)], LintConfig(), rules=["OBS001"])
    (f,) = rep["findings"]
    assert "rogue.recorder_event" in f["message"]


def test_lintconfig_testing_package_is_configurable():
    cfg = LintConfig(testing_package="other_pkg/testing/")
    assert cfg.testing_package == "other_pkg/testing/"


# ---------------- suppression parsing ----------------

def test_suppression_parsing_unit():
    lines = [
        "x = 1  # lint: ok[SYNC001] the gate",
        "# lint: ok[SYNC001, OBS001] guards the next line",
        "y = 2",
        "z = 3  # lint: ok[DONATE001]",          # missing reason
        "plain = 4",
    ]
    sups = parse_suppressions(lines)
    assert sups[1][0].rules == ("SYNC001",)
    assert sups[1][0].reason == "the gate"
    # own-line comment guards line 3, and carries both rules
    assert sups[3][0].rules == ("SYNC001", "OBS001")
    assert 2 not in sups
    assert sups[4][0].reason == ""


def test_own_line_suppression_skips_blank_and_comment_lines():
    """An own-line marker guards the next CODE line even across blank
    lines and ordinary comments — otherwise a reformat silently
    disarms the suppression and the gate flags a suppressed site."""
    sups = parse_suppressions([
        "# lint: ok[SYNC001] the gate",
        "",
        "# ordinary comment",
        "x = float(conv)",
    ])
    assert list(sups) == [4]
    assert sups[4][0].rules == ("SYNC001",)


def test_unused_suppression_is_flagged_LINT003(tmp_path):
    """A marker whose line settles nothing is stale — it would
    pre-authorize a future violation, so it is its own finding. A
    marker for a rule excluded from the run is NOT judged."""
    p = tmp_path / "stale.py"
    p.write_text("x = 1   # lint: ok[OBS001] nothing to settle here\n")
    rep = lint_paths([str(p)], LintConfig(), rules=["OBS001"])
    (f,) = rep["findings"]
    assert f["rule"] == "LINT003" and "unused suppression" in f["message"]
    # same file, rule filtered out of the run: marker not judged
    rep = lint_paths([str(p)], LintConfig(), rules=["PURE001"])
    assert rep["findings"] == []


def test_reasonless_marker_reports_LINT001_once(tmp_path):
    """Two findings settled by ONE bare marker emit one LINT001, not
    one per finding."""
    p = tmp_path / "two.py"
    p.write_text(
        "from mpisppy_tpu import obs\n"
        "def f():\n"
        "    # lint: ok[OBS001]\n"
        '    obs.counter_add("rogue.a"); obs.gauge_set("rogue.b", 1)\n')
    rep = lint_paths([str(p)], LintConfig(), rules=["OBS001"])
    rules = sorted(f["rule"] for f in rep["findings"])
    assert rules == ["LINT001", "OBS001", "OBS001"]


def test_trace001_local_shadowing_is_not_a_closure(tmp_path):
    """A jitted function that ASSIGNS a name shadowing a mutable
    module global reads its own local, not the global — no finding
    (Python scoping); an explicit `global` declaration still flags."""
    p = tmp_path / "shadow.py"
    p.write_text(
        "import jax\n"
        "LOOKUP = {}\n"
        "@jax.jit\n"
        "def ok(x):\n"
        "    LOOKUP = {'k': x}\n"
        "    return LOOKUP['k']\n"
        "@jax.jit\n"
        "def bad(x):\n"
        "    global LOOKUP\n"
        "    LOOKUP = {'k': x}\n"
        "    return LOOKUP['k']\n")
    rep = lint_paths([str(p)], LintConfig(), rules=["TRACE001"])
    lines = {f["line"] for f in rep["findings"]}
    assert lines and all(ln >= 10 for ln in lines), rep["findings"]


def test_missing_reason_does_not_suppress(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text("from mpisppy_tpu import obs\n"
                 "def f():\n"
                 "    obs.counter_add('nope.metric')  "
                 "# lint: ok[OBS001]\n".replace("'", '"'))
    rep = lint_paths([str(p)], LintConfig(), rules=["OBS001"])
    rules = sorted(f["rule"] for f in rep["findings"])
    assert rules == ["LINT001", "OBS001"]       # finding stays + policy hit
    assert rep["suppressed"] == []


def test_unparseable_file_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    rep = lint_paths([str(p)], LintConfig())
    assert [f["rule"] for f in rep["findings"]] == ["LINT002"]
    # NUL bytes raise ValueError from ast.parse (not SyntaxError) —
    # a torn write must be a finding too, never a linter crash
    n = tmp_path / "nul.py"
    n.write_text("x = 1\x00\n")
    rep = lint_paths([str(n)], LintConfig())
    assert [f["rule"] for f in rep["findings"]] == ["LINT002"]


def test_suppression_markers_in_strings_are_inert(tmp_path):
    """A module QUOTING the suppression syntax (docstring, string
    literal) must not mint phantom suppressions — only real comment
    tokens count. Otherwise a docstring example could silently settle
    a genuine finding that later lands on the same line."""
    p = tmp_path / "doc.py"
    p.write_text(
        '"""Docs:\n'
        '    x()  # lint: ok[OBS001] docstring example\n'
        '"""\n'
        'from mpisppy_tpu import obs\n'
        'obs.counter_add("rogue.phantom_metric")'
        '  # line 5 = docstring example target +3\n')
    # marker line 2 would (if parsed from the string) guard line 2;
    # build one where the phantom would guard the violating line:
    q = tmp_path / "doc2.py"
    q.write_text(
        'S = "# lint: ok[OBS001] in a string"\n'
        'from mpisppy_tpu import obs\n'
        'obs.counter_add("rogue.phantom_metric2")\n')
    sups = parse_suppressions(q.read_text())
    assert sups == {}
    rep = lint_paths([str(p), str(q)], LintConfig(), rules=["OBS001"])
    assert len(rep["findings"]) == 2
    assert rep["suppressed"] == []


def test_obs001_missing_catalog_is_a_finding(tmp_path):
    """An unreadable/absent catalog must not silently disable OBS001 —
    a module with emissions gets a configuration finding instead of a
    clean pass with zero enforcement."""
    p = tmp_path / "emits.py"
    p.write_text("from mpisppy_tpu import obs\n"
                 'obs.counter_add("app.requests")\n')
    cfg = LintConfig(repo_root=str(tmp_path),
                     catalog_paths=("doc/does_not_exist.md",))
    rep = lint_paths([str(p)], cfg, rules=["OBS001"])
    (f,) = rep["findings"]
    assert "missing catalog" in f["message"]
    # a module with NO emissions stays clean under the same config
    c = tmp_path / "quiet.py"
    c.write_text("x = 1\n")
    assert lint_paths([str(c)], cfg, rules=["OBS001"])["findings"] == []


# ---------------- CLI: --json schema + exit codes ----------------

def _cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.lint", *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=120)


def test_cli_exit_0_clean(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text("x = 1\n")
    r = _cli([str(p)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_cli_exit_3_findings_and_json_schema(tmp_path):
    p = tmp_path / "dirty.py"
    p.write_text("from mpisppy_tpu import obs\n"
                 "def f():\n"
                 '    obs.counter_add("rogue.lint_test_metric")\n')
    out = tmp_path / "lint.json"
    r = _cli([str(p), "--json", "--out", str(out)])
    assert r.returncode == 3, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep["schema_version"] == 1
    assert rep["files_checked"] == 1
    assert set(rep["rules"]) >= set(RULES)
    (f,) = rep["findings"]
    assert f["rule"] == "OBS001" and f["line"] == 3
    assert {"rule", "path", "line", "col", "message"} <= set(f)
    # --out mirrors stdout
    assert json.loads(out.read_text())["findings"] == rep["findings"]


def test_cli_exit_2_usage():
    assert _cli(["definitely/not/a/path.py"]).returncode == 2
    assert _cli(["--rule", "BOGUS999", "tools"]).returncode == 2


def test_cli_list_rules():
    r = _cli(["--list-rules"])
    assert r.returncode == 0
    for rule in RULES:
        assert rule in r.stdout


# ---------------- the tier-1 gate: the tree is lint-clean ----------

def test_repo_tree_is_lint_clean():
    """THE acceptance test: ``python -m tools.lint mpisppy_tpu tools``
    exits 0 on this tree — every violation is fixed or carries a
    reasoned suppression. Run through the API (same code path, no
    subprocess) so the failure message lists the findings."""
    rep = lint_paths(["mpisppy_tpu", "tools"], LintConfig())
    pretty = "\n".join(f"{f['path']}:{f['line']}: {f['rule']} "
                       f"{f['message']}" for f in rep["findings"])
    assert rep["findings"] == [], f"unsuppressed findings:\n{pretty}"
    # the suppression inventory only ever shrinks or grows with a
    # reasoned entry; every settled one carries its reason
    assert all(f["reason"] for f in rep["suppressed"])
    assert rep["files_checked"] > 80


def test_regression_gate_fails_fast_on_lint_findings(monkeypatch,
                                                     tmp_path):
    """tools/regression_gate.py runs the linter BEFORE the bench: a
    lint failure exits immediately (no bench subprocess is spawned —
    run_bench here would blow the test budget, so reaching it IS the
    failure)."""
    import tools.regression_gate as rg
    monkeypatch.setattr(rg, "run_lint", lambda out_path=None: 3)

    def _no_bench(*a, **k):     # pragma: no cover - must not run
        raise AssertionError("bench ran despite lint failure")

    monkeypatch.setattr(rg, "run_bench", _no_bench)
    assert rg.main(["--keep", str(tmp_path / "fresh")]) == 3


# ---------------- purity consolidation (ISSUE 12 satellite) --------
# PURE001 is the STATIC side of two contracts that used to live only
# in per-path fresh-interpreter probes; each keeps exactly ONE runtime
# probe as the dynamic backstop:
#  - clean-path mpisppy_tpu.testing:
#    tests/test_faults.py::test_clean_path_never_imports_testing
#  - jax-free modules: the probe below.

def test_pure001_static_over_real_tree():
    """Every declared-jax-free module and every clean-path file passes
    PURE001 on all paths at once — the static consolidation of the
    fresh-interpreter import probes (which each cover one import
    path per run)."""
    rep = lint_paths(["mpisppy_tpu", "tools"], LintConfig(),
                     rules=["PURE001"])
    assert rep["findings"] == [], rep["findings"]
    # the env-gated fault-injector sites (worker side in multiproc,
    # serve side in the manager) are the only sanctioned suppressions
    # of this contract
    assert len(rep["suppressed"]) == 3
    assert sorted({f["path"] for f in rep["suppressed"]}) == [
        "mpisppy_tpu/serve/manager.py",
        "mpisppy_tpu/utils/multiproc.py"]


def test_jax_free_modules_import_without_jax():
    """THE runtime backstop for the jax-free contract (one probe for
    the whole contract, replacing per-module claims): ckpt/, obs
    analyze/merge, utils/config and tools/lint all import in a fresh
    interpreter where jax is poisoned — any static OR lazy jax import
    raises immediately."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None   # import attempts now raise\n"
        "import mpisppy_tpu.ckpt.bundle\n"
        "import mpisppy_tpu.ckpt.manager\n"
        "import mpisppy_tpu.ckpt.spoke_state\n"
        "import mpisppy_tpu.obs.analyze\n"
        "import mpisppy_tpu.obs.merge\n"
        "import mpisppy_tpu.utils.config\n"
        # the serving layer's HTTP/queue/cache/batch plane must import
        # without jax (doc/serving.md layering contract); only
        # serve/manager — the wheel runner — may touch the engine
        "import mpisppy_tpu.serve.cache\n"
        "import mpisppy_tpu.serve.queue\n"
        "import mpisppy_tpu.serve.batch\n"
        "import mpisppy_tpu.serve.http\n"
        "import mpisppy_tpu.serve.timing\n"
        "import tools.lint.rules\n"
        "import tools.regression_gate\n"
        "print('JAXFREE')\n")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "JAXFREE" in out.stdout


def test_seeded_violations_fail_scratch_copy(tmp_path):
    """Acceptance rider: seeding a SYNC001 / PURE001 / OBS001
    violation into a scratch copy of the tree layout makes the linter
    fail — the default path classification catches each."""
    (tmp_path / "mpisppy_tpu" / "core").mkdir(parents=True)
    (tmp_path / "mpisppy_tpu" / "utils").mkdir(parents=True)
    (tmp_path / "doc").mkdir()
    (tmp_path / "doc" / "observability.md").write_text(
        "| `ph.gate_syncs` | documented |\n")
    # SYNC001 seed: a stray readback in the hot-loop module
    (tmp_path / "mpisppy_tpu" / "core" / "ph.py").write_text(
        "def solve_loop(state):\n"
        "    return float(state.conv_dev)\n")
    # PURE001 seed: jax import in the declared-jax-free config module
    (tmp_path / "mpisppy_tpu" / "utils" / "config.py").write_text(
        "import jax\n")
    # OBS001 seed: an uncatalogued metric name
    (tmp_path / "mpisppy_tpu" / "core" / "extra.py").write_text(
        "from mpisppy_tpu import obs\n"
        'obs.counter_add("rogue.seeded_metric")\n')
    rep = lint_paths(["mpisppy_tpu"],
                     LintConfig(repo_root=str(tmp_path)))
    rules = {f["rule"] for f in rep["findings"]}
    assert {"SYNC001", "PURE001", "OBS001"} <= rules, rep["findings"]
