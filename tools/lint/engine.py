"""graft-lint engine: file walking, suppression parsing, rule driving.

jax-free by contract (PURE001 lints this package too): stdlib ``ast``
only. The engine knows nothing about individual rules — it parses each
file once, hands the :class:`Module` to every registered rule, and
settles the returned findings against the per-line suppressions.

Suppression syntax (doc/lint.md):

    some_call()          # lint: ok[SYNC001] reason why this is safe
    # lint: ok[SYNC001, OBS001] an own-line comment guards the NEXT line

Every suppression MUST carry a non-empty reason — a bare ``ok[RULE]``
does not suppress and instead raises a ``LINT001`` finding, so the
policy ("every allowlisted violation explains itself") is enforced by
the tool, not by review.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize

LINT_SCHEMA_VERSION = 1

# repo root = two levels above tools/lint/
REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

_SUPP_RE = re.compile(r"#\s*lint:\s*ok\[([A-Za-z0-9_,\s]+)\]\s*(.*)$")

# ---------------------------------------------------------------- data


@dataclasses.dataclass
class Finding:
    """One rule violation at one source location."""
    rule: str
    path: str          # repo-relative, "/"-separated
    line: int
    col: int
    message: str
    suppressed: bool = False
    reason: str | None = None     # the suppression's reason, when suppressed

    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_json(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "col": self.col, "message": self.message}
        if self.suppressed:
            d["suppressed"] = True
            d["reason"] = self.reason
        return d


@dataclasses.dataclass
class Suppression:
    rules: tuple
    reason: str
    line: int           # the source line the suppression guards
    comment_line: int   # where the comment itself lives
    used: bool = False


def parse_suppressions(lines) -> dict:
    """``# lint: ok[RULE[,RULE2]] reason`` comments, keyed by the line
    they guard. A trailing comment guards its own line; a comment-only
    line guards the next line (long flagged statements keep readable).

    Markers are taken from REAL comment tokens only (tokenize), never
    from string literals or docstrings — a module *documenting* the
    suppression syntax must not mint phantom suppressions that could
    mask a later genuine finding on the same line."""
    if not isinstance(lines, str):
        lines = list(lines)
        src = "\n".join(lines)
    else:
        src = lines
        lines = src.splitlines()
    sups: dict[int, list[Suppression]] = {}
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError,
            ValueError):
        # untokenizable source: no suppressions — findings surface
        # rather than being silently settled (the conservative side)
        return sups
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPP_RE.search(tok.string)
        if not m:
            continue
        rules = tuple(r.strip().upper()
                      for r in m.group(1).split(",") if r.strip())
        reason = m.group(2).strip()
        i = tok.start[0]
        before = lines[i - 1][:tok.start[1]] if i <= len(lines) else ""
        own_line = before.strip() == ""
        if own_line:
            # guard the next CODE line: blank lines and further
            # comments between the marker and the statement must not
            # leave the marker silently inert
            target = i + 1
            while target <= len(lines) and (
                    lines[target - 1].strip() == ""
                    or lines[target - 1].lstrip().startswith("#")):
                target += 1
        else:
            target = i
        sups.setdefault(target, []).append(
            Suppression(rules, reason, target, i))
    return sups


class Module:
    """One parsed source file: tree + lines + suppressions, parsed
    exactly once and shared by every rule."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.suppressions = parse_suppressions(self.lines)


# ------------------------------------------------------------- config

# the engine's hot loop: modules where a single stray blocking readback
# serializes a chunk chain (doc/pipelining.md, doc/roofline.md) — the
# SYNC001 scope
HOT_LOOP_DEFAULT = (
    "mpisppy_tpu/core/ph.py",
    "mpisppy_tpu/ops/qp_solver.py",
    "mpisppy_tpu/ops/kernels/",
    "mpisppy_tpu/ops/incumbent.py",
    "mpisppy_tpu/ops/shrink.py",
    "mpisppy_tpu/parallel/mesh.py",
    # the scenario streaming engine (doc/streaming.md): chunk staging
    # runs INSIDE the chunked hot loop — a stray blocking readback in
    # the source/pipeline serializes the chunk chain exactly like one
    # in core/ph
    "mpisppy_tpu/stream/",
    # the device-paced APH wheel (ISSUE 16, doc/aph.md): the whole
    # iteration's host traffic is ONE stacked gate read — any other
    # readback in the loop or the dispatch ops breaks the O(1)
    # aph.gate_syncs contract
    "mpisppy_tpu/core/aph.py",
    "mpisppy_tpu/ops/dispatch.py",
    # wheel forensics (ISSUE 19, doc/forensics.md): the attribution
    # reduction runs against the live hub state every sampled
    # iteration — its ONE designed fetch (unpack) carries a reasoned
    # suppression; anything else syncing here breaks the O(1)
    # ph.gate_syncs contract exactly like a readback in core/ph
    "mpisppy_tpu/ops/forensics.py",
)

# modules that document themselves jax-free (CHANGES/doc claims backed
# by the fresh-interpreter probes) — the PURE001 scope
JAX_FREE_DEFAULT = (
    "mpisppy_tpu/ckpt/",
    "mpisppy_tpu/obs/analyze.py",
    "mpisppy_tpu/obs/merge.py",
    "mpisppy_tpu/utils/config.py",
    "mpisppy_tpu/testing/faults.py",
    "tools/",
    # the serving layer's HTTP/queue/cache/batch plane (doc/serving.md
    # layering contract): only serve/manager.py — the wheel runner —
    # may touch jax
    "mpisppy_tpu/serve/__init__.py",
    "mpisppy_tpu/serve/cache.py",
    "mpisppy_tpu/serve/queue.py",
    "mpisppy_tpu/serve/batch.py",
    "mpisppy_tpu/serve/http.py",
    "mpisppy_tpu/serve/migrate.py",
    "mpisppy_tpu/serve/timing.py",
    # the diagnosis engine (ISSUE 19, doc/forensics.md): the hub
    # status plane, bench's signal handler, and serve read its
    # snapshots as plain dict lookups — it must never pull in jax
    "mpisppy_tpu/obs/diagnose.py",
)

# SYNC001's allowlisted gate sites: functions in hot-loop modules that
# are host-side or gate-time BY DESIGN — each entry names the reason
# (doc/lint.md renders this table; the tier-1 gate-sync counter tests
# are the runtime backstop for the claims). Entries match the function
# qualname and everything nested inside it.
SYNC_ALLOW_DEFAULT = {
    "mpisppy_tpu/core/ph.py": {
        "_book_exits":
            "host numpy over rows the gate read (or _book_admm_iters' "
            "device_get) already brought to the host: no device value "
            "enters it",
        "_row_map":
            "host numpy over host chunk ids, once per layout or per "
            "dispatch pass",
        "PHBase.residual_summary":
            "gate-time diagnostics: reads residuals AFTER the stacked "
            "gate synced them",
        "PHBase._hospitalize":
            "recovery path: runs only after the fused gate flagged a "
            "pathological row",
        "PHBase.iter0_feasible_mask":
            "iter0 feasibility screen, once per run before the hot "
            "loop starts",
        "PHBase.nonant_integer_mask":
            "host problem-structure metadata (batch.integer), "
            "setup-time",
        "PHBase.round_nonants":
            "host-side rounding helper for incumbent staging, per "
            "round not per chunk",
        "PHBase.Ebound":
            "bound evaluation: one scalar D2H per publish — the "
            "designed readback",
        "PHBase.Eobjective_value":
            "bound evaluation: one scalar D2H per publish — the "
            "designed readback",
        "PHBase.W_disabled_Ebound":
            "bound evaluation: one scalar D2H per publish — the "
            "designed readback",
        "PHBase.update_best_bound":
            "bound-ledger update: host scalar bookkeeping at the gate",
        "PHBase.calculate_incumbent":
            "sequential incumbent fallback: per-candidate syncs are "
            "its documented honest cost (incumbent.gate_syncs)",
        "PHBase.dive_nonant_candidates":
            "host pool staging per dive round, outside the chunk chain",
        "PHBase.evaluate_incumbent_pool":
            "pool staging + the ONE stacked verdict D2H per round "
            "(O(1) asserted by tests/test_incumbent.py)",
        "PHBase._forensic_sample":
            "gate-time diagnostics: fetches the packed forensic "
            "vector AFTER the iteration gate synced conv "
            "(residual_summary's license; O(1) asserted by "
            "tests/test_forensics.py)",
    },
    "mpisppy_tpu/core/aph.py": {
        "APH.aph_state_arrays":
            "checkpoint capture: explicit D2H at the bundle boundary "
            "(ckpt/manager), never in the iteration loop",
        "APH.install_aph_state":
            "checkpoint resume installer: runs once before the wheel "
            "starts",
    },
    "mpisppy_tpu/ops/qp_solver.py": {
        "_trace_seg":
            "MPISPPY_TPU_SOLVE_TRACE stamp forces a sync by documented "
            "design (doc/observability.md), never default-on",
        "_kkt_host":
            "the host factor path is host-side by design "
            "(qp.host_rho_refactors, doc/tpu_numerics.md)",
        "_factorize_host":
            "the host factor path is host-side by design "
            "(qp.host_rho_refactors, doc/tpu_numerics.md)",
        "_host_adapt_rho":
            "host rho adaptation at segment boundaries — the designed "
            "host sync point (xfer.d2h_bytes books it)",
        "host_dense_A":
            "factor-build host conversion, runs at state (re)build "
            "not per segment",
        "split_f32_np":
            "factor-build host conversion, runs at state (re)build "
            "not per segment",
    },
    "mpisppy_tpu/ops/kernels/__init__.py": {
        "prepare":
            "plan preparation is host+eager once per factorization by "
            "documented contract",
        "KernelPlan.descriptor":
            "plan metadata for bench/telemetry: host bools on the plan",
    },
    "mpisppy_tpu/ops/incumbent.py": {
        "build_pool":
            "pool construction: host staging of the small candidate "
            "inputs once per round, then ONE jitted op",
        "slam_rows":
            "consensus-block host staging shared with the slam spokes, "
            "once per round",
    },
    "mpisppy_tpu/parallel/mesh.py": {
        "make_mesh": "mesh construction, once per engine",
        "pad_batch_for_mesh":
            "zero-probability padding at engine build, setup-time",
    },
    "mpisppy_tpu/ops/forensics.py": {
        "unpack":
            "decodes the ALREADY-FETCHED packed stats vector: its one "
            "np.asarray is the designed per-sample fetch at the "
            "already-synced gate (doc/forensics.md), every float() "
            "after it is host math on the numpy copy",
    },
    "mpisppy_tpu/ops/shrink.py": {
        "build_plan":
            "compaction planning is host+eager once per BUCKET "
            "TRANSITION by documented contract (one fixed-mask read + "
            "one row-pattern read, never per iteration)",
    },
    # the scenario streaming engine (doc/streaming.md): these sites
    # are HOST staging by design — the source's whole job is moving
    # host-resident data toward the device (H2D, not the D2H readbacks
    # SYNC001 hunts), and the setup/install passes run at engine
    # build / tenant swap, never in the chunk chain
    "mpisppy_tpu/stream/source.py": {
        "_eq_pattern":
            "pure host-numpy setup helper (the exact eq-pattern "
            "surrogate math, engine-dtype cast included), consumed "
            "only by the once-per-engine setup_arrays passes",
        "ScenarioSource._put":
            "the loader's deliberate H2D device_put — the transfer "
            "streaming exists to make (books xfer.device_put_bytes); "
            "host-side size reads only, no device readback",
        "ScenarioSource.bind":
            "layout staging once per chunk-layout change (callers "
            "gate on bound_key), never per iteration",
        "ScenarioSource.rows":
            "exceptional-path row staging (hospital fetches): host id "
            "conversion feeding the host-store gather",
        "StreamedSource.install":
            "host store build at engine construction / serve tenant "
            "install — reads the HOST batch arrays, setup-time",
        "StreamedSource._stage_rows":
            "host gather of the host store feeding the H2D put — "
            "host numpy indexing, no device readback",
        "StreamedSource.stage_full":
            "once-per-compaction-transition full restage (build_plan "
            "input) — out-of-band by contract, booked on "
            "stream.compacted_restage_bytes, never per iteration",
        "StreamedSource.install_compacted":
            "once-per-transition compacted host-store rebuild: the "
            "single D2H pull of the plan's folded blocks plus host "
            "const/int8 re-packing — transition-time, the iteration "
            "chain never enters it",
        "StreamedSource.setup_arrays":
            "setup-time host reductions over the host store (the "
            "exact eq-pattern/cost-scale surrogates), once per engine",
        "SynthesizedSource.bind":
            "per-chunk id vectors staged once per layout change",
        "SynthesizedSource.rows":
            "exceptional-path row staging (hospital fetches), host "
            "id conversion only",
        "SynthesizedSource.setup_arrays":
            "setup-time streaming host pass of the generator (exact "
            "surrogates), once per engine — the np.asarray reads the "
            "generator's batch output, deliberately on host",
    },
    "mpisppy_tpu/stream/quant.py": {
        "quantize_field":
            "the int8 gate MUST run on host over the host store "
            "(reproduces the device's f32 dequant arithmetic exactly); "
            "build/install-time, never in the chunk chain",
        "_reconstruct_f32":
            "host twin of the device dequantization — pure numpy on "
            "the host store (the gate's measurement basis)",
    },
    "mpisppy_tpu/stream/synth.py": {
        "materialize":
            "host materialization of the generator for resident/"
            "streamed twins and setup stats — a build-time tool, "
            "deliberately reading the jitted generator's output to "
            "host",
        "synth_batch":
            "batch construction: host stacking at build time",
    },
}

# hub state shared with the status-server HTTP threads: attribute ->
# the lock that must be held to MUTATE it (cylinders/hub.py; reads are
# out of scope — the ledger dicts are only ever swapped under the lock)
LOCK_GUARDS_DEFAULT = {
    "_spoke_flow": "_flow_lock",
    "_watchdog_fired": "_watchdog_lock",
    "_preempted": "_preempt_lock",
}

# donated-jit entry points: callable name -> (donated kwarg name,
# donated positional index, requires donate=... kwarg to actually
# donate). The wrappers (qp_solve etc.) donate their ``state`` only
# when called with a ``donate`` argument that is not literally False.
DONATING_DEFAULT = {
    "_qp_solve_jit_donated": ("state", 3, False),
    "_solve_lo_jit_donated": (None, 3, False),
    "_fused_mixed_jit_donated": ("iterates", 4, False),
    "qp_solve": ("state", 3, True),
    "qp_solve_segmented": ("state", 3, True),
    "qp_solve_mixed": ("state", 3, True),
    "fused_mixed_solve": ("state", 4, True),
    "kernel_solve": ("state", 4, True),
}


@dataclasses.dataclass
class LintConfig:
    """Path classification + rule knobs. Tests point these at fixture
    trees; the CLI uses the defaults rooted at the repo."""
    repo_root: str = REPO_ROOT
    hot_loop: tuple = HOT_LOOP_DEFAULT
    jax_free: tuple = JAX_FREE_DEFAULT
    lock_guards: dict = dataclasses.field(
        default_factory=lambda: dict(LOCK_GUARDS_DEFAULT))
    sync_allow: dict = dataclasses.field(
        default_factory=lambda: {k: dict(v) for k, v
                                 in SYNC_ALLOW_DEFAULT.items()})
    donating: dict = dataclasses.field(
        default_factory=lambda: dict(DONATING_DEFAULT))
    # OBS001 catalog: repo-relative doc files metric/event names must
    # resolve against (substring semantics, matching the historical
    # grep guard so the two agree)
    catalog_paths: tuple = ("doc/observability.md",)
    testing_package: str = "mpisppy_tpu/testing/"
    _catalog_cache: str | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def _matches(self, relpath: str, prefixes) -> bool:
        return any(relpath == p or relpath.startswith(p)
                   for p in prefixes)

    def is_hot(self, relpath: str) -> bool:
        return self._matches(relpath, self.hot_loop)

    def is_jax_free(self, relpath: str) -> bool:
        return self._matches(relpath, self.jax_free)

    def catalog_text(self) -> str:
        if self._catalog_cache is None:
            parts = []
            for p in self.catalog_paths:
                fp = os.path.join(self.repo_root, p)
                if os.path.exists(fp):
                    parts.append(open(fp, encoding="utf-8").read())
            self._catalog_cache = "\n".join(parts)
        return self._catalog_cache


# ------------------------------------------------------------- rules


class Rule:
    """Base class; subclasses register via :func:`register`."""
    name = "RULE000"
    summary = ""

    def check(self, mod: Module, cfg: LintConfig) -> list:
        raise NotImplementedError


_REGISTRY: dict[str, Rule] = {}


def register(rule_cls):
    """Class decorator: instantiate + register a rule by name."""
    _REGISTRY[rule_cls.name] = rule_cls()
    return rule_cls


def registry() -> dict:
    # import-for-effect: the rule modules self-register
    from . import rules  # noqa: F401
    return dict(_REGISTRY)


# ------------------------------------------------------------ running


def iter_py_files(paths, repo_root):
    """Yield (abspath, relpath) for every .py under ``paths``. Relative
    paths resolve against ``repo_root`` first (the tool is repo-scoped:
    the default ``mpisppy_tpu tools`` paths and scratch-tree configs
    must track their root), falling back to the caller's cwd so
    ``python -m tools.lint some/local/file.py`` works from anywhere."""
    for p in paths:
        ap = p
        if not os.path.isabs(ap):
            rooted = os.path.join(repo_root, p)
            ap = rooted if os.path.exists(rooted) else p
        if os.path.isfile(ap):
            yield ap, os.path.relpath(ap, repo_root)
        elif os.path.isdir(ap):
            for dirpath, dirnames, files in os.walk(ap):
                dirnames[:] = sorted(d for d in dirnames
                                     if not d.startswith(".")
                                     and d != "__pycache__")
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        fp = os.path.join(dirpath, fn)
                        yield fp, os.path.relpath(fp, repo_root)
        else:
            raise FileNotFoundError(p)


def lint_paths(paths, cfg: LintConfig | None = None, rules=None):
    """Run ``rules`` (default: all registered) over every .py under
    ``paths``. Returns the report dict (see ``--json``): open findings
    under ``"findings"``, settled suppressions under ``"suppressed"``."""
    cfg = cfg or LintConfig()
    active = registry()
    if rules:
        unknown = sorted(set(rules) - set(active))
        if unknown:
            raise KeyError(f"unknown rule(s): {unknown}")
        active = {k: v for k, v in active.items() if k in rules}

    open_findings: list[Finding] = []
    suppressed: list[Finding] = []
    n_files = 0
    for ap, rel in iter_py_files(paths, cfg.repo_root):
        n_files += 1
        try:
            src = open(ap, encoding="utf-8").read()
            mod = Module(ap, rel, src)
        # ValueError: ast.parse raises it (not SyntaxError) for NUL
        # bytes in source — a torn write must be a finding, not a
        # linter crash
        except (SyntaxError, UnicodeDecodeError, ValueError) as e:
            open_findings.append(Finding(
                "LINT002", rel.replace(os.sep, "/"),
                getattr(e, "lineno", 1) or 1, 0,
                f"unparseable source: {e.__class__.__name__}: {e}"))
            continue
        found: list[Finding] = []
        for rule in active.values():
            found.extend(rule.check(mod, cfg))
        # settle against suppressions
        reasonless_seen: set[int] = set()
        for f in sorted(found, key=lambda f: (f.line, f.col, f.rule)):
            sup = next((s for s in mod.suppressions.get(f.line, ())
                        if f.rule in s.rules), None)
            if sup is None:
                open_findings.append(f)
            elif not sup.reason:
                sup.used = True
                open_findings.append(f)
                if id(sup) not in reasonless_seen:   # once per marker
                    reasonless_seen.add(id(sup))
                    open_findings.append(Finding(
                        "LINT001", mod.relpath, sup.comment_line, 0,
                        f"suppression ok[{f.rule}] has no reason — "
                        "every allowlisted violation must explain "
                        "itself (doc/lint.md)"))
            else:
                sup.used = True
                f.suppressed, f.reason = True, sup.reason
                suppressed.append(f)
        # stale markers: a suppression for an ACTIVE rule that settled
        # nothing pre-authorizes a future violation on its line — flag
        # it so fixed violations shed their markers (rules filtered
        # out of this run are not judged)
        for sup_list in mod.suppressions.values():
            for s in sup_list:
                if not s.used and any(r in active for r in s.rules):
                    open_findings.append(Finding(
                        "LINT003", mod.relpath, s.comment_line, 0,
                        f"unused suppression ok[{','.join(s.rules)}] — "
                        "no matching finding on its line; remove the "
                        "stale marker (doc/lint.md)"))
    return {
        "schema_version": LINT_SCHEMA_VERSION,
        "root": cfg.repo_root,
        "paths": list(paths),
        "rules": sorted(active),
        "files_checked": n_files,
        "findings": [f.to_json() for f in open_findings],
        "suppressed": [f.to_json() for f in suppressed],
    }


# ------------------------------------------------------- ast helpers


def call_name(call: ast.Call) -> str | None:
    """The bare callee name of a Call: ``f(...)`` -> "f",
    ``a.b.f(...)`` -> "f"."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def dotted(node) -> str | None:
    """``a.b.c`` -> "a.b.c" for pure Name/Attribute chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None
