"""The FWPH cell ``uc_s256_fwph_hot`` (PR 48): its own data files
rehearsed at toy counts on the CPU (contract line, ``correct``, the
control not correct, a broken timed path for each compared number that
can be broken here), its readers and its QP model on hand-made
observations, its configuration against cell 1's and its entries in
``BENCHMARK.json``."""

import os

import numpy as np
import pytest

import fwph_qp_model
import harness
from test_rehearsal import UC_TOY_VARIANT

CELL = "uc_s256_fwph_hot"
CELL_1 = "uc_s256_hub_hot"
CONFIG = "uc90x48_df32_fwph"
TRAFFIC = "fwph_hot_s256"
LAYER = "FWPH engine"
NEW = {"fwph.passes_per_iter": ("passes/iter", "program_counter",
                                "ph_iter_s"),
       "fwph.linearized_s": ("s", "program_span", "ph_iter_s"),
       "fwph.column_s": ("s", "program_span", "ph_iter_s"),
       "fwph.qp_s": ("s", "program_span", "ph_iter_s"),
       "fwph.host_reads_per_pass": ("reads/pass", "program_counter",
                                    "ph_iter_s"),
       "fwph.bound_gain": ("%", "program_counter", "solves_per_s"),
       "fwph.simplex_qp_roofline": ("%", "device_trace", "ph_iter_s")}
# eight scenarios, four rows a device call: two chunk solves a pass
FW_TOY = {"scenarios": 8, "subproblem_chunk": 4, "reference_sample": 3,
          "qp_sample": 3, "ph_iter_range": 2}
# the violations at the toy width (3 generators x 6 hours) and the dual
# certificate's looseness there; every other limit is the cell's own
FW_TOY_LIMITS = {"hot_violation_q1": 1e-2, "hot_primal_violation": 1e-2,
                 "bound_under_lp": 0.05, "window_xbar_move_min": 1e-3}


def reader(name):
    return harness.load_module("metrics", name).read


def rehearse(trace=False, variant=None, limits=None, seconds=1.0, **over):
    """The cell's own files at toy counts; only the instance width and
    the counts are a test's."""
    return harness.run_cell(
        CELL, 2 ** 31 + 41, seconds, trace, require_chip=False,
        overrides=dict(FW_TOY, **over),
        limits={**FW_TOY_LIMITS, **(limits or {})},
        variant={**UC_TOY_VARIANT, **(variant or {})})


def failed(line):
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_contract_line_and_the_new_metrics(monkeypatch):
    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["attempted"] % 16 == 0      # two passes of eight rows
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    exact = {c["name"]: c["value"] for c in line["checks"]}
    for name in ("w_t_err", "gamma_err", "xn_err", "reduce_xbar_err",
                 "update_w_err", "update_conv_err", "w_manifold_err",
                 "qp_feas_err"):
        assert exact[name] <= 1e-12, (name, exact[name])
    assert exact["pool_slot_ok"] == exact["bound_monotone"] == 1.0
    assert exact["bound_above_lp"] <= 0 < exact["bound_under_lp"]
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", "fwph.qp_s"):
            read = mod.read
            mod.read = lambda obs: (seen.update(obs=obs), read(obs))[1]
        return mod

    monkeypatch.setattr(harness, "load_module", spy)
    traced = rehearse(trace=True)
    assert traced["correct"] is True, traced["checks"]
    obs = seen["obs"]
    assert obs["chunk_solves_per_iteration"] == 2
    fw, phase = obs["phase"]["fwph"], obs["phase"]
    assert fw["passes"] == 2 * fw["iterations"] == phase["calls"]
    assert fw["host_reads"] == fw["passes"] + fw["iterations"]
    assert fw["columns_written"] == fw["bounds_published"] * 2 \
        == fw["passes"]
    assert fw["bounds_dropped"] == fw["passes_ended_by_gamma"] == 0
    assert obs["fwph_qp_shape"] == {"rows": 8, "slots": 16, "nonants": 36,
                                    "iters": 400, "itemsize": 8}
    m = traced["metrics"]
    assert m["fwph.passes_per_iter"] == {"value": 2.0,
                                         "unit": "passes/iter"}
    assert m["fwph.host_reads_per_pass"] == {"value": 1.0,
                                             "unit": "reads/pass"}
    for name in ("fwph.linearized_s", "fwph.column_s", "fwph.qp_s"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "s"
    assert m["fwph.bound_gain"]["value"] > 0
    # the linearized solve's seconds hold the chunked loop's four phases
    assert m["fwph.linearized_s"]["value"] >= sum(
        phase["seconds_per_call"].values()) * 0.99
    # no device metric without the chip
    assert "busy_s" not in traced["device"]
    assert not any(k.startswith(("device.idle", "solve."))
                   or k == "fwph.simplex_qp_roofline"
                   for k in traced["metrics"]), traced["metrics"]


def test_control_below_df32_is_not_correct():
    """``chip_controls.UC_CONTROL`` (the split-f32 tail off) through the
    FWPH path at 6 generators x 12 hours, where a prox-off solve still
    converges inside its budget (``tests/test_fwph_reference.py`` reads
    the same pair): the violation quartile separates sound from control
    (CPU: 4.6e-5 against 1.8e-4), while the QP and the outer update,
    float64 under both, stay exact. The window is ONE outer iteration
    (the third: a window of time holds nine times as many of the
    control's cheaper iterations, and the quartile falls with them)."""
    from chip_controls import UC_CONTROL
    mid = {"instance": {"num_gens": 6, "num_hours": 12}}
    limits = {"hot_violation_q1": 9e-5, "bound_under_lp": 0.5}
    over = {"warm_outer_iterations": 2, "seconds": 1e-3}
    sound = rehearse(variant=mid, limits=limits, **over)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse(variant={**mid, **UC_CONTROL}, limits=limits, **over)
    assert ctl["correct"] is False
    assert "hot_violation_q1" in failed(ctl)
    assert not failed(ctl) & {"xn_err", "qp_feas_err", "reduce_xbar_err",
                              "update_w_err", "gamma_err", "pool_slot_ok",
                              "w_manifold_err"}


def _break(monkeypatch, what):
    """One fault in the TIMED path, each of the kind a wrong
    optimisation would make."""
    import jax.numpy as jnp

    from mpisppy_tpu.core import fwph as mod

    if what == "xbar_off_by_1e-6":
        real = mod._ph_combine

        def combine(*a, **kw):
            xbar, xsq, W, conv = real(*a, **kw)
            return xbar + 1e-6, xsq, W, conv
        monkeypatch.setattr(mod, "_ph_combine", combine)
    elif what == "state_handed_back":
        real = mod._ph_combine

        def combine(xn, prob, xw, mem, W, *a, **kw):
            _xbar, xsq, _W, conv = real(xn, prob, xw, mem, W, *a, **kw)
            keep = combine.xbar if combine.xbar is not None else _xbar
            combine.xbar = keep
            return keep, xsq, W, conv
        combine.xbar = None
        monkeypatch.setattr(mod, "_ph_combine", combine)
    elif what in ("qp_stops_early", "xn_not_a_times_G", "weights_in_f32"):
        real = mod.simplex_qp_solve

        def solve(G, b, w, rho, xbar, a0, iters):
            if what == "qp_stops_early":
                return real(G, b, w, rho, xbar, a0, iters=2)
            if what == "weights_in_f32":
                f = lambda v: v.astype(jnp.float32)
                a, xn = real(f(G), f(b), f(w), f(rho), f(xbar), f(a0),
                             iters=iters)
                return a.astype(G.dtype), xn.astype(G.dtype)
            a, xn = real(G, b, w, rho, xbar, a0, iters=iters)
            return a, xn * (1.0 + 1e-6)
        monkeypatch.setattr(mod, "simplex_qp_solve", solve)
    elif what in ("gamma_without_c0", "bound_from_primal"):
        real = mod._column_step

        def step(columns, G, base, a, xn_t, w_t, x_star, dual, c, c0,
                 *rest, **kw):
            if what == "bound_from_primal":
                # the primal objective of an inexact solve, with the
                # df32 gate's tolerance as its unconverged excess
                # (``wheel_hot.CONTROL_EXCESS``): no dual certifies it
                q = jnp.sum(c * x_star, axis=-1) + c0 + jnp.sum(
                    w_t * x_star[:, rest[3]], axis=-1)
                dual = q + 5e-3 * jnp.abs(q)
            out = real(columns, G, base, a, xn_t, w_t, x_star, dual, c,
                       c0, *rest, **kw)
            if what == "gamma_without_c0":
                row = out[-1]
                out = out[:-1] + (row.at[0].add(1e-6 * row[1]),)
            return out
        monkeypatch.setattr(mod, "_column_step", step)
    elif what == "column_in_the_wrong_slot":
        real = mod.FWPH._next_slot

        def slot(self):
            real(self)
            return jnp.asarray(0, jnp.int32)
        monkeypatch.setattr(mod.FWPH, "_next_slot", slot)
    elif what == "w_off_the_manifold":
        real = mod.FWPH.iter0

        def iter0(self):
            real(self)
            self.W = self.W.at[0].add(1.0)
        monkeypatch.setattr(mod.FWPH, "iter0", iter0)
    else:
        raise KeyError(what)


@pytest.mark.parametrize("what,names", [
    ("xbar_off_by_1e-6", {"reduce_xbar_err"}),
    ("state_handed_back", {"window_xbar_move", "reduce_xbar_err"}),
    ("qp_stops_early", {"qp_obj_gap"}),
    ("xn_not_a_times_G", {"xn_err"}),
    ("weights_in_f32", {"xn_err"}),
    ("gamma_without_c0", {"gamma_err"}),
    ("bound_from_primal", {"bound_value_err"}),
    ("column_in_the_wrong_slot", {"pool_slot_ok"}),
    ("w_off_the_manifold", {"w_manifold_err", "bounds_dropped"}),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, what, names):
    _break(monkeypatch, what)
    line = rehearse()
    assert line["correct"] is False
    assert names <= failed(line), (what, failed(line))


PHASE = {"fwph": {"iterations": 4, "passes": 8, "passes_ended_by_gamma": 0,
                  "host_reads": 12, "linearized_seconds": 24.0,
                  "column_seconds": 0.08, "qp_seconds": 0.16,
                  "bound_gamma_seconds": 0.004, "update_seconds": 0.02,
                  "bounds_published": 4, "bounds_dropped": 0,
                  "columns_written": 8, "pool_wraps": 0, "qp_iters": 3200}}


@pytest.mark.parametrize("name,want", [
    ("fwph.passes_per_iter", 2.0), ("fwph.linearized_s", 3.0),
    ("fwph.column_s", 0.01), ("fwph.qp_s", 0.02),
    ("fwph.host_reads_per_pass", 1.0)])
def test_readers(name, want):
    read = reader(name)
    assert read({"phase": PHASE}) == pytest.approx(want)
    # a program without the entry (the parent's), an engine that made
    # no pass, a run with no phase at all: nothing, never a raise
    assert read({"phase": {}}) is None
    assert read({"phase": {"fwph": {k: 0 for k in PHASE["fwph"]}}}) is None
    assert read({"phase": None}) is None and read({}) is None


def test_bound_gain_reader():
    read = reader("fwph.bound_gain")
    assert read({"fwph_bound_gain": 0.068}) == pytest.approx(6.8)
    assert read({}) is None


SHAPE = {"rows": 256, "slots": 16, "nonants": 8640, "iters": 400,
         "itemsize": 8}


def test_the_qp_model_holds_its_arithmetic():
    """The cell's own QP by hand: the (256, 16, 8640) float64 block is
    283,115,520 B."""
    block = 256 * 16 * 8640 * 8
    assert block == 283_115_520
    vectors = 4 * 256 * 8640 * 8
    hessian = 256 * 16 * 16 * 8
    trips = 400 * 4 * 256 * 16 * 8
    assert fwph_qp_model.qp_bytes(**SHAPE) \
        == 2 * block + vectors + hessian + trips == 689_963_008
    assert fwph_qp_model.qp_multiply_adds(**SHAPE) \
        == 256 * 16 * 16 * 8640 + 2 * 256 * 16 * 8640 + 400 * 256 * 256 \
        == 663_224_320
    peaks = harness.peaks_for("TPU v5 lite")
    floor, bound = fwph_qp_model.floor_seconds(SHAPE, peaks)
    assert bound == "hbm"
    assert floor == pytest.approx(689_963_008 / 819e9)
    # a chip with a tenth of the rate for operations is bound by them
    slow = dict(peaks, bf16_flops=1e12)
    assert fwph_qp_model.floor_seconds(SHAPE, slow) == (
        pytest.approx(2 * 663_224_320 / 1e12), "flops")


def test_the_roofline_reader():
    read = reader("fwph.simplex_qp_roofline")
    floor = 689_963_008 / 819e9
    obs = {"platform": "tpu", "device_kind": "TPU v5 lite",
           "fwph_qp_shape": SHAPE,
           "trace": {"modules": {"jit_simplex_qp_solve(123)": [0.028, 2.0],
                                 "jit__column_step(9)": [0.004, 2.0]}}}
    assert read(obs) == pytest.approx(100.0 * floor / 0.014)
    assert 0 < read(obs) < 100
    # no trace, no chip, a slice without the program, a program without
    # the shape: nothing, never a raise
    assert read(dict(obs, trace=None)) is None
    assert read(dict(obs, platform="cpu")) is None
    assert read(dict(obs, trace={"modules": {}})) is None
    assert read({k: v for k, v in obs.items()
                 if k != "fwph_qp_shape"}) is None


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "fwph_step.py")
    with open(path, encoding="utf-8") as f:
        imports = [ln.split()[1] for ln in f
                   if ln.startswith(("import ", "from "))]
    assert imports == ["numpy", "scipy.optimize", "scipy.sparse"]


def test_the_configuration_is_cell_1s_instance_as_the_fwph_cylinder():
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    fw = harness.load_json("configs", f"{CONFIG}.json")
    one = harness.load_json("configs", "uc90x48_df32.json")
    for key in ("instance", "shape", "recipe", "outer_dtype",
                "subproblem_chunk", "scenarios", "chips",
                "scenarios_per_chip"):
        assert fw[key] == one[key], key
    for key, value in one["guarantees"].items():
        assert fw["guarantees"][key] == value, key
    assert set(fw["guarantees"]) - set(one["guarantees"]) \
        == {"outer_bound", "qp"}
    assert fw["architecture"] is None
    assert (fw["cylinder"], fw["FW_iter_limit"], fw["FW_conv_thresh"],
            fw["fwph_max_columns"], fw["fwph_qp_iters"]) == \
        ("fwph", 2, 1e-4, 16, 400)
    assert (fw["name"], fw["source"], fw["reduced"]) == \
        (entry["name"], entry["source"], entry["reduced"])
    assert fw["reduced"] == ["subproblem_chunk", "ranks", "cylinders",
                             "FW_iter_limit"]
    assert len(fw["source"]) <= 200
    assert all((c["source"], c["file"]) != (entry["source"], entry["file"])
               for c in bench["configs"] if c is not entry)
    assert set(fw["reduced"]) <= set(fw["changed_from_source"])
    assert set(fw["assumed"]) - set(fw["changed_from_source"]) \
        == {"fwph_max_columns", "fwph_qp_iters"}    # under fwph_parameters
    assert {"pool", "linearized_solve", "bound"} \
        <= set(fw["changed_from_source"])
    p = harness.load_json("traffic", f"{TRAFFIC}.json")
    assert p["driver"] == "fwph_hot"
    assert p["parameters"] == {
        "scenarios": fw["scenarios_per_chip"], "scenario_base": 0,
        "subproblem_chunk": 64, "warm_outer_iterations": 1,
        "ph_iter_range": 4, "reference_sample": 12, "qp_sample": 16,
        "trace_seconds": 0.5}


def test_benchmark_json_holds_the_cell_and_its_metrics():
    """By name, not by position: a later PR appends its own entries."""
    bench = harness.load_benchmark()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ph_iter_s", "solves_per_s", "setup_s"}
    per = {m["name"] for m in bench["per_layer"]
           if CELL in m.get("workloads", [])}
    # what cell 1 reports, and its own
    assert per == {m["name"] for m in bench["per_layer"]
                   if CELL_1 in m.get("workloads", [])} | set(NEW)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, moves) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == \
            (unit, source, LAYER, moves)
        assert m["workloads"] == [CELL] and callable(reader(name))
    assert np.isfinite(list(cell["limits"].values())).all()
