"""The APH cell ``uc_s256_aph_hot`` (PR 34): its own data files
rehearsed at toy counts on the CPU (contract line, ``correct``, the
control not correct, a selection that ignores φ not correct), its four
readers on hand-made observations, its configuration against cell 1's
and its entries in ``BENCHMARK.json``."""

import os

import numpy as np
import pytest

import harness
from test_rehearsal import UC_TOY_LIMITS, UC_TOY_VARIANT

CELL = "uc_s256_aph_hot"
CELL_1 = "uc_s256_hub_hot"
CONFIG = "uc90x48_df32_aph"
TRAFFIC = "aph_hot_s256"
NEW = {"aph.step_s": ("s", "program_span"),
       "aph.gate_syncs": ("syncs/iter", "program_counter"),
       "dispatch.restage_s": ("s", "program_span"),
       "dispatch.solved_share": ("%", "program_counter")}
# 16 scenarios, four rows a device call: a partial pass at the
# configuration's frac 0.25 is ONE chunk solve of four, as the cell's
# 256 in 64s make one of 64
APH_TOY = {"scenarios": 16, "subproblem_chunk": 4, "reference_sample": 3,
           "ph_iter_range": 2}
APH_TOY_LIMITS = dict(UC_TOY_LIMITS, last_pass_violation_q1=1e-2,
                      window_z_move_min=0.01)


def reader(name):
    return harness.load_module("metrics", name).read


def rehearse(trace=False, variant=None, limits=None, **over):
    """The cell's own files at toy counts; only the instance width and
    the counts are a test's."""
    return harness.run_cell(
        CELL, 2 ** 31 + 37, 1.0, trace, require_chip=False,
        overrides=dict(APH_TOY, **over),
        limits={**APH_TOY_LIMITS, **(limits or {})},
        variant={**UC_TOY_VARIANT, **(variant or {})})


def failed(line):
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_contract_line_and_the_four_metrics(monkeypatch):
    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # every pass solves ceil(0.25 x 16) rows and no more
    assert line["attempted"] % 4 == 0
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    exact = {c["name"]: c["value"] for c in line["checks"]}
    for name in ("aph_w_err", "aph_z_err", "aph_scalars_err"):
        assert exact[name] <= 1e-12, (name, exact[name])
    assert exact["dispatched_rows"] == 4
    assert exact["dispatch_mask_mismatch"] == 0 \
        == exact["dispatch_select_mismatch"] \
        == exact["undispatched_rows_changed"]
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", "aph.step_s"):
            read = mod.read
            mod.read = lambda obs: (seen.update(obs=obs), read(obs))[1]
        return mod

    monkeypatch.setattr(harness, "load_module", spy)
    traced = rehearse(trace=True)
    obs = seen["obs"]
    assert obs["chunk_solves_per_iteration"] == 1
    phase = obs["phase"]
    d, a = phase["dispatch"], phase["aph"]
    assert d["passes"] == d["chunks"] == a["iterations"] == a["gate_syncs"]
    assert d["solved"] == 4 * d["passes"] and d["skipped"] == 12 * d["passes"]
    assert d["bucket_compiles"] == 0    # the warm-up saw the bucket
    assert phase["calls"] == d["passes"]
    # a dispatch pass's chunk solve is counted as a full pass's are
    assert phase["admm_iters_per_call"]["bulk"] > 0
    assert phase["assemble_programs_per_call"] == 1
    m = traced["metrics"]
    assert m["aph.gate_syncs"] == {"value": 1.0, "unit": "syncs/iter"}
    assert m["dispatch.solved_share"] == {"value": 25.0, "unit": "%"}
    assert m["aph.step_s"]["value"] > 0 and m["aph.step_s"]["unit"] == "s"
    assert m["dispatch.restage_s"]["value"] == pytest.approx(
        (d["gather_seconds"] + d["scatter_seconds"]) / d["passes"])
    # the gather is booked as assembly, the scatter-back in the reduce
    assert m["ph.assemble_s"]["value"] * d["passes"] >= d["gather_seconds"]
    assert m["reduce.host_s"]["value"] * d["passes"] >= d["scatter_seconds"]
    # no device metric without the chip
    assert "busy_s" not in traced["device"]
    assert not any(k.startswith(("device.idle", "solve."))
                   for k in traced["metrics"]), traced["metrics"]


def test_control_below_df32_is_not_correct():
    """``chip_controls.UC_CONTROL`` (the split-f32 tail off) through the
    APH path at 20 generators x 24 hours, where ``test_rehearsal`` reads
    it for cell 1: the violation quartiles separate sound from control
    here too (CPU: 8.9e-7 against 3.2e-5 over all rows), while the outer
    mathematics and the selection, float64 under both, stay exact."""
    from chip_controls import UC_CONTROL
    mid = {"instance": {"num_gens": 20, "num_hours": 24}}
    limits = {"hot_violation_q1": 1e-5, "last_pass_violation_q1": 1e-5}
    sound = rehearse(variant=mid, limits=limits, reference_sample=4)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse(variant={**mid, **UC_CONTROL}, limits=limits,
                   reference_sample=4)
    assert ctl["correct"] is False
    assert "hot_violation_q1" in failed(ctl)
    assert not failed(ctl) & {"aph_w_err", "aph_z_err", "aph_scalars_err",
                              "dispatch_mask_mismatch",
                              "undispatched_rows_changed"}


def test_a_selection_that_ignores_phi_is_not_correct(monkeypatch):
    """The first ceil(frac S) rows every pass, whatever φ says: the
    replay's mask disagrees."""
    import jax.numpy as jnp

    from mpisppy_tpu.core import aph as aph_mod

    real = aph_mod.dispatch_gate

    def first_rows(tau, phi, theta, conv, phis, last, *, scnt, S_real):
        g = real(tau, phi, theta, conv, phis, last, scnt=scnt,
                 S_real=S_real)
        mask = (jnp.arange(phis.shape[0]) < scnt).astype(g.dtype)
        return jnp.concatenate([g[:aph_mod.GATE_HEAD], mask])

    monkeypatch.setattr(aph_mod, "dispatch_gate", first_rows)
    line = rehearse()
    assert line["correct"] is False
    assert {"dispatch_mask_mismatch", "dispatch_select_mismatch"} \
        <= failed(line)
    assert "undispatched_rows_changed" not in failed(line)


def test_a_pass_that_rewrites_an_undispatched_row_is_not_correct(
        monkeypatch):
    from mpisppy_tpu.core.aph import APH

    real = APH._aph_solve

    def leaky(self, mask, didx=None):
        real(self, mask, didx=didx)
        if didx is not None:
            row = int(np.flatnonzero(~mask)[0])
            self.x = self.x.at[row, 0].add(1e-9)

    monkeypatch.setattr(APH, "_aph_solve", leaky)
    line = rehearse()
    assert failed(line) == {"undispatched_rows_changed"}


PHASE = {"seconds_per_call": {"assemble": 0.004, "reduce": 0.009},
         "aph": {"iterations": 50, "project_seconds": 0.25,
                 "gate_seconds": 0.5, "gate_syncs": 50},
         "dispatch": {"passes": 50, "chunks": 50, "solved": 3200,
                      "skipped": 9600, "gather_seconds": 0.1,
                      "scatter_seconds": 0.3, "bucket_compiles": 0}}


@pytest.mark.parametrize("name,want,key", [
    ("aph.step_s", 0.015, "aph"), ("aph.gate_syncs", 1.0, "aph"),
    ("dispatch.restage_s", 0.008, "dispatch"),
    ("dispatch.solved_share", 25.0, "dispatch")])
def test_readers(name, want, key):
    read = reader(name)
    assert read({"phase": PHASE}) == pytest.approx(want)
    # a program without the entry (the parent's), an engine that made
    # no such pass, a run with no phase at all: nothing, never a raise
    assert read({"phase": {k: v for k, v in PHASE.items()
                           if k != key}}) is None
    assert read({"phase": dict(PHASE, **{key: {k: 0 for k in PHASE[key]}})
                 }) is None
    assert read({"phase": None}) is None and read({}) is None


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "aph_step.py")
    with open(path, encoding="utf-8") as f:
        imports = [ln.split()[1] for ln in f
                   if ln.startswith(("import ", "from "))]
    assert imports == ["numpy"]


def test_the_configuration_is_cell_1s_instance_under_the_aph_hub():
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    aph = harness.load_json("configs", f"{CONFIG}.json")
    one = harness.load_json("configs", "uc90x48_df32.json")
    for key in ("instance", "shape", "recipe", "outer_dtype",
                "subproblem_chunk", "scenarios", "chips",
                "scenarios_per_chip"):
        assert aph[key] == one[key], key
    for key, value in one["guarantees"].items():
        if key != "consensus":      # restated for x-bar AND y-bar
            assert aph["guarantees"][key] == value, key
    assert (aph["hub"], aph["dispatch_frac"], aph["APHnu"],
            aph["APHgamma"], aph["aph_use_lag"]) == \
        ("aph", 0.25, 1.0, 1.0, False)
    assert aph["dispatched_per_chip"] == \
        aph["dispatch_frac"] * aph["scenarios_per_chip"] == \
        aph["subproblem_chunk"]         # ONE full chunk a pass
    assert (aph["name"], aph["source"], aph["reduced"]) == \
        (entry["name"], entry["source"], entry["reduced"])
    assert len(aph["source"]) <= 200
    assert all((c["source"], c["file"]) != (entry["source"], entry["file"])
               for c in bench["configs"] if c is not entry)
    assert set(aph["reduced"]) <= set(aph["changed_from_source"])
    assert set(aph["assumed"]) <= set(aph["changed_from_source"])
    p = harness.load_json("traffic", f"{TRAFFIC}.json")
    assert p["driver"] == "aph_hot"
    assert p["parameters"] == {
        "scenarios": aph["scenarios_per_chip"], "scenario_base": 0,
        "warm_partial_iterations": 2, "ph_iter_range": 32,
        "reference_sample": 12, "trace_seconds": 0.75}


def test_benchmark_json_holds_the_cell_and_its_four_metrics():
    """By name, not by position: a later PR appends its own entries."""
    bench = harness.load_benchmark()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    # cell 1's solve checks with cell 1's names, and the replay's
    assert set(harness.load_json("workloads", f"{CELL_1}.json")["limits"]) \
        - set(cell["limits"]) == {"reduce_xbar_err", "reduce_conv_err",
                                  "window_xbar_move_min"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ph_iter_s", "solves_per_s", "setup_s"}
    per = {m["name"] for m in bench["per_layer"]
           if CELL in m.get("workloads", [])}
    # what cell 1 reports, and its own four
    assert per == {m["name"] for m in bench["per_layer"]
                   if CELL_1 in m.get("workloads", [])} | set(NEW)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == \
            (unit, source, "PH engine", "ph_iter_s")
        assert m["workloads"] == [CELL] and callable(reader(name))
