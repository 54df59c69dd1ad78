"""The cell ``sslp_10_50_s2000_hub_hot`` (driver ``ph_runs``) rehearsed
on the CPU at toy counts: the contract line, the files' parameters, the
control (the recipe below its stated precision comes out not correct),
a reset that leaves something of the run before (``window_runs_
identical`` catches it), and the three readers this cell brings."""

import pytest

import harness

CELL = "sslp_10_50_s2000_hub_hot"
TOY = {"scenarios": 6, "run_hot_iterations": 4, "ph_iter_range": 4,
       "reference_sample": 6, "trace_seconds": 1.0}
# 3 sites x 8 clients: a width only a test may run (the driver refuses
# it on the chip)
TOY_VARIANT = {"instance": {"num_servers": 3, "num_clients": 8,
                            "server_budget": 3, "capacity": 60.0}}
# at that width the budget-capped recipe lands elsewhere (CPU readings:
# iter-0 gap 1.6e-3 .. 1.6e-2, q1 2.0e-4 .. 3.0e-4, x-bar moves 0.04);
# the width the limits in workloads/*.json were read at is the chip's
TOY_LIMITS = {"iter0_obj_gap": 0.05, "trivial_bound_gap": 0.05,
              "iter0_primal_violation": 0.05, "hot_primal_violation": 0.01,
              "hot_violation_q1": 1e-3, "window_xbar_move_min": 0.01}
# the published width at 8 scenarios and 6 hot iterations a run
MID = {"scenarios": 8, "run_hot_iterations": 6, "ph_iter_range": 6,
       "reference_sample": 8}


def rehearse(trace=False, seconds=1.0, seed=2 ** 31 + 11, variant=None,
             limits=None, toy=TOY, base=TOY_VARIANT, **over):
    return harness.run_cell(CELL, seed, seconds, trace, require_chip=False,
                            overrides=dict(toy, **over),
                            limits={**TOY_LIMITS, **(limits or {})},
                            variant={**(base or {}), **(variant or {})})


def failed(line):
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_contract_line():
    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # whole runs: 1 + 4 iterations of 6 scenario solves each
    assert line["attempted"] % (5 * 6) == 0
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    names = {c["name"] for c in line["checks"]}
    assert names >= {"window_runs_identical", "kernel_as_stated",
                     "trivial_bound_gap", "trivial_bound_below_lp",
                     "iter0_obj_gap", "hot_violation_q1",
                     "reduce_xbar_err", "window_compiles"}
    traced = rehearse(trace=True)
    assert traced["correct"] is True
    assert "busy_s" not in traced["device"]
    got = traced["metrics"]
    # no device metric, no solve.* count from a CPU rehearsal
    assert not any(k.startswith(("device.idle", "solve.")) for k in got)
    assert {"ph.run_s", "ph.run_reset_s", "ph.assemble_s", "ph.gate_s",
            "reduce.host_s", "ph.iter_median_s",
            "setup.host_build_s"} <= set(got)
    assert 0 < got["ph.run_reset_s"]["value"] < got["ph.run_s"]["value"]
    assert got["ph.gate_s"]["value"] == 0.0       # un-chunked: no gate


def test_the_files_state_the_cell_as_issue_32_names_it():
    bench = harness.load_benchmark()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("sslp_10_50_df32", "hub_runs_s2000", 1) == \
        (cell["config"], cell["traffic"], cell["chips"])
    traffic = harness.load_json("traffic", "hub_runs_s2000.json")
    assert traffic["driver"] == "ph_runs"
    p = traffic["parameters"]
    assert p == {"scenarios": 2000, "scenario_base": 0,
                 "run_hot_iterations": 50, "warm_runs": 1,
                 "ph_iter_range": 50, "reference_sample": 2000,
                 "trace_seconds": 0.25}
    cfg_entry, = [c for c in bench["configs"]
                  if c["name"] == "sslp_10_50_df32"]
    cfg = harness.load_json("configs", "sslp_10_50_df32.json")
    assert cfg_entry["reduced"] == cfg["reduced"] == ["spokes"]
    assert cfg["shape"] == {"n": 520, "m": 61, "binary_nonants": 10}
    assert (cfg["scenarios"], cfg["chips"], cfg["subproblem_chunk"],
            cfg["outer_dtype"]) == (2000, 1, 0, "float64")
    assert cfg["max_iterations"] == p["run_hot_iterations"] \
        == cfg["recipe"]["PHIterLimit"]
    assert cfg["recipe"]["convthresh"] == 0.0
    assert cfg["recipe"]["defaultPHrho"] == 1.0
    assert not any(k.startswith("subproblem_kernel") for k in cfg["recipe"])
    assert set(cfg["assumed"]) <= set(cfg["changed_from_source"])
    # the recipe is bench.DF32's tolerance and budget keys
    import bench as program_bench
    for k, v in program_bench.DF32.items():
        if k not in ("defaultPHrho", "display_timing"):
            assert cfg["recipe"][k] == v, k
    uc = harness.load_json("configs", "uc90x48_df32.json")
    assert cfg["guarantees"]["pri_rel_gate"] \
        == uc["guarantees"]["pri_rel_gate"]
    # every per-layer metric that lists the cell has a reader
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert hasattr(harness.load_module("metrics", m["name"]),
                           "read")
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ph_iter_s", "solves_per_s", "setup_s"}


def test_control_below_df32_is_not_correct():
    """The control: ``subproblem_tail_iter`` 0 through ``run.variant``
    (what ``chip_controls.UC_CONTROL`` is), every solve the f32 bulk
    alone under the same float64 outer arithmetic. At the published
    width, 8 scenarios, the lower quartile of the float64 primal
    violation after the last run reads 1.36e-4 sound and 9.8e-4 under
    the control (CPU); the limit here sits between them as the cell's
    own sits between the chip's readings (PERF.md section 2)."""
    from chip_controls import UC_CONTROL
    wide = {"instance": {}}
    limits = {"hot_violation_q1": 3.7e-4}
    sound = rehearse(toy=MID, base=wide, limits=limits)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse(toy=MID, base=wide, limits=limits, variant=UC_CONTROL)
    assert "hot_violation_q1" in failed(ctl) and ctl["correct"] is False
    # the control changes the plan (no tail, no inverse to repay): the
    # stated-plan check steps aside and the NUMBERS fail it
    assert "kernel_as_stated" not in {c["name"] for c in ctl["checks"]}


def test_a_reset_that_keeps_the_warm_start_states(monkeypatch):
    """A ``reset_run`` that leaves the last run's QP states: the next
    run warm-starts from them and ends somewhere else."""
    from mpisppy_tpu.core.ph import PHBase

    real = PHBase.reset_run

    def broken(self):
        kept = dict(self._qp_states)
        real(self)
        self._qp_states.update(kept)

    monkeypatch.setattr(PHBase, "reset_run", broken)
    line = rehearse()
    assert line["attempted"] >= 2 * 5 * 6        # at least two runs
    assert "window_runs_identical" in failed(line)
    assert line["correct"] is False


def test_a_reduce_that_is_not_exact(monkeypatch):
    from mpisppy_tpu.core.ph import PHBase

    real = PHBase.solve_loop

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        self.xbar = self.xbar * (1.0 + 1e-6)
        return out

    monkeypatch.setattr(PHBase, "solve_loop", broken)
    assert "reduce_xbar_err" in failed(rehearse())


def test_width_is_held_on_the_chip():
    """``run.py`` (``on_chip``) refuses a cut instance or scenario
    count; only a test's variant may run one."""
    with pytest.raises(AssertionError, match="width was cut"):
        harness.run_cell(CELL, 1, 1.0, False, require_chip=False,
                         overrides=dict(TOY))     # S = 6 of 2000


@pytest.mark.parametrize("metric", ["ph.run_s", "ph.run_reset_s",
                                    "solve.linv_builds"])
def test_new_readers_on_a_program_without_the_counters(metric):
    """The parent commit's ``phase_timing`` has no ``runs`` and no
    ``linv_builds``: the readers return nothing and do not raise."""
    read = harness.load_module("metrics", metric).read
    parent = {"platform": "tpu", "chunk_solves_per_iteration": 1,
              "phase": {"seconds_per_call": {"solve": 0.05},
                        "admm_iters_per_call": {"bulk": 400.0,
                                                "tail": 100.0,
                                                "refactors": 0.0}}}
    assert read(parent) is None
    assert read({}) is None
    change = dict(parent, phase=dict(
        parent["phase"],
        admm_iters_per_call={"bulk": 400.0, "tail": 100.0,
                             "refactors": 0.0, "linv_builds": 0.04},
        runs={"count": 4, "seconds": 12.0, "reset_seconds": 0.02}))
    want = {"ph.run_s": 3.0, "ph.run_reset_s": 0.005,
            "solve.linv_builds": 0.04}[metric]
    assert read(change) == pytest.approx(want)
    if metric == "solve.linv_builds":
        assert read(dict(change, platform="cpu")) is None
