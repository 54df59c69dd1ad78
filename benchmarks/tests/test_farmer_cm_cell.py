"""The cell ``farmer_cm32_s1024_hub_hot`` (driver ``ph_hot_stack``)
rehearsed on the CPU at toy counts (``crops_multiplier`` 2, S = 8): the
contract line, the files' parameters, the kernel check that ends a run
before iter-0 where the program's rules differ from the configuration's
(what the parent tree does on the chip), the control below the stated
precision, and the four readers this cell brings."""

import jax
import pytest

import harness

CELL = "farmer_cm32_s1024_hub_hot"
TOY = {"scenarios": 8, "warm_hot_iterations": 1, "ph_iter_range": 3,
       "reference_sample": 8, "reference_sample_factors": 4,
       "trace_seconds": 1.0}
# n = 24, m = 13: a width only a test may run (the driver refuses it on
# the chip)
TOY_VARIANT = {"instance": {"crops_multiplier": 2}}
# CPU readings at that width: iter-0 gap 1e-11, violation 3e-13; hot
# violation 5e-7 (q1 2e-7); x-bar moves 100
TOY_LIMITS = {"iter0_obj_gap": 1e-6, "trivial_bound_gap": 1e-4,
              "iter0_primal_violation": 1e-8,
              "hot_primal_violation": 1e-5, "hot_violation_q1": 5e-6,
              "window_xbar_move_min": 1.0}


@pytest.fixture
def tpu_rules(monkeypatch):
    """The rules answer as on the TPU (they read the backend's NAME;
    ``jax.lax.platform_dependent`` still lowers this backend's library
    calls), so the stated kernel block can be held in a rehearsal."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def rehearse(trace=False, seconds=1.0, seed=2 ** 31 + 11, variant=None,
             limits=None, **over):
    return harness.run_cell(CELL, seed, seconds, trace, require_chip=False,
                            overrides=dict(TOY, **over),
                            limits={**TOY_LIMITS, **(limits or {})},
                            variant={**TOY_VARIANT, **(variant or {})})


def failed(line):
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_contract_line(tpu_rules):
    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["attempted"] % 8 == 0
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    names = {c["name"] for c in line["checks"]}
    assert names >= {"window_pri_rel_max", "reduce_xbar_err",
                     "reduce_conv_err", "window_xbar_move",
                     "kernel_as_stated", "kkt_inverse_err",
                     "iter0_obj_gap", "trivial_bound_gap",
                     "trivial_bound_below_lp", "iter0_primal_violation",
                     "hot_primal_violation", "hot_violation_q1",
                     "window_compiles"}
    traced = rehearse(trace=True)
    assert traced["correct"] is True
    assert "busy_s" not in traced["device"]
    got = traced["metrics"]
    # no device metric, no solve.* number from a CPU rehearsal
    assert not any(k.startswith(("device.idle", "solve.")) for k in got)
    assert {"ph.assemble_s", "ph.gate_s", "reduce.host_s",
            "ph.iter_median_s", "setup.host_build_s",
            "setup.compile_s"} <= set(got)
    assert got["ph.gate_s"]["value"] == 0.0       # un-chunked: no gate


def test_rules_that_differ_from_the_stated_kernel_end_the_run_at_once():
    """On a backend whose rules answer otherwise (here the CPU: the
    library forms; on the chip the parent tree: ``host`` and
    ``library``) the driver exits non-zero before iter-0."""
    with pytest.raises(SystemExit) as e:
        rehearse()
    assert e.value.code == 4


def test_the_files_state_the_cell_as_issue_45_names_it():
    bench = harness.load_benchmark()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("farmer_cm32_f64", "stack_hot_s1024", 1) == \
        (cell["config"], cell["traffic"], cell["chips"])
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    traffic = harness.load_json("traffic", "stack_hot_s1024.json")
    assert traffic["driver"] == "ph_hot_stack"
    assert traffic["parameters"] == {
        "scenarios": 1024, "scenario_base": 0, "warm_hot_iterations": 2,
        "ph_iter_range": 8, "reference_sample": 1024,
        "reference_sample_factors": 16, "trace_seconds": 0.5}
    cfg_entry, = [c for c in bench["configs"]
                  if c["name"] == "farmer_cm32_f64"]
    cfg = harness.load_json("configs", "farmer_cm32_f64.json")
    assert cfg_entry["reduced"] == cfg["reduced"] == ["spokes"]
    assert cfg["architecture"] is None
    assert cfg["shape"] == {"n": 384, "m": 193, "nonants": 96}
    assert cfg["instance"] == {"crops_multiplier": 32}
    assert (cfg["scenarios"], cfg["chips"], cfg["subproblem_chunk"],
            cfg["outer_dtype"]) == (1024, 1, 0, "float64")
    assert cfg["recipe"]["subproblem_precision"] == "native"
    assert cfg["recipe"]["defaultPHrho"] == 1.0
    assert not any(k.startswith("subproblem_kernel") for k in cfg["recipe"])
    assert set(cfg["assumed"]) <= set(cfg["changed_from_source"])
    assert set(cfg["recipe"]) - {"iter0"} <= set(cfg["recipe_what"])
    assert cell["limits"]["kkt_inverse_err"] == 1e-10
    uc = harness.load_json("configs", "uc90x48_df32.json")
    assert cfg["guarantees"]["pri_rel_gate"] \
        == uc["guarantees"]["pri_rel_gate"]
    for k in ("subproblem_eps_hot", "subproblem_eps_dua_hot",
              "subproblem_polish_hot"):
        assert cfg["recipe"][k] == uc["recipe"][k]
    # every per-layer metric that lists the cell has a reader
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    for name in listed:
        assert hasattr(harness.load_module("metrics", name), "read")
    assert {"solve.f64_refactor_build_s", "solve.f64_refactor_roofline",
            "solve.f64_stack_roofline", "solve.refactors"} <= set(listed)
    assert not {"solve.fused_mixed_roofline", "solve.linv_builds",
                "solve.linv_build_s", "solve.linv_applies"} & set(listed)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ph_iter_s", "solves_per_s", "setup_s"}


@pytest.mark.parametrize("control", ["float32", "mixed_no_tail"])
def test_a_control_below_float64_is_not_correct(control):
    """The controls (``benchmarks/tests/chip_controls.py`` runs them on
    the chip): the engine in float32, or the recipe mixed with no
    float64 tail. In float32 the consensus reduce itself is a float32
    one (x-bar 3e-8, conv 2e-6 from the float64 recomputation: the
    limits that catch it on the chip too, PERF.md section 2; the hot
    violation does NOT move, a float32 loop stops at the hot tolerance
    where the float64 one does); with no tail iter-0 itself is the f32
    bulk's point and its objectives miss the reference LPs. The
    stated-kernel check steps aside and the NUMBERS fail the run."""
    variant = {"float32": {"outer_dtype": "float32"},
               "mixed_no_tail": {"recipe": {
                   "subproblem_precision": "mixed",
                   "subproblem_tail_iter": 0}}}[control]
    ctl = rehearse(variant=variant)
    assert ctl["correct"] is False
    caught = {"float32": {"reduce_xbar_err", "reduce_conv_err"},
              "mixed_no_tail": {"iter0_obj_gap"}}[control]
    assert caught <= failed(ctl)
    assert "kernel_as_stated" not in {c["name"] for c in ctl["checks"]}


def _phase(admm, solve_s, build):
    return {"platform": "tpu", "device_kind": "TPU v5 lite",
            "chunk_solves_per_iteration": 1,
            "phase": {"admm_iters_per_call": admm,
                      "seconds_per_call": {"solve": solve_s},
                      "solve_shape": {"n": 384, "m": 193, "s_chunk": 1024},
                      "kernel": {"mode": "fused", "f64_products": "reduce",
                                 "f64_refactor": "blocked"},
                      "f64_refactor_build": build}}


def test_the_four_readers():
    """The arithmetic of the readers this cell brings, on a made-up
    observation: 100 ADMM iterations and half a rebuild a solve of 0.5
    s; one eager build of 3 s. And what a program without the span, the
    counter or such a factor gives: nothing, not an error."""
    import f64_stack_model
    read = lambda name, obs: harness.load_module("metrics", name).read(obs)
    obs = _phase({"bulk": 0, "tail": 100.0, "refactors": 0.5}, 0.5,
                 {"builds": 1, "seconds": 3.0, "rows": 1024, "n": 384})
    per_iter = 8 * 1024 * (2 * 193 * 384 + 384 * 384) \
        + 48 * 1024 * (193 + 384)
    assert f64_stack_model.admm_iteration_bytes(rows=1024, m=193, n=384) \
        == per_iter
    assert read("solve.f64_stack_roofline", obs) == pytest.approx(
        100.0 * 100 * per_iter / (0.5 * 819e9))
    assert 0 < read("solve.f64_stack_roofline", obs) < 100
    assert read("solve.refactors", obs) == 0.5
    assert read("solve.f64_refactor_build_s", obs) == 3.0
    build = 8 * 1024 * (193 * 384 + 384 * 384 + 193 + 384)
    assert f64_stack_model.refactor_build_bytes(rows=1024, m=193, n=384) \
        == build
    assert read("solve.f64_refactor_roofline", obs) == pytest.approx(
        100.0 * build / (3.0 * 819e9))
    # the parent's program: no such span; a df32 cell: no such factor;
    # a CPU rehearsal: no device number
    bare = _phase({"bulk": 0, "tail": 100.0, "refactors": 0.5}, 0.5, None)
    del bare["phase"]["f64_refactor_build"]
    assert read("solve.f64_refactor_build_s", bare) is None
    assert read("solve.f64_refactor_roofline", bare) is None
    df32 = dict(obs, phase=dict(obs["phase"], kernel={"mode": "fused",
                                                      "f64_products": None,
                                                      "f64_refactor": None}))
    assert read("solve.f64_stack_roofline", df32) is None
    for name in ("solve.f64_stack_roofline", "solve.refactors",
                 "solve.f64_refactor_build_s",
                 "solve.f64_refactor_roofline"):
        assert read(name, dict(obs, platform="cpu")) is None
        assert read(name, {"platform": "tpu", "phase": None}) is None
