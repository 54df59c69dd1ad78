"""The cell ``uc_s256_chunk128`` (driver ``ph_hot``, traffic
``hub_hot_s256``, configuration ``uc90x48_df32_chunk128``) rehearsed on
the CPU at toy counts: two chunk solves an iteration, an explicit
inverse built in more than one panel, the control below df32, the
files against cell 1's, and the three readers the cell brings with the
yardstick for the build's bytes."""

import jax
import pytest

import harness
import linv_bytes_model

CELL = "uc_s256_chunk128"
CONFIG = "uc90x48_df32_chunk128"
# 8 scenarios in two chunks of 4: the cell's two chunk solves a hot
# iteration
TOY = {"scenarios": 8, "subproblem_chunk": 4, "reference_sample": 3,
       "ph_iter_range": 2}
TOY_VARIANT = {"instance": {"num_gens": 3, "num_hours": 6}}
TOY_LIMITS = {"iter0_obj_gap": 0.5, "hot_violation_q1": 1e-2,
              "iter0_primal_violation": 1e-2,
              "hot_primal_violation": 1e-2}
NEW = ("solve.linv_build_s", "solve.linv_build_roofline",
       "solve.linv_applies")


def rehearse(trace=False, seconds=1.0, seed=2 ** 31 + 41, variant=None,
             limits=None, **over):
    return harness.run_cell(CELL, seed, seconds, trace, require_chip=False,
                            overrides=dict(TOY, **over),
                            limits={**TOY_LIMITS, **(limits or {})},
                            variant={**TOY_VARIANT, **(variant or {})})


def reader(name):
    return harness.load_module("metrics", name).read


def rehearsed_obs(monkeypatch, **kw):
    """The observations a traced CPU rehearsal hands the readers."""
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", "solve.linv_applies"):
            read = mod.read
            mod.read = lambda obs: (seen.update(obs=obs), read(obs))[1]
        return mod

    monkeypatch.setattr(harness, "load_module", spy)
    line = rehearse(trace=True, **kw)
    assert line["correct"] is True, line["checks"]
    assert not any(k in line["metrics"] for k in NEW)   # chip only
    return seen["obs"]


@pytest.fixture
def small_panels(monkeypatch):
    """The build's constants below the toy's n = 66, so that its
    inverse spans several panels (jitted entries cache on shapes and
    never see a patched constant: jax's caches are cleared around)."""
    import mpisppy_tpu.ops.qp_solver as qs
    monkeypatch.setattr(qs, "_LINV_PANEL", 32)
    monkeypatch.setattr(qs, "_TRI_BLOCK", 16)
    jax.clear_caches()
    yield qs
    jax.clear_caches()


def test_rehearsal_two_chunks_and_a_panelled_inverse(monkeypatch,
                                                    small_panels):
    """The contract line, then what the readers are handed: two chunk
    solves an iteration; with the explicit inverse asked for (the
    program's option, ``run.variant``) it is built in more than one
    panel, the build tells its seconds and shape, and the solves tell
    their products: 2 a solve, (1 + sweeps) solves a tail iteration."""
    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] % 8 == 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    obs = rehearsed_obs(monkeypatch, variant={
        "recipe": {"subproblem_kernel_l_inv": "on"}})
    assert obs["chunk_solves_per_iteration"] == 2
    phase = obs["phase"]
    assert phase["kernel"]["l_inv"] is True
    build = phase["linv_build"]
    assert build["n"] == phase["solve_shape"]["n"] == 66
    assert build["panels"] == small_panels.l_inv_panels(66) == 3
    assert build["builds"] >= 1 and build["seconds"] > 0
    admm = phase["admm_iters_per_call"]
    assert admm["linv_applies"] == 4 * admm["tail"] > 0
    # on the chip the three report; never from a rehearsal
    chip = dict(obs, platform="tpu", device_kind="TPU v5 lite")
    assert reader("solve.linv_applies")(chip) == admm["linv_applies"] / 2
    assert reader("solve.linv_build_s")(chip) == \
        build["seconds"] / build["builds"]
    assert 0 < reader("solve.linv_build_roofline")(chip) < 100
    assert all(reader(n)(obs) is None for n in NEW)


def test_control_below_df32_is_not_correct():
    """``chip_controls.UC_CONTROL`` (the split-f32 refinement tail off)
    at 20 generators x 24 hours in two chunks of 4, as cell 1's
    rehearsal holds it: the limit between the sound and the control
    reading of ``hot_violation_q1``, which the control fails."""
    from chip_controls import UC_CONTROL
    mid = {"instance": {"num_gens": 20, "num_hours": 24}}
    limits = {"hot_violation_q1": 1e-5, "window_xbar_move_min": 0.01}
    sound = rehearse(variant=mid, limits=limits, reference_sample=8)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse(variant={**mid, **UC_CONTROL}, limits=limits,
                   reference_sample=8)
    failed = {c["name"] for c in ctl["checks"] if not c["ok"]}
    assert "hot_violation_q1" in failed and ctl["correct"] is False


def test_the_files_state_cell_1_at_chunk_128():
    """The configuration is ``uc90x48_df32`` key for key but for the
    chunk and what says so; the cell runs it under cell 1's traffic and
    cell 1's guards; ``BENCHMARK.json`` lists it wherever cell 1 is
    listed, in ``solve.linv_builds`` and in the new readers that find
    something to read in it."""
    bench = harness.load_benchmark()
    cfg = harness.load_json("configs", f"{CONFIG}.json")
    base = harness.load_json("configs", "uc90x48_df32.json")
    told = {"name", "source", "deployment", "subproblem_chunk", "kernel",
            "kernel_what", "changed_from_source", "reduced"}
    assert {k for k in set(cfg) | set(base)
            if cfg.get(k) != base.get(k)} == told
    assert cfg["subproblem_chunk"] == 128 == 2 * base["subproblem_chunk"]
    assert cfg["scenarios_per_chip"] == 2 * cfg["subproblem_chunk"]
    assert cfg["reduced"] == ["spokes"] and cfg["assumed"] == base["assumed"]
    changed = dict(base["changed_from_source"])
    changed.pop("subproblem_chunk")
    assert cfg["changed_from_source"] == changed
    assert set(cfg["kernel"]) == {"mode", "l_inv"}
    for text in ("1000scenarios_wind", "2013-05-11/Scenario_1.dat",
                 "bench_1024"):
        assert text in cfg["source"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = harness.load_json("workloads", f"{CELL}.json")
    one = harness.load_json("workloads", "uc_s256_hub_hot.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, one["traffic"], 1)
    assert set(cell["limits"]) == set(one["limits"])
    assert cell["limits"]["reduce_xbar_err"] == 1e-9 \
        == cell["limits"]["reduce_conv_err"]
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    assert "subproblem_chunk" not in traffic["parameters"]
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == \
        (CONFIG, cell["traffic"], 1)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if CELL in m.get("workloads", ())}
    cell1 = {m["name"] for g in ("end_to_end", "per_layer")
             for m in bench[g] if "uc_s256_hub_hot" in m.get("workloads", ())}
    assert listed >= cell1 | {"solve.linv_builds", "solve.linv_applies"}
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by[name]["layer"] == "chunk solve"
        assert "sslp_10_50_s2000_hub_hot" in by[name]["workloads"]
    assert (by["solve.linv_build_s"]["moves"],
            by["solve.linv_build_roofline"]["moves"],
            by["solve.linv_applies"]["moves"]) == \
        ("setup_s", "setup_s", "ph_iter_s")
    # a build reader lists this cell only if its plan builds an inverse
    builds = cfg["kernel"]["l_inv"]
    for name in NEW[:2]:
        assert (CELL in by[name]["workloads"]) is builds


def test_build_bytes_equal_the_programs():
    from mpisppy_tpu.ops.kernels import est_l_inv_build_bytes
    for n in (13056, 520, 66, 1):
        assert linv_bytes_model.linv_build_bytes(n=n) == \
            est_l_inv_build_bytes(n=n) == n * n * 4
    assert linv_bytes_model.linv_build_bytes(n=10, factor_bytes=8) == \
        est_l_inv_build_bytes(n=10, factor_bytes=8) == 800


CHIP = {"platform": "tpu", "device_kind": "TPU v5 lite",
        "chunk_solves_per_iteration": 2}


def test_readers_on_made_up_observations():
    """0.25 s for one build at n = 13,056: 681.8 MB / (0.25 s x 819
    GB/s) = 0.333%; two builds in 0.5 s read the same."""
    ph = {"admm_iters_per_call": {"tail": 60.0, "linv_applies": 240.0},
          "linv_build": {"builds": 2, "seconds": 0.5, "n": 13056,
                         "panels": 6}}
    obs = dict(CHIP, phase=ph)
    assert reader("solve.linv_build_s")(obs) == 0.25
    assert reader("solve.linv_build_roofline")(obs) == pytest.approx(
        100 * 13056 ** 2 * 4 / (0.25 * 819e9))
    assert reader("solve.linv_applies")(obs) == 120.0
    off = dict(CHIP, phase={"admm_iters_per_call": {"tail": 60.0,
                                                    "linv_applies": 0.0},
                            "linv_build": {}})
    assert reader("solve.linv_applies")(off) == 0.0
    assert reader("solve.linv_build_s")(off) is None
    assert reader("solve.linv_build_roofline")(off) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_on_a_program_without_the_span_and_counter(name):
    """The parent's ``phase_timing`` has neither key: nothing is
    reported and nothing raises; nor off the TPU, nor with no phase."""
    parent = dict(CHIP, phase={"admm_iters_per_call": {
        "bulk": 50.0, "tail": 60.0, "refactors": 0.0, "linv_builds": 0.0}})
    assert reader(name)(parent) is None
    assert reader(name)(dict(CHIP, phase=None)) is None
    assert reader(name)({"platform": "cpu", "phase": {
        "admm_iters_per_call": {"linv_applies": 8.0},
        "linv_build": {"builds": 1, "seconds": 0.1, "n": 66}},
        "chunk_solves_per_iteration": 2}) is None
