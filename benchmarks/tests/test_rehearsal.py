"""CPU rehearsal of every cell at toy counts: the contract line's keys,
the refusal to report without a TPU, the control (the recipe below its
stated precision comes out not correct) and the broken timed path."""

import json
import os
import subprocess
import sys

import pytest

import harness

ROOT = os.path.dirname(harness.HERE)
UC_TOY = {"scenarios": 8, "subproblem_chunk": 4, "reference_sample": 3,
          "ph_iter_range": 2}
# 3 generators x 6 hours: a width only a test may run (the driver
# refuses it on the chip)
UC_TOY_VARIANT = {"instance": {"num_gens": 3, "num_hours": 6}}
# at that width the budget-capped df32 recipe lands within 15% of the LP
# optimum at iter-0 (CPU readings 0.06-0.13); the width the limits in
# workloads/*.json were read at is the chip's
UC_TOY_LIMITS = {"iter0_obj_gap": 0.5, "hot_violation_q1": 1e-2,
                 "iter0_primal_violation": 1e-2,
                 "hot_primal_violation": 1e-2}
SERVE_TOY = {"reference_sample": 3, "trace_seconds": 2.0}
# name -> (cell, traffic parameters, limits, variant, chips)
CELLS = {
    "uc_s256_hub_hot": ("uc_s256_hub_hot", UC_TOY, UC_TOY_LIMITS,
                        UC_TOY_VARIANT, None),
    # the driver's mesh path (the four-chip cell of PERF.md's Open
    # question 1), on four virtual devices
    "uc_hub_hot_on_a_mesh": ("uc_s256_hub_hot",
                             dict(UC_TOY, subproblem_chunk=1),
                             UC_TOY_LIMITS, UC_TOY_VARIANT, 4),
    "farmer3_serve_c8": ("farmer3_serve_c8", SERVE_TOY,
                         {"objective_above_ef": 0.2}, None, None),
}


def rehearse(name, trace=False, seconds=1.0, seed=2 ** 31 + 11,
             variant=None, limits=None, **over):
    cell, toy, toy_limits, base, chips = CELLS[name]
    variant = {**(base or {}), **(variant or {})}
    return harness.run_cell(cell, seed, seconds, trace, require_chip=False,
                            overrides=dict(toy, **over),
                            limits={**toy_limits, **(limits or {})},
                            variant=variant, chips=chips)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_contract_line(cell):
    line = rehearse(cell, seconds=3.0 if "serve" in cell else 1.0)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert len(line["metrics"]) >= 2
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # no device metric without the chip: a CPU rehearsal's trace has no
    # device plane, so busy_s / idle shares / kernel times are left out
    traced = rehearse(cell, trace=True,
                      seconds=3.0 if "serve" in cell else 1.0)
    assert "busy_s" not in traced["device"]
    assert not any(k.startswith(("device.idle", "solve."))
                   for k in traced["metrics"]), traced["metrics"]
    assert traced["metrics"], "a traced run reports its host-side layers"


def test_run_py_refuses_without_a_tpu():
    bench = harness.load_benchmark()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def test_benchmark_json_matches_the_cell_files():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.load_json("workloads", f"{w['name']}.json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        harness.load_json("traffic", f"{w['traffic']}.json")
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            for name in m.get("workloads", []):
                assert any(w["name"] == name for w in bench["workloads"])
    for m in bench["per_layer"]:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")


def test_uc_control_below_df32_is_not_correct():
    """The control (``chip_controls.UC_CONTROL``): the recipe with its
    split-f32 refinement tail off, so every solve is f32 only, float64
    outer arithmetic kept. At 20 generators x 24 hours (n = 1,456; at
    3 x 6, f32 is as good as df32) the lower quartile of the scenarios'
    float64 primal violation after the window reads 7.6e-7 under the
    sound recipe and 3.2e-5 under the control (CPU); the limit here
    sits between them as the cell's own sits between the chip's
    readings at its width (1.40e-5 and 5.61e-5, PERF.md section 2)."""
    from chip_controls import UC_CONTROL
    mid = {"instance": {"num_gens": 20, "num_hours": 24}}
    limits = {"hot_violation_q1": 1e-5, "window_xbar_move_min": 0.01}
    sound = rehearse("uc_s256_hub_hot", variant=mid, limits=limits,
                     reference_sample=8)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse("uc_s256_hub_hot", variant={**mid, **UC_CONTROL},
                   limits=limits, reference_sample=8)
    failed = {c["name"] for c in ctl["checks"] if not c["ok"]}
    assert "hot_violation_q1" in failed and ctl["correct"] is False


def test_uc_reduce_that_is_not_exact(monkeypatch):
    """An x-bar that is off by one part in 1e6 (a reduce in float32, a
    shard left out of the psum) fails the exact recomputation."""
    from mpisppy_tpu.core.ph import PHBase

    real = PHBase.solve_loop

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        self.xbar = self.xbar * (1.0 + 1e-6)
        return out

    monkeypatch.setattr(PHBase, "solve_loop", broken)
    line = rehearse("uc_s256_hub_hot")
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "reduce_xbar_err" in failed and line["correct"] is False


def test_uc_step_that_returns_its_state_unchanged(monkeypatch):
    from mpisppy_tpu.core.ph import PHBase

    real, calls = PHBase.solve_loop, {"hot": 0}
    fields = ("x", "W_new", "xbar", "conv")

    def broken(self, w_on=True, prox_on=True, **kw):
        if not w_on:
            return real(self, w_on=w_on, prox_on=prox_on, **kw)
        calls["hot"] += 1
        keep = {f: getattr(self, f) for f in fields}
        out = real(self, w_on=w_on, prox_on=prox_on, **kw)
        if calls["hot"] > 2:        # the two warm-up iterations are real
            for f, v in keep.items():
                setattr(self, f, v)
        return out

    monkeypatch.setattr(PHBase, "solve_loop", broken)
    line = rehearse("uc_s256_hub_hot")
    assert calls["hot"] > 2 and line["correct"] is False


def test_serve_answer_altered_where_it_is_produced(monkeypatch):
    """A stacked wheel that hands tenants a result that is not their
    own: the solo re-send disagrees and the run is not correct."""
    from mpisppy_tpu.serve import manager

    real = manager.consensus_results

    def altered(engine, blocks, *a, **kw):
        out = real(engine, blocks, *a, **kw)
        for k, res in enumerate(out):
            if res["objective"] is not None:
                res["objective"] *= 1.0 + 0.01 * k
        return out

    monkeypatch.setattr(manager, "consensus_results", altered)
    line = rehearse("farmer3_serve_c8", seconds=3.0, reference_sample=12)
    assert line["correct"] is False
