"""The wheel cell ``uc_s256_wheel`` (PR 39): its own data files
rehearsed at toy counts on the CPU (contract line, ``correct``, the
``wheel.*`` metrics in the traced line), the two controls not
correct, a program without the arbiter refused at once, its
configuration's shared keys against cell 1's, its entries in
``BENCHMARK.json`` and the readers on hand-made observations."""

import json
import os

import pytest

import harness
from test_rehearsal import UC_TOY_LIMITS, UC_TOY_VARIANT

CELL, CELL_1 = "uc_s256_wheel", "uc_s256_hub_hot"
CONFIG, CONFIG_1 = "uc90x48_df32_wheel", "uc90x48_df32"
NEW = {"wheel.hub_queue_wait_s": ("s", "ph_iter_s"),
       "wheel.exchange_s": ("s", "ph_iter_s"),
       "wheel.spoke_turn_share": ("%", "ph_iter_s"),
       "wheel.outer_period_s": ("s", "solves_per_s"),
       "wheel.inner_round_s": ("s", "solves_per_s"),
       "wheel.bound_lag_iters": ("syncs", "solves_per_s"),
       "wheel.spoke_capped_share": ("%", "solves_per_s")}
# 8 scenarios, four rows a device call in all three engines: two chunk
# solves a hub pass, two a Lagrangian pass, ten a pool round
TOY = {"scenarios": 8, "subproblem_chunk": 4, "reference_sample": 3,
       "ph_iter_range": 2, "lagrangian_sample": 3, "incumbent_sample": 3,
       "setup_deadline_s": 300}
# a toy window of a second holds no whole pool round, and its x-bar
# barely moves; the bound checks keep limits of the cell's kind
TOY_LIMITS = dict(UC_TOY_LIMITS, window_xbar_move_min=0.0,
                  inner_turns_min=2, outer_over_lp=1e-5, outer_slack=0.05,
                  inner_under_lp=2e-3, inner_slack=1e-2,
                  screen_under_lp=2e-3, screen_slack=0.2)


def rehearse(trace=False, control=None):
    variant = dict(UC_TOY_VARIANT)
    if control:
        variant["control"] = control
    return harness.run_cell(CELL, 2 ** 31 + 37, 1.0, trace,
                            require_chip=False, overrides=TOY,
                            limits=TOY_LIMITS, variant=variant)


def failed(line):
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_contract_line():
    line = rehearse()
    assert line["correct"] is True, failed(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    names = {c["name"] for c in line["checks"]}
    assert {"w_dual_feasible_err", "outer_over_lp", "outer_slack",
            "inner_under_lp", "inner_slack", "xhat_feasible",
            "inner_publishes_verified", "screen_in_window",
            "screen_feasible_candidates", "screen_plans_feasible",
            "screen_under_lp", "screen_slack", "outer_le_inner",
            "hub_bounds_published", "outer_updates", "inner_turns",
            "window_compiles"} <= names


def test_traced_line_reports_the_wheel_metrics():
    line = rehearse(trace=True)
    assert line["correct"] is True, failed(line)
    assert set(NEW) <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["wheel.spoke_turn_share"] < 100
    assert m["wheel.hub_queue_wait_s"] > 0 and m["wheel.exchange_s"] > 0
    assert m["wheel.bound_lag_iters"] >= 0
    assert 0 <= m["wheel.spoke_capped_share"] <= 100
    # never a device number from a rehearsal
    assert not any(k.startswith("solve.") for k in m)


# an uncertified outer bound shows on a sampled row, or (three rows of
# eight, a toy budget that leaves a solve under its LP) as the valid
# incumbent it crosses and the hub therefore rejects
@pytest.mark.parametrize("control, check", [
    ("uncertified_bound", {"outer_over_lp", "hub_bounds_published"}),
    ("unverified_incumbent", {"inner_publishes_verified"})])
def test_control_is_not_correct(control, check):
    line = rehearse(control=control)
    assert line["correct"] is False
    assert failed(line) & check, failed(line)


def test_a_program_without_the_arbiter_is_refused_at_once(monkeypatch):
    from mpisppy_tpu.cylinders.hub import Hub
    monkeypatch.delattr(Hub, "wheel_timing")
    with pytest.raises(SystemExit) as e:
        rehearse()
    assert "cannot run a wheel cell" in str(e.value)


def test_configuration_shares_cell_1s_keys():
    a = harness.load_json("configs", f"{CONFIG}.json")
    b = harness.load_json("configs", f"{CONFIG_1}.json")
    for key in ("instance", "shape", "recipe", "outer_dtype",
                "subproblem_chunk", "scenarios", "chips",
                "scenarios_per_chip"):
        assert a[key] == b[key], key
    for key, val in b["guarantees"].items():
        assert a["guarantees"][key] == val, key
    assert a["hub"] == "ph" and a["cylinders_per_chip"] == 3
    assert [s["kind"] for s in a["spokes"]] == ["lagrangian", "dive"]
    assert "spokes" not in a["reduced"]
    assert set(a["reduced"]) <= set(a["changed_from_source"])
    assert len(a["source"]) <= 200


def test_benchmark_json_lists_the_cell():
    """By name, not by position: a later PR appends its own entries."""
    bench = harness.load_benchmark()
    assert len(json.dumps(bench, indent=1)) < 64 * 1024
    cell = harness.load_json("workloads", f"{CELL}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert {k: cell[k] for k in entry} == entry
    assert entry["chips"] == 1 and traffic["driver"] == "wheel_hot"
    cfg = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["subproblem_chunk", "ranks"]
    assert cfg["source"] == harness.load_json(
        "configs", f"{CONFIG}.json")["source"]
    e2e = {m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"ph_iter_s", "solves_per_s", "setup_s"}
    per = {m["name"]: m for m in harness.metrics_of(bench, "per_layer", CELL)}
    for name, (unit, moves) in NEW.items():
        m = per[name]
        assert (m["unit"], m["moves"], m["layer"], m["workloads"]) == \
            (unit, moves, "cylinders", [CELL])
        assert os.path.isfile(os.path.join(harness.HERE, "metrics",
                                           f"{name}.py"))
    # the hub's solve seconds hold no queue wait (the arbiter books
    # that), so its chunk-solve readers report here too
    assert {"solve.fused_mixed_roofline", "solve.chunk_s",
            "solve.bulk_iters", "solve.tail_iters",
            # how the hub's solves ended (PR 37's six)
            "solve.tail_capped_share", "solve.bulk_capped_share",
            "solve.cap_rows", "solve.cap_rows_dual_share",
            "solve.cap_top8_share", "solve.capped_call_s"} <= set(per)


def reader(name):
    return harness.load_module("metrics", name).read


def test_readers_on_hand_made_observations():
    wheel = {
        "cylinders": {"hub": {"turns": 8, "rows": 512, "queue_wait_s": 6.0,
                              "device_s": 2.0},
                      "spoke0": {"turns": 8, "rows": 512,
                                 "queue_wait_s": 1.0, "device_s": 3.0},
                      "spoke1": {"turns": 10, "rows": 640,
                                 "queue_wait_s": 1.0, "device_s": 5.0}},
        "sync": {"syncs": 4, "seconds": 0.5},
        "spokes": {
            "spoke0": {"char": "L", "accepted_at": [1.0, 3.0, 4.0, 8.0],
                       "lag_iters": [1, 1, 2]},
            "spoke1": {"char": "D", "accepted_at": [5.0], "lag_iters": [4],
                       "own": {"rounds": {"round_s": [9.0, 7.0, 8.0]}}}}}
    obs = {"wheel": wheel, "hub_iterations": 2,
           "spoke_exits": {
               "spoke0": {"solves": 24, "tail_capped": 2, "bulk_capped": 0},
               "spoke1": {"solves": 24, "tail_capped": 22,
                          "bulk_capped": 24}}}
    assert reader("wheel.hub_queue_wait_s")(obs) == 3.0
    assert reader("wheel.exchange_s")(obs) == 0.125
    assert reader("wheel.spoke_turn_share")(obs) == 80.0
    assert reader("wheel.outer_period_s")(obs) == 2.0
    assert reader("wheel.inner_round_s")(obs) == 8.0
    wheel["spokes"]["spoke1"]["own"]["rounds"]["round_s"] = []
    assert reader("wheel.inner_round_s")(obs) is None    # none ended inside
    assert reader("wheel.bound_lag_iters")(obs) == 1.5
    assert reader("wheel.spoke_capped_share")(obs) == 50.0
    # a program with no arbiter (the parent, a hub-only cell): silent
    for name in NEW:
        assert reader(name)({}) is None
        assert reader(name)({"wheel": {"cylinders": None, "sync": None,
                                       "spokes": {}},
                             "hub_iterations": 3}) is None
    json.dumps(obs)         # what the readers get is plain data
