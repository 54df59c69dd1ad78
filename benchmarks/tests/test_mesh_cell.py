"""The four-chip cell ``uc_s1024_mesh4_hub_hot`` (PR 28): its own data
files rehearsed at toy counts on four virtual CPU devices (contract
line, ``correct``, the control not correct), its three readers on the
recorded one-chip trace, on made-up two-chip events and on hand-made
observations, and its entries in ``BENCHMARK.json``."""

import os

import pytest

import harness
import trace_reduce as tr
from test_rehearsal import UC_TOY, UC_TOY_LIMITS, UC_TOY_VARIANT

CELL = "uc_s1024_mesh4_hub_hot"
CELL_1 = "uc_s256_hub_hot"
CONFIG = "uc90x48_df32_mesh4"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = {"mesh.collective_s": ("s", "device_trace", "device"),
       "mesh.collective_exposed_s": ("s", "device_trace", "device"),
       "reduce.collective_bytes": ("B", "program_counter",
                                   "consensus reduce")}
# 8 scenarios over the cell's four chips, one row per device call: two
# SPMD chunk solves an iteration, as the cell's 256 a chip in 64s make four
MESH_TOY = dict(UC_TOY, subproblem_chunk=1)


def reader(name):
    return harness.load_module("metrics", name).read


def rehearse(trace=False, variant=None, limits=None, **over):
    """The cell's own files (configuration, traffic mix, chips = 4) at
    toy counts; only the instance width and the counts are a test's."""
    return harness.run_cell(
        CELL, 2 ** 31 + 29, 1.0, trace, require_chip=False,
        overrides=dict(MESH_TOY, **over),
        limits={**UC_TOY_LIMITS, **(limits or {})},
        variant={**UC_TOY_VARIANT, **(variant or {})})


def test_contract_line_on_four_virtual_devices(monkeypatch):
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", "reduce.collective_bytes"):
            read = mod.read
            mod.read = lambda obs: (seen.update(obs=obs), read(obs))[1]
        return mod

    line = rehearse()
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] >= 4
    assert set(line["metrics"]) == {"ph_iter_s", "solves_per_s", "setup_s"}
    exact = {c["name"]: c for c in line["checks"]}
    assert exact["reduce_xbar_err"]["value"] <= 1e-12
    assert exact["reduce_conv_err"]["value"] <= 1e-12
    monkeypatch.setattr(harness, "load_module", spy)
    traced = rehearse(trace=True)
    phase = seen["obs"]["phase"]
    assert phase["mode"] == "sharded" and phase["devices"] == 4
    assert seen["obs"]["chunk_solves_per_iteration"] == 2
    # the psum's payload is a count, so a CPU rehearsal reports it: one
    # combine a hot call, num/den/squares of (1 node, K nonants) + conv
    K = 2 * 3 * 6                   # u and st of 3 generators x 6 hours
    assert phase["collective"] == {"combines": 1.0,
                                   "bytes": float(3 * K * 8 + 8)}
    assert traced["metrics"]["reduce.collective_bytes"] == {
        "value": float(3 * K * 8 + 8), "unit": "B"}
    # no device metric without the chip
    assert "busy_s" not in traced["device"]
    assert not any(k.startswith(("mesh.", "device.idle", "solve."))
                   for k in traced["metrics"]), traced["metrics"]


def test_control_below_df32_is_not_correct_on_the_mesh():
    """``chip_controls.UC_CONTROL`` (the split-f32 tail off) through the
    mesh path, at the width ``test_rehearsal`` reads it on one device
    (20 generators x 24 hours): the lower quartile of the float64 primal
    violation separates sound from control here too, and it is that
    number which fails."""
    from chip_controls import UC_CONTROL
    mid = {"instance": {"num_gens": 20, "num_hours": 24}}
    limits = {"hot_violation_q1": 1e-5, "window_xbar_move_min": 0.01}
    sound = rehearse(variant=mid, limits=limits, reference_sample=8)
    assert sound["correct"] is True, sound["checks"]
    ctl = rehearse(variant={**mid, **UC_CONTROL}, limits=limits,
                   reference_sample=8)
    failed = {c["name"] for c in ctl["checks"] if not c["ok"]}
    assert "hot_violation_q1" in failed and ctl["correct"] is False
    assert not failed & {"reduce_xbar_err", "reduce_conv_err"}


TWO_CHIPS = {"n_device_planes": 2, "window_s": 0.25, "busy_s": 0.2,
             "collective_s": 0.004, "collective_exposed_s": 0.001}


@pytest.mark.parametrize("name,key", [
    ("mesh.collective_s", "collective_s"),
    ("mesh.collective_exposed_s", "collective_exposed_s")])
def test_trace_readers(name, key):
    read = reader(name)
    assert read({"trace": dict(TWO_CHIPS)}) == TWO_CHIPS[key]
    # a mesh whose slice holds no collective reads 0, not nothing
    assert read({"trace": dict(TWO_CHIPS, **{key: 0.0})}) == 0.0
    # off the chip, and on a one-chip cell
    assert read({"trace": None}) is None and read({}) is None
    assert read({"trace": dict(TWO_CHIPS, n_device_planes=1)}) is None
    one_chip = tr.reduce_file(os.path.join(DATA, "small_v5e.xplane.pb"))
    assert one_chip["n_device_planes"] == 1
    assert read({"trace": one_chip}) is None
    # the reduction they read, on made-up events of two chips
    ms = 1_000_000
    dev = {"XLA Ops": [("while", 0, 10 * ms), ("fusion.1", 0, 5 * ms),
                       ("all-reduce.1", 4 * ms, 7 * ms),
                       ("fusion.2", 7 * ms, 10 * ms)]}
    r = tr.reduce_events({"device": {"/device:TPU:0": dev,
                                     "/device:TPU:1": dev},
                          "host": [("bench.traced", 0, 10 * ms)]})
    assert reader("mesh.collective_s")({"trace": r}) == \
        pytest.approx(0.003)
    assert reader("mesh.collective_exposed_s")({"trace": r}) == \
        pytest.approx(0.002)


def test_collective_bytes_reader():
    read = reader("reduce.collective_bytes")
    phase = {"seconds_per_call": {"reduce": 0.05},
             "collective": {"combines": 1.0, "bytes": 207368.0}}
    assert read({"phase": phase}) == 207368.0
    # a one-chip engine books none; the parent's program has no counter
    assert read({"phase": dict(phase, collective={"combines": 0.0,
                                                  "bytes": 0.0})}) is None
    assert read({"phase": {"seconds_per_call": {}}}) is None
    assert read({"phase": None}) is None and read({}) is None


def test_the_mesh_configuration_is_cell_1s_instance_on_four_chips():
    """The deployment's own file: what the driver builds from it is
    cell 1's configuration key for key (one program at one per-chip
    share), and what differs is the layout it states."""
    bench = harness.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    mesh4 = harness.load_json("configs", f"{CONFIG}.json")
    one = harness.load_json("configs", "uc90x48_df32.json")
    for key in ("instance", "shape", "recipe", "outer_dtype",
                "subproblem_chunk", "scenarios_per_chip"):
        assert mesh4[key] == one[key], key
    assert mesh4["guarantees"]["pri_rel_gate"] == \
        one["guarantees"]["pri_rel_gate"]
    assert (mesh4["name"], mesh4["source"], mesh4["reduced"]) == \
        (entry["name"], entry["source"], entry["reduced"])
    # no other configuration's source and cuts: a deployment of its own
    assert all((c["source"], c["reduced"]) !=
               (entry["source"], entry["reduced"])
               for c in bench["configs"] if c is not entry)
    p = harness.load_json("traffic", "hub_hot_s1024.json")["parameters"]
    assert mesh4["chips"] == 4 and mesh4["scenarios"] == p["scenarios"] \
        == mesh4["chips"] * mesh4["scenarios_per_chip"]
    assert set(mesh4["reduced"]) <= set(mesh4["changed_from_source"])


def test_benchmark_json_holds_the_cell_and_its_three_metrics():
    """By name, not by position: a later PR appends its own entries."""
    bench = harness.load_benchmark()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "hub_hot_s1024", 4)
    p = harness.load_json("traffic", "hub_hot_s1024.json")["parameters"]
    assert (p["scenarios"], p["scenario_base"]) == (1024, 0)
    assert "subproblem_chunk" not in p       # the configuration's 64
    # every compared number of the driver has its limit
    assert set(cell["limits"]) == set(harness.load_json(
        "workloads", f"{CELL_1}.json")["limits"])
    # the cell reports whatever cell 1 reports, and its own three
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL_1 in m.get("workloads", []):
                assert CELL in m["workloads"], m["name"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
        assert m["moves"] == "ph_iter_s" and m["workloads"] == [CELL]
        assert m["better"] == "lower" and callable(reader(name))
