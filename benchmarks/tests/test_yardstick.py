"""The yardstick's own arithmetic: the trace reduction on the recorded
v5e trace and on made-up intervals, the bytes model against the
program's, the peaks table, the plain references."""

import itertools
import os

import numpy as np
import pytest

import bytes_model
import harness
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduction_on_the_recorded_trace():
    """``record_trace.py`` on one v5e chip (PR 25): three rounds of a
    matmul program, a 20 ms host sleep and a loop program."""
    r = tr.reduce_file(os.path.join(DATA, "small_v5e.xplane.pb"))
    assert r["n_device_planes"] == 1
    assert 0.06 < r["window_s"] < 0.08
    assert 0 < r["busy_s"] < 1e-3 < r["window_s"]
    mods = {n.split("(")[0]: v for n, v in r["modules"].items()}
    assert set(mods) == {"jit_small_loop", "jit_small_matmul"}
    assert mods["jit_small_loop"][1] == 3
    names = [n for n, _ in r["top_ops"]]
    assert "multiply_add_fusion.2" in names and "while" in names
    # the while's self time excludes its body
    ops = dict(r["top_ops"])
    assert ops["while"] < ops["multiply_add_fusion.2"]
    assert r["collective_s"] == 0.0
    assert r["idle_gaps"][0][0].startswith("bench.sleep")
    assert abs(sum(v for _, v in r["idle_gaps"]) + r["busy_s"]
               - r["window_s"]) < 1e-9
    assert 0.019 < r["longest_gap_s"] < 0.03


def test_reduction_on_made_up_events():
    ms = 1_000_000
    dev = {"XLA Ops": [("while", 0, 10 * ms), ("fusion.1", 0, 4 * ms),
                       ("all-reduce.1", 4 * ms, 7 * ms),
                       ("fusion.2", 7 * ms, 10 * ms),
                       ("fusion.3", 14 * ms, 16 * ms)],
           "XLA Modules": [("jit_step(1)", 0, 10 * ms),
                           ("jit_step(1)", 14 * ms, 16 * ms)]}
    host = [("bench.traced", 0, 20 * ms), ("bench.wait", 10 * ms, 14 * ms),
            ("Transfer::D2H", 11 * ms, 13 * ms)]
    r = tr.reduce_events({"device": {"/device:TPU:0": dev,
                                     "/device:TPU:1": dev}, "host": host})
    assert r["n_device_planes"] == 2
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.012)
    assert r["collective_s"] == pytest.approx(0.003)
    assert r["collective_exposed_s"] == pytest.approx(0.003)
    assert dict(r["top_ops"])["while"] == pytest.approx(0.0)
    assert r["modules"]["jit_step(1)"] == [pytest.approx(0.012), 2]
    assert r["idle_gaps"][0] == ["bench.wait / Transfer::D2H",
                                 pytest.approx(0.004)]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]


def test_bytes_model_equals_the_programs():
    from mpisppy_tpu.ops.kernels import est_hbm_bytes_per_iter
    for n, m, s, pk, sweeps, bd in itertools.product(
            (13056, 768), (26016, 1500), (8, 64, 128), (None, 41_000_000),
            (1, 2), ("f32", "bf16")):
        kw = dict(n=n, m=m, s_chunk=s, pk_pass_bytes=pk, ir_sweeps=sweeps,
                  block_dtype=bd)
        assert bytes_model.hbm_bytes_per_iter(**kw) == \
            est_hbm_bytes_per_iter(**kw)


def test_peaks_table_is_the_programs_and_refuses_unknown_devices():
    from mpisppy_tpu.obs.profile import _PEAKS_BY_KIND
    row = harness.peaks_for("TPU v5 lite")
    assert (row["bf16_flops"], row["hbm_gbps"]) == \
        _PEAKS_BY_KIND["tpu v5 lite"]
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9")


def test_references():
    import farmer_ef
    import scenario_lp
    assert farmer_ef.ef_optimum() == pytest.approx(-108390.0, abs=1e-6)
    A = scenario_lp.sparse(np.array([[1.0, 1.0]]))
    # min -x0 - 2 x1 s.t. x0 + x1 <= 1, 0 <= x <= 1
    obj = scenario_lp.solve_lp(A, [-1.0, -2.0], 5.0, [-np.inf], [1.0],
                               [0.0, 0.0], [1.0, 1.0])
    assert obj == pytest.approx(3.0)
    # per scenario row: x0 + x1 = 1.5 breaks the row by 0.5; feasible
    viol = scenario_lp.primal_violation(
        A, [[1.0, 0.5], [0.5, 0.5]], np.full((2, 1), -np.inf),
        np.ones((2, 1)), np.zeros((2, 2)), np.ones((2, 2)))
    assert viol.tolist() == pytest.approx([0.5 / 1.5, 0.0])
    xbar, conv = scenario_lp.consensus([[0.0, 1.0], [1.0, 1.0]],
                                       [0.25, 0.75])
    assert xbar.tolist() == [0.75, 1.0]
    assert conv == pytest.approx((0.25 * 0.75 + 0.75 * 0.25) / 2)
