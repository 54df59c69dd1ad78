"""The thirteen per-layer metrics of PR 50 that read the serving layer's
own record (``mpisppy_tpu.serve.timing``): each reader on the CPU
rehearsal of ``farmer3_serve_c8``, what each gives where there is
nothing to read, ``BENCHMARK.json`` against them BY NAME, and the
recorded trace with a gap under one of the new spans."""

import math
import os

import pytest

import harness
import trace_reduce as tr
from test_rehearsal import rehearse

CELL = "farmer3_serve_c8"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# name -> (unit, source, layer)
NEW = {
    "serve.wheel_engine_s": ("s", "program_span", "serving"),
    "serve.wheel_hub_setup_s": ("s", "program_span", "serving"),
    "serve.wheel_main_s": ("s", "program_span", "serving"),
    "serve.wheel_finalize_s": ("s", "program_span", "serving"),
    "serve.wheel_results_s": ("s", "program_span", "serving"),
    "serve.wheel_solve_s": ("s", "program_span", "serving"),
    "serve.wheel_admm_iters": ("iters/wheel", "program_counter",
                               "serving"),
    "serve.between_wheels_s": ("s", "program_span", "serving queue"),
    "serve.finish_s": ("s", "program_span", "serving queue"),
    "serve.queue_idle_s": ("s", "program_span", "serving queue"),
    "serve.batch_hold_s": ("s", "program_span", "serving queue"),
    "serve.prepare_s": ("s", "program_span", "serving queue"),
    "serve.req_queue_s": ("s", "program_span", "serving queue"),
}
STEPS = ("engine", "hub_setup", "main", "finalize", "results")


def reader(name):
    return harness.load_module("metrics", name).read


@pytest.fixture(scope="module")
def traced():
    """The traced CPU rehearsal's line: its readers ran against the
    record of the service the driver started (and stopped)."""
    return rehearse(CELL, trace=True, seconds=3.0)


def test_every_reader_reports_on_the_rehearsal(traced):
    m = traced["metrics"]
    assert traced["correct"] is True, traced["checks"]
    for name, (unit, _src, _layer) in NEW.items():
        assert name in m, (name, sorted(m))
        assert m[name]["unit"] == unit
        assert math.isfinite(m[name]["value"]) and m[name]["value"] >= 0
    val = {k: m[k]["value"] for k in m}
    # the five steps are the stamp's seconds; the solve is inside them
    steps = sum(val[f"serve.wheel_{k}_s"] for k in STEPS)
    assert steps == pytest.approx(val["serve.wheel_s"], rel=0.25)
    assert 0 < val["serve.wheel_solve_s"] < val["serve.wheel_main_s"] \
        + val["serve.wheel_results_s"]
    assert val["serve.wheel_admm_iters"] >= 12      # 12 solves a wheel
    parts = sum(val[k] for k in ("serve.finish_s", "serve.queue_idle_s",
                                 "serve.batch_hold_s", "serve.prepare_s"))
    assert parts == pytest.approx(val["serve.between_wheels_s"], rel=0.5)
    # the readers read memory: the record of the stopped service
    from mpisppy_tpu.serve import timing
    snap = timing.latest().snapshot()
    assert snap["totals"]["wheels"] == len(snap["wheels"]) > 0
    assert snap["totals"]["requests"] == len(snap["requests"]) \
        >= traced["attempted"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none_not_an_error(traced, name):
    """Wheels that match no record (another service's stamps), no
    wheels, no observations at all; and a program without
    ``serve.timing`` (the parent of PR 50)."""
    read = reader(name)
    for obs in ({"wheels": [{"seconds": 123.456, "stack": 8}]},
                {"wheels": []}, {}):
        assert read(obs) is None
    # under half of the window's wheels found: still nothing
    from mpisppy_tpu.serve import timing
    have = [w["seconds"] for w in timing.latest().snapshot()["wheels"]]
    obs = {"wheels": [{"seconds": have[-1], "stack": 1}]
           + [{"seconds": 1000.0 + k, "stack": 1} for k in range(2)]}
    assert read(obs) is None


def test_a_program_without_the_record_reads_none(traced, monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "mpisppy_tpu.serve.timing", None)
    from mpisppy_tpu import serve
    monkeypatch.delattr(serve, "timing", raising=False)
    obs = {"wheels": [{"seconds": 0.25, "stack": 8}]}
    for name in NEW:
        assert reader(name)(obs) is None


def test_benchmark_json_lists_them_by_name():
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"], m["workloads"]) == \
            (unit, source, layer, "req_per_s", "lower", [CELL]), m
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    listed = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                    CELL)}
    assert set(NEW) <= listed
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    assert {"serving", "serving queue"} <= layers   # no layer is new


def test_a_gap_under_a_batch_window_span_is_attributed():
    """The recorded v5e trace (``record_trace.py``): its largest gap is
    the 20 ms host sleep under the benchmark's own ``bench.sleep``,
    which names no span of the program. The same trace with that wait
    under the program's ``serve.batch.window`` is labelled so, and the
    serve cell's unattributed share falls by that gap."""
    events = tr.load(os.path.join(DATA, "small_v5e.xplane.pb"))
    unattributed = reader("device.idle_unattributed.serve")
    before = tr.reduce_events(events)
    assert before["idle_gaps"][0][0].startswith("bench.sleep")
    events["host"] = [("serve.batch.window" if n == "bench.sleep" else n,
                       s, e) for n, s, e in events["host"]]
    after = tr.reduce_events(events)
    label, seconds = after["idle_gaps"][0]
    assert label.split(" / ")[0] == "serve.batch.window"
    assert seconds == before["idle_gaps"][0][1] > 0.019
    idle = sum(v for _l, v in after["idle_gaps"])
    assert unattributed({"trace": before}) - unattributed({"trace": after}) \
        == pytest.approx(100.0 * seconds / idle)
    assert not any(lab == "bench.traced" and v > 5e-3
                   for lab, v in after["idle_gaps"])
