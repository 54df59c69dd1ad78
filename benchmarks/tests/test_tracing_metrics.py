"""The five per-layer metrics that read the program's own tracing
(PR 26): each reader on made-up observations, the roofline share
against a hand sum, what a program WITHOUT the counters or spans gives
(nothing, and no error), the CPU rehearsal's counts, and
``BENCHMARK.json`` against ``benchmarks/metrics/``."""

import pytest

import bytes_model
import harness

NEW = {"solve.bulk_iters": ("iter/solve", "program_counter", "chunk solve",
                            "ph_iter_s", ["uc_s256_hub_hot"]),
       "solve.tail_iters": ("iter/solve", "program_counter", "chunk solve",
                            "ph_iter_s", ["uc_s256_hub_hot"]),
       "solve.fused_mixed_roofline": ("%", "program_span", "chunk solve",
                                      "ph_iter_s", ["uc_s256_hub_hot"]),
       "device.idle_unattributed.ph": ("%", "device_trace", "device",
                                       "ph_iter_s", ["uc_s256_hub_hot"]),
       "device.idle_unattributed.serve": ("%", "device_trace", "device",
                                          "req_per_s",
                                          ["farmer3_serve_c8"])}
SHAPE = {"n": 13056, "m": 26016, "s_chunk": 64, "ir_sweeps": 1,
         "pk_pass_bytes": 41_000_000, "block_dtype": "f32"}


def reader(name):
    return harness.load_module("metrics", name).read


def uc_obs(platform="tpu", **phase):
    """What ``ph_hot`` hands the readers: four chunk solves a call."""
    ph = {"seconds_per_call": {"assemble": 0.1, "solve": 5.4,
                               "gate": 0.003, "reduce": 0.05},
          "admm_iters_per_call": {"bulk": 4 * 175.0, "tail": 4 * 100.0},
          "solve_shape": dict(SHAPE)}
    ph.update(phase)
    return {"phase": ph, "chunk_solves_per_iteration": 4,
            "platform": platform, "device_kind": "TPU v5 lite"}


def test_count_readers():
    assert reader("solve.bulk_iters")(uc_obs()) == 175.0
    assert reader("solve.tail_iters")(uc_obs()) == 100.0
    # a solve with no low-precision phase: everything is tail
    native = uc_obs(admm_iters_per_call={"bulk": 0, "tail": 4 * 1200})
    assert reader("solve.bulk_iters")(native) == 0.0
    assert reader("solve.tail_iters")(native) == 1200.0


def test_roofline_against_a_hand_sum():
    b = bytes_model.hbm_bytes_per_iter(**SHAPE)
    # per call: 700 bulk and 400 tail iterations; 5.4 s at 819 GB/s
    want = 100.0 * (700 * b["bulk"] + 400 * b["tail"]) / (5.4 * 819e9)
    got = reader("solve.fused_mixed_roofline")(uc_obs())
    assert got == pytest.approx(want, rel=1e-12)
    assert 2.8e9 < b["tail"] < 3.1e9 and b["bulk"] < b["tail"] / 2
    assert 0.0 < got < 100.0
    # twice the seconds for the same work: half the share
    slow = uc_obs(seconds_per_call={"solve": 10.8})
    assert reader("solve.fused_mixed_roofline")(slow) == \
        pytest.approx(got / 2)
    with pytest.raises(SystemExit):     # no default peak
        reader("solve.fused_mixed_roofline")(
            dict(uc_obs(), device_kind="TPU v9"))


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_is_none_not_an_error(name):
    """A program without the counters (the parent of PR 26), a serve
    cell's observations, a run off the TPU, a run with no trace."""
    read = reader(name)
    parent = uc_obs()
    del parent["phase"]["admm_iters_per_call"]
    del parent["phase"]["solve_shape"]
    for obs in (parent, {"wheels": [], "platform": "tpu", "trace": None},
                {"platform": "tpu"}):
        assert read(obs) is None
    if name.startswith("solve."):
        assert read(uc_obs(platform="cpu")) is None


def test_idle_unattributed():
    read = reader("device.idle_unattributed.ph")
    assert read is not reader("device.idle_unattributed.serve") \
        and read({"trace": None}) is None
    gaps = [["ph.assemble / DeferredTpuAllocator::Allocate", 0.030],
            ["ph.solve.chunk / DoEnqueueProgram", 0.010],
            ["qp.segment / np.asarray(jax.Array)", 0.020],
            ["serve.wheel.results", 0.005],     # a span, no runtime event
            ["ReadSyncFlag", 0.017],            # a runtime event alone
            ["bench.traced / CommonPjRtBuffer::ToLiteral", 0.008],
            ["no host span", 0.010]]
    for name in ("device.idle_unattributed.ph",
                 "device.idle_unattributed.serve"):
        got = reader(name)({"trace": {"idle_gaps": gaps}})
        assert got == pytest.approx(100.0 * 0.035 / 0.100)
    # the parent's labels: runtime events and the benchmark's own span
    assert read({"trace": {"idle_gaps": gaps[4:]}}) == 100.0
    assert read({"trace": {"idle_gaps": gaps[:4]}}) == 0.0
    assert read({"trace": {"idle_gaps": []}}) == 0.0


def test_rehearsal_counts_the_iterations(monkeypatch):
    """A CPU rehearsal of the UC cell: the driver's ``phase`` carries
    the program's counts for the window (no telemetry session), and
    the count readers divide them per chunk solve. Counts may come from
    a CPU run; they are still reported from the chip only, because
    ``test_rehearsal.test_contract_line`` holds a rehearsal to no
    ``solve.*`` metric. The roofline and the idle shares never do."""
    from test_rehearsal import rehearse
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", "solve.bulk_iters"):
            read = mod.read
            mod.read = lambda obs, **kw: (seen.update(obs=obs),
                                          read(obs, **kw))[1]
        return mod

    monkeypatch.setattr(harness, "load_module", spy)
    line = rehearse("uc_s256_hub_hot", trace=True)
    assert not any(k in line["metrics"] for k in NEW)
    obs = seen["obs"]
    admm, calls = obs["phase"]["admm_iters_per_call"], obs["phase"]["calls"]
    assert calls >= 1 and admm["bulk"] > 0 and admm["tail"] >= 0
    assert obs["phase"]["solve_shape"]["s_chunk"] == 4
    on_chip = dict(obs, platform="tpu")
    n = obs["chunk_solves_per_iteration"]
    assert reader("solve.bulk_iters")(on_chip) == admm["bulk"] / n
    assert reader("solve.tail_iters")(on_chip) == admm["tail"] / n
    # 3 x 6 toy width: the recipe's caps bound a solve's counts
    recipe = harness.load_json("configs", "uc90x48_df32.json")["recipe"]
    assert admm["bulk"] / n <= recipe["subproblem_max_iter"] + 25
    assert admm["tail"] / n <= recipe["subproblem_tail_iter"] + 25


def test_benchmark_json_lists_the_five_with_their_readers():
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, (unit, source, layer, moves, cells) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, source, layer, moves, cells)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # every listed cell reports the end-to-end metric it moves
        assert set(cells) <= set(e2e[moves]["workloads"])
        assert callable(reader(name))
    assert by_name["solve.fused_mixed_roofline"]["better"] == "higher"
    assert all(by_name[n]["better"] == "lower" for n in NEW
               if n != "solve.fused_mixed_roofline")
