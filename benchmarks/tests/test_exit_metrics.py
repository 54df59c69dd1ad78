"""The six per-layer metrics that read how the chunk solves ENDED
(PR 37: ``PHBase.phase_timing(True)["exits"]``): each reader on a
recorded ``phase`` dict (capped, uncapped, empty, a parent-shaped
program without the entry), the six entries of ``BENCHMARK.json``, and
CPU rehearsals of the APH cell and the sslp cell at toy counts whose
``phase`` the five count readers read. Like every ``solve.*`` reader
they report from the chip only (``test_rehearsal`` and the cells' own
tests hold a rehearsal to no ``solve.*`` metric), so a rehearsal's
counts are read here as ``test_tracing_metrics`` reads them: from the
observations the driver handed the readers."""

import pytest

import harness

CELLS = ["uc_s256_hub_hot", "uc_s1024_mesh4_hub_hot",
         "sslp_10_50_s2000_hub_hot", "uc_s256_aph_hot"]
NEW = {"solve.tail_capped_share": ("%", "program_counter"),
       "solve.bulk_capped_share": ("%", "program_counter"),
       "solve.cap_rows": ("rows", "program_counter"),
       "solve.cap_rows_dual_share": ("%", "program_counter"),
       "solve.cap_top8_share": ("%", "program_counter"),
       "solve.capped_call_s": ("s", "program_span")}
COUNTS = [n for n in NEW if n != "solve.capped_call_s"]


def reader(name):
    return harness.load_module("metrics", name).read


def obs_of(exits, platform="tpu"):
    phase = {"calls": 10, "seconds_per_call": {"solve": 1.0}}
    if exits is not None:
        phase["exits"] = exits
    return {"phase": phase, "chunk_solves_per_iteration": 4,
            "platform": platform}


# cell 1's shape: 10 calls of four chunk solves, 8 tails at the cap in
# 6 calls, 12 rows over, one scenario at every capped exit
CAPPED = {"solves": 40, "bulk_hist": {25: 36, 50: 4},
          "tail_hist": {25: 30, 50: 2, 100: 8},
          "bulk_capped": 0, "tail_capped": 8, "rows_read": 8,
          "rows_over": 12, "rows_over_pri_only": 3, "rows_over_dua_only": 8,
          "rows_over_both": 1, "worst_pri": 3.1, "worst_dua": 1.7,
          "rows_over_uncapped": 5, "rows_over_gate": 0,
          "capped_calls": 6, "capped_solve_seconds": 7.5,
          "scenarios_over": 4, "top": [[17, 8], [3, 2], [40, 1], [41, 1]],
          "per_call": [[[100, 25, 25, 25], 2]]}
UNCAPPED = dict(CAPPED, tail_hist={25: 38, 50: 2}, tail_capped=0,
                rows_read=0, rows_over=0, rows_over_pri_only=0,
                rows_over_dua_only=0, rows_over_both=0, capped_calls=0,
                capped_solve_seconds=0.0, scenarios_over=0, top=[])
EMPTY = dict(UNCAPPED, solves=0, bulk_hist={}, tail_hist={}, per_call=[])


def test_readers_on_a_capped_window():
    got = {n: reader(n)(obs_of(CAPPED)) for n in NEW}
    assert got == {"solve.tail_capped_share": 20.0,
                   "solve.bulk_capped_share": 0.0,
                   "solve.cap_rows": 1.5,
                   "solve.cap_rows_dual_share": pytest.approx(100 * 8 / 12),
                   "solve.cap_top8_share": 100.0,
                   "solve.capped_call_s": 1.25}
    # "anybody": the eight carry what eight of many carry
    spread = dict(CAPPED, rows_over=64, top=[[g, 1] for g in range(8)])
    assert reader("solve.cap_top8_share")(obs_of(spread)) == 12.5
    # the sslp cell: every solve capped in both phases
    sslp = dict(CAPPED, solves=50, bulk_capped=50, tail_capped=50,
                rows_read=50, rows_over=50 * 700)
    assert reader("solve.bulk_capped_share")(obs_of(sslp)) == 100.0
    assert reader("solve.tail_capped_share")(obs_of(sslp)) == 100.0
    assert reader("solve.cap_rows")(obs_of(sslp)) == 700.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_uncapped_empty_and_parent_shaped(name):
    """A window with no capped exit reads 0 (the entry is there to be
    read); a window with no solve, and a program without the entry (the
    parent), read nothing and raise nothing; never from a rehearsal."""
    read = reader(name)
    assert read(obs_of(UNCAPPED)) == 0.0
    assert read(obs_of(EMPTY)) is None
    assert read(obs_of(None)) is None
    assert read({"phase": None, "platform": "tpu"}) is None
    assert read({"platform": "tpu"}) is None
    assert read(obs_of(CAPPED, platform="cpu")) is None


def test_benchmark_json_lists_the_six_with_their_readers():
    """By name, not by position: a later PR appends its own entries."""
    bench = harness.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, (unit, source) in NEW.items():
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, "chunk solve", "ph_iter_s",
                                 "lower")
        assert m["workloads"] == CELLS
        assert set(CELLS) <= set(e2e["ph_iter_s"]["workloads"])
        assert callable(reader(name))
    # the serve cell runs the host-segmented un-chunked path, whose
    # rows the booking does not fetch: on none of the lists
    assert not any("farmer3_serve_c8" in by_name[n]["workloads"]
                   for n in NEW)
    # what they stand beside stays
    for name in ("solve.bulk_iters", "solve.tail_iters", "solve.chunk_s"):
        assert name in by_name


def _rehearsed_obs(monkeypatch, run, first_metric):
    """The observations a traced CPU rehearsal hands the readers."""
    seen = {}
    real = harness.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("metrics", first_metric):
            read = mod.read
            mod.read = lambda obs, **kw: (seen.update(obs=obs),
                                          read(obs, **kw))[1]
        return mod

    monkeypatch.setattr(harness, "load_module", spy)
    line = run()
    assert line["correct"] is True, line["checks"]
    assert not any(k in line["metrics"] for k in NEW)   # chip only
    return seen["obs"]


def _check_entry(obs, rows_per_solve):
    phase = obs["phase"]
    ex, admm = phase["exits"], phase["admm_iters_per_call"]
    assert ex["solves"] == phase["calls"] * obs["chunk_solves_per_iteration"]
    assert sum(ex["tail_hist"].values()) == ex["solves"]
    assert sum(k * v for k, v in ex["tail_hist"].items()) \
        == admm["tail"] * phase["calls"]
    assert sum(k * v for k, v in ex["bulk_hist"].items()) \
        == admm["bulk"] * phase["calls"]
    on_chip = dict(obs, platform="tpu")
    got = {n: reader(n)(on_chip) for n in COUNTS}
    assert all(v is not None for v in got.values()), got
    assert got["solve.tail_capped_share"] \
        == 100.0 * ex["tail_capped"] / ex["solves"]
    assert 0 <= got["solve.cap_rows"] <= rows_per_solve
    assert 0 <= got["solve.cap_rows_dual_share"] <= 100
    assert 0 <= got["solve.cap_top8_share"] <= 100
    # never the seconds from a rehearsal, nor anything on the CPU
    assert reader("solve.capped_call_s")(obs) is None
    assert all(reader(n)(obs) is None for n in COUNTS)
    return ex, got


def test_aph_rehearsal_reports_the_five_counts(monkeypatch):
    """``uc_s256_aph_hot`` at 16 scenarios, chunk 4: every pass ONE
    chunk solve of four rows, booked by the dispatch pass with the ids
    it solved."""
    from test_aph_cell import rehearse
    obs = _rehearsed_obs(monkeypatch, lambda: rehearse(trace=True),
                         "solve.tail_capped_share")
    assert obs["chunk_solves_per_iteration"] == 1
    ex, got = _check_entry(obs, rows_per_solve=4)
    assert ex["solves"] == obs["phase"]["dispatch"]["passes"]
    assert all(len(t) == 1 for t, _ in ex["per_call"])
    assert all(0 <= g < 16 for g, _ in ex["top"])
    assert ex["rows_over_gate"] == 0


def test_sslp_rehearsal_reports_the_five_counts(monkeypatch):
    """``sslp_10_50_s2000_hub_hot`` at 6 scenarios: the un-chunked
    fused body, whose rows ride ``_book_admm_iters``' read; the budget
    ends every solve in both phases there, as on the chip."""
    from test_sslp_cell import rehearse
    obs = _rehearsed_obs(monkeypatch, lambda: rehearse(trace=True),
                         "solve.tail_capped_share")
    assert obs["chunk_solves_per_iteration"] == 1
    ex, got = _check_entry(obs, rows_per_solve=6)
    recipe = harness.load_json("configs", "sslp_10_50_df32.json")["recipe"]
    assert set(ex["bulk_hist"]) <= {recipe["subproblem_max_iter"]} \
        | set(range(25, recipe["subproblem_max_iter"], 25))
    if got["solve.tail_capped_share"] == 100.0:
        assert ex["tail_hist"] == {
            recipe["subproblem_tail_iter"]: ex["solves"]}
        assert ex["rows_read"] == ex["solves"]
