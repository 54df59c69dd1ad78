"""Wheel forensics (ISSUE 19): the device-side convergence-attribution
reduction (ops/forensics), the jax-free diagnosis engine
(obs/diagnose), and their surfaces (ph.iteration records, analyze's
``== forensics ==`` section, the live snapshot).

Coverage demanded by the issue's acceptance criteria:
 - device-vs-host parity: the jitted ``forensic_reduce`` matches a
   plain-numpy reference stat for stat, pads excluded,
 - ``ph.gate_syncs`` per iteration is UNCHANGED with forensics on,
   pinned on 1/2/4-device meshes (the O(1) gate-sync contract),
 - the verdict rules fire and hold their units on synthetic inputs,
 - disabled mode allocates nothing and touches no engine state,
 - a synthetic stalled wheel makes analyze name the frozen spoke and
   the top-k culprit slots in both the report and ``--json``,
 - ``--json`` never emits bare NaN/Infinity (satellite 1),
 - merged hub+spoke timelines still attribute STALLED_OUTER to the
   correct spoke role (satellite 4).
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.obs import analyze, diagnose
from mpisppy_tpu.ops import forensics
from mpisppy_tpu.parallel.mesh import make_mesh


@pytest.fixture
def telemetry(tmp_path):
    rec = obs.configure(out_dir=str(tmp_path))
    yield rec, tmp_path
    obs.shutdown()


# same shapes as tests/test_telemetry.py so the UC programs compile
# once per suite run
def _uc_batch(S, G=3, T=6, **kw):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T, **kw},
                       vector_patch=uc.scenario_vector_patch)


# ---------------- device-vs-host parity ----------------

def _np_reduce(st, x, xbar, w, p):
    """Plain-numpy twin of ops.forensics.forensic_reduce for one
    sample; ``st`` is a dict carry {prev_w, prev_dw, flip_ema,
    prev_xbar, samples}."""
    eps = 1e-12
    adev = np.abs(x - xbar)
    slot_mass = p @ adev
    pri = p * adev.sum(axis=1)
    pri_total = pri.sum()
    conv = pri_total / x.shape[1]
    dw = w - st["prev_w"]
    valid_dw = 1.0 if st["samples"] >= 1 else 0.0
    valid_flip = 1.0 if st["samples"] >= 2 else 0.0
    dwa = np.abs(dw)
    dua_slot = (p @ dwa) * valid_dw
    dua = p * dwa.sum(axis=1) * valid_dw
    flip = ((np.sign(dw) * np.sign(st["prev_dw"])) < 0).astype(float)
    fe = (forensics.FLIP_DECAY * st["flip_ema"]
          + (1.0 - forensics.FLIP_DECAY) * (p @ flip) * valid_flip)
    fe = fe * valid_flip
    log_ratio = np.clip(np.log10((slot_mass + eps) / (dua_slot + eps)),
                        -6.0, 6.0) * valid_dw
    xbar_slot = p @ xbar
    xbar_move = np.abs(xbar_slot - st["prev_xbar"]).mean() * valid_dw
    out = {"conv": conv, "pri_total": pri_total, "dua_total": dua.sum(),
           "osc_mean": fe.mean(), "rho_log_ratio_mean": log_ratio.mean(),
           "xbar_move": xbar_move, "slot_mass": slot_mass,
           "flip_ema": fe, "pri": pri, "dua": dua}
    new_st = {"prev_w": w, "prev_dw": dw, "flip_ema": fe,
              "prev_xbar": xbar_slot, "samples": st["samples"] + 1}
    return new_st, out


def test_forensic_reduce_matches_numpy_reference():
    """Three consecutive samples through the jitted reduction track the
    numpy reference stat for stat — including the validity gating of
    the dual/oscillation stats on early samples."""
    rng = np.random.default_rng(7)
    S, K = 5, 6                       # 4 real scenarios + 1 mesh pad
    p = np.array([0.3, 0.25, 0.25, 0.2, 0.0])
    rho = np.full((S, K), 2.5)
    kk, ks = K, S
    st_d = forensics.init_state(S, K, dtype=jnp.float64)
    st_n = {"prev_w": np.zeros((S, K)), "prev_dw": np.zeros((S, K)),
            "flip_ema": np.zeros(K), "prev_xbar": np.zeros(K),
            "samples": 0}
    for i in range(3):
        x = rng.normal(size=(S, K)) * (i + 1)
        xbar = np.broadcast_to(p @ x, (S, K)).copy()
        w = rng.normal(size=(S, K))
        st_d, packed = forensics.forensic_reduce(
            st_d, jnp.asarray(x), jnp.asarray(xbar), jnp.asarray(w),
            jnp.asarray(p), jnp.asarray(rho), kk=kk, ks=ks)
        st_n, ref = _np_reduce(st_n, x, xbar, w, p)
        fx = forensics.unpack(packed, kk, ks)
        assert fx["samples"] == i + 1
        for key in ("conv", "pri_total", "dua_total", "osc_mean",
                    "rho_log_ratio_mean", "xbar_move"):
            assert fx[key] == pytest.approx(ref[key], rel=1e-9), key
        assert fx["rho_mean"] == pytest.approx(2.5)
        # slot leaderboard: ids ranked by mass, values exact
        order = np.argsort(-ref["slot_mass"])
        assert [s for s, _ in fx["top_slots"]] == list(order)
        for (sid, v), j in zip(fx["top_slots"], order):
            assert v == pytest.approx(ref["slot_mass"][j], rel=1e-9)
        # scenario shares: pads (prob 0) are dropped, real shares
        # normalize against the totals
        ids = [s for s, _ in fx["scen_pri_shares"]]
        assert 4 not in ids and len(ids) == 4
        for sid, share in fx["scen_pri_shares"]:
            assert share == pytest.approx(
                ref["pri"][sid] / (ref["pri"].sum() + 1e-12), rel=1e-9)
    # sample 1 reported no dual/oscillation garbage (validity gates)
    assert st_n["samples"] == 3


def test_conv_decomposition_and_forced_oscillation():
    """slot mass decomposes the convergence scalar EXACTLY
    (conv == sum_k m_k / K), and a slot whose ΔW flips sign every
    sample saturates the flip EMA at the prob mass of the flippers."""
    S, K = 3, 4
    p = np.array([0.5, 0.5, 0.0])
    x = np.array([[1.0, 0.0, 2.0, 0.0],
                  [-1.0, 0.0, 0.0, 0.0],
                  [9.0, 9.0, 9.0, 9.0]])     # pad row: must not count
    xbar = np.broadcast_to(p @ x, (S, K)).copy()
    rho = np.ones((S, K))
    st = forensics.init_state(S, K, dtype=jnp.float64)
    fx = None
    for i in range(4):
        w = np.zeros((S, K))
        w[:, 1] = (-1.0) ** i              # slot 1 oscillates
        w[:, 2] = float(i)                 # slot 2 moves monotonically
        st, packed = forensics.forensic_reduce(
            st, jnp.asarray(x), jnp.asarray(xbar), jnp.asarray(w),
            jnp.asarray(p), jnp.asarray(rho), kk=K, ks=S)
        fx = forensics.unpack(packed, K, S)
        assert fx["conv"] == pytest.approx(
            sum(m for _, m in fx["top_slots"]) / K, rel=1e-12)
    osc = dict((int(s), v) for s, v in fx["osc_slots"])
    # slot 1's delta flips sign every sample: EMA -> 0.5*old + 0.5*1
    # over 2 valid flip samples = 0.75; slot 2 never flips
    assert osc[1] == pytest.approx(0.75)
    assert osc[2] == 0.0
    # the pad scenario never enters the share leaderboard
    assert all(s != 2 for s, _ in fx["scen_pri_shares"])
    assert all(s != 2 for s, _ in fx["scen_dua_shares"])


def test_unpack_rejects_wrong_shape():
    with pytest.raises(ValueError, match="packed forensics"):
        forensics.unpack(np.zeros(7), 3, 3)


# ---------------- the O(1) gate-sync contract ----------------

@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_gate_syncs_unchanged_with_forensics_on(telemetry, ndev):
    """THE cost contract: forensics rides the already-synced gate, so
    ``ph.gate_syncs`` per iteration is IDENTICAL with sampling on
    (every iteration) and off — on host mode and on 2/4-device
    meshes."""
    opts = {"defaultPHrho": 50.0, "PHIterLimit": 3, "convthresh": 0.0,
            "subproblem_max_iter": 1200, "subproblem_eps": 1e-6,
            "subproblem_chunk": 2}

    def run(interval):
        kw = {} if ndev == 1 else {"mesh": make_mesh(ndev)}
        ph = PH(_uc_batch(8), {**opts, "forensics_interval": interval},
                **kw)
        base = obs.counter_value("ph.gate_syncs")
        ph.ph_main()
        return obs.counter_value("ph.gate_syncs") - base, ph

    d_off, _ = run(0)
    d_on, ph_on = run(1)
    assert d_on == d_off, \
        f"forensics changed gate syncs: {d_off} -> {d_on}"
    # and the sampling actually happened, every iteration
    assert ph_on._forensic_last is not None
    assert ph_on._forensic_last["samples"] == 3


def test_ph_embeds_forensics_block_and_events(telemetry):
    """End-to-end farmer wheel: every sampled iteration's record
    carries the forensics block, the sample's conv matches the
    engine's own convergence scalar, and the live engine booked the
    events/counters/gauges."""
    rec, path = telemetry
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PH(batch, {"defaultPHrho": 1.0, "PHIterLimit": 3,
                    "convthresh": 0.0, "subproblem_max_iter": 1500,
                    "forensics_interval": 1})
    ph.ph_main()
    assert obs.counter_value("forensics.samples") == 3
    snap = diagnose.snapshot()
    assert snap is not None and snap["samples"] == 3
    obs.shutdown()
    lines = [json.loads(ln)
             for ln in open(path / "events.jsonl", encoding="utf-8")]
    recs = [e for e in lines if e.get("type") == "ph.iteration"
            and isinstance(e.get("forensics"), dict)]
    assert [e["forensics"]["it"] for e in recs] == [1, 2, 3]
    for e in recs:
        fx = e["forensics"]
        # the sample's conv is the engine's conv, computed on-device
        assert fx["conv"] == pytest.approx(e["conv"], rel=1e-9)
        assert fx["n_scens"] == 3 and len(fx["top_slots"]) > 0
    assert sum(1 for e in lines
               if e.get("type") == "forensics.sample") == 3
    mx = json.load(open(path / "metrics.json"))
    assert mx["counters"]["forensics.samples"] == 3
    assert mx["gauges"]["forensics.unhealthy"] == 0.0
    assert "forensics.top_slot" in mx["gauges"]


def test_forensics_inert_without_telemetry():
    """Telemetry off: iteration_record never runs, so the forensic
    state is never built — the zero-cost-when-off contract at the
    engine level."""
    assert not obs.enabled()
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    ph = PH(batch, {"defaultPHrho": 1.0, "PHIterLimit": 2,
                    "convthresh": 0.0, "subproblem_max_iter": 1500,
                    "forensics_interval": 1})
    ph.ph_main()
    assert ph._forensic_state is None and ph._forensic_last is None


def test_disabled_mode_allocates_nothing():
    """With no session every diagnose call is a global read + None
    test; tracemalloc sees no allocations attributed to the diagnose
    module. (Attribution is scoped to diagnose.py, not the whole obs
    package — in full-suite runs, background threads left by earlier
    tests can allocate elsewhere in obs during the window. Even so,
    a frame passing through diagnose can be charged noise from GC
    timing, so the probe takes up to three measurement windows and a
    real leak — which would recur every window — must show in ALL of
    them to fail.)"""
    import gc
    import tracemalloc

    assert not obs.enabled()
    fx = {"samples": 1, "it": 1}
    assert diagnose.note_sample(fx) is None
    assert diagnose.note_bound_check(1, -1.0, 0.0, 0.5) is None
    assert diagnose.snapshot() is None
    mod = diagnose.__file__
    leaked = None
    for _window in range(3):
        gc.collect()
        tracemalloc.start()
        before = tracemalloc.take_snapshot()
        for _ in range(500):
            diagnose.note_sample(fx)
            diagnose.note_bound_check(1, -1.0, 0.0, 0.5)
            diagnose.snapshot()
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        leaked = sum(s.size_diff
                     for s in after.compare_to(before, "lineno")
                     if s.size_diff > 0
                     and any(str(fr.filename) == mod
                             for fr in s.traceback))
        if leaked < 500:
            return
    assert leaked < 500, \
        f"disabled-mode diagnose calls allocated {leaked} B in every " \
        f"measurement window"


# ---------------- the verdict rules ----------------

def _checks(n, outer=-100.0, gap=0.1, spoke="lagrangian"):
    return [{"it": i + 1, "outer": outer, "inner": -90.0,
             "rel_gap": gap, "spoke": spoke} for i in range(n)]


def test_rule_stalled_outer_units():
    v = diagnose.rule_stalled_outer(_checks(6))
    assert v and v["verdict"] == "STALLED_OUTER"
    assert v["evidence"]["spoke"] == "lagrangian"
    assert v["evidence"]["flat_checks"] == 6
    # gap below the floor = effectively converged, no verdict
    assert diagnose.rule_stalled_outer(_checks(6, gap=1e-6)) is None
    # a moving bound is healthy
    moving = [{"it": i, "outer": -100.0 - i, "inner": -90.0,
               "rel_gap": 0.1, "spoke": None} for i in range(6)]
    assert diagnose.rule_stalled_outer(moving) is None
    # too few checks to call it
    assert diagnose.rule_stalled_outer(_checks(3)) is None
    # flatness tolerance is RELATIVE to the bound magnitude
    jitter = [{"it": i, "outer": -1e6 + i * 1e-4, "inner": -9e5,
               "rel_gap": 0.1, "spoke": None} for i in range(6)]
    assert diagnose.rule_stalled_outer(jitter) is not None


def test_rule_oscillating_units():
    fx = {"samples": 3, "it": 9, "osc_mean": 0.1,
          "osc_slots": [[4, 0.6], [2, 0.1]]}
    v = diagnose.rule_oscillating([fx])
    assert v and v["evidence"]["slots"] == [4]
    assert v["advice"] == "rho up"
    # flip stats need 3 samples to be real (two deltas)
    assert diagnose.rule_oscillating([{**fx, "samples": 2}]) is None
    # calm wheel: low mean, no hot slot
    calm = {"samples": 5, "osc_mean": 0.05, "osc_slots": [[0, 0.1]]}
    assert diagnose.rule_oscillating([calm]) is None
    # high mean fires even without a single hot slot
    assert diagnose.rule_oscillating(
        [{"samples": 5, "osc_mean": 0.4, "osc_slots": []}]) is not None


def test_rule_culprit_scenarios_units():
    fx = {"samples": 2, "it": 4, "n_scens": 8,
          "scen_pri_shares": [[3, 0.4], [5, 0.2], [0, 0.1], [1, 0.1]]}
    v = diagnose.rule_culprit_scenarios([fx])
    assert v and v["evidence"]["ids"] == [3, 5]
    assert v["evidence"]["share"] == pytest.approx(0.6)
    # evenly-spread residual: the 50% prefix is too wide to name
    spread = {"samples": 2, "n_scens": 8,
              "scen_pri_shares": [[i, 0.125] for i in range(8)]}
    assert diagnose.rule_culprit_scenarios([spread]) is None
    # concentration is meaningless on tiny S
    assert diagnose.rule_culprit_scenarios(
        [{**fx, "n_scens": 3}]) is None


def test_rule_fixing_stall_units():
    shrink = {"compactions": 0, "fixed": 1, "free": 9,
              "first_bucket": 0.25}
    v = diagnose.rule_fixing_stall(shrink, 30)
    assert v and v["evidence"]["bucket"] == 0.25
    # a compaction happened: shrinking is working
    assert diagnose.rule_fixing_stall(
        {**shrink, "compactions": 1}, 30) is None
    # too early to call
    assert diagnose.rule_fixing_stall(shrink, 10) is None
    # bucket crossed
    assert diagnose.rule_fixing_stall(
        {**shrink, "fixed": 5, "free": 5}, 30) is None


def test_diagnose_ranks_by_severity():
    fx = {"samples": 3, "osc_mean": 0.4, "osc_slots": [], "it": 30}
    verdicts = diagnose.diagnose(
        [fx], _checks(6),
        shrink={"compactions": 0, "fixed": 0, "free": 10,
                "first_bucket": 0.25}, it=30)
    assert [v["verdict"] for v in verdicts] \
        == ["STALLED_OUTER", "OSCILLATING", "FIXING_STALL"]
    assert diagnose.overall(verdicts) == "STALLED_OUTER"
    assert diagnose.overall([]) == "HEALTHY"


def test_live_engine_verdict_transition(telemetry):
    """Flat bound checks through the live engine flip the verdict to
    STALLED_OUTER exactly once: one transition event, one counter
    bump, the unhealthy gauge raised, the snapshot lock-free."""
    rec, path = telemetry
    snap = None
    for i in range(7):
        snap = diagnose.note_bound_check(i + 1, -100.0, -90.0, 0.1,
                                         spoke="lagrangian")
    assert snap["verdict"] == "STALLED_OUTER"
    assert diagnose.snapshot()["verdict"] == "STALLED_OUTER"
    assert obs.counter_value("forensics.verdict_changes") == 1
    obs.shutdown()
    lines = [json.loads(ln)
             for ln in open(path / "events.jsonl", encoding="utf-8")]
    tr = [e for e in lines if e.get("type") == "forensics.verdict"]
    assert len(tr) == 1
    assert tr[0]["prev"] == "HEALTHY" \
        and tr[0]["verdict"] == "STALLED_OUTER"
    assert tr[0]["evidence"]["spoke"] == "lagrangian"
    mx = json.load(open(path / "metrics.json"))
    assert mx["gauges"]["forensics.unhealthy"] == 1.0


# ---------------- analyze: the stalled-wheel post-mortem ----------------

def _fx_block(i):
    return {"samples": i, "it": i, "conv": 5.0, "pri_total": 15.0,
            "dua_total": 0.1, "osc_mean": 0.05,
            "rho_log_ratio_mean": 2.0, "xbar_move": 0.01,
            "rho_mean": 1.0, "n_scens": 3, "n_slots": 4,
            "top_slots": [[7, 4.2], [1, 1.1], [0, 0.3]],
            "osc_slots": [[7, 0.1]], "rho_slots": [[7, 2.5]],
            "scen_pri_shares": [[2, 0.8], [0, 0.15], [1, 0.05]],
            "scen_dua_shares": [[2, 0.9], [0, 0.1]]}


def _stalled_dir(tmp_path, name="stalled"):
    """Synthesize a stalled wheel's artifacts: six flat outer-bound
    checks over a 10% gap, forensics blocks riding the iteration
    records, and a screen row naming the lagrangian spoke as the
    outer-bound producer."""
    d = str(tmp_path / name)
    os.makedirs(d)
    events = [{"type": "run_header", "schema": obs.SCHEMA_VERSION,
               "t": 0.0, "run_id": name, "wall_time_unix": 0.0}]
    for i in range(1, 7):
        events.append({"type": "ph.iteration", "t": float(i),
                       "iter": i, "conv": 5.0, "seconds": 0.1,
                       "forensics": _fx_block(i)})
        events.append({"type": "hub.iteration", "t": float(i),
                       "iter": i, "outer": -100.0, "inner": -90.0,
                       "abs_gap": 10.0, "rel_gap": 0.1})
    events.append({"type": "hub.screen_row", "t": 1.0, "iter": 1,
                   "outer": -100.0, "inner": -90.0, "rel_gap": 0.1,
                   "ob_char": "L", "ib_char": "X"})
    events.append({"type": "run_footer", "t": 7.0})
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(e) for e in events) + "\n")
    return d


def test_stalled_wheel_report_names_spoke_and_slots(tmp_path, capsys):
    d = _stalled_dir(tmp_path)
    rc = analyze.main([d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "== forensics ==" in out
    assert "verdict: STALLED_OUTER" in out
    assert "spoke=lagrangian" in out          # the frozen spoke, named
    assert "top culprit slots" in out and "7: 4.2" in out
    assert "scenario residual shares" in out and "2: 0.8" in out


def test_stalled_wheel_json_carries_forensics(tmp_path, capsys):
    d = _stalled_dir(tmp_path)
    rc = analyze.main([d, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    fo = doc["forensics"]
    assert fo["verdict"] == "STALLED_OUTER"
    assert fo["samples"] == 6 and fo["bound_checks"] == 6
    v = fo["verdicts"][0]
    assert v["evidence"]["spoke"] == "lagrangian"
    assert v["evidence"]["flat_checks"] == 6
    assert fo["last"]["top_slots"][0] == [7, 4.2]


def test_healthy_run_judges_healthy(tmp_path, capsys):
    """Moving outer bound, same forensics stream: no verdict fires."""
    d = _stalled_dir(tmp_path, name="moving")
    ev = os.path.join(d, "events.jsonl")
    out = []
    for ln in open(ev, encoding="utf-8"):
        e = json.loads(ln)
        if e.get("type") == "hub.iteration":
            e["outer"] = -100.0 - e["iter"]
        out.append(json.dumps(e))
    open(ev, "w").write("\n".join(out) + "\n")
    rc = analyze.main([d, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["forensics"]["verdict"] == "HEALTHY"
    assert doc["forensics"]["verdicts"] == []


def test_rho_starved_wheel_judges_unhealthy_with_evidence(tmp_path):
    """The false-negative side on a REAL wheel, end to end through the
    artifacts: rho 1e-9 barely moves W, so the Lagrangian outer bound
    freezes while a real gap remains; analyze must name a non-HEALTHY
    verdict and carry its evidence (the diagnosis rules went blind if
    it reads HEALTHY)."""
    from mpisppy_tpu.__main__ import config_from_args, make_parser, run
    d = str(tmp_path / "starved")
    run(config_from_args(make_parser().parse_args(
        ["farmer", "--num-scens", "3", "--max-iterations", "14",
         "--convthresh", "-1", "--subproblem-max-iter", "1500",
         "--with-lagrangian", "--with-xhatshuffle",
         "--rel-gap", "1e-6", "--default-rho", "1e-9",
         "--forensics-interval", "1", "--telemetry-dir", d])))
    fo = analyze.forensics_summary(analyze.load_run(d))
    assert fo is not None and fo["samples"] > 0
    assert fo["verdict"] != "HEALTHY", fo
    assert fo["verdicts"][0]["evidence"], fo["verdicts"][0]


# ---------------- satellite 1: no bare NaN in --json ----------------

def _nan_dir(tmp_path, name="nandir"):
    d = str(tmp_path / name)
    os.makedirs(d)
    events = [
        {"type": "run_header", "schema": obs.SCHEMA_VERSION, "t": 0.0,
         "run_id": name, "wall_time_unix": 0.0},
        {"type": "ph.iteration", "t": 1.0, "iter": 1,
         "conv": float("nan"), "seconds": 0.1,
         "forensics": {**_fx_block(1), "osc_mean": float("nan"),
                       "xbar_move": float("inf")}},
        {"type": "run_footer", "t": 2.0},
    ]
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        # json.dumps happily writes bare NaN — exactly the artifact
        # state that used to leak into analyze --json output
        f.write("\n".join(json.dumps(e) for e in events) + "\n")
    with open(os.path.join(d, "metrics.json"), "w") as f:
        json.dump({"counters": {"ph.gate_syncs": 1},
                   "gauges": {"ph.conv": float("nan")}}, f)
    return d


def _strict_loads(text):
    def boom(tok):
        raise AssertionError(f"bare {tok} in --json output")
    return json.loads(text, parse_constant=boom)


def test_report_json_sanitizes_nonfinite(tmp_path, capsys):
    d = _nan_dir(tmp_path)
    rc = analyze.main([d, "--json"])
    assert rc == 0
    doc = _strict_loads(capsys.readouterr().out)   # round-trips strict
    assert doc["forensics"]["last"]["osc_mean"] is None
    assert doc["forensics"]["last"]["xbar_move"] is None


def test_compare_json_sanitizes_nonfinite(tmp_path, capsys):
    a = _nan_dir(tmp_path, "a")
    b = _nan_dir(tmp_path, "b")
    rc = analyze.main(["--compare", a, b, "--json"])
    assert rc == 0
    doc = _strict_loads(capsys.readouterr().out)
    assert "forensics" in doc


# ---------------- satellite 4: merged multi-role attribution ----------------

def test_merged_hub_spoke_timeline_attributes_spoke(tmp_path):
    """A merged multi-process capture (hub stream + a role-suffixed
    spoke stream in ONE dir): the samples come off the standalone
    ``forensics.sample`` events, and STALLED_OUTER attribution falls
    back to the live engine's recorded verdict evidence when no
    screen rows survived."""
    d = str(tmp_path)
    hub_events = [{"type": "run_header", "schema": obs.SCHEMA_VERSION,
                   "t": 0.0, "run_id": "m", "wall_time_unix": 0.0}]
    for i in range(1, 7):
        hub_events.append({"type": "hub.iteration", "t": float(i),
                           "iter": i, "outer": -100.0, "inner": -90.0,
                           "rel_gap": 0.1})
        hub_events.append({"type": "forensics.sample", "t": float(i),
                           **{k: v for k, v in _fx_block(i).items()
                              if k != "samples"}})
    hub_events.append({"type": "forensics.verdict", "t": 6.5,
                       "verdict": "STALLED_OUTER", "prev": "HEALTHY",
                       "it": 6, "summary": "outer bound flat",
                       "evidence": {"spoke": "lagrangian",
                                    "flat_checks": 6}})
    hub_events.append({"type": "run_footer", "t": 7.0})
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(e) for e in hub_events) + "\n")
    spoke_events = [
        {"type": "run_header", "schema": obs.SCHEMA_VERSION, "t": 0.0,
         "run_id": "m", "wall_time_unix": 0.0},
        {"type": "spoke.bound", "t": 1.0, "kind": "outer",
         "char": "L", "value": -100.0},
        {"type": "run_footer", "t": 7.0},
    ]
    with open(os.path.join(d, "events-spoke0-lagrangian.jsonl"),
              "w") as f:
        f.write("\n".join(json.dumps(e) for e in spoke_events) + "\n")
    run = analyze.load_run(d)
    # both role streams merged onto one timeline
    assert run.of("spoke.bound", role="spoke0-lagrangian")
    fo = analyze.forensics_summary(run)
    assert fo["verdict"] == "STALLED_OUTER"
    assert fo["samples"] == 6          # the forensics.sample fallback
    assert fo["verdicts"][0]["evidence"]["spoke"] == "lagrangian"
    assert fo["verdict_events"][0]["verdict"] == "STALLED_OUTER"
