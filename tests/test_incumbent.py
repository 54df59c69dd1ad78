"""Device-side batched incumbent search (ops/incumbent + DiveInnerBound,
ISSUE 9): candidate-pool determinism, batched-vs-sequential evaluation
equivalence, slam-dominance on the UC fixture with ZERO host oracle
imports (the clean-path guard pattern), oracle-vs-device value agreement
on farmer (LP-relaxation-integral), O(1) gate syncs + zero device_put on
multi-device meshes, the mode wiring/satellite fixes, and a live
spawn-context wheel where the dive spoke publishes a bound the hub
accepts (bound-flow verdict HEALTHY)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.ops import incumbent as inc
from mpisppy_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _uc_batch(S=4, G=3, T=6, **kw):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T,
                                       "relax_integrality": False, **kw},
                       vector_patch=uc.scenario_vector_patch)


def _farmer_batch(S=3):
    return build_batch(farmer.scenario_creator, farmer.make_tree(S))


def _uc_masks(batch):
    """(pin u-only, dive = binary&pin) like the wheel configs."""
    idx = np.asarray(batch.nonant_idx)
    col = np.zeros(batch.n, bool)
    col[batch.template.var_slices["u"]] = True
    pin = col[idx]
    lb0 = np.asarray(batch.lb)[0][idx]
    ub0 = np.asarray(batch.ub)[0][idx]
    integer = np.asarray(batch.integer)[idx]
    dive = integer.astype(bool) & ((ub0 - lb0) <= 1.0 + 1e-9) & pin
    return pin, dive, lb0, ub0


# ---------------- candidate pool ----------------

def test_candidate_pool_deterministic_and_anatomy():
    batch = _uc_batch()
    pin, dive, lb0, ub0 = _uc_masks(batch)
    imask = np.asarray(batch.integer)[np.asarray(batch.nonant_idx)]
    rng = np.random.RandomState(3)
    X = rng.rand(batch.S, batch.K)
    prob = np.full(batch.S, 1.0 / batch.S)
    kw = dict(thresholds=(0.3, 0.5, 0.7), flips=4, n_random=3, ball=2,
              seed=11)
    p1 = np.asarray(inc.build_pool(X, prob, dive, imask, lb0, ub0,
                                   round_index=0, **kw))
    p2 = np.asarray(inc.build_pool(X, prob, dive, imask, lb0, ub0,
                                   round_index=0, **kw))
    # deterministic under a fixed (seed, round)
    np.testing.assert_array_equal(p1, p2)
    assert p1.shape == (inc.pool_size(dive.sum(), **{
        k: kw[k] for k in ("thresholds", "flips", "n_random")}), batch.K)
    # a different round re-seeds the random rows (fresh exploration)
    p3 = np.asarray(inc.build_pool(X, prob, dive, imask, lb0, ub0,
                                   round_index=1, **kw))
    assert not np.array_equal(p1, p3)
    # ...but only the random rows: vote/flip/slam/bound rows are pure
    # functions of X
    det = np.r_[np.arange(7), np.arange(10, 14)]   # 3 vote + 4 flip, tail
    np.testing.assert_array_equal(p1[det], p3[det])
    # dive slots are integral everywhere
    assert np.all(np.abs(p1[:, dive] - np.round(p1[:, dive])) < 1e-12)
    # slam rows are the per-variable max/min over scenarios (rounded on
    # integer slots) — the slam_rows helper is the shared source
    up, down = inc.slam_rows(X)
    np.testing.assert_array_equal(
        p1[-4], np.where(imask, np.round(up), up))
    np.testing.assert_array_equal(
        p1[-3], np.where(imask, np.round(down), down))
    # bound rows: dive slots at ub / lb
    np.testing.assert_array_equal(p1[-2][dive], ub0[dive])
    np.testing.assert_array_equal(p1[-1][dive], lb0[dive])
    # random_only keeps the static shape; deterministic rows replaced
    pr = np.asarray(inc.build_pool(X, prob, dive, imask, lb0, ub0,
                                   round_index=2, random_only=True, **kw))
    assert pr.shape == p1.shape
    # no dive slots -> no neighborhood to vary -> None (skip the round)
    none_mask = np.zeros(batch.K, bool)
    assert inc.build_pool(X, prob, none_mask, imask, lb0, ub0,
                          random_only=True, **kw) is None


# ---------------- batched-vs-sequential equivalence ----------------

def test_pool_eval_matches_sequential_uc():
    """The vmapped-dive contract: evaluate_incumbent_pool's verdict is
    P sequential calculate_incumbent calls. Feasibility flags match
    exactly; round-0 objectives are tolerance-equivalent (pool solves
    run at FIXED rho with a shared budget — doc/incumbents.md), and the
    warm-started round converges to the sequential values."""
    batch = _uc_batch(min_up_down=True, num_gens=4)
    pin, dive, lb0, ub0 = _uc_masks(batch)
    opts = {"defaultPHrho": 10.0, "subproblem_max_iter": 2500}
    ph = PHBase(batch, dict(opts))
    ph.solve_loop(w_on=False, prox_on=False)
    X = np.asarray(ph._hub_nonants())
    imask = ph.nonant_integer_mask
    # small pool: every infeasible row burns the full solve budget in
    # the sequential reference, so P sizes this test's wall-clock
    pool = inc.build_pool(X, np.asarray(ph.prob), dive, imask, lb0, ub0,
                          thresholds=(0.3, 0.5), flips=1, n_random=1,
                          seed=7, round_index=0)
    obs.configure()
    try:
        before = obs.counters_snapshot()
        objs0, feas0 = ph.evaluate_incumbent_pool(pool, pin_mask=pin)
        objs1, feas1 = ph.evaluate_incumbent_pool(pool, pin_mask=pin)
        after = obs.counters_snapshot()
        # the 1-device half of the O(1) gate-sync acceptance (the mesh
        # test covers 2/4 devices): one stacked D2H per round
        assert after.get("incumbent.gate_syncs", 0) \
            - before.get("incumbent.gate_syncs", 0) == 2
        assert after.get("xfer.device_put_bytes", 0) \
            == before.get("xfer.device_put_bytes", 0)
    finally:
        obs.shutdown()
    # an independent engine for the sequential reference (warm-start
    # cross-talk would blur what is being compared); a SUBSET of rows —
    # every infeasible row burns the full solve budget sequentially,
    # and the flags must match on all P anyway via the subset's mix
    # (vote rows, the feasible max-commitment anchor, the lb row)
    ph_ref = PHBase(batch, dict(opts))
    ph_ref.solve_loop(w_on=False, prox_on=False)
    check = [0, 1, pool.shape[0] - 2, pool.shape[0] - 1]
    for p in check:
        v = ph_ref.calculate_incumbent(np.asarray(pool[p]), pin_mask=pin)
        assert feas0[p] == feas1[p] == (v is not None), p
        if v is None:
            assert not np.isfinite(objs0[p])
            continue
        # round 0: valid but loose (fixed rho); round 1: warm-started
        # to the sequential value
        assert abs(objs0[p] - v) <= 1e-2 * (1.0 + abs(v)), (p, objs0[p], v)
        assert abs(objs1[p] - v) <= 1e-5 * (1.0 + abs(v)), (p, objs1[p], v)


def test_pool_eval_farmer_fallback_matches_sequential():
    """Per-scenario-A batches (farmer) take the sequential fallback —
    same verdict contract, and the infeasible-state poisoning fix keeps
    consecutive evaluations honest (an infeasible candidate used to
    corrupt the NEXT candidate's warm-started value)."""
    batch = _farmer_batch()
    ph = PHBase(batch, {"defaultPHrho": 1.0, "subproblem_max_iter": 4000})
    ph.solve_loop(w_on=False, prox_on=False)
    X = np.asarray(ph._hub_nonants())
    cons = X.mean(axis=0)
    # consensus, an INFEASIBLE row (sum over 500 acres), consensus again
    pool = np.stack([cons, cons + 100.0, cons])
    objs, feas = ph.evaluate_incumbent_pool(pool)
    assert list(feas) == [True, False, True]
    assert not np.isfinite(objs[1])
    # the two consensus rows agree with each other and with a fresh
    # sequential evaluation despite the infeasible row between them
    ph_ref = PHBase(batch, {"defaultPHrho": 1.0,
                            "subproblem_max_iter": 4000})
    ph_ref.solve_loop(w_on=False, prox_on=False)
    v = ph_ref.calculate_incumbent(cons)
    assert v is not None
    for p in (0, 2):
        assert abs(objs[p] - v) <= 1e-3 * (1.0 + abs(v)), (p, objs[p], v)


def test_infeasible_candidate_does_not_poison_next_eval():
    """The latent pre-existing bug the pool equivalence surfaced: an
    infeasible candidate's diverged fixed-mode state (blown rho_scale,
    ~1e9 duals) used to warm-start the next evaluation into a
    'converged' WRONG value. calculate_incumbent now drops the state on
    an infeasible verdict."""
    batch = _farmer_batch()
    ph = PHBase(batch, {"defaultPHrho": 1.0, "subproblem_max_iter": 4000})
    ph.solve_loop(w_on=False, prox_on=False)
    cons = np.asarray(ph._hub_nonants()).mean(axis=0)
    v1 = ph.calculate_incumbent(cons)
    assert v1 is not None
    assert ph.calculate_incumbent(cons + 100.0) is None   # infeasible
    v2 = ph.calculate_incumbent(cons)
    assert v2 is not None
    assert abs(v2 - v1) <= 1e-3 * (1.0 + abs(v1)), (v1, v2)
    # the CHUNKED path keeps its authoritative warm starts under the
    # ("chunks", ...) key — the fix must drop those too (review catch)
    bu = _uc_batch(S=4)
    pin, dive, lb0, ub0 = _uc_masks(bu)
    # recovery off-ramps: the infeasible candidate would otherwise
    # trigger the chunk retry's escalated budget + the hospital's
    # per-scenario factorizations — minutes of rescue work for a
    # candidate that is SUPPOSED to fail
    phc = PHBase(bu, {"defaultPHrho": 50.0, "subproblem_max_iter": 1000,
                      "subproblem_chunk": 2, "subproblem_hospital": False,
                      "subproblem_tail_iter": 100})
    phc.solve_loop(w_on=False, prox_on=False)
    ones = np.where(pin, ub0, 0.0)
    w1 = phc.calculate_incumbent(ones, pin_mask=pin)
    assert w1 is not None
    assert phc.calculate_incumbent(np.where(pin, lb0, 0.0),
                                   pin_mask=pin) is None   # all-off
    assert ("chunks", ("fixed", False)) not in phc._qp_states
    w2 = phc.calculate_incumbent(ones, pin_mask=pin)
    assert w2 is not None
    assert abs(w2 - w1) <= 1e-3 * (1.0 + abs(w1)), (w1, w2)


# ---------------- oracle-vs-device agreement (farmer) ----------------

def test_oracle_vs_device_incumbent_agreement_farmer():
    """LP-relaxation-integral case: the device evaluation of a pinned
    candidate agrees with the exact host oracle's incumbent_value."""
    from mpisppy_tpu.utils.host_oracle import OraclePool

    batch = _farmer_batch()
    ph = PHBase(batch, {"defaultPHrho": 1.0, "subproblem_max_iter": 5000})
    ph.solve_loop(w_on=False, prox_on=False)
    cons = np.asarray(ph._hub_nonants()).mean(axis=0)
    objs, feas = ph.evaluate_incumbent_pool(cons[None, :])
    assert feas[0]
    pool = OraclePool(batch, n_workers=0)
    try:
        exact = pool.incumbent_value(cons, np.asarray(batch.prob))
    finally:
        pool.close()
    assert exact is not None
    assert abs(objs[0] - exact) <= 1e-4 * (1.0 + abs(exact)), \
        (objs[0], exact)


# ---------------- gate syncs / device_put on meshes ----------------

@pytest.mark.parametrize(
    "ndev", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_pool_gate_syncs_o1_and_zero_device_put(ndev, tmp_path):
    """Acceptance: the candidate-pool solve books O(1) gate syncs per
    round and ZERO new device_put bytes on multi-device meshes (the
    1-device case is asserted inside the equivalence test; the ISSUE's
    tier-1 satellite is the 2-device mesh, the 4-device case rides the
    nightly full suite) — the pool rows are ordinary chunks of the
    sharded dispatch."""
    mesh = make_mesh(ndev)
    batch = _uc_batch(S=4)
    pin, dive, lb0, ub0 = _uc_masks(batch)
    ph = PHBase(batch, {"defaultPHrho": 50.0, "subproblem_max_iter": 1000,
                        "subproblem_chunk": 2}, dtype=jnp.float64,
                mesh=mesh)
    ph.solve_loop(w_on=False, prox_on=False)
    X = np.asarray(ph._hub_nonants())[:batch.S]
    pool = inc.build_pool(X, np.asarray(ph.prob), dive,
                          ph.nonant_integer_mask, lb0, ub0,
                          thresholds=(0.5,), flips=1, n_random=0)
    obs.configure(out_dir=str(tmp_path / f"mesh{ndev}"))
    try:
        ph.evaluate_incumbent_pool(pool, pin_mask=pin)    # warm/compile
        before = obs.counters_snapshot()
        objs, feas = ph.evaluate_incumbent_pool(pool, pin_mask=pin)
        after = obs.counters_snapshot()
    finally:
        obs.shutdown()
    assert after.get("incumbent.gate_syncs", 0) \
        - before.get("incumbent.gate_syncs", 0) == 1, f"ndev={ndev}"
    assert after.get("xfer.device_put_bytes", 0) \
        == before.get("xfer.device_put_bytes", 0), f"ndev={ndev}"
    assert feas.any()          # the max-commitment anchor is feasible


# ---------------- mode wiring + satellites ----------------

def test_incumbent_mode_validation_and_device_gates():
    from mpisppy_tpu.cylinders.xhat_bounders import (DiveInnerBound,
                                                     XhatShuffleInnerBound)

    batch = _farmer_batch()
    ph = PHBase(batch, {"defaultPHrho": 1.0})
    with pytest.raises(ValueError, match="incumbent_mode"):
        XhatShuffleInnerBound(ph, options={"incumbent_mode": "bogus"})
    sp = DiveInnerBound(ph)
    assert sp._incumbent_mode == "device"          # the spoke's default
    # oracle-only is contradictory for the device-pool spoke: rejected
    # at construction with a pointer at the oracle-configured xhats
    with pytest.raises(ValueError, match="oracle"):
        DiveInnerBound(ph, options={"incumbent_mode": "oracle"})
    # device mode never constructs the oracle: exact eval reports
    # unavailable without importing host_oracle machinery
    assert sp._exact_eval(np.zeros(batch.K)) == ("unavailable", None)
    # run-level plumbing: RunConfig validates and vanilla seeds the
    # option into every spoke
    from mpisppy_tpu.utils.config import RunConfig, SpokeConfig
    from mpisppy_tpu.utils.vanilla import spoke_dict
    with pytest.raises(ValueError, match="incumbent_mode"):
        RunConfig(incumbent_mode="nope").validate()
    cfg = RunConfig(model="farmer", num_scens=3, incumbent_mode="device",
                    spokes=[SpokeConfig(kind="dive")]).validate()
    sd = spoke_dict(cfg, cfg.spokes[0], batch=batch)
    assert sd["opt_kwargs"]["options"]["incumbent_mode"] == "device"
    assert sd["spoke_class"] is DiveInnerBound
    # CLI surface
    from mpisppy_tpu.__main__ import config_from_args, make_parser
    args = make_parser().parse_args(
        ["farmer", "--num-scens", "3", "--with-dive",
         "--incumbent-mode", "device"])
    cfg2 = config_from_args(args)
    assert cfg2.incumbent_mode == "device"
    assert [s.kind for s in cfg2.spokes] == ["dive"]


def test_stash_consensus_skips_identical_blocks(mem_obs=None):
    """ISSUE 9 satellite: an identical consecutive consensus block
    skips the candidate regeneration entirely (incumbent.pool_reused)
    instead of re-running the build."""
    from mpisppy_tpu.cylinders.xhat_bounders import XhatShuffleInnerBound

    batch = _uc_batch()
    ph = PHBase(batch, {"defaultPHrho": 10.0})
    sp = XhatShuffleInnerBound(ph, options={
        "xhat_consensus_candidates": True, "xhat_pin_vars": ["u"]})
    rng = np.random.RandomState(5)
    X = rng.rand(batch.S, batch.K)
    obs.configure()
    try:
        sp._stash_consensus(X)
        cand = sp._consensus_cand.copy()
        c0 = obs.counters_snapshot().get("incumbent.pool_reused", 0)
        sp._stash_consensus(X)                     # identical block
        c1 = obs.counters_snapshot().get("incumbent.pool_reused", 0)
        assert c1 == c0 + 1
        np.testing.assert_array_equal(sp._consensus_cand, cand)
        sp._stash_consensus(X + 1e-6)              # moved: rebuild
        c2 = obs.counters_snapshot().get("incumbent.pool_reused", 0)
        assert c2 == c1
    finally:
        obs.shutdown()


def test_dive_spoke_reuse_and_auto_oracle_polish(monkeypatch):
    """DiveInnerBound round mechanics on a stubbed evaluator: identical
    hub blocks count incumbent.pool_reused and evaluate random-only
    pools; auto mode triggers the oracle POLISH after N dry rounds."""
    from mpisppy_tpu.cylinders.spcommunicator import Window
    from mpisppy_tpu.cylinders.xhat_bounders import DiveInnerBound

    batch = _uc_batch()
    ph = PHBase(batch, {"defaultPHrho": 10.0})
    sp = DiveInnerBound(ph, options={
        "incumbent_mode": "auto", "incumbent_oracle_after": 2,
        "xhat_pin_vars": ["u"], "incumbent_pool_random": 2})
    sp.hub_window = Window(sp.remote_window_length())
    sp.my_window = Window(sp.local_window_length())
    P = inc.pool_size(sp._dive_mask.sum())
    vals = [np.full(P, 100.0), np.full(P, 200.0), np.full(P, 200.0)]
    feas = np.ones(P, bool)
    calls = []
    monkeypatch.setattr(
        ph, "evaluate_incumbent_pool",
        lambda pool, pin_mask=None: (vals[min(len(calls), 2)], feas))
    # the publish-time verification returns the screen value unchanged
    monkeypatch.setattr(
        ph, "calculate_incumbent",
        lambda cand, feas_tol=None, pin_mask=None: 100.0)
    polished = []
    monkeypatch.setattr(sp, "_exact_eval",
                        lambda cand: (polished.append(1) or ("ok", 99.0)))
    rng = np.random.RandomState(2)
    X = rng.rand(batch.S, batch.K)
    obs.configure()
    try:
        sp.try_pool(X)                 # round 1: improves, publishes
        calls.append(1)
        assert sp.bound == 100.0 and sp._dry == 0
        sp.try_pool(X)                 # identical block: reused + dry 1
        calls.append(1)
        c = obs.counters_snapshot()
        assert c.get("incumbent.pool_reused", 0) == 1
        assert sp._dry == 1 and not polished
        sp.try_pool(X + 1e-3)          # dry 2 -> auto oracle polish
        assert polished and sp.bound == 99.0
        assert obs.counters_snapshot().get("incumbent.oracle_polish",
                                           0) == 1
    finally:
        obs.shutdown()


def test_oracle_pool_kill_check_between_queued_tasks():
    """ISSUE 9 satellite: a tripped kill_check stops the oracle batch
    BETWEEN queued tasks (drive threads poll it too) and the call
    reports None instead of partial results."""
    from mpisppy_tpu.utils.host_oracle import OraclePool

    batch = _farmer_batch()
    pool = OraclePool(batch, n_workers=1)
    try:
        polls = []

        def kill_after_first():
            polls.append(1)
            return len(polls) > 1

        out = pool.incumbent_value(
            np.zeros(batch.K), np.asarray(batch.prob),
            kill_check=kill_after_first)
        assert out is None
        assert len(polls) >= 2
    finally:
        pool.close()


# ---------------- the acceptance wheel (clean-path guard) ------------

_DEVICE_WHEEL = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
# x64 + the suite's persistent compile cache (tests/conftest.py uses
# the same owner): the fresh interpreter re-lowers but skips the XLA
# compiles
from mpisppy_tpu.utils.runtime import setup_jax_runtime
setup_jax_runtime()
import numpy as np
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.core.ph import PH, PHBase
from mpisppy_tpu.cylinders.hub import PHHub
from mpisppy_tpu.cylinders.xhat_bounders import (DiveInnerBound,
                                                 XhatShuffleInnerBound)
from mpisppy_tpu.cylinders.slam_heuristic import (SlamUpHeuristic,
                                                  SlamDownHeuristic)
from mpisppy_tpu.utils.sputils import spin_the_wheel
from mpisppy_tpu.models import uc

batch = build_batch(uc.scenario_creator, uc.make_tree(4),
                    creator_kwargs=dict(num_gens=3, num_hours=6,
                                        relax_integrality=False),
                    vector_patch=uc.scenario_vector_patch)
opts = {"defaultPHrho": 50.0, "PHIterLimit": 6, "convthresh": -1.0,
        "subproblem_max_iter": 3000, "xhat_pin_vars": ["u"],
        "incumbent_mode": "device"}
hub_dict = {"hub_class": PHHub, "hub_kwargs": {"options": {}},
            "opt_class": PH,
            "opt_kwargs": {"batch": batch, "options": dict(opts)}}
spoke_dicts = [
    {"spoke_class": cls, "opt_class": PHBase,
     "opt_kwargs": {"batch": batch, "options": dict(opts)}}
    for cls in (SlamUpHeuristic, SlamDownHeuristic,
                XhatShuffleInnerBound, DiveInnerBound)]
wheel = spin_the_wheel(hub_dict, spoke_dicts)
# ZERO host oracle subprocesses: the module is never even imported
assert "mpisppy_tpu.utils.host_oracle" not in sys.modules, \
    "device-mode wheel imported the host oracle"
bounds = [res[0] if isinstance(res, tuple) else res
          for res in wheel.spoke_results]
print("BOUNDS", [None if b is None else float(b) for b in bounds])
"""


def test_uc_device_wheel_beats_slams_without_oracle():
    """Acceptance: with incumbent_mode=device the UC fixture wheel's
    dive spoke reaches an inner bound at least as good as the best of
    slam-up/slam-down/xhatshuffle in the same iteration budget, and the
    host oracle module is NEVER imported (the clean-path guard
    pattern)."""
    out = subprocess.run(
        [sys.executable, "-c", _DEVICE_WHEEL],
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("BOUNDS")][0]
    bounds = eval(line[len("BOUNDS "):])       # [slamup, slamdown, xs, dive]
    dive = bounds[3]
    assert dive is not None and np.isfinite(dive), bounds
    others = [b for b in bounds[:3] if b is not None]
    if others:
        # minimization: the device incumbent is at least as good (tiny
        # slack for wheel-timing noise in which block each spoke saw)
        assert dive <= min(others) + 1e-2 * (1.0 + abs(min(others))), \
            bounds


# ---------------- live spawn-ctx wheel ----------------

def test_dive_wheel_process_bound_flow_healthy(tmp_path):
    """A real spawn-context process wheel with the dive spoke: it
    publishes a bound the hub ACCEPTS, and the bound-flow ledger's
    verdict for it is HEALTHY (doc/incumbents.md wire contract)."""
    from mpisppy_tpu.obs import analyze
    from mpisppy_tpu.utils.config import (AlgoConfig, RunConfig,
                                          SpokeConfig)
    from mpisppy_tpu.utils.multiproc import spin_the_wheel_processes

    tdir = str(tmp_path / "run")
    cfg = RunConfig(
        model="farmer", num_scens=3,
        algo=AlgoConfig(default_rho=10.0, max_iterations=50000,
                        convthresh=-1.0, subproblem_max_iter=2000,
                        subproblem_eps=1e-7),
        # the lagrangian spoke supplies the outer bound the rel_gap
        # termination needs (without one the hub would burn its whole
        # iteration budget) — the dive spoke is the one under test
        spokes=[SpokeConfig(kind="lagrangian"),
                SpokeConfig(kind="dive")],
        rel_gap=0.05, wheel_deadline=600.0, telemetry_dir=tdir,
    )
    try:
        hub = spin_the_wheel_processes(cfg, join_timeout=180.0)
        assert np.isfinite(hub.BestInnerBound)
        f = hub._spoke_flow[1]
        assert f["accepted"] >= 1
    finally:
        obs.shutdown()
    r = analyze.load_run(tdir)
    bf = analyze.bound_flow_summary(r)
    assert bf is not None and bf["spoke1"].get("kind") == "dive"
    assert bf["spoke1"]["verdict"] == "HEALTHY", bf["spoke1"]
    # the analyze incumbent section renders from the spoke's role
    # counters + round events
    s = analyze.incumbent_summary(r)
    assert s is not None and s["rounds"] >= 1 and s["improvements"] >= 1
    assert s["pool_size"] >= 1
    assert "== incumbent ==" in analyze.render_report(r)
