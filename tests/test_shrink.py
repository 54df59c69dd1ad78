"""Progressive problem shrinking (ISSUE 14): device-native fixing,
active-set compaction, per-slot adaptive rho.

Covers the ISSUE's test satellite: device-fixer vs host-Fixer parity
on UC (identical fix decisions + final objective), compaction
round-trip equivalence (compact -> solve -> expand == uncompacted to
solver tolerance) on farmer, chunked UC, and 2/4-device sharded
meshes, the O(1) gate-sync counter assertion on the compacted path,
and the compile-count pin (compiles only at bucket transitions; a
same-shape second wheel's transition compiles nothing). The df32
compacted gather of ISSUE 17: tests/test_shrink_df32.py.
"""

import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.extensions.fixer import (DeviceFixer, Fixer,
                                          uniform_fix_list)
from mpisppy_tpu.extensions.norm_rho_updater import (
    DeviceNormRhoUpdater, NormRhoUpdater)
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.ops import shrink as shrink_ops
from mpisppy_tpu.parallel.mesh import make_mesh

BIG = 2 ** 30


def farmer_batch(S=3):
    return build_batch(farmer.scenario_creator, farmer.make_tree(S))


def uc_batch(S=4, G=2, T=4):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T,
                                       "relax_integrality": False},
                       vector_patch=uc.scenario_vector_patch)


def slot0_fix_list(b):
    """Only slot 0 ever fixes — guarantees a PARTIAL fixed set so
    compaction has free slots to keep."""
    spec = uniform_fix_list(b, tol=5e-1, nb=3, lb=3, ub=3,
                            integer_only=False)
    for k in ("nb", "lb", "ub"):
        a = np.minimum(spec[k], BIG).copy()
        a[1:] = BIG
        spec[k] = a
    return spec


FARMER_OPTS = {"defaultPHrho": 5.0, "PHIterLimit": 25, "convthresh": 0.0,
               "subproblem_max_iter": 3000, "subproblem_eps": 1e-8,
               "shrink_fix": True, "id_fix_list_fct": slot0_fix_list}

UC_OPTS = {"defaultPHrho": 50.0, "PHIterLimit": 10, "convthresh": 0.0,
           "subproblem_max_iter": 4000, "subproblem_eps": 1e-6,
           "subproblem_chunk": 3, "iter0_infeasibility_abort": False,
           "shrink_fix": True,
           "id_fix_list_fct":
               lambda b: uniform_fix_list(b, tol=1e-2, nb=3, lb=3,
                                          ub=3)}


@pytest.fixture
def telemetry(tmp_path):
    rec = obs.configure(out_dir=str(tmp_path))
    yield rec, tmp_path
    obs.shutdown()


# ---------------- device fixer ----------------

def test_device_fixer_matches_host_fixer_on_uc():
    """ISSUE 14 satellite: the jitted test-and-fix makes IDENTICAL fix
    decisions to the host Fixer (same mask, same values, same final
    objective) — the device op is the host pass, relocated."""
    spec_fct = lambda b: uniform_fix_list(b, tol=1e-2, nb=2, lb=2, ub=2)
    opts = dict(UC_OPTS, PHIterLimit=8)
    opts.pop("shrink_fix")
    opts.pop("id_fix_list_fct")
    host = Fixer({"id_fix_list_fct": spec_fct})
    ph_h = PH(uc_batch(), dict(opts), extensions=host)
    ph_h.ph_main()
    dev = DeviceFixer({"id_fix_list_fct": spec_fct})
    ph_d = PH(uc_batch(), dict(opts), extensions=dev)
    ph_d.ph_main()
    assert host.nfixed > 0, "fixture must actually fix something"
    assert dev.nfixed == host.nfixed
    m_h = np.asarray(host.fixed_mask)
    m_d = np.asarray(ph_d._fixed_mask)
    np.testing.assert_array_equal(m_d, m_h)
    np.testing.assert_allclose(
        np.asarray(ph_d._fixed_vals)[m_d], host.fixed_vals[m_h],
        atol=1e-9)
    assert ph_d.Eobjective_value() == pytest.approx(
        ph_h.Eobjective_value(), rel=1e-9)


def test_device_fixer_never_fixes_without_integer_slots():
    """Default spec on a continuous model (integer_only) must fix
    nothing — the INT_NEVER sentinel survives the int32 cast."""
    opts = {"defaultPHrho": 5.0, "PHIterLimit": 6, "convthresh": 0.0,
            "subproblem_max_iter": 2000, "subproblem_eps": 1e-7,
            "shrink_fix": True, "shrink_fix_iters": 1,
            "shrink_fix_tol": 10.0}
    ph = PH(farmer_batch(), opts)
    ph.ph_main()
    assert ph.extensions.nfixed == 0
    assert not bool(np.asarray(ph._fixed_mask).any())


# ---------------- compaction round-trip equivalence ----------------

def test_compaction_roundtrip_farmer():
    """Compact -> solve -> expand == uncompacted pinned wheel to
    solver tolerance on the batched-A farmer (fused path), including
    the certified prox-off dual bound through the dual fold."""
    base = dict(FARMER_OPTS, PHIterLimit=40)   # settle W so the
    #   dual-bound comparison below is not dominated by W drift
    ph0 = PH(farmer_batch(), base)
    ph0.ph_main()
    o = dict(base, shrink_compact=True, shrink_buckets="0.2")
    ph1 = PH(farmer_batch(), o)
    ph1.ph_main()
    st = ph1._shrink_status
    assert st["compactions"] == 1 and st["bucket"] == 0.2
    assert st["n_cols"] < ph1.batch.n
    assert ph1._shrink is not None
    # full-width state for every consumer (hub wire, extensions)
    assert np.asarray(ph1.x).shape == np.asarray(ph0.x).shape
    # solver-tolerance equivalence: per-iteration solve differences
    # (each solve converges to sub_eps, not exactly) accumulate over
    # 25 iterations of W updates — the band is relative to the
    # trajectory's ~1e2 value scale
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ph1.W), np.asarray(ph0.W),
                               atol=5e-2)
    assert ph1.Eobjective_value() == pytest.approx(
        ph0.Eobjective_value(), rel=1e-5)
    # Lagrangian-mode certified bound (prox-off, W on): the compacted
    # dual + fold must certify the same bound as the pinned full solve
    ph0.solve_loop(w_on=True, prox_on=False, update=False)
    ph1.solve_loop(w_on=True, prox_on=False, update=False)
    assert ph1.Ebound() == pytest.approx(ph0.Ebound(), rel=1e-5)
    # fixed-mode consumers (incumbent evaluation) keep the FULL
    # system by design — and still agree after the compaction
    xhat = np.asarray(ph1.xbar)[0]
    assert ph1.calculate_incumbent(xhat) == pytest.approx(
        ph0.calculate_incumbent(xhat), rel=1e-5)
    # and the compacted hot loop keeps working after the detour
    ph1.solve_loop(w_on=True, prox_on=True)
    assert np.asarray(ph1.x).shape[1] == ph1.batch.n


def test_compaction_roundtrip_uc_chunked(telemetry):
    """Shared-structure UC through the CHUNKED loop: the compacted
    chunk chain must reproduce the pin-boxes trajectory essentially
    exactly (same shared factor math, smaller system), with the gate
    still ONE stacked D2H per iteration and the est-HBM figure
    tracking the active set."""
    rec, tmp = telemetry
    ph0 = PH(uc_batch(6, 3, 6), dict(UC_OPTS))
    ph0.ph_main()
    hbm_full = ph0._shrink_status["est_hbm_bytes_per_iter"]
    o = dict(UC_OPTS, shrink_compact=True, shrink_buckets="0.1,0.5")
    ph1 = PH(uc_batch(6, 3, 6), o)
    c_before = obs.counters_snapshot().get("ph.gate_syncs", 0)
    calls_before = obs.counters_snapshot().get("ph.solve_loop_calls", 0)
    ph1.ph_main()
    st = ph1._shrink_status
    assert st["compactions"] >= 1
    assert st["n_cols"] < ph1.batch.n and st["m_rows"] <= ph1.batch.m
    assert st["est_hbm_bytes_per_iter"] < hbm_full
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar), atol=1e-8)
    np.testing.assert_allclose(np.asarray(ph1.W), np.asarray(ph0.W),
                               atol=1e-6)
    assert ph1.Eobjective_value() == pytest.approx(
        ph0.Eobjective_value(), rel=1e-8)
    # O(1) gate-sync counter assertion on the compacted path: the
    # pipelined chunked loop pays ONE stacked-residual D2H per
    # solve_loop call, compacted or not
    syncs = obs.counters_snapshot().get("ph.gate_syncs", 0) - c_before
    calls = obs.counters_snapshot().get("ph.solve_loop_calls", 0) \
        - calls_before
    n_chunks = -(-ph1.batch.S // 3)
    assert n_chunks > 1
    assert syncs <= calls + 2, \
        f"{syncs} gate syncs over {calls} solve calls — compaction " \
        f"must not reintroduce per-chunk syncs (chunks={n_chunks})"
    assert ph1.phase_timing(True)["gate_d2h_syncs_per_call"] == 1.0


@pytest.mark.parametrize("ndev", [2, 4])
def test_compaction_sharded_mesh_matches_single_device(ndev):
    """Compaction under scenario-axis sharding: the sharded compacted
    wheel tracks the single-device compacted wheel within the sharded
    suite's usual tolerance (collective reduction reorderings)."""
    opts = dict(FARMER_OPTS, PHIterLimit=20, shrink_compact=True,
                shrink_buckets="0.2")
    ph0 = PH(farmer_batch(8), dict(opts))
    ph0.ph_main()
    ph1 = PH(farmer_batch(8), dict(opts), mesh=make_mesh(ndev))
    ph1.ph_main()
    assert ph1._shrink_status["compactions"] == 1
    assert ph1._shrink_status["n_cols"] \
        == ph0._shrink_status["n_cols"]
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar), atol=5e-3)
    np.testing.assert_allclose(np.asarray(ph1.W), np.asarray(ph0.W),
                               atol=5e-2)
    assert ph1.trivial_bound == pytest.approx(ph0.trivial_bound,
                                              rel=1e-5)


def test_compile_count_tracks_bucket_transitions(telemetry):
    """ISSUE 14 acceptance: a wheel pays at most one compile burst per
    bucket transition — after warmup, the only iterations with a
    nonzero ``jax.compiles`` delta are the ones right after a
    transition; and a SECOND same-shape wheel's transition re-uses the
    registered shape bucket (cache hit) and compiles NOTHING."""
    rec, tmp = telemetry
    # the registry is process-global by design (it mirrors the jit
    # cache); start this test from a clean slate so the compile /
    # cache-hit accounting below is self-contained
    shrink_ops._BUCKET_REGISTRY.clear()
    o = dict(FARMER_OPTS, shrink_compact=True, shrink_buckets="0.2")
    ph_a = PH(farmer_batch(), dict(o))
    ph_a.ph_main()
    assert ph_a._shrink_status["compactions"] == 1
    ctr = obs.counters_snapshot()
    assert ctr.get("shrink.bucket.compile", 0) == 1
    c0 = ctr.get("jax.compiles", 0)
    # wheel B: same config, same shapes — every program (full-shape
    # AND compacted-shape) is warm in the process jit cache, and its
    # bucket transition must hit the shape registry
    ph_b = PH(farmer_batch(), dict(o))
    ph_b.ph_main()
    assert ph_b._shrink_status["compactions"] == 1
    ctr2 = obs.counters_snapshot()
    assert ctr2.get("shrink.bucket.cache_hit", 0) >= 1
    assert ctr2.get("jax.compiles", 0) - c0 == 0, \
        "a same-shape wheel's bucket transition must compile nothing"
    fp = ph_b._shrink.fingerprint
    assert fp in shrink_ops.bucket_registry()


def test_failed_compaction_target_memoized(monkeypatch):
    """Review fix: when ALL slots fix (no free columns) the plan comes
    back None — the host staging must run once per target, not every
    miditer (the once-per-transition contract)."""
    calls = {"n": 0}
    orig = shrink_ops.build_plan

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(shrink_ops, "build_plan", counting)
    o = {"defaultPHrho": 5.0, "PHIterLimit": 12, "convthresh": 0.0,
         "subproblem_max_iter": 2000, "subproblem_eps": 1e-7,
         "shrink_fix": True, "shrink_compact": True,
         "shrink_buckets": "0.5",
         # nb=1 + a huge tol: EVERY slot fixes at the same miditer,
         # so the crossed target finds no free columns at all
         "id_fix_list_fct": lambda b: uniform_fix_list(
             b, tol=50.0, nb=1, lb=1, ub=1, integer_only=False)}
    ph = PH(farmer_batch(), o)
    ph.ph_main()
    assert ph.extensions.nfixed == ph.batch.K   # everything fixed
    assert ph._shrink is None                   # nothing to compact
    assert calls["n"] == 1, \
        "build_plan must run once per failed target, not per miditer"


def test_full_width_consumers_bypass_compacted_factors():
    """Review fix: dive_nonant_candidates builds full-width operands
    against self.c — with an active shrink plan it must pair them
    with FULL factors (and not clobber the compacted hot-loop warm
    state)."""
    o = dict(UC_OPTS, shrink_compact=True, shrink_buckets="0.1")
    ph = PH(uc_batch(6, 3, 6), o)
    ph.ph_main()
    assert ph._shrink is not None
    cands, feas = ph.dive_nonant_candidates()
    assert cands.shape == (ph.batch.S, ph.batch.K)
    # the compacted hot loop still works after the full-width detour
    ph.solve_loop(w_on=True, prox_on=True)
    assert np.asarray(ph.x).shape[1] == ph.batch.n


def test_install_batch_resets_shrink_and_extension_state():
    """Review fix: a re-leased serve engine must not leak the previous
    tenant's fixer streaks / latched bounds / compaction state (the
    folded constants bake tenant data)."""
    from mpisppy_tpu.serve.manager import install_batch
    o = dict(FARMER_OPTS, shrink_compact=True, shrink_buckets="0.2")
    ph = PH(farmer_batch(), o)
    ph.ph_main()
    assert ph._shrink is not None and ph.extensions.nfixed == 1
    hbm_compact = ph._shrink_status["est_hbm_bytes_per_iter"]
    install_batch(ph, farmer_batch())
    assert ph._shrink is None and not ph._shrink_factors
    st = ph._shrink_status
    assert st["compactions"] == 0 and st["fixed"] == 0
    assert st["n_cols"] == ph.batch.n
    assert st["est_hbm_bytes_per_iter"] > hbm_compact
    ext = ph.extensions
    assert ext.nfixed == 0 and not ext._init_done
    assert not bool(np.asarray(ph._fixed_mask).any())
    # and the engine runs the new tenant cleanly end to end
    ph.ph_main()
    assert ph._shrink_status["compactions"] == 1


# ---------------- cross-bucket warm transplant (ISSUE 17) ----------------

def test_warm_transplant_reconverges_in_fewer_iterations(
        monkeypatch, telemetry):
    """ISSUE 17 acceptance: at a bucket transition the surviving
    free-slot rows/cols of the per-scenario ADMM states transplant
    into the compacted width, and the transplanted start re-converges
    in STRICTLY fewer solver iterations than a cold restart of the
    same solve. The spy re-runs the first compacted-width solve from
    both starts at the hot-loop tolerance band — warm-start payoff
    lives at loose/moderate eps (the hot loop's regime); at tight eps
    the comparison would instead measure tail-convergence noise."""
    import mpisppy_tpu.core.ph as ph_mod
    from mpisppy_tpu.ops.qp_solver import qp_cold_state

    rec_t, tmp = telemetry
    o = dict(FARMER_OPTS, shrink_compact=True, shrink_buckets="0.2",
             subproblem_segment=25)
    ph = PH(farmer_batch(), o)
    flag, rec = {}, {}
    pull_orig = ph._transplant_pull

    def pull(key, fnew):
        tp = pull_orig(key, fnew)
        if tp is not None:
            flag["armed"], flag["n"] = True, tp["x"].shape[-1]
        return tp

    monkeypatch.setattr(ph, "_transplant_pull", pull)
    orig = ph_mod._solver_call

    def spy(fac, d, q, st, **kw):
        out = orig(fac, d, q, st, **kw)
        if flag.get("armed") and "warm" not in rec \
                and st.x.shape[-1] == flag["n"] \
                and bool(np.any(np.asarray(st.x))):
            kw2 = dict(kw, sub_eps=1e-4, sub_eps_hot=1e-4,
                       sub_eps_dua_hot=1e-4)
            rec["warm"] = int(orig(fac, d, q, st, **kw2)[0].iters)
            rec["cold"] = int(
                orig(fac, d, q, qp_cold_state(fac, d), **kw2)[0].iters)
        return out

    monkeypatch.setattr(ph_mod, "_solver_call", spy)
    ph.ph_main()
    st = ph._shrink_status
    assert st["transplants"] >= 1, "transition never transplanted"
    assert st["transplant_cold"] == 0, \
        "healthy farmer wheel must not book cold fallbacks"
    ctr = obs.counters_snapshot()
    assert ctr.get("shrink.transplants", 0) >= 1
    assert ctr.get("shrink.transplant_cold_fallbacks", 0) == 0
    assert "warm" in rec, "compacted-width transition solve not seen"
    assert rec["warm"] < rec["cold"], \
        f"warm transplant must beat cold restart: {rec}"
    # post-transition determinism: a transplant-off wheel lands on the
    # SAME trajectory — each solve converges to sub_eps regardless of
    # its start, so the transplant buys iterations, not a different
    # answer
    ph_c = PH(farmer_batch(), dict(o, shrink_transplant=False))
    ph_c.ph_main()
    assert ph_c._shrink_status["transplants"] == 0
    # solver-tolerance bands, same rationale as the round-trip tests:
    # each solve converges to sub_eps from either start, and the
    # per-solve differences accumulate over the W updates
    np.testing.assert_allclose(np.asarray(ph.xbar),
                               np.asarray(ph_c.xbar),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ph.W),
                               np.asarray(ph_c.W), atol=5e-2)


def test_transplant_poisoned_rows_zeroed_to_cold(telemetry):
    """The capture gate is self-certifying: a scenario whose device
    iterates fail the unscaled consensus checks (x != zB or A x != zA
    — e.g. hospital-rescued rows whose residual fields were scattered
    clean over diverged iterates, see _hospitalize) must be zeroed to
    a cold start inside the transplant, surfacing as ``cold_rows`` in
    the ``shrink.transplant`` event — NOT carried warm."""
    import json

    import jax.numpy as jnp

    rec_t, tmp = telemetry
    o = dict(FARMER_OPTS, shrink_compact=True, shrink_buckets="0.2")
    ph = PH(farmer_batch(), o)
    cap_orig = ph._transplant_capture

    def poison_then_capture(plan_new):
        for mode in (True, False):
            st = ph._qp_states.get(mode)
            if hasattr(st, "_replace"):
                ph._qp_states[mode] = st._replace(
                    x=st.x.at[0].set(jnp.full_like(st.x[0], 1e6)))
        return cap_orig(plan_new)

    ph._transplant_capture = poison_then_capture
    ph.ph_main()
    assert ph._shrink_status["transplants"] >= 1
    obs.shutdown()
    events = [json.loads(ln) for ln in
              (tmp / "events.jsonl").read_text().splitlines()]
    tps = [e for e in events if e.get("type") == "shrink.transplant"]
    assert tps and all(e["cold_rows"] >= 1 for e in tps), \
        f"a diverged row passed the consensus gate: {tps}"


# ---------------- per-slot adaptive rho ----------------

def test_per_slot_rho_update_op():
    """Unit: slots with primal residual dominating scale UP, dual-
    dominating slots scale DOWN, balanced slots hold; rho stays
    uniform across scenarios; one packed stats row."""
    import jax.numpy as jnp
    S, K = 4, 3
    rho = jnp.full((S, K), 2.0)
    prob = jnp.full((S,), 0.25)
    xbar = jnp.zeros((S, K))
    prev = xbar.at[:, 1].add(-10.0)     # slot 1: big dual residual
    xn = xbar.at[:, 0].add(8.0)         # slot 0: big primal residual
    new_rho, stats = shrink_ops.per_slot_rho_update(
        rho, prob, xn, xbar, prev, 2.0, 3.0)
    r = np.asarray(new_rho)
    assert (r == r[:1]).all()           # uniform across scenarios
    assert r[0, 0] == pytest.approx(6.0)    # primal-heavy: *3
    assert r[0, 1] == pytest.approx(2.0 / 3.0)  # dual-heavy: /3
    assert r[0, 2] == pytest.approx(2.0)    # balanced: unchanged
    st = np.asarray(stats)
    assert st.shape == (3,) and st[0] == 1.0


def test_device_rho_updater_runs_and_bounds_history():
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 10, "convthresh": 0.0,
            "subproblem_max_iter": 2000, "subproblem_eps": 1e-7,
            "shrink_rho": True, "primal_dual_mult": 0.5,
            "rho_update_factor": 1.5, "history_cap": 4}
    ph = PH(farmer_batch(), opts)
    ph.ph_main()
    ext = ph.extensions
    assert isinstance(ext, DeviceNormRhoUpdater)
    assert ext.updates > 0
    rho = np.asarray(ph.rho)
    assert (rho == rho[:1]).all(), \
        "per-slot rho must stay uniform across scenarios (the " \
        "single-factor prox path depends on it)"
    assert len(set(np.round(rho[0], 9))) > 1, \
        "per-slot update should move slots independently"
    assert len(ext.prim_hist) == 4 and len(ext.dual_hist) == 4


def test_host_rho_updater_history_bounded():
    """ISSUE 14 satellite: prim_hist/dual_hist are bounded deques —
    long serve-hosted wheels must not leak host memory."""
    upd = NormRhoUpdater({"primal_dual_mult": 0.5,
                          "rho_update_factor": 1.5, "history_cap": 3})
    ph = PH(farmer_batch(), {"defaultPHrho": 1.0, "PHIterLimit": 12,
                             "convthresh": 0.0,
                             "subproblem_max_iter": 2000,
                             "subproblem_eps": 1e-7},
            extensions=upd)
    ph.ph_main()
    assert len(upd.prim_hist) == 3 and len(upd.dual_hist) == 3
    assert upd.prim_hist.maxlen == 3


# ---------------- config / serve bucket identity ----------------

def test_shrink_config_validation():
    from mpisppy_tpu.utils.config import (AlgoConfig,
                                          parse_shrink_buckets)
    assert parse_shrink_buckets("0.25,0.5,0.75") == (0.25, 0.5, 0.75)
    assert parse_shrink_buckets((0.1,)) == (0.1,)
    with pytest.raises(ValueError):
        parse_shrink_buckets("0.5,0.25")     # not increasing
    with pytest.raises(ValueError):
        parse_shrink_buckets("1.5")          # out of range
    with pytest.raises(ValueError):
        parse_shrink_buckets("")
    AlgoConfig(shrink_fix=True, shrink_compact=True).validate()
    with pytest.raises(ValueError):
        AlgoConfig(shrink_compact=True).validate()   # needs shrink_fix
    with pytest.raises(ValueError):
        AlgoConfig(shrink_fix_iters=0).validate()
    with pytest.raises(ValueError):
        AlgoConfig(shrink_rho_interval=0).validate()
    with pytest.raises(ValueError):
        AlgoConfig(shrink_fix=True, shrink_buckets="2.0",
                   shrink_compact=True).validate()


def test_shrink_cli_flags_reach_algo_config():
    from mpisppy_tpu.__main__ import config_from_args, make_parser
    cfg = config_from_args(make_parser().parse_args(
        ["farmer", "--shrink-compact", "--shrink-buckets", "0.3,0.6",
         "--shrink-rho", "--shrink-rho-interval", "2"]))
    cfg.validate()
    assert cfg.algo.shrink_fix and cfg.algo.shrink_compact
    assert cfg.algo.shrink_buckets == "0.3,0.6"
    assert cfg.algo.shrink_rho and cfg.algo.shrink_rho_interval == 2
    opts = cfg.algo.to_options()
    assert opts["shrink_compact"] and opts["shrink_buckets"] == "0.3,0.6"


def test_serve_bucket_key_separates_shrink_configs():
    """ISSUE 14 satellite: shrink knobs are bucket identity — a
    shrink-enabled request must never share a leased engine with a
    shrink-disabled one (the compacted factor caches and folded
    constants are per-tenant state)."""
    from mpisppy_tpu.serve.batch import bucket_key
    base = {"model": "farmer", "num_scens": 3}
    on = dict(base, algo={"shrink_fix": True, "shrink_compact": True})
    assert bucket_key(base) != bucket_key(on)
    assert bucket_key(dict(base, algo={"shrink_buckets": "0.5"})) \
        != bucket_key(on)
    assert bucket_key(base) == bucket_key(dict(base, algo={}))


# ---------------- analyze shrinking section ----------------

def test_analyze_shrinking_section(tmp_path):
    obs.configure(out_dir=str(tmp_path))
    try:
        o = dict(FARMER_OPTS, shrink_compact=True, shrink_buckets="0.2")
        ph = PH(farmer_batch(), o)
        ph.ph_main()
    finally:
        obs.shutdown()
    from mpisppy_tpu.obs.analyze import (load_run, render_report,
                                         shrink_summary)
    run = load_run(str(tmp_path))
    sh = shrink_summary(run)
    assert sh is not None
    assert sh["compactions"] == 1
    assert sh["fixed_final"] == 1
    assert sh["bucket_compiles"] + sh["bucket_cache_hits"] >= 1
    assert sh["compaction_events"][0]["n_cols"] < ph.batch.n
    assert sh["per_bucket"], "per-bucket s/iter rows must exist"
    buckets = {r["bucket"] for r in sh["per_bucket"]}
    assert 0.2 in buckets
    report = render_report(run)
    assert "== shrinking ==" in report
    assert "per-bucket s/iter" in report
    # ISSUE 17: transplant totals + per-bucket post-transition
    # re-convergence ride the same summary (and therefore --json)
    assert sh["transplants"] >= 1
    assert sh["transplant_cold_fallbacks"] == 0
    rec_rows = sh["reconvergence"]
    assert [r["bucket"] for r in rec_rows] == [0.2]
    assert rec_rows[0]["mode"] == "warm"
    assert rec_rows[0]["pre_conv"] is not None
    assert "cross-bucket transplants" in report
    assert "post-transition re-convergence" in report
    # self-compare at an equal bucket schedule: the cold-fallback
    # verdict row renders and passes (the REGRESSION arm is counter
    # arithmetic on the same summaries)
    from mpisppy_tpu.obs.analyze import compare
    text, passed = compare(run, run)
    assert "cold-fallback verdict [PASS]" in text


def test_compacted_hospital_treats_flagged_rows(telemetry):
    """ISSUE 15 satellite (the ROADMAP item 5 remainder): the
    per-scenario hospital runs AGAINST THE COMPACTED SYSTEM instead of
    bypassing compacted passes — the rescue assembles from the
    compacted cost block + free-slot hub state, factors at the
    compacted width, and scatters cured rows back into the
    compacted-width records; chunk retries + blacklist re-admission
    keep running on the compacted system as before."""
    import jax.numpy as jnp

    rec, tmp = telemetry
    o = dict(UC_OPTS, shrink_compact=True, shrink_buckets="0.01",
             id_fix_list_fct=slot0_fix_list)
    ph = PH(uc_batch(6, 3, 6), o)
    ph.ph_main()
    shrink = ph._shrink
    assert shrink is not None, "compaction never engaged"
    factors, data = ph._get_factors(True)
    # compacted width: the hospital must size its batched factors to
    # THIS system, not the full one
    assert data.lb.shape[-1] == shrink.n_c < ph.batch.n
    slices = ph._chunk_index(3)
    states = ph._qp_states[("chunks", True)]
    nc, mc = shrink.n_c, data.l.shape[-1]
    recs = []
    for ci, (idx_c, real) in enumerate(slices):
        st = states[ci]
        if ci == 1:     # flag one row of chunk 1 as grossly unconverged
            st = st._replace(pri_rel=st.pri_rel.at[0].set(1.0))
        recs.append([st, jnp.zeros((3, nc)), jnp.zeros((3, mc)),
                     jnp.zeros((3, nc)), None, None])
    kw = dict(prox_on=True, precision=ph.sub_precision,
              sub_max_iter=ph.sub_max_iter, sub_eps=ph.sub_eps,
              sub_eps_hot=ph.sub_eps_hot,
              sub_eps_dua_hot=ph.sub_eps_dua_hot,
              tail_iter=ph.sub_tail_iter, stall_rel=ph.sub_stall_rel,
              segment=ph.sub_segment, polish_hot=ph.sub_polish_hot,
              polish_chunk=0, segment_lo=ph.sub_segment_lo)
    treated0 = obs.counters_snapshot().get("ph.hospital_treated", 0)
    ph._hospitalize(True, slices, recs, data, thr=1e-2, w_on=True,
                    prox_on=True, kw=kw, shrink=shrink)
    assert obs.counters_snapshot().get("ph.hospital_treated", 0) \
        - treated0 == 1
    # cured at the COMPACTED width and scattered back
    assert float(recs[1][0].pri_rel[0]) < 1e-2
    assert recs[1][1].shape == (3, nc)
    assert float(jnp.abs(recs[1][1][0]).max()) > 0.0
    # unflagged rows untouched
    assert float(jnp.abs(recs[0][1]).max()) == 0.0
    # and the full compacted loop keeps working with the hospital
    # armed (it no longer bypasses): retries/blacklists path included
    ph.solve_loop(w_on=True, prox_on=True)
    assert np.asarray(ph.x).shape[1] == ph.batch.n
