"""Kernel layer (ops/kernels, ISSUE 7): fused-vs-segmented
equivalence at micro and PH level (farmer + uc shapes, f32 bulk and
df32 tail, pathological-chunk recovery), what ``prepare`` resolves for
every mode / factor kind / sweep count, the L⁻¹-matmul roofline
trade's guard, mesh gate-sync invariants, and the combined
kernel-mode/ir-sweeps config validation."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.ops import kernels
from mpisppy_tpu.ops.kernels.reference import fused_mixed_solve
from mpisppy_tpu.ops.packed import Packed
from mpisppy_tpu.ops.qp_solver import (LInv, PackedMatrix, QPData,
                                       SplitMatrix, make_l_inv,
                                       qp_cold_state, qp_setup,
                                       qp_solve_mixed, qp_solve_segmented,
                                       _chol_solve)
from mpisppy_tpu.parallel.mesh import make_mesh


# ---------------- fixtures ----------------

def _tiny_qp(S=3, m=6, n=4, seed=0, dtype=jnp.float64):
    """Small well-posed box-constrained QP with shared structure (the
    representation every kernel backend supports)."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(m, n)), dtype)
    P = jnp.asarray(np.abs(rng.normal(size=n)) + 0.5, dtype)
    mid = rng.normal(size=(S, m))
    d = QPData(P_diag=P, A=A,
               l=jnp.asarray(mid - 3.0, dtype),
               u=jnp.asarray(mid + 3.0, dtype),
               lb=jnp.full((S, n), -5.0, dtype),
               ub=jnp.full((S, n), 5.0, dtype))
    q = jnp.asarray(rng.normal(size=(S, n)), dtype)
    fac = qp_setup(d, q_ref=q)
    return fac, d, q, qp_cold_state(fac, d)


def _uc_batch(S, G=3, T=6, **kw):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T, **kw},
                       vector_patch=uc.scenario_vector_patch)


def _run_ph(batch_fn, opts, iters=3, mesh=None):
    ph = PHBase(batch_fn(), dict(opts), dtype=jnp.float64, mesh=mesh)
    for it in range(iters):
        ph.solve_loop(w_on=(it > 0), prox_on=(it > 0))
        ph.W = ph.W_new
    return ph


# ---------------- micro-parity (the fast CI drift guard) ----------------

def test_micro_parity_fused_native_vs_segmented():
    """The seconds-scale backend drift guard (ISSUE 7 CI satellite):
    5 ADMM iterations of the fused reference backend on a tiny
    synthetic QP agree with the segmented driver to 1e-10 — any edit
    that desyncs the two dispatch paths fails here, not only in the
    minutes-scale PH equivalence suite below."""
    fac, d, q, st = _tiny_qp()
    kw = dict(check_every=1, eps_abs=0.0, eps_rel=0.0, polish=False)
    st_s, x_s, yA_s, yB_s = qp_solve_segmented(fac, d, q, st, max_iter=5,
                                               segment=5, **kw)
    plan = kernels.prepare(fac, mode="fused", precision="native")
    assert plan.mode == "fused"
    st_f, x_f, yA_f, yB_f = kernels.kernel_solve(
        plan, fac, d, q, st, precision="native", max_iter=5, tail_iter=0,
        e_pri=0.0, e_dua=0.0, stall_rel=0.0, polish=False, polish_chunk=0,
        ir_sweeps=1, check_every=1)
    assert int(st_f.iters) == int(st_s.iters) == 5
    for a, b in ((x_s, x_f), (yA_s, yA_f), (yB_s, yB_f),
                 (st_s.pri_rel, st_f.pri_rel)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-10)


def test_micro_parity_fused_mixed_vs_mixed_driver():
    """Same guard for the precision-escalated program: with both
    phases inside one segment the fused mixed solve is bit-compatible
    with qp_solve_mixed (segment boundaries are the only semantic the
    fusion removes)."""
    fac, d, q, st = _tiny_qp(seed=1)
    kw = dict(eps_abs=1e-9, eps_rel=1e-9, polish=True)
    st_m, x_m, _, _ = qp_solve_mixed(fac, d, q, st, max_iter=50,
                                     tail_iter=50, segment=50, **kw)
    plan = kernels.prepare(fac, mode="fused", precision="mixed")
    st_f, x_f, _, _ = fused_mixed_solve(
        fac, plan.A_lo, d, q, st, bulk_iter=50, tail_iter=50,
        check_every=25, eps_abs=1e-9, eps_rel=1e-9, eps_abs_dua=1e-9,
        eps_rel_dua=1e-9, polish=True, polish_iters=12, polish_chunk=0,
        stall_rel=0.0, ir_sweeps=1, l_inv=False)
    np.testing.assert_allclose(np.asarray(x_m), np.asarray(x_f),
                               atol=1e-10)
    np.testing.assert_allclose(np.asarray(st_m.pri_rel),
                               np.asarray(st_f.pri_rel), atol=1e-10)
    assert int(st_f.iters) == int(st_m.iters)


@pytest.mark.parametrize("driver", ["fused", "segmented", "native"])
def test_bulk_tail_split_rule(driver):
    """One rule for the bulk/tail split of a solve's ADMM iterations
    (``QPState.iters_lo`` of ``iters``), whichever driver ran it: the
    low-precision phase's iterations are the bulk, everything else the
    tail; a solve with no low-precision phase is all tail."""
    fac, d, q, st = _tiny_qp(seed=1)
    kw = dict(eps_abs=1e-9, eps_rel=1e-9, polish=True)
    st_m, _, _, _ = qp_solve_mixed(fac, d, q, st, max_iter=50,
                                   tail_iter=50, segment=50, **kw)
    bulk, total = int(st_m.iters_lo), int(st_m.iters)
    assert 0 < bulk <= 50 and 0 < total - bulk <= 50
    if driver == "fused":
        plan = kernels.prepare(fac, mode="fused", precision="mixed")
        st_f, _, _, _ = fused_mixed_solve(
            fac, plan.A_lo, d, q, st, bulk_iter=50, tail_iter=50,
            check_every=25, eps_abs=1e-9, eps_rel=1e-9, eps_abs_dua=1e-9,
            eps_rel_dua=1e-9, polish=True, polish_iters=12,
            polish_chunk=0, stall_rel=0.0, ir_sweeps=1, l_inv=False)
        assert (int(st_f.iters_lo), int(st_f.iters)) == (bulk, total)
    elif driver == "segmented":
        # the same budgets cut into two segments a phase (another
        # trajectory: a boundary resets the stall window): the host's
        # lo_total is what lands in iters_lo, whole segments of it
        st_s, _, _, _ = qp_solve_mixed(fac, d, q, st, max_iter=50,
                                       tail_iter=50, segment=25, **kw)
        lo, hi = int(st_s.iters_lo), int(st_s.iters) - int(st_s.iters_lo)
        assert lo in (25, 50) and 0 < hi <= 50
    else:
        st_n, _, _, _ = qp_solve_segmented(fac, d, q, st, max_iter=50,
                                           segment=25, **kw)
        assert int(st_n.iters_lo) == 0 and int(st_n.iters) > 0
        plan = kernels.prepare(fac, mode="fused", precision="native")
        st_k, _, _, _ = kernels.kernel_solve(
            plan, fac, d, q, st, precision="native", max_iter=50,
            tail_iter=0, e_pri=1e-9, e_dua=1e-9, stall_rel=0.0,
            polish=True, polish_chunk=0, ir_sweeps=1)
        assert int(st_k.iters_lo) == 0 and int(st_k.iters) > 0


def test_fused_program_carries_named_scopes():
    """The fused program's phases and the solver's steps are named in
    op metadata (jax.named_scope: no op, shape or output changes), so
    an xprof view groups its anonymous fusions by ``qp.*`` scope."""
    from mpisppy_tpu.ops.kernels.reference import (_FUSED_STATICS,
                                                   _fused_mixed_impl)
    fac, d, q, st = _tiny_qp(seed=1)
    plan = kernels.prepare(fac, mode="fused", precision="mixed")
    iterates = (st.x, st.yA, st.yB, st.zA, st.zB)
    aux = (st.L, st.rho_scale, st.iters)
    lowered = jax.jit(_fused_mixed_impl,
                      static_argnames=_FUSED_STATICS).lower(
        fac, plan.A_lo, d, q, iterates, aux, 1e-9, 1e-9, 1e-9, 1e-9,
        bulk_iter=50, tail_iter=50, check_every=25,
        adaptive_rho=np.bool_(True), polish=True, polish_iters=12,
        polish_chunk=0, stall_rel=0.0, ir_sweeps=1, l_inv=False)
    text = lowered.as_text(debug_info=True)
    for scope in ("qp.bulk", "qp.handoff", "qp.tail", "qp.kkt_solve",
                  "qp.Ax", "qp.ATy", "qp.check", "qp.rho_adapt",
                  "qp.polish"):
        assert scope in text, scope
    assert "qp.tail/" in text and "qp.kkt_solve" in text.split(
        "qp.tail/", 1)[1]          # the steps nest under the phase


def test_one_fused_program_serves_donating_and_frozen_rho_callers():
    """At UC width every distinct fused program is minutes of compile
    and GiBs of host memory, so the hot loop's donating passes, a first
    pass that keeps its state, and the incumbent pool's frozen-rho
    solves must all run ONE executable: ``adaptive_rho`` is traced, and
    ``donate=False`` hands the donating program private copies."""
    from mpisppy_tpu.ops.kernels import reference as ref

    fac, d, q, st = _tiny_qp(seed=2)
    plan = kernels.prepare(fac, mode="fused", precision="mixed")
    kw = dict(bulk_iter=40, tail_iter=40, check_every=10, eps_abs=1e-9,
              eps_rel=1e-9, eps_abs_dua=1e-9, eps_rel_dua=1e-9,
              polish=False, polish_iters=12, polish_chunk=0,
              stall_rel=0.0, ir_sweeps=1, l_inv=False)
    jitted = ref._fused_mixed_jit_donated.__wrapped__
    st_a, x_a, _, _ = fused_mixed_solve(fac, plan.A_lo, d, q, st, **kw)
    size = jitted._cache_size()
    np.asarray(st.x)             # donate=False left the caller's state
    st_f, x_f, _, _ = fused_mixed_solve(fac, plan.A_lo, d, q, st,
                                        adaptive_rho=False, **kw)
    st_d, x_d, _, _ = fused_mixed_solve(fac, plan.A_lo, d, q, st_a,
                                        donate=True, **kw)
    assert jitted._cache_size() == size
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st_a.x)       # donate=True consumed the iterates
    # the traced flag really freezes the stepsize
    assert float(st_f.rho_scale) == float(st.rho_scale)
    assert np.isfinite(np.asarray(x_d)).all()


# ---------------- PH-level fused-vs-segmented equivalence ----------------

def test_fused_matches_segmented_ph_uc_chunked():
    """Native-precision chunked PH on the UC shape: fused and
    segmented kernel modes track each other to solver tolerance when
    the iteration budget does not bind (budget-capped solves disagree
    by construction — the segmented driver overshoots to full
    segments). Also pins the plan bookkeeping phase_timing reports."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 6000,
            "subproblem_eps": 1e-8, "subproblem_chunk": 3,
            "subproblem_segment": 1000}
    ph_s = _run_ph(lambda: _uc_batch(6),
                   {**opts, "subproblem_kernel_mode": "segmented"})
    ph_f = _run_ph(lambda: _uc_batch(6),
                   {**opts, "subproblem_kernel_mode": "fused"})
    assert ph_s.phase_timing(True)["kernel"]["mode"] == "segmented"
    assert ph_f.phase_timing(True)["kernel"]["mode"] == "fused"
    assert ph_f.conv == pytest.approx(ph_s.conv, abs=1e-8)
    np.testing.assert_allclose(np.asarray(ph_f.xbar),
                               np.asarray(ph_s.xbar), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ph_f.W), np.asarray(ph_s.W),
                               atol=1e-5)
    for ph in (ph_s, ph_f):
        assert float(np.asarray(ph._qp_states[True].pri_rel).max()) < 1e-6


def test_fused_matches_segmented_ph_farmer_mixed():
    """Farmer under 'mixed' precision (the f32 bulk + f64 tail
    escalation, non-chunked path): fused and segmented agree at the
    converged-solve level."""
    def mk():
        return build_batch(farmer.scenario_creator, farmer.make_tree(3))

    opts = {"defaultPHrho": 1.0, "subproblem_precision": "mixed",
            "subproblem_max_iter": 4000, "subproblem_eps": 1e-8,
            "subproblem_segment": 1000}
    ph_s = _run_ph(mk, {**opts, "subproblem_kernel_mode": "segmented"})
    ph_f = _run_ph(mk, {**opts, "subproblem_kernel_mode": "fused"})
    assert ph_f.conv == pytest.approx(ph_s.conv, rel=1e-6, abs=1e-9)
    np.testing.assert_allclose(np.asarray(ph_f.xbar),
                               np.asarray(ph_s.xbar), rtol=1e-6,
                               atol=1e-6)


def test_fused_matches_segmented_ph_uc_df32_with_pathological_chunk():
    """df32 chunked PH (split matvecs, f32 factor flow, L⁻¹ tail under
    the auto profitability check) with a forced-pathological chunk
    (tests/test_pipeline.py's poison pattern): the fused path must
    recover through the SAME segmented native-precision retry — the
    recovery machinery is the fused path's full-precision fallback —
    and land the same blacklist decisions."""
    from mpisppy_tpu.ops.qp_solver import _factorize

    # bulk budget 450 = three whole 150-iteration segments: both
    # drivers then stop at the SAME cap (at 400 the segmented driver
    # overshoots to 450 while the fused program stops at 400, and one
    # healthy chunk's capped residual lands within 3% of the 1e-2 retry
    # gate — which side it falls is decided by f32 rounding order, so
    # the blacklist comparison below compared rounding, not recovery).
    # Tail 600 for the same reason: at 150 the capped solves sit at the
    # df32 noise floor and conv after the recovery differs 3x between
    # the two drivers; at 600 they agree to 10%.
    opts = {"defaultPHrho": 50.0, "subproblem_precision": "df32",
            "subproblem_max_iter": 450, "subproblem_eps": 1e-5,
            "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
            "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 600,
            "subproblem_segment": 150, "subproblem_polish_hot": False,
            "subproblem_hospital": False, "subproblem_chunk": 2}

    def poisoned(mode):
        ph = _run_ph(lambda: _uc_batch(4),
                     {**opts, "subproblem_kernel_mode": mode}, iters=2)
        sts = ph._qp_states[("chunks", True)]
        factors, _ = ph._get_factors(True)
        bad_rho = jnp.full_like(sts[0].rho_scale, 1e-6)
        sts[0] = sts[0]._replace(rho_scale=bad_rho,
                                 L=_factorize(factors, bad_rho))
        ph.solve_loop(w_on=True, prox_on=True)
        return ph

    ph_f = poisoned("fused")
    ph_s = poisoned("segmented")
    # the fused df32 plan engaged the L⁻¹ trade (profitable at this
    # budget/chunk) — the poisoned run exercised LInv wrap + refactor
    assert ph_f.phase_timing(True)["kernel"]["l_inv"]
    pr_f = np.asarray(ph_f._qp_states[True].pri_rel)
    pr_s = np.asarray(ph_s._qp_states[True].pri_rel)
    assert pr_f.max() < 1e-2, f"fused recovery missed: {pr_f.max():.1e}"
    assert pr_s.max() < 1e-2
    assert ph_f._chunk_no_retry.get(True, set()) \
        == ph_s._chunk_no_retry.get(True, set())
    # budget-capped df32 trajectories are tolerance-equivalent, not
    # iterate-equal (the segmented driver overshoots to full segments,
    # the fused program stops at the cap) — same ballpark, not same
    # vertex
    assert ph_f.conv == pytest.approx(ph_s.conv, rel=0.25)


def test_fused_gate_syncs_o1_on_1_2_4_device_meshes(tmp_path):
    """Acceptance criterion: the fused reference backend on 1-, 2- and
    4-virtual-device meshes keeps ph.gate_syncs at O(1) per iteration
    and tracks the segmented trajectory at the consensus level."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 6000,
            "subproblem_eps": 1e-8, "subproblem_chunk": 2,
            "subproblem_segment": 1000}
    for ndev in (1, 2, 4):
        mesh = make_mesh(ndev) if ndev > 1 else None
        ph_s = _run_ph(lambda: _uc_batch(16),
                       {**opts, "subproblem_kernel_mode": "segmented"},
                       iters=2, mesh=mesh)
        obs.configure(out_dir=str(tmp_path / f"mesh{ndev}"))
        try:
            ph_f = _run_ph(lambda: _uc_batch(16),
                           {**opts, "subproblem_kernel_mode": "fused"},
                           iters=2, mesh=mesh)
            before = obs.counters_snapshot()
            ph_f.solve_loop(w_on=True, prox_on=True)   # steady state
            ph_f.W = ph_f.W_new
            after = obs.counters_snapshot()
            assert after.get("ph.gate_syncs", 0) \
                - before.get("ph.gate_syncs", 0) == 1, f"ndev={ndev}"
            assert after.get("kernel.fused_iters", 0) > 0
        finally:
            obs.shutdown()
        pt = ph_f.phase_timing(True)
        assert pt["devices"] == ndev
        assert pt["kernel"]["mode"] == "fused"
        np.testing.assert_allclose(np.asarray(ph_f.xbar),
                                   np.asarray(ph_s.xbar), atol=5e-3)


# ---------------- the L⁻¹ trade ----------------

def test_l_inv_matmul_vs_triangular_solve_parity():
    """x = L⁻ᵀ(L⁻¹ b) via two matmuls must agree with the triangular
    back-substitutions within the κ·eps32 forward-error band — the
    measured envelope doc/kernels.md quotes for the trade."""
    rng = np.random.default_rng(7)
    n = 48
    B = rng.normal(size=(n, n))
    M = B @ B.T + n * np.eye(n)
    L32 = jnp.linalg.cholesky(jnp.asarray(M, jnp.float32))
    b = jnp.asarray(rng.normal(size=(5, n)))            # f64 rhs
    x_exact = np.linalg.solve(M, np.asarray(b).T).T
    x_tri = np.asarray(_chol_solve(L32, b))
    li = make_l_inv(L32)
    assert isinstance(li, LInv)
    np.testing.assert_array_equal(np.asarray(li.tri), np.asarray(L32))
    x_inv = np.asarray(_chol_solve(li, b))
    kappa = np.linalg.cond(M)
    band = kappa * np.finfo(np.float32).eps
    scale = np.abs(x_exact).max()
    assert np.abs(x_tri - x_exact).max() / scale <= 8 * band
    assert np.abs(x_inv - x_exact).max() / scale <= 8 * band
    assert np.abs(x_inv - x_tri).max() / scale <= 8 * band


def test_l_inv_profitability_check():
    """The inverse's build must break even within one solve's TAIL (the
    bulk never applies it), and an apply must beat the prepared
    substitution's at that width and row count (measured on the v5e,
    PERF.md §6, PR 41): narrow factors and production budgets engage;
    short exploratory solves must not; and UC width must not at any
    row count: there the chip read the substitution faster at 64 rows
    (1.73 against 1.92 ms an apply) and at 128 (2.21 against 2.85)."""
    from mpisppy_tpu.ops.kernels.reference import (l_inv_apply_s,
                                                   prepared_apply_s)
    # the uc1024 production shape (tail 100, 128-scenario chunks): the
    # build would amortize (25,600 applies >= n), the apply loses
    assert not kernels.l_inv_profitable(n=13056, s_chunk=128,
                                        tail_iter=100, ir_sweeps=1)
    assert not kernels.l_inv_profitable(n=13056, s_chunk=128,
                                        tail_iter=500, ir_sweeps=1)
    assert not kernels.l_inv_profitable(n=13056, s_chunk=1,
                                        tail_iter=100, ir_sweeps=1)
    # the benchmark's cells: (13056, 64) off, sslp's (520, 2000) ON
    assert not kernels.l_inv_profitable(n=13056, s_chunk=64,
                                        tail_iter=100, ir_sweeps=1)
    assert kernels.l_inv_profitable(n=520, s_chunk=2000, tail_iter=100,
                                    ir_sweeps=1)
    # a mid width, where the substitution's steps still dominate
    assert kernels.l_inv_profitable(n=2944, s_chunk=64, tail_iter=100,
                                    ir_sweeps=1)
    assert not kernels.l_inv_profitable(n=2944, s_chunk=1, tail_iter=100,
                                        ir_sweeps=1)     # never repaid
    # the model IS the four readings it was fitted to, to 1%
    for rows, prep_ms, inv_ms in ((64, 1.733, 1.923), (128, 2.214, 2.849)):
        assert prepared_apply_s(13056, rows) * 1e3 == pytest.approx(
            prep_ms, rel=0.01)
        assert l_inv_apply_s(13056, rows) * 1e3 == pytest.approx(
            inv_ms, rel=0.01)


def test_host_factor_path_matches_device_path(monkeypatch):
    """The TPU's path for non-shared f64 factors (its batched f64
    device inverse is shape-dependently wrong — doc/tpu_numerics.md):
    host inversion, rho adaptation on the host between segments. Forced
    on here, farmer (per-scenario A) must walk the same PH trajectory
    as the device path, and the host refactorization's scatter must be
    ONE program however many rows moved (the serving layer's
    compile-once contract rides on it)."""
    import mpisppy_tpu.ops.qp_solver as qps

    def batch():
        return build_batch(farmer.scenario_creator, farmer.make_tree(3))

    opts = {"defaultPHrho": 1.0, "subproblem_max_iter": 3000,
            "subproblem_eps": 1e-8, "subproblem_segment": 100}
    ph_dev = _run_ph(batch, opts, iters=4)
    monkeypatch.setattr(qps, "_device_f64_linalg_trusted", lambda: False)
    obs.configure(out_dir=None)
    try:
        ph_host = _run_ph(batch, opts, iters=4)
        moved = obs.counter_value("qp.host_rho_refactors")
        fac, _ = ph_host._get_factors(True)
        assert qps._needs_host_factor(fac)
        assert ph_host.phase_timing(True) is None \
            or ph_host.phase_timing(True)["kernel"]["mode"] == "segmented"
        # rows-moved counts of 1, 2 and 3 share one scatter program
        st = ph_host._qp_states[True]
        def adapt(k):
            pr = np.where(np.arange(3) < k, 1e-2, 1e-9)
            qps._host_adapt_rho(fac, st._replace(
                pri_rel=jnp.asarray(pr),
                dua_rel=jnp.asarray(np.full(3, 1e-9))))

        adapt(3)
        compiles = obs.counter_value("jax.compiles")
        adapt(1)
        adapt(2)
        assert obs.counter_value("qp.host_rho_refactors") == moved + 6
        assert obs.counter_value("jax.compiles") == compiles
    finally:
        obs.shutdown()
    assert moved > 0
    assert ph_host.conv == pytest.approx(ph_dev.conv, abs=1e-6)
    np.testing.assert_allclose(np.asarray(ph_host.xbar),
                               np.asarray(ph_dev.xbar), atol=1e-4)


def test_fused_mode_eligibility_guards(monkeypatch):
    """Explicit 'fused' on factors whose rho adaptation must
    refactorize on the host is a config error (the in-trace _factorize
    would produce an untrusted device inverse); 'auto' falls back.
    Nothing else makes 'auto' refuse: every backend fuses, whatever
    the budget."""
    fac, d, q, st = _tiny_qp()
    monkeypatch.setattr(kernels, "_needs_host_factor", lambda f: True)
    with pytest.raises(ValueError, match="host"):
        kernels.prepare(fac, mode="fused", precision="native")
    assert kernels.prepare(fac, mode="auto",
                           precision="native").mode == "segmented"
    monkeypatch.setattr(kernels, "_needs_host_factor", lambda f: False)
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        for mode in ("auto", "fused"):
            assert kernels.prepare(fac, mode=mode,
                                   precision="native").mode == "fused"
        assert kernels.prepare(fac, mode="auto", precision="mixed",
                               tail_iter=1500).mode == "fused"


# ---------------- what prepare() resolves ----------------

def _mini_packed():
    rng = np.random.default_rng(3)
    vals = rng.uniform(0.5, 2.0, size=(2, 3, 4)).astype(np.float32)
    return Packed(g_rows=jnp.zeros((0,), jnp.int32),
                  g_vals=jnp.zeros((0, 4), jnp.float32),
                  l_rows=jnp.zeros((2, 3), jnp.int32),
                  l_cols=jnp.zeros((2, 4), jnp.int32),
                  l_vals=jnp.asarray(vals),
                  row_src=jnp.zeros((6,), jnp.int32),
                  col_src=jnp.zeros((4,), jnp.int32))


def _prepare_factors(kind):
    """(factors, precision) of one factor kind prepare() tells apart."""
    if kind == "split-df32":            # shared packed SplitMatrix
        hi = jnp.asarray(np.ones((6, 4)), jnp.float32)
        sm = SplitMatrix(hi, jnp.zeros_like(hi), struct=object(),
                         pk_hi=_mini_packed(), pk_lo=_mini_packed())
        return types.SimpleNamespace(A_s=sm), "df32"
    if kind == "dense-mixed":           # one shared dense A
        return _tiny_qp()[0], "mixed"
    if kind == "dense-native":
        return _tiny_qp()[0], "native"
    # per-scenario f64 A on a backend whose batched f64 device inverse
    # is not trusted (the chip; the test patches the trust off)
    A = jnp.asarray(np.ones((3, 6, 4)), jnp.float64)
    return types.SimpleNamespace(A_s=A), "native"


# outcome of prepare(mode, ir_sweeps) by factor kind. "F": one fused
# program; "FL": fused with the explicit inverse (tail 100 x 2 rows x 2
# applies >= n = 4: l_inv_profitable); "S": the segmented drivers;
# "E:<word>": a ValueError naming <word>
_PREPARE_TABLE = {
    "split-df32": {("auto", 1): "FL", ("fused", 1): "FL",
                   ("segmented", 1): "S", ("auto", 5): "S",
                   ("fused", 5): "E:ir_sweeps", ("segmented", 5): "S"},
    "dense-mixed": {("auto", 1): "F", ("fused", 1): "F",
                    ("segmented", 1): "S", ("auto", 5): "S",
                    ("fused", 5): "E:ir_sweeps", ("segmented", 5): "S"},
    "dense-native": {("auto", 1): "F", ("fused", 1): "F",
                     ("segmented", 1): "S", ("auto", 5): "S",
                     ("fused", 5): "E:ir_sweeps", ("segmented", 5): "S"},
    "per-scenario-f64-host": {("auto", 1): "S", ("fused", 1): "E:host",
                              ("segmented", 1): "S", ("auto", 5): "S",
                              ("fused", 5): "E:ir_sweeps",
                              ("segmented", 5): "S"},
}


@pytest.mark.parametrize("ir_sweeps", [1, 5])
@pytest.mark.parametrize("kind", list(_PREPARE_TABLE))
@pytest.mark.parametrize("mode", ["auto", "fused", "segmented"])
def test_prepare_resolution(monkeypatch, mode, kind, ir_sweeps):
    """All of what ``prepare`` decides: the mode a solve runs in (or
    the config error), whether the tail carries the explicit inverse,
    and the bulk phase's operand — f32, the factors' own arrays, never
    a substitute. ``descriptor()`` is what ``phase_timing()["kernel"]``
    and the benchmark's driver print."""
    import mpisppy_tpu.ops.qp_solver as qps
    if kind == "per-scenario-f64-host":
        monkeypatch.setattr(qps, "_device_f64_linalg_trusted",
                            lambda: False)
    fac, precision = _prepare_factors(kind)
    want = _PREPARE_TABLE[kind][mode, ir_sweeps]
    kw = dict(mode=mode, precision=precision, ir_sweeps=ir_sweeps,
              tail_iter=100, s_chunk=2)
    if want.startswith("E:"):
        with pytest.raises(ValueError, match=want[2:]):
            kernels.prepare(fac, **kw)
        return
    plan = kernels.prepare(fac, **kw)
    assert plan.descriptor() == {
        "mode": "segmented" if want == "S" else "fused",
        "backend": "reference", "l_inv": want == "FL",
        "block_dtype": "f32",
        # a per-scenario float64 matrix's batched products: the
        # library dot on this backend (tests/test_f64_products.py)
        "f64_products": "dot" if kind == "per-scenario-f64-host"
        else None,
        # a float64 polish over these factors: the library calls on
        # this backend (tests/test_f64_polish.py); a split matrix
        # never polishes
        "f64_polish": None if kind == "split-df32" else "library",
        # the explicit float64 inverse's rebuild: numpy between device
        # calls where the batched linalg is not trusted and the TPU's
        # unrolled form does not apply (tests/test_f64_refactor.py)
        "f64_refactor": {"split-df32": None,
                         "per-scenario-f64-host": "host"}.get(kind,
                                                              "library"),
        # the loop that adapts rho inside the program: the rebuild
        # under a ``lax.cond`` for the shared float64 inverses; none
        # where no program rebuilds a float64 inverse
        "f64_loop": {"split-df32": None,
                     "per-scenario-f64-host": None}.get(kind,
                                                        "conditional"),
        # none of these is a wide per-scenario stack rebuilt on the
        # device (tests/test_f64_rule_table.py)
        "f64_stack_block": None}
    if want == "S":
        assert plan.A_lo is None
    elif kind == "split-df32":
        assert isinstance(plan.A_lo, PackedMatrix)
        assert plan.A_lo.dense is fac.A_s.hi
        assert plan.A_lo.pk is fac.A_s.pk_hi
        assert plan.A_lo.pk.l_vals.dtype == jnp.float32
        # the inverse is a rule the user can override either way
        assert kernels.prepare(fac, **kw, l_inv="off").l_inv is False
        assert kernels.prepare(fac, **{**kw, "tail_iter": 0},
                               l_inv="on").l_inv is True
    elif kind == "dense-mixed":
        assert plan.A_lo is fac.A_s
    else:
        assert plan.A_lo is None


# ---------------- config validation (the small fix) ----------------

def test_kernel_mode_ir_sweeps_validated_together():
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig

    AlgoConfig(subproblem_kernel_mode="fused",
               subproblem_ir_sweeps=4).validate()
    with pytest.raises(ValueError, match="ir_sweeps"):
        AlgoConfig(subproblem_kernel_mode="fused",
                   subproblem_ir_sweeps=7).validate()
    with pytest.raises(ValueError, match="kernel_mode"):
        AlgoConfig(subproblem_kernel_mode="fusedd").validate()
    # the RunConfig surface routes through AlgoConfig.validate
    rc = RunConfig()
    rc.algo.subproblem_kernel_mode = "fused"
    rc.algo.subproblem_ir_sweeps = 9
    with pytest.raises(ValueError, match="ir_sweeps"):
        rc.validate()
    # segmented mode accepts any sweep count (the host drivers do not
    # unroll)
    AlgoConfig(subproblem_kernel_mode="segmented",
               subproblem_ir_sweeps=9).validate()


def test_engine_rejects_bad_kernel_options():
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    with pytest.raises(ValueError, match="subproblem_kernel_mode"):
        PHBase(batch, {"subproblem_kernel_mode": "turbo"},
               dtype=jnp.float64)
    with pytest.raises(ValueError, match="ir_sweeps"):
        PHBase(batch, {"subproblem_kernel_mode": "fused",
                       "subproblem_ir_sweeps": 8}, dtype=jnp.float64)
    # the same sweep count is fine when the kernel layer is off
    PHBase(batch, {"subproblem_kernel_mode": "segmented",
                   "subproblem_ir_sweeps": 8}, dtype=jnp.float64)


# ---------------- analyze --compare verdict row ----------------

def test_analyze_compare_fused_vs_segmented_reports_pass(tmp_path):
    """Acceptance criterion: fused-vs-segmented telemetry from the
    same farmer instance compares PASS, and the compare output carries
    the kernel verdict row identifying the two modes."""
    from mpisppy_tpu.core.ph import PH
    from mpisppy_tpu.obs.analyze import compare, kernel_summary, load_run

    def mk():
        return build_batch(farmer.scenario_creator, farmer.make_tree(3))

    def run(mode, out_dir=None):
        if out_dir is not None:
            obs.configure(out_dir=str(out_dir))
        try:
            ph = PH(mk(), {"PHIterLimit": 2, "defaultPHrho": 1.0,
                           "convthresh": 0.0,
                           "subproblem_kernel_mode": mode},
                    dtype=jnp.float64)
            ph.ph_main(finalize=False)
        finally:
            if out_dir is not None:
                obs.shutdown()

    run("segmented")                      # warm the jit caches so the
    run("fused")                          # recorded runs compare clean
    run("segmented", tmp_path / "seg")
    run("fused", tmp_path / "fus")
    a, b = load_run(str(tmp_path / "seg")), load_run(str(tmp_path / "fus"))
    assert kernel_summary(a)["mode"] == "segmented"
    assert kernel_summary(b)["mode"] == "fused"
    assert kernel_summary(b)["fused_iters"] > 0
    # every time row is a ratio of two CPU wall times of a few
    # milliseconds to tenths of a second, and the suite runs under six
    # xdist workers that share the cores: one side descheduled for a
    # moment passes the default 1.5x / 1 ms rule (the driver's run of
    # PR 27's tree failed here, the builder's own passed). What this
    # test is for does not ride on the clock: the kernel row naming both
    # modes, and the count and convergence rows. So the time rows get a
    # rule this test's own noise cannot reach (a minute's difference AND
    # 50x); count and convergence rows keep theirs.
    text, passed = compare(a, b, threshold=50.0, abs_floor=60.0)
    assert "kernel: A=segmented" in text and "B=fused" in text
    for count_row in ("gate_syncs_per_solve_call", "xla_compiles_total"):
        assert f"  {count_row}: " in text, text
    assert "per-iteration verdict [PASS]" in text
    assert passed, text
