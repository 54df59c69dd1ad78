"""The prepared f32 Cholesky factor (ops/qp_solver.PreparedFactor,
ISSUE 27): the x-update's blocked substitution from stored diagonal-
block inverses against the ``lax.linalg.triangular_solve`` pair it
replaces on the TPU, what the fused program's scan body no longer
holds there, and the in-loop refactorization that prepares the factor
anew (counted as ``QPState.refactors``). ``_chol_solve`` picks the
substitution per platform at lowering time (the TPU's expander re-
derives what the preparation stores; LAPACK's trsm has nothing to
hoist), so the CPU cases call ``_prepared_solve`` itself."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import uc
from mpisppy_tpu.ops.qp_solver import (PreparedFactor, QPData, _chol_solve,
                                       _factorize, _pair_solve,
                                       _prepare_factor, _prepared_solve,
                                       qp_cold_state, qp_setup, qp_solve)

EPS32 = float(np.finfo(np.float32).eps)


def _spd_factor(n, seed):
    rng = np.random.default_rng(seed)
    B = rng.normal(size=(n, n))
    M = B @ B.T + n * np.eye(n)
    return M, jnp.linalg.cholesky(jnp.asarray(M, jnp.float32))


# n below one block, not a multiple of 128, several whole blocks
@pytest.mark.parametrize("n", [48, 300, 512])
@pytest.mark.parametrize("rhs", [1, 64])
def test_prepared_solve_matches_triangular_solve_pair(n, rhs):
    """Same blocked algorithm XLA's expander runs on the TPU, so the
    prepared solve agrees with the lax pair on the bare factor to a few
    ulp of the solution's scale (the CPU's pair is LAPACK's
    substitution: another summation order, nothing more), and both sit
    inside the kappa x eps32 band around the f64 solve."""
    M, L = _spd_factor(n, seed=n + rhs)
    F = _prepare_factor(L)
    assert isinstance(F, PreparedFactor)
    assert (F.tri.dtype, F.tri.shape) == (jnp.float32, (n, n))
    bs = min(128, n)
    assert F.dinv.shape == (-(-n // bs), bs, bs)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(rhs, n)),
                    jnp.float32)
    x_p = np.asarray(_prepared_solve(F, b))
    x_l = np.asarray(_pair_solve(L, b))
    scale = np.abs(x_l).max()
    assert np.abs(x_p - x_l).max() / scale <= 16 * EPS32
    x_exact = np.linalg.solve(M, np.asarray(b, np.float64).T).T
    band = 8 * np.linalg.cond(M) * EPS32
    assert np.abs(x_p - x_exact).max() / scale <= band


def test_chol_solve_picks_the_substitution_per_platform():
    """``_chol_solve`` on a PreparedFactor: one ``platform_dependent``
    switch whose TPU branch is the prepared substitution (dots only, no
    ``triangular_solve``) and whose default is the pair on ``.tri``.
    Here, on the CPU, it is therefore bit-equal to the pair on the bare
    factor, and to ``_chol_solve`` of the bare factor (the arithmetic
    the CPU had before the container existed); an f64 right-hand side
    (the df32 x-update's seed) solves in f32 and comes back f64."""
    _, L = _spd_factor(300, seed=9)
    F = _prepare_factor(L)
    b = jnp.asarray(np.random.default_rng(2).normal(size=(5, 300)),
                    jnp.float32)
    x = _chol_solve(F, b)
    np.testing.assert_array_equal(np.asarray(x),
                                  np.asarray(_pair_solve(L, b)))
    np.testing.assert_array_equal(np.asarray(x),
                                  np.asarray(_chol_solve(L, b)))
    x64 = _chol_solve(F, b.astype(jnp.float64))
    assert x64.dtype == jnp.float64
    np.testing.assert_array_equal(np.asarray(x64, np.float32),
                                  np.asarray(x))
    switches = [e for e, *_ in _walk(jax.make_jaxpr(_chol_solve)(F, b).jaxpr)
                if e.params.get("branches_platforms")]
    assert len(switches) == 1
    for plats, br in zip(switches[0].params["branches_platforms"],
                         switches[0].params["branches"]):
        prims = {e.primitive.name for e, *_ in _walk(br.jaxpr)}
        if plats == ("tpu",):
            assert "dot_general" in prims and "triangular_solve" not in prims
        else:
            assert plats is None and "triangular_solve" in prims


def test_whole_solve_on_the_tpus_substitution_matches_the_pairs(monkeypatch):
    """The TPU's branch driven through a whole f32 ADMM solve on the
    CPU (n = 300: three blocks, the last short; one in-loop
    refactorization on the way): forced in place of the pair, the
    prepared substitution converges in the same number of checks to
    the same point within solver tolerance."""
    import mpisppy_tpu.ops.qp_solver as qs

    fac, d, q, st = _tiny_f32_qp(S=3, m=400, n=300, seed=11)
    bad = jnp.full_like(st.rho_scale, 1e-3)     # rho far off: adapts
    st = st._replace(rho_scale=bad, L=_factorize(fac, bad))
    kw = dict(max_iter=2000, check_every=25, eps_abs=1e-4, eps_rel=1e-4,
              polish=False)
    st_l, x_l, _, _ = qs._solve_impl(fac, d, q, st, **kw)
    monkeypatch.setattr(
        qs, "_pair_solve",
        lambda L, b: _prepared_solve(_prepare_factor(L), b))
    st_p, x_p, _, _ = qs._solve_impl(fac, d, q, st, **kw)
    assert int(st_l.iters) < 2000 and int(st_l.refactors) >= 1
    assert (int(st_p.iters), int(st_p.refactors)) \
        == (int(st_l.iters), int(st_l.refactors))
    scale = np.abs(np.asarray(x_l)).max()
    assert np.abs(np.asarray(x_p) - np.asarray(x_l)).max() / scale < 1e-3
    assert float(st_p.pri_rel.max()) < 2 * float(st_l.pri_rel.max()) + 1e-4


def test_prepared_blocks_invert_the_factors_diagonal_blocks():
    """dinv[k] · L[k-th diagonal block] = I; the short last block is
    padded with an identity that the solve never reads."""
    n = 300
    _, L = _spd_factor(n, seed=3)
    F = _prepare_factor(L)
    Ln = np.asarray(L, np.float64)
    for k, j0 in enumerate(range(0, n, 128)):
        j1 = min(j0 + 128, n)
        got = np.asarray(F.dinv[k], np.float64)[:j1 - j0, :j1 - j0] \
            @ Ln[j0:j1, j0:j1]
        np.testing.assert_allclose(got, np.eye(j1 - j0), atol=1e-5)
    r = n % 128
    np.testing.assert_array_equal(np.asarray(F.dinv[-1])[r:, r:],
                                  np.eye(128 - r, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(F.tri), np.asarray(L))


# ---------------- what the fused program's hot loop holds ----------------

def _uc_batch(S=4, G=8, T=12):
    return build_batch(
        uc.scenario_creator, uc.make_tree(S),
        creator_kwargs=dict(num_gens=G, num_hours=T, min_up_down=True,
                            ramping=True, t0_state=True,
                            startup_shutdown_ramps=True,
                            relax_integrality=False),
        vector_patch=uc.scenario_vector_patch)


_DF32 = {"defaultPHrho": 100.0, "subproblem_precision": "df32",
         "subproblem_max_iter": 50, "subproblem_eps": 1e-5,
         "subproblem_tail_iter": 25, "subproblem_hospital": False,
         "subproblem_chunk": 2, "iter0_feas_tol": 1.0}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for u in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr           # ClosedJaxpr
            elif hasattr(u, "eqns"):
                yield u                 # Jaxpr


def _walk(jaxpr, in_scan=False, platform=None, scope=""):
    """(eqn, inside_a_scan_body, named scopes down to it) for every
    equation, recursively (a sub-jaxpr's name stacks are relative to
    the equation that holds it); with ``platform``, only that
    platform's branch of a ``lax.platform_dependent`` switch (what its
    lowering keeps)."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, in_scan, here
        subs = list(_sub_jaxprs(eqn))
        plats = eqn.params.get("branches_platforms")
        if platform and plats:
            pick = [i for i, p in enumerate(plats) if p and platform in p] \
                or [i for i, p in enumerate(plats) if p is None]
            subs = [eqn.params["branches"][i].jaxpr for i in pick]
        for sub in subs:
            yield from _walk(sub, in_scan or eqn.primitive.name == "scan",
                             platform, here)


def _fused_jaxpr(monkeypatch):
    """(jaxpr, aux L) of ``_fused_mixed_impl`` as a packed df32 UC
    engine calls it (a factor that spans several 128-blocks)."""
    import mpisppy_tpu.ops.kernels.reference as ref

    calls = {}
    fn = ref._fused_mixed_jit_donated

    def record(*a, **kw):
        calls.setdefault("args", (a, kw))
        return fn(*a, **kw)
    monkeypatch.setattr(ref, "_fused_mixed_jit_donated", record)
    ph = PHBase(_uc_batch(), dict(_DF32), dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    args, kw = calls["args"]
    assert args[0].A_s.pk_hi is not None         # factors: packed split A
    statics = {k: kw.pop(k) for k in ref._FUSED_STATICS if k in kw}
    jaxpr = jax.make_jaxpr(partial(ref._fused_mixed_impl, **statics))(
        *args, **kw).jaxpr
    return jaxpr, args[5][0]             # aux = (L, rho_scale, iters)


def test_fused_scan_body_holds_no_per_iteration_factor_pass(monkeypatch):
    """What the fused df32 program (a UC whose factor spans several
    128-blocks) lowers to ON THE TPU: its ADMM scan body does no per-
    iteration work on L as a whole: no ``triangular_solve`` (so none
    with ``transpose_a``: the expander's triangle mask and diagonal-
    block inversion went with it), no (n, n)-shaped transpose, no
    equation with an (n, n) result at all. The preparation lives
    outside the scan, under the in-loop refactorization's ``cond``. On
    the default (CPU) branch the scan body keeps the pair, as it always
    had."""
    jaxpr, L = _fused_jaxpr(monkeypatch)
    assert isinstance(L, PreparedFactor)
    n = L.tri.shape[-1]
    assert n > 2 * 128 and L.dinv.shape[0] == -(-n // 128) >= 3
    assert any(in_scan and eqn.primitive.name == "triangular_solve"
               for eqn, in_scan, _ in _walk(jaxpr, platform="cpu"))
    seen_scan = prepared_outside = False
    for eqn, in_scan, _ in _walk(jaxpr, platform="tpu"):
        name = eqn.primitive.name
        if name == "triangular_solve" and not in_scan:
            prepared_outside = True
        if not in_scan:
            continue
        seen_scan = True
        assert name != "triangular_solve", eqn
        for v in eqn.outvars:
            assert getattr(v.aval, "shape", ()) != (n, n), eqn
    assert seen_scan and prepared_outside


def test_fused_program_places_its_matvecs_without_a_scatter(monkeypatch):
    """ISSUE 29: the packed matvecs place their block results with a
    gather through the structure's inverse index, so on every platform
    the fused program accumulates nothing by index: no ``scatter-add``
    inside either ADMM scan body (f32 bulk and df32 tail) or under
    ``qp.check``, and no scatter of any kind under ``qp.Ax`` /
    ``qp.ATy`` anywhere (the scan bodies' remaining ``scatter``s are the
    prepared solve's static block writes under ``qp.kkt_solve``). The
    matvecs are there, in both dtypes: each ends in a gather of its
    (S, m) or (S, n) result."""
    jaxpr, _ = _fused_jaxpr(monkeypatch)
    placed = set()
    for eqn, in_scan, scope in _walk(jaxpr):
        name = eqn.primitive.name
        matvec = "qp.Ax" in scope or "qp.ATy" in scope
        if in_scan or "qp.check" in scope:
            assert name != "scatter-add", (scope, eqn)
        if matvec:
            assert not name.startswith("scatter"), (scope, eqn)
        if matvec and in_scan and name == "gather":
            placed.add((scope.rstrip("/").rsplit("/", 1)[-1],
                        str(eqn.outvars[0].aval.dtype)))
    assert placed == {("qp.Ax", "float32"), ("qp.ATy", "float32"),
                      ("qp.Ax", "float64"), ("qp.ATy", "float64")}


# ---------------- the in-loop refactorization ----------------

def _tiny_f32_qp(S=3, m=6, n=4, seed=5):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    A = jnp.asarray(rng.normal(size=(m, n)), f32)
    mid = rng.normal(size=(S, m))
    d = QPData(P_diag=jnp.asarray(np.abs(rng.normal(size=n)) + 0.5, f32),
               A=A, l=jnp.asarray(mid - 3.0, f32),
               u=jnp.asarray(mid + 3.0, f32),
               lb=jnp.full((S, n), -5.0, f32),
               ub=jnp.full((S, n), 5.0, f32))
    q = jnp.asarray(rng.normal(size=(S, n)), f32)
    fac = qp_setup(d, q_ref=q)
    return fac, d, q, qp_cold_state(fac, d)


def test_in_loop_refactorization_prepares_the_factor_anew():
    """A solve forced through ONE in-loop rho refactorization (a rho
    far off, one adaptation point inside the budget) keeps the carry's
    pytree structure, hands back a factor whose prepared parts are
    those of a fresh factorization at the adapted rho, and counts
    ``refactors`` = 1; the next, settled solve counts 0."""
    fac, d, q, st = _tiny_f32_qp()
    assert isinstance(st.L, PreparedFactor) and int(st.refactors) == 0
    bad = jnp.full_like(st.rho_scale, 1e-4)
    st = st._replace(rho_scale=bad, L=_factorize(fac, bad))
    # adaptation fires on every 4th residual check: 4 checks = 1 chance
    kw = dict(max_iter=100, check_every=25, eps_abs=0.0, eps_rel=0.0,
              polish=False)
    st1, _, _, _ = qp_solve(fac, d, q, st, **kw)
    assert int(st1.iters) == 100 and int(st1.refactors) == 1
    assert float(st1.rho_scale) != float(bad)
    assert jax.tree.structure(st1) == jax.tree.structure(st)
    fresh = _factorize(fac, st1.rho_scale)
    for got, want in zip(jax.tree.leaves(st1.L), jax.tree.leaves(fresh)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
    again = _prepare_factor(st1.L.tri)
    np.testing.assert_array_equal(np.asarray(again.dinv),
                                  np.asarray(st1.L.dinv))
    st2, _, _, _ = qp_solve(fac, d, q, st1, adaptive_rho=False, **kw)
    assert int(st2.refactors) == 0


def test_non_split_linv_carry_prepares_outside_the_scan():
    """An ``LInv`` carry on a non-split A runs UN-refined solves, which
    must not use the explicit inverse: they get ``LInv.tri`` prepared
    once per ``check_every`` iterations, outside the ADMM scan (no
    ``triangular_solve`` in the scan body of the TPU's lowering). Here,
    on the pair, the solve is bit-equal to the PreparedFactor carry's
    and keeps its container through a refactorization."""
    import mpisppy_tpu.ops.qp_solver as qs

    fac, d, q, st = _tiny_f32_qp()
    bad = jnp.full_like(st.rho_scale, 1e-4)
    st = st._replace(rho_scale=bad, L=_factorize(fac, bad))
    st_i = st._replace(L=qs.make_l_inv(st.L))
    kw = dict(max_iter=100, check_every=25, eps_abs=0.0, eps_rel=0.0,
              polish=False)
    st0, x0, _, _ = qp_solve(fac, d, q, st, **kw)
    st1, x1, _, _ = qp_solve(fac, d, q, st_i, **kw)
    assert isinstance(st1.L, qs.LInv) and int(st1.refactors) == 1
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x0))
    np.testing.assert_array_equal(np.asarray(st1.L.tri),
                                  np.asarray(st0.L.tri))
    jaxpr = jax.make_jaxpr(partial(qs._solve_impl, **kw))(
        fac, d, q, st_i).jaxpr
    in_scan = [eqn.primitive.name
               for eqn, inside, _ in _walk(jaxpr, platform="tpu") if inside]
    assert "dot_general" in in_scan and "triangular_solve" not in in_scan


@pytest.mark.parametrize("mode", ["fused", "segmented"])
def test_refactors_counted_beside_the_iterations(mode):
    """``phase_timing()["admm_iters_per_call"]["refactors"]`` is the
    sum of the pass-1 chunk solves' ``QPState.refactors`` (both phases
    of a fused solve, every segment of a segmented one), with no
    telemetry session, reset with the seconds."""
    assert not obs.enabled()
    opts = {**_DF32, "subproblem_max_iter": 200,
            "subproblem_tail_iter": 100, "subproblem_segment": 100,
            "subproblem_kernel_mode": mode}
    ph = PHBase(_uc_batch(G=3, T=6), opts, dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    sts = ph._qp_states[("chunks", False)]
    admm = ph.phase_timing(False)["admm_iters_per_call"]
    assert set(admm) == {"bulk", "tail", "refactors", "linv_builds",
                         "linv_applies"}
    assert admm["refactors"] == sum(int(s.refactors) for s in sts)
    # a cold UC solve adapts rho at least once, far less often than it
    # iterates: the hoisted preparation is paid per refactorization
    assert 1 <= admm["refactors"] <= (admm["bulk"] + admm["tail"]) / 100
    ph.reset_phase_timing()
    assert ph.phase_timing(False) is None
