"""Structure-packed matvec (ops/packed.py): exactness against the dense
paths and end-to-end df32 solves through the packed representation.

The packed form is the r5 hot-loop representation (the round-4 kernel
measured 3.8% MFU with dense A-passes streaming ~99.6% zeros at reference-UC
scale); these tests pin (a) the discovery/pack/apply pipeline against
dense ground truth on a real UC matrix, and (b) that a df32 engine
solving through it reproduces the unpacked engine's results."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu.ir.standard_form import lower
from mpisppy_tpu.models import uc
from mpisppy_tpu.ops.packed import (analyze_structure, pack, pk_ATy,
                                    pk_ATy_split, pk_Ax, pk_Ax_split)
from mpisppy_tpu.ops.qp_solver import split_f32


def _uc_A(G=6, T=12):
    sf = lower(uc.scenario_creator(
        "scen0", num_gens=G, num_hours=T, relax_integrality=True,
        min_up_down=True, ramping=True))
    return np.asarray(sf.A, np.float64)


def test_analyze_uc_structure():
    A = _uc_A()
    rows, cols = np.nonzero(A)
    m, n = A.shape
    st = analyze_structure(rows, cols, m, n)
    assert st is not None
    # local components = one per generator; the global set holds the
    # coupling rows (balance/reserve, plus — at this toy scale — the
    # wide min-up/down windows that cross the chosen nnz threshold)
    assert st.l_rows.shape[0] == 6
    assert st.g_rows.shape[0] < 0.2 * m
    # packed operands must beat the analyzer's own profitability bar
    packed = st.l_rows.shape[0] * st.l_rows.shape[1] * st.l_cols.shape[1] \
        + st.g_rows.shape[0] * n
    assert packed < 0.35 * m * n


def test_packed_apply_matches_dense():
    A = _uc_A()
    rows, cols = np.nonzero(A)
    m, n = A.shape
    st = analyze_structure(rows, cols, m, n)
    pk = pack(st, jnp.asarray(A))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, n))
    y = jnp.asarray(rng.randn(3, m))
    np.testing.assert_allclose(np.asarray(pk_Ax(pk, x, m)),
                               np.asarray(x) @ A.T, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(np.asarray(pk_ATy(pk, y, n)),
                               np.asarray(y) @ A, rtol=1e-12, atol=1e-9)


def test_packed_split_apply_matches_dense_split():
    A = _uc_A()
    rows, cols = np.nonzero(A)
    m, n = A.shape
    st = analyze_structure(rows, cols, m, n)
    sp = split_f32(jnp.asarray(A))
    pk_hi = pack(st, sp.hi)
    pk_lo = pack(st, sp.lo)
    rng = np.random.RandomState(1)
    x64 = rng.randn(2, n)
    xh = jnp.asarray(x64, jnp.float32)
    xl = jnp.asarray(x64 - np.asarray(xh, np.float64), jnp.float32)
    got = np.asarray(pk_Ax_split(pk_hi, pk_lo, xh, xl, m))
    np.testing.assert_allclose(got, x64 @ A.T,
                               rtol=2e-6, atol=2e-6 * np.abs(A).max())
    y64 = rng.randn(2, m)
    yh = jnp.asarray(y64, jnp.float32)
    yl = jnp.asarray(y64 - np.asarray(yh, np.float64), jnp.float32)
    gotT = np.asarray(pk_ATy_split(pk_hi, pk_lo, yh, yl, n))
    np.testing.assert_allclose(gotT, y64 @ A,
                               rtol=2e-6, atol=2e-6 * np.abs(A).max())


def test_unstructured_matrix_falls_back():
    # a dense-ish random pattern has one giant component — no packing
    rng = np.random.RandomState(2)
    m, n = 400, 300
    A = (rng.rand(m, n) < 0.2).astype(float)
    rows, cols = np.nonzero(A)
    assert analyze_structure(rows, cols, m, n) is None


def test_df32_engine_solves_through_packed():
    """A df32 PH engine over the UC batch must route A through the
    packed form and land each scenario LP on the scipy ground-truth
    optimum — correctness of the representation end-to-end, not
    trajectory equality (loosely-converged ADMM trajectories diverge
    from f32 summation-order noise; the deterministic equivalence
    check is test_packed_kernel_trajectory_matches_dense)."""
    from scipy.optimize import linprog

    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ops.qp_solver import ScaledView, SplitMatrix

    opts = {"subproblem_precision": "df32", "defaultPHrho": 50.0,
            "subproblem_max_iter": 4000, "subproblem_eps": 1e-7,
            "subproblem_segment": 1000}
    # >= 6 gens so the reserve row (nnz = G) clears the analyzer's
    # lowest nnz threshold and the per-generator structure is found
    kwargs = dict(num_gens=6, num_hours=8, relax_integrality=True,
                  min_up_down=True, ramping=True)
    batch = build_batch(uc.scenario_creator, uc.make_tree(3),
                        creator_kwargs=kwargs,
                        vector_patch=uc.scenario_vector_patch)
    ph = PHBase(batch, opts, dtype=jnp.float64)
    A_raw = ph.qp_data.A
    assert isinstance(A_raw, SplitMatrix) and A_raw.struct is not None
    obj = np.asarray(ph.solve_loop(w_on=False, prox_on=False))
    # packed engine actually used the packed path
    fac, _ = ph._factors[False]
    assert isinstance(fac.A_s, SplitMatrix) and fac.A_s.pk_hi is not None
    assert isinstance(ph.qp_data.A, ScaledView)
    # scipy ground truth per scenario
    A = np.asarray(batch.A if batch.A.ndim == 2 else batch.A[0])
    for s in range(3):
        u_s = np.asarray(batch.u)[s]
        l_s = np.asarray(batch.l)[s]
        fin_u, fin_l = np.isfinite(u_s), np.isfinite(l_s)
        lp = linprog(np.asarray(batch.c)[s],
                     A_ub=np.vstack([A[fin_u], -A[fin_l]]),
                     b_ub=np.concatenate([u_s[fin_u], -l_s[fin_l]]),
                     bounds=list(zip(np.asarray(batch.lb)[s],
                                     np.asarray(batch.ub)[s])),
                     method="highs")
        assert lp.status == 0
        truth = lp.fun + float(np.asarray(batch.c0)[s])
        # df32 lands at its ~1e-3 relative-residual floor on this
        # degenerate LP (measured identical in the dense/2-sweep r4
        # config — packing and the 1-sweep IR change neither the floor
        # nor the objective slack; certified values come from the host
        # oracle paths, see doc/tpu_numerics.md)
        np.testing.assert_allclose(obj[s], truth, rtol=2.5e-2)
    st = ph._qp_states[False]
    assert float(np.asarray(st.pri_rel).max()) < 2e-3


def test_packed_kernel_trajectory_matches_dense():
    """Same cold state, adaptation off: the packed and dense kernels
    run the IDENTICAL deterministic ADMM recursion, so iterates may
    differ only by f32 summation order (~1e-6 relative per pass)."""
    from mpisppy_tpu.ops.qp_solver import (QPData, qp_cold_state,
                                           qp_setup, qp_solve, split_f32)

    A = _uc_A()
    rows, cols = np.nonzero(A)
    m, n = A.shape
    st = analyze_structure(rows, cols, m, n)
    rng = np.random.RandomState(3)
    S = 2
    q = jnp.asarray(rng.rand(S, n) * 10.0)
    l = jnp.asarray(np.tile(np.where(rng.rand(m) < 0.5, 0.0, -1e3), (S, 1)))
    u = jnp.asarray(np.tile(np.full(m, 1e3), (S, 1)))
    lb = jnp.zeros((S, n))
    ub = jnp.full((S, n), 1e2)
    P = jnp.full(n, 1e-3)
    outs = {}
    for tag, struct in (("packed", st), ("dense", None)):
        sp = split_f32(jnp.asarray(A))
        data = QPData(P, sp._replace(struct=struct), l, u, lb, ub)
        fac = qp_setup(data, q_ref=q)
        assert (fac.A_s.pk_hi is not None) == (struct is not None)
        state = qp_cold_state(fac, data)
        state, x, yA, yB = qp_solve(fac, data, q, state, max_iter=200,
                                    adaptive_rho=False, polish=False)
        outs[tag] = np.asarray(x)
    np.testing.assert_allclose(outs["packed"], outs["dense"],
                               rtol=2e-4, atol=2e-4)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
