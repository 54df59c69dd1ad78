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
                                    pk_ATy_split, pk_Ax, pk_Ax_split,
                                    structure_from_lists)
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
    np.testing.assert_allclose(np.asarray(pk_Ax(pk, x)),
                               np.asarray(x) @ A.T, rtol=1e-12, atol=1e-9)
    np.testing.assert_allclose(np.asarray(pk_ATy(pk, y)),
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
    got = np.asarray(pk_Ax_split(pk_hi, pk_lo, xh, xl))
    np.testing.assert_allclose(got, x64 @ A.T,
                               rtol=2e-6, atol=2e-6 * np.abs(A).max())
    y64 = rng.randn(2, m)
    yh = jnp.asarray(y64, jnp.float32)
    yl = jnp.asarray(y64 - np.asarray(yh, np.float64), jnp.float32)
    gotT = np.asarray(pk_ATy_split(pk_hi, pk_lo, yh, yl))
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


# ---------------- placement by the inverse index (ISSUE 29) ----------------
#
# The matvecs place their block results with a gather through
# ``row_src`` / ``col_src``. The reference below is the form they
# replaced: the same products scattered into a zero vector with
# ``.at[].add``. Placing is not arithmetic, so the two must agree to
# the BIT, on every structure the analyser can return.

def _ref_Ax(pk, x, m):
    S = x.shape[0]
    loc = jnp.einsum("scn,cmn->scm", x[:, pk.l_cols], pk.l_vals)
    out = jnp.zeros((S, m), x.dtype)
    out = out.at[:, pk.l_rows.reshape(-1)].add(loc.reshape(S, -1))
    if pk.g_rows.size:
        out = out.at[:, pk.g_rows].add(x @ pk.g_vals.T)
    return out


def _ref_ATy(pk, y, n):
    S = y.shape[0]
    loc = jnp.einsum("scm,cmn->scn", y[:, pk.l_rows], pk.l_vals)
    out = jnp.zeros((S, n), y.dtype)
    out = out.at[:, pk.l_cols.reshape(-1)].add(loc.reshape(S, -1))
    if pk.g_rows.size:
        out = out + y[:, pk.g_rows] @ pk.g_vals
    return out


def _ref_Ax_split(pk_hi, pk_lo, xh, xl, m):
    S = xh.shape[0]
    f64 = jnp.float64
    xgh = xh[:, pk_hi.l_cols]
    xgl = xl[:, pk_hi.l_cols]
    loc = (jnp.einsum("scn,cmn->scm", xgh, pk_hi.l_vals).astype(f64)
           + jnp.einsum("scn,cmn->scm", xgh, pk_lo.l_vals).astype(f64)
           + jnp.einsum("scn,cmn->scm", xgl, pk_hi.l_vals).astype(f64))
    out = jnp.zeros((S, m), f64)
    out = out.at[:, pk_hi.l_rows.reshape(-1)].add(loc.reshape(S, -1))
    if pk_hi.g_rows.size:
        g = ((xh @ pk_hi.g_vals.T).astype(f64)
             + (xh @ pk_lo.g_vals.T).astype(f64)
             + (xl @ pk_hi.g_vals.T).astype(f64))
        out = out.at[:, pk_hi.g_rows].add(g)
    return out


def _ref_ATy_split(pk_hi, pk_lo, yh, yl, n):
    S = yh.shape[0]
    f64 = jnp.float64
    ygh = yh[:, pk_hi.l_rows]
    ygl = yl[:, pk_hi.l_rows]
    loc = (jnp.einsum("scm,cmn->scn", ygh, pk_hi.l_vals).astype(f64)
           + jnp.einsum("scm,cmn->scn", ygh, pk_lo.l_vals).astype(f64)
           + jnp.einsum("scm,cmn->scn", ygl, pk_hi.l_vals).astype(f64))
    out = jnp.zeros((S, n), f64)
    out = out.at[:, pk_hi.l_cols.reshape(-1)].add(loc.reshape(S, -1))
    if pk_hi.g_rows.size:
        g = ((yh[:, pk_hi.g_rows] @ pk_hi.g_vals).astype(f64)
             + (yh[:, pk_hi.g_rows] @ pk_lo.g_vals).astype(f64)
             + (yl[:, pk_hi.g_rows] @ pk_hi.g_vals).astype(f64))
        out = out + g
    return out


def _blocks_A(sizes, n_global, seed, empty_rows=(), unused_cols=()):
    """Block-diagonal matrix (one dense block per (rows, cols) entry of
    ``sizes``) plus ``n_global`` full coupling rows; the named rows and
    columns are then emptied."""
    rng = np.random.RandomState(seed)
    m = sum(r for r, _ in sizes) + n_global
    n = sum(c for _, c in sizes)
    A = np.zeros((m, n))
    r0 = c0 = 0
    for r, c in sizes:
        A[r0:r0 + r, c0:c0 + c] = rng.randn(r, c)
        r0, c0 = r0 + r, c0 + c
    A[r0:] = rng.randn(n_global, n)
    A[list(empty_rows)] = 0.0
    A[:, list(unused_cols)] = 0.0
    return A


_STRUCTURES = {
    # the UC toy: six generator components and the coupling rows
    "uc_toy": lambda: _uc_A(G=6, T=12),
    # components of unlike sizes: -1 padded slots in l_rows AND l_cols
    "ragged": lambda: _blocks_A([(7, 3), (4, 5), (9, 2), (5, 5), (6, 4),
                                 (3, 6)] * 2, 2, seed=11),
    # rows and a column that no block owns: the zero slot
    "holes": lambda: _blocks_A([(6, 4)] * 6, 2, seed=12,
                               empty_rows=(0, 13, 35), unused_cols=(5,)),
    # nothing couples the blocks: R = 0
    "no_global": lambda: _blocks_A([(6, 4), (5, 4), (6, 3), (6, 4),
                                    (4, 4), (6, 4)], 0, seed=13),
}


@pytest.fixture(scope="module", params=list(_STRUCTURES))
def structured(request):
    A = _STRUCTURES[request.param]()
    m, n = A.shape
    st = analyze_structure(*np.nonzero(A), m, n)
    assert st is not None
    lr, lc = np.asarray(st.l_rows), np.asarray(st.l_cols)
    if request.param == "ragged":
        assert (lr < 0).any() and (lc < 0).any()
    if request.param == "holes":
        assert (np.asarray(st.row_src) == lr.size + st.g_rows.size).sum() \
            == 3 and (np.asarray(st.col_src) == lc.size).sum() == 1
    if request.param == "no_global":
        assert st.g_rows.size == 0
    else:
        assert st.g_rows.size > 0
    return A, st


def _vectors(kind, shape, seed):
    v = np.random.RandomState(seed).randn(*shape)
    if kind == "signed_zeros":
        # exact zeros of both signs among the inputs, and whole rows of
        # each: block results that are exactly +-0
        flat = v.reshape(-1)
        flat[::3] = 0.0
        flat[1::7] = -0.0
        v[0] = -0.0
        v[1] = 0.0
    return v


def _bits(a):
    a = np.asarray(a)
    return a.view({4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


@pytest.mark.parametrize("inputs", ["randn", "signed_zeros"])
@pytest.mark.parametrize("matvec", ["Ax", "ATy", "Ax_split", "ATy_split"])
def test_placement_is_bit_equal_to_the_scatter_add(structured, matvec,
                                                   inputs):
    """Every output element of the gather form carries the bits the
    accumulating form gave it. Op by op (no enclosing jit), so both
    forms run the SAME compiled products and the comparison is of the
    placement alone: inside one jit XLA:CPU picks a small dot's
    summation order by what consumes it. Signed zeros: the accumulating
    form computes ``0 + v`` and could never return -0.0; the gather
    form returns what the slot holds, and a slot holds a sum that began
    at +0, so neither does it: no zero has to be added back."""
    A, st = structured
    m, n = A.shape
    S = 3
    split = matvec.endswith("_split")
    width, out = (n, m) if matvec.startswith("Ax") else (m, n)
    v64 = _vectors(inputs, (S, width), seed=21)
    vh = jnp.asarray(v64, jnp.float32)
    if split:
        sp = split_f32(jnp.asarray(A))
        pks = (pack(st, sp.hi), pack(st, sp.lo))
        vl = np.asarray(v64 - np.asarray(vh, np.float64), np.float32)
        if inputs == "signed_zeros":
            vl[2] = -0.0
        args = (*pks, vh, jnp.asarray(vl))
        new, ref = {"Ax_split": (pk_Ax_split, _ref_Ax_split),
                    "ATy_split": (pk_ATy_split, _ref_ATy_split)}[matvec]
    else:
        args = (pack(st, jnp.asarray(A, jnp.float32)), vh)
        new, ref = {"Ax": (pk_Ax, _ref_Ax),
                    "ATy": (pk_ATy, _ref_ATy)}[matvec]
    got = new(*args)
    want = ref(*args, out)
    assert got.dtype == want.dtype == (jnp.float64 if split
                                       else jnp.float32)
    assert got.shape == want.shape == (S, out)
    assert np.array_equal(_bits(got), _bits(want))
    # and they are the matvec: against numpy on the dense matrix
    dense = v64 @ (A.T if matvec.startswith("Ax") else A)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-4,
                               atol=1e-4 * np.abs(A).max() * width ** 0.5)


def test_inverse_index_names_each_owner_once(structured):
    """``row_src`` / ``col_src`` against the skeleton they invert:
    every real block slot and every global row is the source of exactly
    its own output element, no padded slot is a source, and an element
    nobody owns reads the zero slot behind the last real one."""
    A, st = structured
    m, n = A.shape
    for owners, src, size in (
            (np.concatenate([np.asarray(st.l_rows).reshape(-1),
                             np.asarray(st.g_rows)]),
             np.asarray(st.row_src), m),
            (np.asarray(st.l_cols).reshape(-1), np.asarray(st.col_src), n)):
        assert src.shape == (size,) and src.dtype == np.int32
        zero_slot = owners.size
        assert src.min() >= 0 and src.max() <= zero_slot
        real = np.flatnonzero(owners >= 0)
        # slot -> element -> slot is the identity on real slots ...
        assert np.array_equal(src[owners[real]], real)
        # ... which are all the sources there are (so none is padded)
        owned = src != zero_slot
        assert np.array_equal(np.sort(src[owned]), real)
        assert (owners[src[owned]] == np.flatnonzero(owned)).all()
        # unowned: the matrix's empty rows; the columns no LOCAL row
        # touches (empty, or met by global rows alone: the dense term)
        if size == m:
            empty = ~(A != 0).any(axis=1)
        else:
            local = np.setdiff1d(np.arange(m), np.asarray(st.g_rows))
            empty = ~(A[local] != 0).any(axis=0)
        assert np.array_equal(~owned, empty)


@pytest.mark.parametrize("rows,cols,g_rows,what", [
    ([[0, 1], [1, 2]], [[0], [1]], [], "rows"),       # two blocks, one row
    ([[0, 1], [2]], [[0, 1], [1]], [3], "columns"),   # two blocks, one col
    ([[0, 1], [2]], [[0], [1]], [2], "rows"),         # global AND local
    ([[0, 1], [2]], [[0], [1]], [3, 3], "rows"),      # a global row twice
    ([[0, 4]], [[0]], [], "rows"),                    # beyond m
])
def test_overlapping_skeleton_is_refused(rows, cols, g_rows, what):
    """The placement is exact only because each output element has one
    owner; a skeleton that breaks that must fail where it is built, not
    drop a contribution silently."""
    with pytest.raises(ValueError, match=what):
        structure_from_lists(rows, cols, g_rows, 4, 2)
    # the same lists made disjoint build
    st = structure_from_lists([[0, 1], [2]], [[0], [1]], [3], 4, 2)
    assert np.asarray(st.row_src).tolist() == [0, 1, 2, 4]
    assert np.asarray(st.col_src).tolist() == [0, 1]


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
