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


# ------------- pack only where packing pays (ISSUE 33) -------------
#
# ``analyze_structure`` says whether a structure EXISTS (a ratio of
# elements); ``pack_profitable`` says whether the call that runs the
# matvecs should USE it, from (m, n, packed elements, rows per device
# call) alone. The shapes below are the benchmark's operands.

# uc90x48_df32: 90 generator blocks of 286 x 144 and 96 global rows
_UC = dict(m=26016, n=13056, elems=90 * 286 * 144 + 96 * 13056)
# sslp_10_50_df32: 51 one-row blocks of 10 columns and 10 global rows
_SSLP = dict(m=61, n=520, elems=51 * 1 * 10 + 10 * 520)


# rows of the engine parity test: un-chunked they are ONE dense call,
# over four devices four packed ones (the rule's answers, stated)
_ENGINE_ROWS = 12


def _sslp_10_50_A():
    from mpisppy_tpu.models import sslp
    sf = lower(sslp.scenario_creator(
        "Scenario0", num_servers=10, num_clients=50, overflow=True,
        server_budget=10, capacity=188.0, demand_is_revenue=True))
    return np.asarray(sf.A, np.float64)


def test_packed_elems_reads_the_analysers_count_back():
    """The rule's third input is the quantity the analyser held against
    its ratio, read from the skeleton: on the published sslp_10_50
    matrix the ten server rows go global and the 51 others are one-row
    blocks of ten columns."""
    from mpisppy_tpu.ops.packed import packed_elems
    A = _sslp_10_50_A()
    assert A.shape == (_SSLP["m"], _SSLP["n"])
    st = analyze_structure(*np.nonzero(A), *A.shape)
    assert st is not None, "the ratio test alone packs this matrix"
    assert (st.g_rows.shape, st.l_rows.shape, st.l_cols.shape) == \
        ((10,), (51, 1), (51, 10))
    assert packed_elems(st) == _SSLP["elems"]
    A_uc = _uc_A()
    st_uc = analyze_structure(*np.nonzero(A_uc), *A_uc.shape)
    C, mr = st_uc.l_rows.shape
    assert packed_elems(st_uc) == C * mr * st_uc.l_cols.shape[1] \
        + st_uc.g_rows.shape[0] * A_uc.shape[1]


@pytest.mark.parametrize("shape,rows,packed", [
    # cell 1 and, per device, the mesh cell: 2.7 GB of zeros a pass
    (_UC, 64, True),
    # a chunk the chip cannot hold today; the rule must not flip there
    (_UC, 1024, True),
    # the sslp cell: 0.2 MB saved against three sweeps of (2000, 520)
    (_SSLP, 2000, False),
    (_SSLP, 64, False),
    # the same matrix at a handful of rows (tier-1's sizes): packed up
    # to three, dense from four (where the chip read dense 2.2x faster)
    (_SSLP, 3, True),
    (_SSLP, 4, False),
], ids=["uc_64", "uc_1024", "sslp_2000", "sslp_64", "sslp_3", "sslp_4"])
def test_rule_at_the_benchmarks_shapes(shape, rows, packed):
    from mpisppy_tpu.ops.packed import pack_profitable
    assert pack_profitable(shape["m"], shape["n"], shape["elems"],
                           rows) is packed


@pytest.mark.parametrize("n,ratio", [(520, 0.18), (520, 0.02),
                                     (2944, 0.1), (13056, 0.015)])
def test_rule_is_monotone_in_rows_and_in_m(n, ratio):
    """More rows a call never turn a dense verdict into a packed one
    (the gathers grow with the rows, the matrix does not), and a taller
    matrix of the same width and packed share never turns a packed
    verdict into a dense one (more zeros to skip, the same vectors)."""
    from mpisppy_tpu.ops.packed import pack_profitable
    ms = [8, 61, 300, 1500, 6000, 26016, 100000]
    rows = [1, 2, 4, 8, 16, 64, 256, 1024, 2000, 8192, 65536]
    table = np.array([[pack_profitable(m, n, int(ratio * m * n), r)
                       for r in rows] for m in ms])
    assert (np.diff(table.astype(int), axis=1) <= 0).all(), table
    assert (np.diff(table.astype(int), axis=0) >= 0).all(), table
    assert table.any() and not table.all()


def test_setup_packs_by_the_rows_of_the_call():
    """``qp_setup`` decides ONCE, for every consumer of the factors:
    the same structured sslp matrix comes back packed for a two-row
    call and dense (the skeleton stays, for ``_ATy``'s row classes; no
    packed values) for a 2000-row one, whether the rows are the data's
    own or the engine's ``rows_per_call``."""
    from mpisppy_tpu.ops.qp_solver import QPData, qp_setup
    A = _sslp_10_50_A()
    m, n = A.shape
    st = analyze_structure(*np.nonzero(A), m, n)
    sp = split_f32(jnp.asarray(A))._replace(struct=st)

    def data(S):
        return QPData(jnp.full(n, 1e-3), sp, jnp.zeros((S, m)),
                      jnp.ones((S, m)), jnp.zeros((S, n)),
                      jnp.ones((S, n)))
    few, many = qp_setup(data(2)), qp_setup(data(2000))
    assert few.A_s.pk_hi is not None and few.A_s.struct is st
    assert many.A_s.pk_hi is None and many.A_s.pk_lo is None \
        and many.A_s.struct is st
    np.testing.assert_array_equal(np.asarray(few.A_s.hi),
                                  np.asarray(many.A_s.hi))
    # a chunked or streamed engine's two-row surrogate of a 2000-row call
    assert qp_setup(data(2), rows_per_call=2000).A_s.pk_hi is None
    assert qp_setup(data(2000), rows_per_call=2).A_s.pk_hi is not None


def test_dense_transpose_pass_keeps_the_packed_forms_row_classes():
    """A structured matrix left dense sums Aᵀy's leading pass by the
    skeleton's row classes (local rows, global rows), as the packed
    form does by construction. With y weighted as ADMM weights it
    (rho 100 on the equality rows, all local here; 0.1 on the ten
    coupling rows, all global) the classed dense product is the packed
    one to 1e-12 and ten times closer to numpy's f64 than one f32
    accumulation over all 61 rows: that rounding is the df32 tail's
    residual floor (a PH engine read it 2x higher on the plain form)."""
    from mpisppy_tpu.ops.packed import global_row_mask
    from mpisppy_tpu.ops.qp_solver import _ATy
    A = _sslp_10_50_A()
    m, n = A.shape
    st = analyze_structure(*np.nonzero(A), m, n)
    g = np.asarray(global_row_mask(st))
    assert sorted(np.flatnonzero(g)) == sorted(np.asarray(st.g_rows))
    sp = split_f32(jnp.asarray(A))
    y = jnp.asarray(np.random.RandomState(0).randn(7, m)
                    * np.where(g, 0.1, 100.0))
    truth = np.asarray(y) @ A
    packed = np.asarray(_ATy(sp._replace(
        struct=st, pk_hi=pack(st, sp.hi), pk_lo=pack(st, sp.lo)), y))
    classed = np.asarray(_ATy(sp._replace(struct=st), y))
    plain = np.asarray(_ATy(sp, y))
    scale = np.abs(truth).max()
    assert np.abs(classed - packed).max() < 1e-12 * scale
    err = {k: np.abs(v - truth).max()
           for k, v in (("classed", classed), ("plain", plain))}
    assert err["classed"] < 2e-8 * scale < 1e-7 * scale > err["plain"]
    assert err["plain"] > 5 * err["classed"]
    # a structure with no global rows has one class: the plain product
    st0 = st._replace(g_rows=st.g_rows[:0])
    np.testing.assert_array_equal(
        np.asarray(_ATy(sp._replace(struct=st0), y)), plain)


def test_dense_and_packed_split_solves_agree_row_for_row():
    """The two representations, each reached by the shape of its call:
    rows 0 and 1 of a 64-row solve (dense split matvecs) against the
    same two rows solved alone (packed). Adaptation off and a fixed
    budget: every row runs the same deterministic ADMM recursion on the
    same factors, so the iterates differ by f32 summation order alone.
    That is 3e-4 on x in [0, 1] here whichever form runs (a dense
    two-row solve sits as far from the dense 64-row one: XLA:CPU blocks
    a product by its row count), so the tolerance is 2e-3, ten times
    ``test_packed_kernel_trajectory_matches_dense``'s."""
    from mpisppy_tpu.ops.qp_solver import (QPData, qp_cold_state,
                                           qp_setup, qp_solve)
    A = _sslp_10_50_A()
    m, n = A.shape
    st = analyze_structure(*np.nonzero(A), m, n)
    rng = np.random.RandomState(5)
    S = 64
    q = jnp.asarray(rng.rand(S, n) * 10.0 - 5.0)
    # the published rows' kinds: equalities (client rows, h in {0, 1})
    # and one-sided capacity rows
    eq = rng.rand(m) < 0.8
    l = np.where(eq, (rng.rand(S, m) < 0.5).astype(float), -1e3)
    u = np.where(eq, l, 0.0)
    outs = {}
    for tag, rows in (("dense", S), ("packed", 2)):
        sp = split_f32(jnp.asarray(A))._replace(struct=st)
        data = QPData(jnp.ones(n), sp, jnp.asarray(l[:rows]),
                      jnp.asarray(u[:rows]), jnp.zeros((rows, n)),
                      jnp.ones((rows, n)))
        # one cost scale for both: q_ref is the 64 rows' either way
        fac = qp_setup(data, q_ref=q)
        assert (fac.A_s.pk_hi is not None) == (tag == "packed")
        state = qp_cold_state(fac, data)
        state, x, yA, _yB = qp_solve(fac, data, q[:rows], state,
                                     max_iter=200, adaptive_rho=False,
                                     polish=False)
        outs[tag] = (np.asarray(x)[:2], np.asarray(yA)[:2])
    for a, b in zip(outs["packed"], outs["dense"]):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_sslp_engine_agrees_dense_against_packed():
    """One sslp_10_50 engine, the same scenarios, both forms reached by
    shape: un-chunked on one device the fused df32 call holds all the
    rows (dense); row-sharded over four devices each device call holds
    a quarter (packed). Same recipe, the explicit inverse on in both,
    the cell's budget-capped 400 + 100 ADMM iterations a solve: x-bar,
    conv and the objectives agree to what f32 summation order and the
    mesh's reduction order leave after three hot iterations."""
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import sslp
    from mpisppy_tpu.parallel.mesh import make_mesh

    S = _ENGINE_ROWS
    opts = {"defaultPHrho": 1.0, "subproblem_precision": "df32",
            "subproblem_max_iter": 400, "subproblem_tail_iter": 100,
            "subproblem_eps": 1e-5, "subproblem_eps_hot": 1e-4,
            "subproblem_eps_dua_hot": 1e-2,
            "subproblem_stall_rel": 1.5e-3,
            "subproblem_polish_hot": False, "subproblem_hospital": False}
    out = {}
    for tag, mesh in (("dense", None), ("packed", make_mesh(4))):
        batch = build_batch(
            sslp.scenario_creator, sslp.make_tree(S),
            creator_kwargs=dict(num_servers=10, num_clients=50,
                                overflow=True, server_budget=10,
                                capacity=188.0, demand_is_revenue=True),
            vector_patch=sslp.scenario_vector_patch)
        ph = PHBase(batch, dict(opts), dtype=jnp.float64, mesh=mesh)
        obj0 = np.asarray(ph.solve_loop(w_on=False, prox_on=False))[:S]
        ph.W = ph.W_new
        for _ in range(3):
            obj = np.asarray(ph.solve_loop(w_on=True, prox_on=True))[:S]
            ph.W = ph.W_new
        pt = ph.phase_timing(True)
        assert pt["kernel"]["l_inv"] and pt["admm_iters_per_call"][
            "bulk"] == 400 and pt["admm_iters_per_call"]["tail"] == 100
        shape = pt["solve_shape"]
        assert shape["s_chunk"] == (S if mesh is None else S // 4)
        assert (shape["pk_pass_bytes"] is None) == (tag == "dense")
        if tag == "packed":
            assert shape["pk_pass_bytes"] == 8 * _SSLP["elems"]
        out[tag] = (np.asarray(ph.xbar)[:S], float(ph.conv), obj0, obj)
    d, p = out["dense"], out["packed"]
    np.testing.assert_allclose(p[0], d[0], atol=5e-3)
    assert abs(p[1] - d[1]) < 1e-3
    np.testing.assert_allclose(p[2], d[2], rtol=2e-3)
    np.testing.assert_allclose(p[3], d[3], rtol=2e-3)


def test_uc_fused_program_is_the_same_text_without_the_rule(monkeypatch):
    """At a UC-structured operand set the rule answers "packed" and
    nothing else changes: the fused df32 program lowered from an engine
    built with the rule in the path is, character for character, the
    one lowered with the rule taken out (every structure packed, as
    before ISSUE 33)."""
    import mpisppy_tpu.ops.kernels.reference as ref
    import mpisppy_tpu.ops.packed as packed_mod
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch

    opts = {"defaultPHrho": 100.0, "subproblem_precision": "df32",
            "subproblem_max_iter": 50, "subproblem_eps": 1e-5,
            "subproblem_tail_iter": 25, "subproblem_hospital": False,
            "subproblem_chunk": 2, "iter0_feas_tol": 1.0}
    fn = ref._fused_mixed_jit_donated
    asked = []
    rule = packed_mod.pack_profitable

    def asking(*a):
        asked.append((a, rule(*a)))
        return asked[-1][1]

    def lowered(patched_rule):
        monkeypatch.setattr(packed_mod, "pack_profitable", patched_rule)
        calls = {}

        def record(*a, **kw):
            calls.setdefault("args", (a, kw))
            return fn(*a, **kw)
        monkeypatch.setattr(ref, "_fused_mixed_jit_donated", record)
        batch = build_batch(
            uc.scenario_creator, uc.make_tree(4),
            creator_kwargs=dict(num_gens=6, num_hours=8,
                                min_up_down=True, ramping=True,
                                relax_integrality=True),
            vector_patch=uc.scenario_vector_patch)
        ph = PHBase(batch, dict(opts), dtype=jnp.float64)
        ph.solve_loop(w_on=False, prox_on=False)
        a, kw = calls["args"]
        assert a[0].A_s.pk_hi is not None
        return fn.lower(*a, **kw).as_text()

    with_rule = lowered(asking)
    assert asked and all(verdict for _a, verdict in asked)
    (m, n, _elems, rows), _ = asked[0]
    assert rows == opts["subproblem_chunk"] and m > n > 100
    assert lowered(lambda *a: True) == with_rule
