"""The FWPH engine against the benchmark's plain reference
(``benchmarks/reference/fwph_step.py``: numpy float64 and HiGHS, imports
nothing of the program), seeded, on the CPU: the weight QP against an
independent minimiser, one ``iterate`` on a toy UC replayed from the
engine's own ``x_star``, a whole toy run against the run whose
linearized subproblems HiGHS solves exactly and against the extensive
form, the pool's in-place slot write, the Σ p w = 0 gate of the bound,
and the control below df32.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu.core.fwph import FWPH, _column_step, _pool_init
from mpisppy_tpu.extensions.extension import Extension
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.ir.tree import two_stage_tree
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.ops.simplex_qp import project_simplex, simplex_qp_solve


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference",
        "fwph_step.py")
    spec = importlib.util.spec_from_file_location("bench_reference_fwph",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# the df32 recipe of benchmarks/configs/uc90x48_df32.json, its budget
# kept: what the cell's engine runs
DF32 = {"subproblem_precision": "df32", "defaultPHrho": 100.0,
        "subproblem_max_iter": 400, "subproblem_eps": 1e-5,
        "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
        "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 100,
        "subproblem_segment": 100, "subproblem_segment_lo": 400,
        "subproblem_polish_hot": False, "subproblem_hospital": False}
TOY = {"num_gens": 4, "num_hours": 6, "min_up_down": True, "ramping": True,
       "t0_state": True, "startup_shutdown_ramps": True,
       "relax_integrality": False}
S = 8


def toy_batch(**inst):
    tree = two_stage_tree([f"scen{i}" for i in range(S)],
                          nonant_names=["u", "st"])
    return build_batch(uc.scenario_creator, tree,
                       creator_kwargs=dict(TOY, **inst),
                       vector_patch=uc.scenario_vector_patch)


class Keep(Extension):
    """The engine's own hook after every ``solve_loop``: what a pass
    started from and what its solve ended with."""

    def __init__(self):
        super().__init__()
        self.passes = []

    def post_solve(self, opt):
        if getattr(opt, "_w_t", None) is None:
            return                      # iter-0
        self.passes.append({
            "k": opt._sdm_k, "ptr": opt._col_ptr, "a": opt._a,
            "base": opt._base, "xn_t": opt._xn_t, "w_t": opt._w_t,
            "x_star": opt.x, "dual": opt._last_dual_obj})


def engine(batch, chunk=0, recipe=None, **opts):
    keep = Keep()
    fw = FWPH(batch, dict(DF32, **(recipe or {}), convthresh=-1.0,
                          subproblem_chunk=chunk, FW_iter_limit=2, **opts),
              extensions=keep, dtype=jnp.float64)
    return fw, keep


def qp_case(Sq, C, K, seed):
    """A pool like the cell's: binary nonant columns, half of the slots
    copies of slot 0 (a pool early in a run), base costs that differ by
    as much as the quadratic term does (the minimiser sits inside a
    face, not at a vertex)."""
    rng = np.random.default_rng(seed)
    G = (rng.random((Sq, C, K)) < 0.5).astype(float)
    G[:, C // 2:] = G[:, :1]
    return (G, 1e5 + 5.0 * K * rng.random((Sq, C)),
            50.0 * rng.normal(size=(Sq, K)), np.full((Sq, K), 100.0),
            rng.random((Sq, K)))


# ---- the weight QP ------------------------------------------------------
# 400 accelerated trips from the barycentre, three numbers. The VALUE
# against the reference minimiser's (which certifies itself: its
# Frank-Wolfe gap over |value| bounds value - optimum): the trips' own
# 1/t² tail and nothing else, 1e-9 of the value at these shapes (read:
# 9e-16 and 2.3e-11). A value is flat at its minimum, so it does not
# tell a float32 solve from a float64 one (read: 4e-13, 2.4e-11); the
# weights do: sum a - 1 and a . G - xn sit at float64 rounding, 1e-12
# (read: 1.4e-14), where float32 reads 4e-7 to 8e-6.
QP_LIMITS = {"gap": 1e-9, "feas": 1e-12, "xn": 1e-12}


@pytest.mark.parametrize("shape", [(8, 4, 12), (8, 16, 200)])
@pytest.mark.parametrize("dtype,sound", [(jnp.float64, True),
                                         (jnp.float32, False)])
def test_weight_qp_against_the_reference_minimiser(shape, dtype, sound):
    Sq, C, K = shape
    G, b, w, rho, xbar = qp_case(Sq, C, K, seed=C * K)
    arr = lambda v: jnp.asarray(v, dtype)
    a, xn = simplex_qp_solve(arr(G), arr(b), arr(w), arr(rho), arr(xbar),
                             jnp.full((Sq, C), 1.0 / C, dtype), iters=400)
    a, xn = np.asarray(a, float), np.asarray(xn, float)
    assert a.min() >= 0
    gaps = []
    for s in range(Sq):
        best = ref.simplex_qp_reference(G[s], b[s], w[s], rho[s], xbar[s])
        assert best["kkt"] <= 1e-12
        got = ref.qp_value(G[s], b[s], w[s], rho[s], xbar[s],
                           a[s] / a[s].sum())
        gaps.append((got - best["value"]) / abs(best["value"]))
    assert min(gaps) >= -1e-12      # nothing beats the minimiser
    read = {"gap": max(gaps), "feas": np.abs(a.sum(axis=1) - 1).max(),
            "xn": np.abs(xn - np.einsum("sc,sck->sk", a, G)).max()}
    over = {k for k, v in read.items() if v > QP_LIMITS[k]}
    assert (not over) is sound, read
    if not sound:
        assert "feas" in over, read


def test_projection_against_the_count_form():
    """The sort-based rule (Held et al.) against the O(C²) form with no
    sort (τ = max over the entries v_j of (Σ_{v_i ≥ v_j} v_i − 1) /
    #{v_i ≥ v_j}), ties and a point already on the simplex included."""
    rng = np.random.default_rng(5)
    v = rng.normal(size=(64, 16))
    v[:8, 4:9] = v[:8, 3:4]             # ties
    v[8] = 1.0 / 16                     # on the simplex already
    ge = v[:, None, :] >= v[:, :, None]
    tau = ((np.where(ge, v[:, None, :], 0.0).sum(-1) - 1.0)
           / ge.sum(-1)).max(-1)
    want = np.maximum(v - tau[:, None], 0.0)
    got = np.asarray(project_simplex(jnp.asarray(v)))
    np.testing.assert_allclose(got, want, atol=1e-15)
    np.testing.assert_allclose(got[8], v[8], atol=1e-16)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=1e-14)


# ---- the pool -----------------------------------------------------------
def test_slot_write_is_in_place_and_equals_at_set():
    rng = np.random.default_rng(11)
    Sq, C, n = 6, 5, 40
    idx = jnp.asarray(np.sort(rng.choice(n, 12, replace=False)))
    x0, x1 = (jnp.asarray(rng.normal(size=(Sq, n))) for _ in range(2))
    c = jnp.asarray(rng.normal(size=(Sq, n)))
    cols, G, base = _pool_init(x0, c, idx, n_slots=C)
    want = (np.asarray(cols.at[:, 3, :].set(x1)),
            np.asarray(G.at[:, 3, :].set(x1[:, idx])),
            np.asarray(base.at[:, 3].set(jnp.sum(c * x1, axis=-1))))
    before = [np.asarray(v).copy() for v in (cols, G, base)]
    K = idx.shape[0]
    vec = lambda *s: jnp.asarray(rng.normal(size=s))
    prob = jnp.full((Sq,), 1.0 / Sq)
    *got, row = _column_step(
        cols, G, base, jnp.full((Sq, C), 1.0 / C), vec(Sq, K), vec(Sq, K),
        x1, vec(Sq), c, vec(Sq), prob, prob, (jnp.ones((Sq, 1)),), idx,
        jnp.asarray(3, jnp.int32), slot_slices=((0, K),))
    assert row.shape == (4,)
    for g, w_, b0 in zip(got, want, before):
        g = np.asarray(g)
        assert (g == w_).all()                      # to the last bit
        keep = [j for j in range(C) if j != 3]
        assert (g[:, keep] == b0[:, keep]).all()    # no other slot moved
    # the two big buffers were donated, not copied
    assert cols.is_deleted() and G.is_deleted() and not base.is_deleted()


# ---- one iterate on a toy UC -------------------------------------------
@pytest.fixture(scope="module")
def toy_runs():
    """The toy UC un-chunked and chunked at 4: iter-0, two outer
    iterations, the second one's arrays kept."""
    out = {}
    for chunk in (0, 4):
        fw, keep = engine(toy_batch(), chunk)
        fw.iter0()
        assert fw.iterate(1) is True
        pre = {"W": fw.W, "xbar": fw.xbar}
        n0 = len(keep.passes)
        assert fw.iterate(2) is True
        out[chunk] = (fw, keep.passes[n0:], pre)
    return out


def test_chunked_and_unchunked_iterates_agree(toy_runs):
    (fa, _, _), (fb, _, _) = toy_runs[0], toy_runs[4]
    # two chunk solves adapt rho apart from the one solve of all rows:
    # the two runs are the same algorithm at the ADMM's tolerance
    assert abs(fa._local_bound - fb._local_bound) \
        <= 2e-3 * abs(fa._local_bound)
    np.testing.assert_allclose(np.asarray(fa.xbar), np.asarray(fb.xbar),
                               atol=0.05)


@pytest.mark.parametrize("chunk", [0, 4])
def test_iterate_against_sdm_pass_and_outer_update(toy_runs, chunk):
    """Fed the engine's own ``x_star``, the reference gives the engine's
    w_t, Γ, slot and outer update to float64 rounding."""
    fw, passes, pre = toy_runs[chunk]
    f = lambda v: np.asarray(v, float)
    assert [p["k"] for p in passes] == [0, 1]
    idx, C = np.asarray(fw.nonant_idx), fw.max_columns
    last = passes[-1]
    want = ref.sdm_pass(f(pre["W"]), f(fw.rho), f(pre["xbar"]),
                        f(last["xn_t"]), f(last["a"]), f(last["base"]),
                        f(fw.c), f(fw.c0), f(last["x_star"]), idx,
                        f(fw.prob), last["ptr"], C)
    np.testing.assert_allclose(f(last["w_t"]), want["w_t"], rtol=1e-13)
    row = fw._sdm_row
    assert abs(row["gamma"] - want["gamma"]) \
        <= 1e-12 * abs(want["E_lin_t"])
    assert abs(row["E_lin_t"] - want["E_lin_t"]) \
        <= 1e-12 * abs(want["E_lin_t"])
    # the slot holds the pass's x_star to the last bit
    assert (f(fw.columns)[:, want["slot"]] == f(last["x_star"])).all()
    assert (f(fw._G)[:, want["slot"]] == f(last["x_star"])[:, idx]).all()
    # the QP's minimiser, held to the reference's
    G, base, a = f(fw._G), f(fw._base), f(fw._a)
    np.testing.assert_allclose(f(fw._xn_t),
                               np.einsum("sc,sck->sk", a, G), atol=1e-12)
    for s in range(S):
        best = ref.simplex_qp_reference(G[s], base[s], f(pre["W"])[s],
                                        f(fw.rho)[s], f(pre["xbar"])[s])
        got = ref.qp_value(G[s], base[s], f(pre["W"])[s], f(fw.rho)[s],
                           f(pre["xbar"])[s], a[s])
        assert -1e-12 <= (got - best["value"]) / abs(best["value"]) <= 1e-9
    up = ref.outer_update(f(fw._xn_t), f(fw.prob), f(pre["W"]), f(fw.rho))
    np.testing.assert_allclose(f(fw.xbar)[0], up["xbar"], atol=1e-13)
    np.testing.assert_allclose(f(fw.xsqbar)[0], up["xsqbar"], atol=1e-13)
    np.testing.assert_allclose(f(fw.W), up["W"], rtol=1e-12, atol=1e-10)
    assert abs(fw.conv - up["conv"]) <= 1e-12 * max(up["conv"], 1.0)
    # first pass: on the manifold, and its bound is the expectation of
    # the certified values, each under its exact LP
    first = passes[0]
    assert ref.w_manifold_err(f(first["w_t"]), f(fw.prob)) <= 1e-12
    A = ref.sparse(fw.batch.A)
    b = fw.batch
    for s in (0, S - 1):
        lp = ref.lagrangian_value(A, b.c[s], b.c0[s], b.l[s], b.u[s],
                                  b.lb[s], b.ub[s], f(first["w_t"])[s], idx)
        assert f(first["dual"])[s] <= lp + 1e-6 * abs(lp)
        # a budget-capped dual certificate is loose by nature (the hot
        # dual tolerance is 1e-2): read 1.4e-3 and 2.0e-3 under
        assert f(first["dual"])[s] >= lp - 1e-2 * abs(lp)


# ---- a whole toy run ----------------------------------------------------
@pytest.fixture(scope="module")
def whole_run():
    """Twelve outer iterations at four slots (24 columns: the pool wraps
    six times) beside the exact-solve run and the extensive form."""
    fw, _ = engine(toy_batch(), 4, fwph_max_columns=4, PHIterLimit=12)
    trail = []
    fw.iter0()
    trail.append(fw._local_bound)
    for it in range(1, 13):
        assert fw.iterate(it) is True
        trail.append(fw._local_bound)
    b = fw.batch
    f = lambda v: np.asarray(v, float)
    data = (ref.sparse(b.A), f(b.c), f(b.c0), f(b.l), f(b.u), f(b.lb),
            f(b.ub), f(fw.prob), np.asarray(fw.nonant_idx))
    exact = ref.fwph_run(*data, rho=100.0, outer_iters=12, fw_iter_limit=2,
                         n_slots=4)
    integer = np.asarray(b.integer, bool) if b.integer is not None else None
    return fw, trail, exact, ref.extensive_form(*data), \
        ref.extensive_form(*data, integer=integer)


def test_every_published_bound_is_an_outer_bound(whole_run):
    fw, trail, exact, ef_lp, ef_mip = whole_run
    assert ef_lp <= ef_mip + 1e-9 * abs(ef_mip)
    # the engine's linearized solve is the LP relaxation: its bound is
    # the relaxation's Lagrangian bound, under both optima
    assert all(b <= ef_lp + 1e-6 * abs(ef_lp) for b in trail), (trail,
                                                                 ef_lp)
    assert all(b <= ef_lp + 1e-9 * abs(ef_lp) for b in exact["bounds"])
    assert all(y >= x for x, y in zip(trail, trail[1:]))    # monotone
    assert trail[-1] > trail[0]
    # the last bound beside the exact-solve run's: the ADMM's dual
    # certificate gives away what its tolerance allows (1e-4 relative
    # primal, 1e-2 dual) and the two runs' columns differ; 2e-3 of the
    # bound holds both (read: 4e-4)
    assert abs(trail[-1] - exact["bounds"][-1]) \
        <= 2e-3 * abs(exact["bounds"][-1])


def test_bound_stays_valid_across_pool_wraps(whole_run):
    fw, trail, _exact, ef_lp, _ = whole_run
    t = fw.phase_timing()["fwph"]
    assert t["columns_written"] == 24 and t["pool_wraps"] == 5
    assert t["bounds_published"] == 12 and t["bounds_dropped"] == 0
    assert t["passes"] == 24 and t["iterations"] == 12
    # one row a pass, conv once an iteration
    assert t["host_reads"] == t["passes"] + t["iterations"]
    assert t["qp_iters"] == 24 * fw.qp_iters
    # the bounds published after the first wrap (pass 5 on) hold too
    assert all(b <= ef_lp + 1e-6 * abs(ef_lp) for b in trail[3:])


# ---- fwph_main is iter0 + the iterate loop ------------------------------
def test_fwph_main_is_iter0_and_the_iterate_loop_on_the_farmer():
    opts = {"defaultPHrho": 10.0, "PHIterLimit": 8, "convthresh": -1.0,
            "FW_iter_limit": 2}
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    a = FWPH(batch, dict(opts))
    conv, bound, trivial = a.fwph_main()
    b = FWPH(batch, dict(opts))
    b.iter0()
    for it in range(1, 9):
        assert b.iterate(it) is True
    assert (conv, bound, trivial) == (b.conv, b._local_bound,
                                      b.trivial_bound)
    assert (np.asarray(a.W) == np.asarray(b.W)).all()
    assert bound <= -108390.0 + 1.0 and bound >= trivial


# ---- a bound off the manifold is dropped --------------------------------
def test_bound_off_the_manifold_is_dropped_and_counted():
    fw, _ = engine(toy_batch(), 4)
    fw.iter0()
    assert fw.iterate(1) is True
    held = fw._local_bound
    # a W that no PH update made: one scenario's row pushed off. The
    # update adds to W only what lies on the manifold, so W stays off
    # it until it is put back
    W = fw.W
    fw.W = W.at[0].add(50.0)
    assert fw.iterate(2) is True
    t = fw.phase_timing()["fwph"]
    assert t["bounds_dropped"] == 1 and t["bounds_published"] == 1
    assert fw._local_bound == held             # nothing was published
    fw.W = fw.W.at[0].add(-50.0)
    assert fw.iterate(3) is True
    t = fw.phase_timing()["fwph"]
    assert t["bounds_dropped"] == 1 and t["bounds_published"] == 2
    assert fw._local_bound >= held


# ---- the control: the recipe below its precision ------------------------
def test_control_below_df32_fails_the_stated_number():
    """``subproblem_tail_iter`` 0 (the f32 bulk phase alone, float64
    outer arithmetic unchanged) at 6 generators x 12 hours, where a
    prox-off solve still converges inside its budget: the
    best-converged quarter of the last x_star's rows sits at the f32
    floor, the stated number ``hot_violation_q1`` (read: 4.6e-5 sound,
    1.8e-4 control; the limit a factor 2 from either); the QP's
    weights, float64 under both, do not move. (At 20 x 24 every
    linearized solve ends at its cap and the two read alike, 2.9e-4 and
    5.0e-4: a budget-capped LP solve reads its cap, not its
    precision.)"""
    def run(recipe):
        fw, keep = engine(toy_batch(num_gens=6, num_hours=12), 4, recipe)
        fw.iter0()
        for it in (1, 2, 3):
            assert fw.iterate(it) is True
        b = fw.batch
        x = np.asarray(keep.passes[-1]["x_star"], float)
        ax = x @ np.asarray(b.A, float).T
        row = np.maximum(np.maximum(b.l - ax, ax - b.u), 0).max(axis=1)
        col = np.maximum(np.maximum(b.lb - x, x - b.ub), 0).max(axis=1)
        viol = np.maximum(row, col) / np.maximum(1.0,
                                                 np.abs(ax).max(axis=1))
        return float(np.quantile(viol, 0.25)), fw
    LIMIT = 9e-5
    sound, fw = run(None)
    control, fc = run({"subproblem_tail_iter": 0})
    assert sound <= LIMIT < control, (sound, control)
    for e in (fw, fc):
        a = np.asarray(e._a)
        assert np.abs(a.sum(axis=1) - 1).max() <= 1e-12
