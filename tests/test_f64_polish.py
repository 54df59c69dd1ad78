"""The factor-and-substitute side of the float64 polish on small
per-scenario systems (ops/qp_solver ``_polish_select``, ISSUE 40): the
Gram product ``Aᵀ diag(r) A``, the Cholesky factor and the solves with
it, which the TPU compiler expands into ``while`` loops with
``dynamic-update-slice``s when they are the library calls, and runs as
ordinary fusions when they are recurrences unrolled over the static n
(``_gram_reduce``, ``_unrolled_cholesky``, ``_unrolled_linv``,
``_linv_pair_solve``). The form is picked per platform at lowering time
(``jax.lax.platform_dependent``) and by the static n, so here, on the
CPU, the polish keeps the library calls; the unrolled forms are called
directly, and a whole polish is steered onto them by handing the solver
the unrolled helpers in place of the library ones
(tests/test_chip_compile_stacked_f64.py and its ``_blocked`` twin hold
what the TPU compiler makes of each form)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu import obs
from mpisppy_tpu.ops.qp_solver import (_POLISH_UNROLL_MAX_N, _gram_reduce,
                                       _linv_pair_solve, _unrolled_cholesky,
                                       _unrolled_linv, f64_polish_form,
                                       qp_dual_objective, qp_objective)

# the chip sweep's shapes (doc/kernels.md §3e): (S, n)
SHAPES = [(3, 12), (24, 12), (192, 12), (24, 24), (24, 48)]


def _penalty_system(S, n, ill, seed):
    """Penalty matrices of the polish's own make, P + sigma I +
    Aᵀ diag(rp) A + diag(g² rpB) with random active sets: rho_big 1e5
    over sigma 1e-6 (cond up to ~1e11-1e12) or both 1 (cond ~10)."""
    rng = np.random.default_rng(seed)
    m = max(1, (7 * n) // 12)
    A = rng.standard_normal((S, m, n)) * (rng.random((S, m, n)) < 0.4)
    big, sig = (1e5, 1e-6) if ill else (1.0, 1.0)
    rpA = np.where(rng.random((S, m)) < 0.5, big, 0.0)
    d = np.where(rng.random((S, n)) < 0.3, rng.random((S, n)), 0.0) + sig \
        + (0.5 + rng.random((S, n))) ** 2 \
        * np.where(rng.random((S, n)) < 0.3, big, 0.0)
    M = np.einsum("smi,sm,smj->sij", A, rpA, A)
    idx = np.arange(n)
    M[:, idx, idx] += d
    b = rng.standard_normal((S, n)) * np.sqrt(np.abs(M).max((1, 2)))[:, None]
    return A, rpA, M, b


@pytest.mark.parametrize("ill", [False, True], ids=["well", "cond1e11"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_unrolled_forms_equal_numpy_in_float64(shape, ill):
    S, n = shape
    A, rpA, M, b = _penalty_system(S, n, ill, S * 1009 + n)
    if ill:
        assert np.linalg.cond(M).max() > 1e10
    scale = np.abs(M).max((1, 2))
    # the Gram product
    G = np.asarray(_gram_reduce(jnp.asarray(A), jnp.asarray(rpA)))
    want = np.einsum("smi,sm,smj->sij", A, rpA, A)
    assert G.dtype == np.float64
    assert np.abs(G - want).max() <= 1e-13 * max(1.0, np.abs(want).max())
    # the factor: lower, L Lᵀ = M to rounding, and numpy's factor
    L = np.asarray(jax.jit(_unrolled_cholesky)(jnp.asarray(M)))
    assert L.dtype == np.float64 and not np.triu(L, 1).any()
    recon = np.abs(L @ L.transpose(0, 2, 1) - M).max((1, 2)) / scale
    assert recon.max() <= 1e-15 * n
    L_np = np.linalg.cholesky(M)
    # the factor itself moves by cond * eps under ANY rounding
    assert np.abs(L - L_np).max() <= (1e-5 if ill else 1e-13) \
        * np.abs(L_np).max()
    # L⁻¹ by the unrolled substitution on the identity
    X = np.asarray(jax.jit(_unrolled_linv)(jnp.asarray(L_np)))
    resid = np.abs(X @ L_np - np.eye(n)).max()
    assert not np.triu(X, 1).any()
    assert resid <= (1e-7 if ill else 1e-13)
    # the pair's solve: a backward-stable solve's residual, as the
    # library pair's, well- or ill-conditioned
    x = np.asarray(_linv_pair_solve(jnp.asarray(X), jnp.asarray(b)))
    r = np.abs(np.einsum("sij,sj->si", M, x) - b).max(1)
    assert (r / (scale * np.abs(x).max(1))).max() <= 1e-14
    x_np = np.linalg.solve(M, b[..., None])[..., 0]
    fwd = np.abs(x - x_np).max(1) / np.abs(x_np).max(1)
    assert fwd.max() <= (1e-3 if ill else 1e-12)


def test_a_matrix_that_is_not_positive_definite_gives_nan():
    """``_polish_select`` relies on NaN candidates losing: a degenerate
    active set's penalty matrix must not come back as a finite factor.
    The unrolled recurrence takes the square root of the negative
    pivot, as the library does, and every solve with that factor (or
    its inverse) is NaN in every entry."""
    _A, _r, M, b = _penalty_system(3, 12, False, 7)
    M[1, 5, 5] = -1.0
    L = np.asarray(_unrolled_cholesky(jnp.asarray(M)))
    bad = np.isnan(L).any((1, 2))
    assert bad.tolist() == [False, True, False]
    assert np.isnan(np.asarray(jnp.linalg.cholesky(jnp.asarray(M)))) \
        .any((1, 2)).tolist() == bad.tolist()
    x = np.asarray(_linv_pair_solve(_unrolled_linv(jnp.asarray(L)),
                                    jnp.asarray(b)))
    assert np.isnan(x[1]).all() and np.isfinite(x[[0, 2]]).all()


def test_the_rule_by_platform_dtype_ndim_and_n(monkeypatch):
    """The TPU's own forms only on the TPU, for a per-scenario float64
    matrix: unrolled with n up to the width the compile seconds set
    (doc/kernels.md §3e), blocked above it (§3h); the library calls
    for a shared 2-D matrix, a stack too large to factor on the device
    and every other backend; None where no float64 polish can run. Shapes and
    dtype only: the rule reads nothing else of its operand."""
    f8 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float64)
    assert _POLISH_UNROLL_MAX_N == 16
    assert jax.default_backend() != "tpu"
    here = {(3, 7, 12): "library", (24, 7, 12): "library",
            (24, 14, 24): "library", (24, 28, 48): "library",
            (7, 12): "library"}
    on_tpu = {(3, 7, 12): "unrolled", (24, 7, 12): "unrolled",
              (192, 7, 12): "unrolled", (24, 9, 16): "unrolled",
              (24, 10, 17): "blocked", (24, 14, 24): "blocked",
              (24, 28, 48): "blocked", (1024, 193, 384): "blocked",
              (24, 700, 1200): "blocked",
              # too large to factor on the device: the library stays
              (4, 26016, 13056): "library", (7, 12): "library"}
    for shape, want in here.items():
        assert f64_polish_form(f8(*shape)) == want, shape
    f32 = jax.ShapeDtypeStruct((24, 7, 12), jnp.float32)
    split = qps.split_f32(jnp.ones((7, 12)))
    assert f64_polish_form(f32) is None and f64_polish_form(split) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for shape, want in on_tpu.items():
        assert f64_polish_form(f8(*shape)) == want, shape
    assert f64_polish_form(f32) is None and f64_polish_form(split) is None


# ---------------- a recorded stacked farmer polish ---------------------

@pytest.fixture(scope="module")
def polish_calls():
    """The polish program's operands (``_solve_impl``, ``max_iter=0``,
    ``polish=True``: what the chip's segmented driver launches last) of
    a full served stack, iter-0 and hot: the recorded solve calls'
    ADMM loops run here first, their end states are what the polish
    starts from."""
    from stacked_farmer import record_stacked_farmer_calls
    calls, plan = record_stacked_farmer_calls()
    fn = jax.jit(qps._solve_impl, static_argnames=qps._SOLVE_STATICS)
    out = []
    for args, kw in (calls[0], calls[-1]):
        kw = {k: v for k, v in kw.items() if k != "_segmented_caller"}
        args = jax.tree.map(lambda v: jnp.asarray(v)
                            if isinstance(v, np.ndarray) else v, args)
        st = fn(*args, **dict(kw, polish=False))[0]
        assert int(st.iters) > 0
        out.append(((args[0], args[1], args[2], st),
                    dict(kw, max_iter=0, polish=True)))
    return out, plan


def _polish(args, kw):
    # a fresh function object each time: jax caches a trace by the
    # function it wraps, and the forms are looked up while tracing
    def impl(factors, data, q, state, **k):
        return qps._solve_impl(factors, data, q, state, **k)
    st, x, yA, yB = jax.jit(impl, static_argnames=qps._SOLVE_STATICS)(
        *args, **kw)
    data, q = args[1], args[2]
    return dict(
        x=np.asarray(x), pri=np.asarray(st.pri_res),
        obj=np.asarray(qp_objective(data, q, jnp.zeros(q.shape[0]), x)),
        dual=np.asarray(qp_dual_objective(data, q, 0.0, yA, yB,
                                          x_witness=x)))


def _on_unrolled_forms(monkeypatch):
    """What the TPU lowering runs, on this backend: the library helpers
    the switch falls back to replaced by the unrolled ones."""
    monkeypatch.setattr(qps, "_penalty_factor_library",
                        qps._penalty_factor_unrolled)
    monkeypatch.setattr(qps, "_tri_solve", _linv_pair_solve)


def test_the_switch_keeps_the_library_calls_off_the_tpu(polish_calls,
                                                        monkeypatch):
    """On this backend a polish at a width the TPU would unroll IS the
    einsum, ``jnp.linalg.cholesky`` and the ``triangular_solve`` pair
    it was, bit for bit (every tier-1 number of a polished native
    solve rides on that): the same polish with the switch taken out of
    the way gives equal outputs."""
    calls, _plan = polish_calls
    args, kw = calls[0]
    assert qps._polish_unrollable(args[0].A_s)
    through_switch = _polish(args, kw)
    monkeypatch.setattr(qps, "_tpu_stack_form", lambda A_s: None)
    library_only = _polish(args, kw)
    for k, v in through_switch.items():
        np.testing.assert_array_equal(v, library_only[k], err_msg=k)


@pytest.mark.parametrize("which", [0, 1], ids=["iter0", "hot"])
def test_stacked_farmer_polish_on_the_unrolled_forms(polish_calls,
                                                     monkeypatch, which):
    """The same polish twice from the SAME recorded inputs: on the
    library calls the CPU lowering keeps, and on the unrolled forms
    (what the TPU lowering runs). float64 both ways: x and the primal
    objective agree to 1e-9, the same scenarios accept a polished
    point. The certified dual objective agrees to 2e-4 only, and no
    re-rounded form can do better: the penalty systems' cond is 4e11,
    the candidates' duals are rho_big times a residual of x, and the
    dual function zeroes reduced costs under a tolerance, so ANY two
    roundings of this polish (the library's on two backends too) part
    by 1e-5 … 4e-5 there (PERF.md §6, PR 40); every candidate is a
    valid bound, which is what the consumers need."""
    calls, _plan = polish_calls
    args, kw = calls[which]
    lib = _polish(args, kw)
    _on_unrolled_forms(monkeypatch)
    unr = _polish(args, kw)
    start_pri = np.asarray(args[3].pri_res)
    assert np.abs(unr["x"] - lib["x"]).max() <= 1e-9 * np.abs(lib["x"]).max()
    assert (np.abs(unr["obj"] - lib["obj"])
            <= 1e-9 * np.abs(lib["obj"])).all()
    assert ((unr["pri"] != start_pri) == (lib["pri"] != start_pri)).all()
    assert np.isfinite(unr["dual"]).all()
    assert (np.abs(unr["dual"] - lib["dual"])
            <= 2e-4 * np.abs(lib["dual"])).all()
    # a valid lower bound either way
    assert (unr["dual"] <= unr["obj"] + 1e-6 * np.abs(unr["obj"])).all()


@pytest.mark.parametrize("form", ["library", "unrolled"])
def test_polish_chunk_gives_the_same_result(polish_calls, monkeypatch, form):
    """``polish_chunk`` maps the same tail over scenario chunks
    (``lax.map``): per-scenario arithmetic, so chunks of 8, and of 5
    with a padded last chunk, give what the whole batch gives (to
    rounding), on either form."""
    calls, _plan = polish_calls
    args, kw = calls[0]
    if form == "unrolled":
        _on_unrolled_forms(monkeypatch)
    whole = _polish(args, dict(kw, polish_chunk=0))
    for chunk in (8, 5):
        part = _polish(args, dict(kw, polish_chunk=chunk))
        start_pri = np.asarray(args[3].pri_res)
        assert ((part["pri"] != start_pri)
                == (whole["pri"] != start_pri)).all()
        for k in ("x", "obj", "dual"):
            # per-scenario arithmetic, but the compiler vectorizes a
            # chunk's shape its own way: rounding-level differences,
            # which the dual objective amplifies as between the forms
            v, tol = whole[k], 2e-4 if k == "dual" else 1e-9
            np.testing.assert_allclose(
                part[k], v, rtol=tol, atol=tol * np.abs(v).max(),
                err_msg=f"{k} chunk {chunk}")


def test_descriptor_names_the_form(polish_calls):
    """``phase_timing()["kernel"]`` says how a float64 polish over the
    engine's factors runs on this backend; a plan with no float64
    polish (a split matrix never polishes) says None."""
    _calls, plan = polish_calls
    assert plan["f64_polish"] == "library"
    from mpisppy_tpu.ops.kernels import KernelPlan
    assert KernelPlan(mode="fused").descriptor()["f64_polish"] is None


def test_trace_time_counter_counts_the_factorizations(polish_calls,
                                                      tmp_path):
    """In a session, each penalty factorization traced books one count
    under the form this backend lowers it to: three a polish program."""
    calls, _plan = polish_calls
    args, kw = calls[0]
    obs.configure(out_dir=str(tmp_path), role="f64polish")
    try:
        before = obs.counter_value("kernel.f64_polish_library")
        _polish(args, kw)
        assert obs.counter_value("kernel.f64_polish_library") == before + 3
        assert obs.counter_value("kernel.f64_polish_unrolled") == 0
    finally:
        obs.shutdown()
