"""The batched float64 products of the per-scenario-matrix branch
(ops/qp_solver ``_batched_matvec`` / ``_batched_rmatvec``, ISSUE 38):
``A x``, ``Aᵀ y`` and the explicit (S, n, n) inverse's apply, which the
TPU compiler emulates as a loop nest when written as a ``dot_general``
and runs as one fusion when written as a multiply and a sum over the
contracted axis. The form is picked per platform at lowering time
(``jax.lax.platform_dependent``), so here, on the CPU, the solver keeps
the library dot; the reduce forms are called directly, and a whole
solve is steered onto them by handing the solver the reduce helpers in
place of the switch (tests/test_chip_compile_stacked_f64.py holds
what the TPU compiler makes of each form)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu import obs
from mpisppy_tpu.ops.qp_solver import (_ATy, _Ax, _chol_solve,
                                       _matvec_reduce, _rmatvec_reduce,
                                       f64_product_form, qp_objective)

# the chip sweep's shapes (doc/kernels.md §3d), then a short last axis,
# a single row and S = 1
SWEPT = [(3, 7, 12), (24, 7, 12), (24, 70, 120), (24, 700, 1200),
         (192, 7, 12)]
SHAPES = SWEPT + [(5, 9, 1), (4, 1, 6), (1, 7, 12), (1, 1, 1)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_reduce_forms_equal_numpy_einsum_in_float64(shape):
    S, m, n = shape
    rng = np.random.default_rng(S * 1009 + m * 31 + n)
    A = rng.standard_normal((S, m, n))
    x = rng.standard_normal((S, n))
    y = rng.standard_normal((S, m))
    for got, want in (
            (_matvec_reduce(jnp.asarray(A), jnp.asarray(x)),
             np.einsum("smn,sn->sm", A, x)),
            (_rmatvec_reduce(jnp.asarray(A), jnp.asarray(y)),
             np.einsum("smn,sm->sn", A, y))):
        assert got.dtype == jnp.float64 and got.shape == want.shape
        assert np.abs(np.asarray(got) - want).max() \
            <= 1e-13 * max(1.0, np.abs(want).max())
    if n <= 120:
        # the explicit inverse's apply: (S, n, n) symmetric
        F = rng.standard_normal((S, n, n)) / n
        F = 0.5 * (F + F.transpose(0, 2, 1))
        got = _matvec_reduce(jnp.asarray(F), jnp.asarray(x))
        want = np.einsum("sij,sj->si", F, x)
        assert np.abs(np.asarray(got) - want).max() \
            <= 1e-13 * max(1.0, np.abs(want).max())


def test_the_switch_keeps_the_library_dot_off_the_tpu():
    """On this backend the helpers behind ``_Ax`` / ``_ATy`` /
    ``_chol_solve`` ARE the einsums they replaced, bit for bit (every
    tier-1 number of a per-scenario-matrix model rides on that), and
    the descriptor says so."""
    rng = np.random.default_rng(38)
    A = jnp.asarray(rng.standard_normal((24, 7, 12)))
    F = jnp.asarray(rng.standard_normal((24, 12, 12)))
    x = jnp.asarray(rng.standard_normal((24, 12)))
    y = jnp.asarray(rng.standard_normal((24, 7)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_Ax)(A, x)),
        np.asarray(jnp.einsum("smn,sn->sm", A, x)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_ATy)(A, y)),
        np.asarray(jnp.einsum("smn,sm->sn", A, y)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_chol_solve)(F, x)),
        np.asarray(jnp.einsum("sij,sj->si", F, x)))
    assert jax.default_backend() != "tpu"
    assert f64_product_form(A) == "dot"
    # nothing but a per-scenario float64 matrix has such products
    assert f64_product_form(A[0]) is None
    assert f64_product_form(A.astype(jnp.float32)) is None
    assert f64_product_form(qps.split_f32(A[0])) is None


def test_the_rule_is_the_sweeps(monkeypatch):
    """On the TPU the rule answers "reduce" at every shape the chip
    sweep timed (the reduction won 7-50x at each, and at the largest it
    streams the matrix near the HBM's rate, so no larger shape turns
    the order): the served farmer's solo and stacked shapes, their
    inverses, and the largest swept one. Shapes only: the rule reads
    nothing else of its operand."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for shape in [(3, 7, 12), (3, 12, 12), (24, 7, 12), (24, 12, 12),
                  (24, 700, 1200), (24, 1200, 1200)]:
        M = jax.ShapeDtypeStruct(shape, jnp.float64)
        assert f64_product_form(M) == "reduce", shape
    assert f64_product_form(
        jax.ShapeDtypeStruct((24, 7, 12), jnp.float32)) is None
    assert f64_product_form(
        jax.ShapeDtypeStruct((7, 12), jnp.float64)) is None


# ---------------- a stacked farmer solve on the reduce forms -----------

@pytest.fixture(scope="module")
def stacked_farmer_calls():
    from stacked_farmer import record_stacked_farmer_calls
    return record_stacked_farmer_calls()


def test_descriptor_names_the_form(stacked_farmer_calls):
    """``phase_timing()["kernel"]`` says how the batched float64
    products run on this backend; a plan whose matrix is shared or
    split has no such product and says None."""
    _calls, plan = stacked_farmer_calls
    assert plan["f64_products"] == "dot"
    from mpisppy_tpu.ops.kernels import KernelPlan
    assert KernelPlan(mode="fused").descriptor()["f64_products"] is None


@pytest.mark.parametrize("which", [0, -1], ids=["iter0", "hot"])
def test_stacked_farmer_solve_on_the_reduce_forms(stacked_farmer_calls,
                                                  monkeypatch, which):
    """The same solve twice through ``_solve_impl``: on the einsums the
    CPU lowering keeps, and with the two switches replaced by the
    reduce forms themselves (what the TPU lowering runs). float64 both
    ways, so the iterates differ by rounding alone: objectives to 1e-9,
    ADMM iteration counts within one ``check_every``."""
    calls, _plan = stacked_farmer_calls
    args, kw = calls[which]
    kw = {k: v for k, v in kw.items() if k != "_segmented_caller"}
    args = jax.tree.map(lambda v: jnp.asarray(v)
                        if isinstance(v, np.ndarray) else v, args)
    check_every = kw.get("check_every", 25)

    def solve():
        # a fresh function object each time: jax caches a trace by the
        # function it wraps, and the switches are looked up while tracing
        def impl(factors, data, q, state, **k):
            return qps._solve_impl(factors, data, q, state, **k)
        fn = jax.jit(impl, static_argnames=qps._SOLVE_STATICS)
        st, x, _yA, _yB = fn(*args, **kw)
        data, q = args[1], args[2]
        return int(st.iters), np.asarray(
            qp_objective(data, q, jnp.zeros(q.shape[0]), x))

    it_dot, obj_dot = solve()
    monkeypatch.setattr(qps, "_batched_matvec", _matvec_reduce)
    monkeypatch.setattr(qps, "_batched_rmatvec", _rmatvec_reduce)
    it_red, obj_red = solve()
    assert it_dot > 0
    assert abs(it_red - it_dot) <= check_every
    assert np.abs(obj_red - obj_dot).max() \
        <= 1e-9 * np.abs(obj_dot).max()


def test_trace_time_counter_counts_the_products(tmp_path):
    """In a session, each batched float64 product traced books one
    count under the form this backend lowers it to."""
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((6, 7, 12)))
    x = jnp.asarray(rng.standard_normal((6, 12)))
    obs.configure(out_dir=str(tmp_path), role="f64products")
    try:
        before = obs.counter_value("kernel.f64_products_dot")
        jax.jit(lambda A, x: _ATy(A, _Ax(A, x)))(A, x)
        assert obs.counter_value("kernel.f64_products_dot") == before + 2
        assert obs.counter_value("kernel.f64_products_reduce") == 0
    finally:
        obs.shutdown()
