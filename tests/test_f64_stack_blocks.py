"""The stacked native-float64 ADMM loop in BLOCKS of scenarios (ISSUE 46;
``ops/qp_solver.py``: ``f64_stack_block_rows``, ``_solve_impl.admm_chunk``;
doc/kernels.md §3i): between two residual checks the scenarios are
independent, so a wide per-scenario stack walked a block at a time gives
the whole-stack scan's iterates. Held here on the CPU, with the budget
that sizes a block made small: the farmer at ``crops_multiplier`` 2
(n = 24 > 16, m = 13) over 8 scenarios, blocked at 2 and 4 rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer

S = 8


@pytest.fixture(scope="module")
def stack():
    """(factors, data, q, cold state) of the hot mode: the program's own
    scaled per-scenario factors, the cost as the first hot solve sees it
    before W moves."""
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(S),
                        creator_kwargs={"crops_multiplier": 2})
    ph = PHBase(batch, {"subproblem_precision": "native",
                        "defaultPHrho": 1.0}, dtype=jnp.float64)
    factors, data = ph._get_factors(True)
    assert factors.A_s.shape == (S, 13, 24) \
        and factors.A_s.dtype == jnp.float64
    return factors, data, ph.c, qps.qp_cold_state(factors, data)


def row_bytes(A_s):
    _, m, n = A_s.shape
    return 8 * (m * n + n * n)


def solve(stack, monkeypatch, rows, **kw):
    """``_solve_impl`` with the budget at ``rows`` rows of the stack
    (None: the module's own, under which this stack is one scan and
    nothing is patched), a trace of its own each time: the rule is read
    while tracing."""
    factors, data, q, st = stack
    if rows is not None:
        monkeypatch.setattr(qps, "_F64_LOOP_BLOCK_BYTES",
                            rows * row_bytes(factors.A_s))
    assert qps.f64_stack_block_rows(factors.A_s) == rows

    def impl(factors, data, q, state, **k):
        return qps._solve_impl(factors, data, q, state, **k)
    fn = jax.jit(impl, static_argnames=qps._SOLVE_STATICS)
    st, x, yA, yB = fn(factors, data, q, st, polish=False, **kw)
    return (int(st.iters), int(st.refactors),
            {k: np.asarray(v) for k, v in
             dict(x=x, yA=yA, yB=yB, zA=st.zA, zB=st.zB,
                  rho_scale=st.rho_scale, L=st.L).items()})


# with a rho move inside the solve (a cold start's first period ends at
# iteration 100 and moves rows), and without one (three checks: the
# adaptation's fourth never comes)
BUDGETS = {"rho_moves": dict(max_iter=400, eps_abs=1e-9, eps_rel=1e-9),
           "rho_stays": dict(max_iter=75, eps_abs=1e-9, eps_rel=1e-9)}


@pytest.fixture(scope="module")
def whole(stack):
    return {k: solve(stack, None, None, **kw) for k, kw in BUDGETS.items()}


@pytest.mark.parametrize("budget", sorted(BUDGETS))
@pytest.mark.parametrize("rows", [2, 4])
def test_blocked_iterates_are_the_whole_stacks(stack, whole, monkeypatch,
                                               rows, budget):
    """Same ``iters``, same ``refactors``; x, y, z, the rho scales and
    the resident inverse equal to 1e-13 of their scale."""
    it_w, ref_w, out_w = whole[budget]
    it_b, ref_b, out_b = solve(stack, monkeypatch, rows, **BUDGETS[budget])
    assert it_w == BUDGETS[budget]["max_iter"]
    assert (ref_w > 0) is (budget == "rho_moves")
    assert (it_b, ref_b) == (it_w, ref_w)
    for k, a in out_w.items():
        assert np.abs(out_b[k] - a).max() <= 1e-13 * np.abs(a).max(), k


def test_a_blocked_solve_converges_where_the_whole_one_does(
        stack, monkeypatch):
    """The exit tests see all rows at once in both forms: a solve left
    to converge stops at the same check."""
    kw = dict(max_iter=4000, eps_abs=1e-5, eps_rel=1e-5)
    it_w, ref_w, out_w = solve(stack, None, None, **kw)
    it_b, ref_b, out_b = solve(stack, monkeypatch, 4, **kw)
    assert 0 < it_w < 4000 and (it_b, ref_b) == (it_w, ref_w)
    assert np.abs(out_b["x"] - out_w["x"]).max() \
        <= 1e-13 * np.abs(out_w["x"]).max()


def test_trace_time_counter_and_descriptor_say_it_engaged(
        stack, monkeypatch, tmp_path):
    """In a session ``kernel.f64_stack_blocked`` counts the ADMM scans
    traced block by block (one a traced ``_solve_impl`` here), and the
    plan's descriptor names the rows of a block."""
    from mpisppy_tpu.ops import kernels
    factors = stack[0]
    assert kernels.prepare(factors).descriptor()["f64_stack_block"] is None
    obs.configure(out_dir=str(tmp_path), role="f64stackblocks")
    try:
        before = obs.counter_value("kernel.f64_stack_blocked")
        solve(stack, monkeypatch, 2, max_iter=25)
        assert obs.counter_value("kernel.f64_stack_blocked") == before + 1
        monkeypatch.undo()
        solve(stack, monkeypatch, None, max_iter=25)
        assert obs.counter_value("kernel.f64_stack_blocked") == before + 1
    finally:
        obs.shutdown()
    monkeypatch.setattr(qps, "_F64_LOOP_BLOCK_BYTES",
                        4 * row_bytes(factors.A_s))
    assert kernels.prepare(factors).descriptor()["f64_stack_block"] == 4
