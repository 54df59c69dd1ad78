"""The farmer widened by ``crops_multiplier`` (every scenario its own
constraint matrix: a per-scenario float64 stack wider than 16) through
the normal path, against the plain reference the benchmark's cell uses
(``benchmarks/reference/farmer_cm_lp.py``: the book's data tiled, the
upstream yield rule, HiGHS), on the library forms this backend lowers
AND on the blocked forms the TPU lowering runs (ISSUE 45).
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "reference"))
import farmer_cm_lp as ref  # noqa: E402

S = 6
HOT = {"subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
       "subproblem_polish_hot": False}


def _on_blocked_forms(monkeypatch, rows_a_chunk=None):
    """What the TPU lowering of a wide stack runs, on this backend: the
    library helpers the per-platform switch falls back to replaced by
    the blocked ones, and the solve entries re-jitted so that no trace
    of another test's is reused. ``rows_a_chunk``: the builds in row
    chunks that small (the cell's stack runs in chunks of 103 rows; at
    S = 6 nothing is chunked unless the test says so), so that a
    rebuild keeps the chunks whose rho did not move."""
    monkeypatch.setattr(qps, "_penalty_factor_library",
                        qps._penalty_factor_blocked)
    monkeypatch.setattr(qps, "_tri_solve", qps._uinv_pair_solve)
    monkeypatch.setattr(
        qps, "_kkt_inverse", lambda A_s: qps._kkt_inverse_blocked)
    if rows_a_chunk:
        monkeypatch.setattr(qps, "_F64_BUILD_BYTES",
                            8 * 48 * 48 * rows_a_chunk)
    for name, fn in _blocked_jits(rows_a_chunk).items():
        monkeypatch.setattr(qps, name, fn)


@functools.lru_cache(maxsize=None)
def _blocked_jits(rows_a_chunk):
    """Entries of their own, used under the patches above only: jax
    keeps a trace by the function a ``jit`` wraps, so the module's own
    entries would hand back another test's library trace; one pair for
    the whole file and chunking, so each shape is traced on the blocked
    forms once."""
    fresh = lambda f: (lambda *a, **k: f(*a, **k))
    return {"_qp_solve_jit": jax.jit(fresh(qps._solve_impl),
                                     static_argnames=qps._SOLVE_STATICS),
            "_cold_state_jit": jax.jit(fresh(qps._cold_state_impl))}


@pytest.fixture(params=["library", "blocked", "blocked_in_chunks"])
def form(request, monkeypatch):
    if request.param != "library":
        _on_blocked_forms(
            monkeypatch, 2 if request.param == "blocked_in_chunks" else None)
    return request.param


def engine(cm, **opts):
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(S),
                        creator_kwargs={"crops_multiplier": cm})
    assert not batch.shared_A and batch.n == 12 * cm > 16
    return PHBase(batch, dict({"subproblem_precision": "native",
                               "defaultPHrho": 1.0}, **HOT, **opts),
                  dtype=jnp.float64)


def step(ph, **kw):
    out = ph.solve_loop(**kw)
    ph.W = ph.W_new
    return np.asarray(out)


@pytest.mark.parametrize("cm", [2, 4])
def test_iter0_objectives_are_the_reference_lps(cm, form):
    """iter-0 (no W, no prox, polished) against HiGHS on the
    reference's own LPs, every scenario; the trivial bound certifies
    their expectation from below."""
    ph = engine(cm)
    obj0 = step(ph, w_on=False, prox_on=False)
    lps = ref.scenario_lps(range(S), cm)
    assert np.abs(obj0 - lps).max() <= 1e-6 * np.abs(lps).max()
    viol = ref.primal_violation(range(S), cm, np.asarray(ph.x))
    assert viol.max() <= 1e-8
    ws = ref.wait_and_see(lps, np.full(S, 1.0 / S))
    assert -1e-4 * abs(ws) <= ph.Ebound() - ws <= 1e-6 * abs(ws)


@pytest.mark.parametrize("cm", [2, 4])
def test_consensus_is_the_references(cm, form):
    """x-bar and conv after two hot iterations against the numpy
    consensus of the engine's own x."""
    ph = engine(cm)
    step(ph, w_on=False, prox_on=False)
    for _ in range(2):
        step(ph, w_on=True, prox_on=True)
    xn = np.asarray(ph.x)[:, np.asarray(ph.nonant_idx)]
    xbar, conv = ref.consensus(xn, np.asarray(ph.prob))
    assert np.abs(np.asarray(ph.xbar)[0] - xbar).max() \
        <= 1e-12 * np.abs(xbar).max()
    assert abs(ph.conv - conv) <= 1e-9 * conv
    assert np.asarray(ph._qp_states[True].pri_rel).max() <= 1e-2


@pytest.mark.parametrize("cm", [2, 4])
def test_ph_goes_to_the_extensive_forms_optimum(cm, form):
    """PH's limit against the reference's extensive form (HiGHS): at a
    rho scaled to the costs, 60 iterations bring the expected objective
    within 5e-3 of the optimum and x-bar's acreage onto the optimal
    plan's total (PH on the farmer converges slowly, as in the
    reference: tests/test_farmer_ph.py)."""
    ef_obj, ef_a = ref.extensive_form(range(S), cm, np.full(S, 1.0 / S))
    ph = engine(cm, defaultPHrho=10.0)
    step(ph, w_on=False, prox_on=False)
    for _ in range(60):
        step(ph, w_on=True, prox_on=True)
    assert ph.conv < 0.5
    eobj = float(np.asarray(ph.prob) @ np.asarray(ph._last_base_obj))
    assert abs(eobj - ef_obj) <= 5e-3 * abs(ef_obj)
    assert abs(np.asarray(ph.xbar)[0].sum() - ef_a.sum()) \
        <= 1e-2 * ef_a.sum()


def test_the_blocked_run_books_refactorizations_inside_the_program(
        monkeypatch):
    """The loop of a wide stack adapts rho INSIDE the one solve program
    (``f64_loop_form`` "conditional"): iter-0's rebuilds are counted by
    ``admm_iters_per_call["refactors"]``, and the plan's eager build of
    the cold state by ``phase_timing()["f64_refactor_build"]``."""
    _on_blocked_forms(monkeypatch)
    ph = engine(2)
    step(ph, w_on=False, prox_on=False)
    t = ph.phase_timing(False)
    assert t["kernel"]["mode"] == "fused"
    assert t["kernel"]["f64_loop"] == "conditional"
    assert t["admm_iters_per_call"]["refactors"] >= 1
    build = t["f64_refactor_build"]
    assert build["builds"] == 1 and build["rows"] == S \
        and build["n"] == 24 and build["seconds"] > 0
