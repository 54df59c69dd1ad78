"""SIPLIB's SSLP as published (ISSUE 32) against the benchmark's plain
reference (``benchmarks/reference/sslp_lp.py``: the formulation written
down from the instance's numbers in scipy sparse, solved by HiGHS; it
imports nothing of the program), and the per-run reset
(``PHBase.reset_run``) that lets one warm engine run the instance again
and again."""

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import sslp

REF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference")


@pytest.fixture(scope="module")
def ref():
    """The reference file itself (one copy: the cell's driver loads the
    same one), imported by path as the harness does."""
    sys.path.insert(0, REF_DIR)       # its own ``import scenario_lp``
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_reference_sslp_lp", os.path.join(REF_DIR, "sslp_lp.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(REF_DIR)
    return mod


# (servers, clients, scenarios, capacity): a toy and the published width
SIZES = {"3x8": (3, 8, 6, 60.0), "10x50": (10, 50, 8, 188.0)}


def build(size):
    nS, nC, S, cap = SIZES[size]
    kw = dict(num_servers=nS, num_clients=nC, overflow=True,
              server_budget=nS, capacity=cap, demand_is_revenue=True)
    batch = build_batch(sslp.scenario_creator, sslp.make_tree(S),
                        creator_kwargs=kw,
                        vector_patch=sslp.scenario_vector_patch)
    return batch, kw


def reference_data(ref, size):
    nS, nC, S, cap = SIZES[size]
    inst = ref.instance(nS, nC, 1, cap, nS, 1000.0)
    hs = np.stack([ref.presence(s, nC) for s in range(S)])
    return inst, hs


@pytest.mark.parametrize("size", sorted(SIZES))
def test_batch_equals_the_reference_entry_for_entry(ref, size):
    """Exact: both sides are the same float64 draws placed, never
    computed with."""
    batch, _ = build(size)
    inst, hs = reference_data(ref, size)
    A, c, lb, ub, l0, u0 = ref.matrices(inst)
    assert batch.shared_A
    np.testing.assert_array_equal(A.toarray(), batch.A)
    for s, h in enumerate(hs):
        l, u = ref.rows(inst, h, l0, u0)
        np.testing.assert_array_equal(l, batch.l[s])
        np.testing.assert_array_equal(u, batch.u[s])
        np.testing.assert_array_equal(c, batch.c[s])
        np.testing.assert_array_equal(lb, batch.lb[s])
        np.testing.assert_array_equal(ub, batch.ub[s])
    assert not batch.c0.any() and not batch.P_diag.any()
    np.testing.assert_array_equal(batch.prob, np.full(len(hs),
                                                      1.0 / len(hs)))


@pytest.mark.parametrize("size", sorted(SIZES))
def test_iter0_objectives_against_the_scenario_lps(ref, size):
    """Native float64 ADMM to 1e-8 scaled residuals: the iter-0
    objectives sit within 1e-5 of HiGHS's (readings 2e-8 .. 3e-6: ADMM
    stops at a residual, not at a vertex). The trivial bound, built
    from the duals, is a LOWER bound on sum p LP, and a loose one on
    this family: the overflow columns have no upper bound, so the dual
    certificate pays its witness penalty on whatever reduced cost the
    inexact duals leave there (``qp_dual_objective``; readings 1.5%
    under at 3 x 8, 9.3% at 10 x 50). Held to 15%: a bound from another
    problem's duals is nowhere near."""
    batch, _ = build(size)
    inst, hs = reference_data(ref, size)
    lps = ref.scenario_lps(inst, hs)
    ph = PH(batch, {"defaultPHrho": 1.0, "subproblem_max_iter": 20000,
                    "subproblem_eps": 1e-8})
    obj0 = np.asarray(ph.solve_loop(w_on=False, prox_on=False))
    np.testing.assert_allclose(obj0, lps, rtol=1e-5)
    ws = ref.wait_and_see(lps, batch.prob)
    tb = ph.Ebound()
    assert tb <= ws + 1e-9 * abs(ws)
    assert tb >= ws - 0.15 * abs(ws)


def test_ph_to_convthresh_against_the_relaxed_extensive_form(ref):
    """PH on the LP relaxation converges to the relaxed extensive form:
    at rho 50 it reaches conv < 1e-4 in ~290 iterations, with the
    expected objective within 2e-3 of HiGHS's (reading 9e-4: at conv
    1e-4 the scenarios' x still differ from x-bar by that much, and
    each is optimal for its own W) and x-bar within 2e-3 of the
    extensive form's x (reading 5e-4)."""
    batch, _ = build("3x8")
    inst, hs = reference_data(ref, "3x8")
    ef_obj, ef_x = ref.extensive_form(inst, hs, batch.prob)
    ph = PH(batch, {"defaultPHrho": 50.0, "PHIterLimit": 400,
                    "convthresh": 1e-4, "subproblem_max_iter": 4000,
                    "subproblem_eps": 1e-8})
    conv, eobj, trivial = ph.ph_main()
    assert conv < 1e-4 and ph._iter < 400
    assert eobj == pytest.approx(ef_obj, rel=2e-3)
    np.testing.assert_allclose(np.asarray(ph.xbar)[0], ef_x, atol=2e-3)
    assert trivial <= ef_obj


DF32 = {"subproblem_precision": "df32", "subproblem_max_iter": 60,
        "subproblem_tail_iter": 30, "subproblem_eps": 1e-5,
        "subproblem_eps_hot": 1e-4, "subproblem_hospital": False}


@pytest.mark.parametrize("opts", [
    {"subproblem_max_iter": 300, "subproblem_eps": 1e-6},
    dict(DF32),
    dict(DF32, subproblem_chunk=4),
], ids=["native", "df32_fused_linv", "df32_chunked"])
def test_reset_run_repeats_the_first_run_bit_for_bit(opts):
    """``reset_run()`` then a second ``ph_main`` on the warm engine:
    every iterate of the first run again, exactly (same programs, same
    operands, nothing of the first run left), and ``phase_timing``
    counts both runs."""
    batch, _ = build("3x8")
    # budgets of a few dozen ADMM iterations: what is compared is the
    # repeat, not the solves' quality (iter0_feas_tol 1: no abort)
    ph = PH(batch, dict(opts, defaultPHrho=5.0, PHIterLimit=6,
                        convthresh=0.0, iter0_feas_tol=1.0),
            dtype=jnp.float64)
    trail = []
    real = ph.solve_loop

    def recording(*a, **kw):
        out = real(*a, **kw)
        trail.append((np.asarray(ph.x).copy(), np.asarray(ph.xbar).copy(),
                      np.asarray(ph.W_new).copy(), ph.conv))
        return out

    ph.solve_loop = recording
    first = ph.ph_main()
    n1 = len(trail)
    ph.reset_run()
    assert ph.x is None and ph.conv is None and ph._iter == 0 \
        and not ph._qp_states and not hasattr(ph, "trivial_bound")
    second = ph.ph_main()
    assert n1 == 7 and len(trail) == 14
    assert first == second
    for a, b in zip(trail[:n1], trail[n1:]):
        for u, v in zip(a[:3], b[:3]):
            np.testing.assert_array_equal(u, v)
        assert a[3] == b[3]
    runs = ph.phase_timing(True)["runs"]
    assert runs["count"] == 2 and runs["seconds"] > 0
    assert 0 < runs["reset_seconds"] < runs["seconds"]
    ph.reset_phase_timing()
    assert ph._run_times == {"count": 0, "seconds": 0.0,
                             "reset_seconds": 0.0}


def test_reset_run_after_a_rho_change_rebuilds_the_prox_factors():
    """A rho updater moved rho (``invalidate_factors``): the next run
    starts from the constructed rho again, so the prox factors built at
    the moved rho must go; without a rho change they stay."""
    batch, _ = build("3x8")
    ph = PH(batch, {"defaultPHrho": 5.0, "PHIterLimit": 2,
                    "convthresh": 0.0, "subproblem_max_iter": 200,
                    "iter0_feas_tol": 1.0})
    first = ph.ph_main()
    fac = ph._factors[True][0]
    ph.reset_run()
    assert ph._factors[True][0] is fac
    ph.rho = ph.rho * 3.0
    ph.invalidate_factors()
    ph.solve_loop(w_on=True, prox_on=True)
    ph.reset_run()
    assert True not in ph._factors and float(ph.rho[0, 0]) == 5.0
    assert ph.ph_main() == first


def test_normal_path_runs_the_configuration(ref):
    """``__main__.run(RunConfig)`` (what ``python -m mpisppy_tpu sslp``
    builds) with the benchmark configuration's instance kwargs and
    recipe as ``hub_options``, at 12 scenarios and 3 iterations: the
    vector-patch build, hub-only ``PH`` / ``ph_main``, the fused plan
    with the explicit inverse chosen by ``auto``, and an outer bound
    under the wait-and-see value of the same 12 scenarios."""
    import json

    import mpisppy_tpu.utils.sputils as sputils
    from mpisppy_tpu.__main__ import run
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig

    cfg = json.load(open(os.path.join(os.path.dirname(REF_DIR), "configs",
                                      "sslp_10_50_df32.json")))
    seen = {}
    spin = sputils.spin_the_wheel

    def spin_and_keep(*a, **kw):
        seen["wheel"] = spin(*a, **kw)
        return seen["wheel"]

    sputils.spin_the_wheel = spin_and_keep
    try:
        out = run(RunConfig(
            model="sslp", num_scens=12, model_kwargs=cfg["instance"],
            algo=AlgoConfig(default_rho=cfg["recipe"]["defaultPHrho"],
                            max_iterations=3, convthresh=0.0),
            hub_options=dict(cfg["recipe"], PHIterLimit=3)))
    finally:
        sputils.spin_the_wheel = spin
    ph = seen["wheel"].hub.opt
    assert (ph.batch.S, ph.batch.n, ph.batch.m, ph.batch.K) == \
        (12, cfg["shape"]["n"], cfg["shape"]["m"],
         cfg["shape"]["binary_nonants"])
    assert ph.batch.shared_A and ph._iter == 3
    pt = ph.phase_timing(True)
    assert pt["kernel"] == dict(cfg["kernel"], backend="reference",
                                block_dtype="f32", f64_products=None,
                                f64_polish=None, f64_refactor=None,
                                f64_loop=None, f64_stack_block=None)
    assert pt["runs"]["count"] == 1
    ik = cfg["instance"]
    inst = ref.instance(ik["num_servers"], ik["num_clients"],
                        ik["base_seed"], ik["capacity"],
                        ik["server_budget"], cfg["overflow_penalty"])
    hs = np.stack([ref.presence(s, ik["num_clients"]) for s in range(12)])
    ws = ref.wait_and_see(ref.scenario_lps(inst, hs), ph.batch.prob)
    assert out["outer_bound"] <= ws
    assert out["outer_bound"] >= ws - 0.15 * abs(ws)
