"""The refactorization of small per-scenario float64 KKTs on the device
(ops/qp_solver ``_factorize``'s non-shared f64 branch, ISSUE 42): the
explicit inverse by the polish's unrolled recurrences
(``_kkt_inverse_unrolled``), the ONE rule that says where the host is
still needed (``f64_refactor_form`` / ``_needs_host_factor`` /
``kernels.resolve_mode``), and a served farmer wheel on the new path.
The form is picked per platform at lowering time
(``jax.lax.platform_dependent``), so here, on the CPU, ``_factorize``
keeps the library pair; the unrolled form is called directly, and a
whole wheel is steered onto it by handing the solver the unrolled
helper in place of the library one
(tests/test_chip_compile_stacked_f64_loop.py holds what the TPU
compiler makes of the solve program)."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu import obs
from mpisppy_tpu.ops import kernels
from mpisppy_tpu.ops.qp_solver import (_POLISH_UNROLL_MAX_N, _factorize,
                                       _kkt_host, _kkt_inverse_unrolled,
                                       _needs_host_factor,
                                       f64_refactor_form)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------- the unrolled explicit inverse ------------------------

@pytest.fixture(scope="module")
def stack_factors():
    """The scaled factors of a full served stack (eight three-scenario
    farmers: A_s (24, 7, 12) float64), as numpy."""
    from stacked_farmer import record_stacked_farmer_calls
    calls, _plan = record_stacked_farmer_calls()
    fac = calls[-1][0][0]
    assert fac.A_s.shape == (24, 7, 12) and fac.A_s.dtype == np.float64
    return fac


def _at_width(fac, n):
    """The stack's factors at another column count: the first n columns,
    or, past the farmer's 12, its columns again at other magnitudes
    (every vector over columns goes the same way)."""
    take = np.arange(n) % 12
    gain = 1.0 + 0.37 * (np.arange(n) // 12)
    cols = lambda a: np.asarray(a)[..., take] * gain
    return fac._replace(A_s=cols(fac.A_s), P_s=cols(fac.P_s), D=cols(fac.D),
                        Eb=cols(fac.Eb), rho_b=cols(fac.rho_b))


@pytest.mark.parametrize("n", [7, 12, 16])
@pytest.mark.parametrize("rho_scale", [1e-6, 1e-3, 1.0, 1e3, 1e6])
def test_unrolled_inverse_equals_numpy_on_farmer_stack_kkts(stack_factors,
                                                            rho_scale, n):
    """At every rho the adaptation's clip allows, the inverse the TPU
    lowering builds is numpy's to rounding, and far inside the bar the
    x-update's thousands of applies need (|M·M⁻¹ − I|max <= 1e-9, the
    host path's): the equilibrated KKTs have cond <= ~10 there."""
    fac = _at_width(stack_factors, n)
    S = fac.A_s.shape[0]
    rs = np.full((S,), rho_scale)
    M = _kkt_host(fac, rs)
    ref = np.linalg.inv(M)
    g = fac.Eb * fac.D
    X = np.asarray(_kkt_inverse_unrolled(
        jnp.asarray(fac.A_s), jnp.asarray(fac.rho_A * rs[:, None]),
        fac.sigma, jnp.asarray(fac.P_s + g * g * fac.rho_b * rs[:, None])))
    assert X.shape == (S, n, n) and X.dtype == np.float64
    eye = np.eye(n)
    assert np.abs(M @ X - eye).max() <= 1e-12
    assert np.abs(X @ M - eye).max() <= 1e-12
    assert np.abs(X - ref).max() <= 1e-12 * np.abs(ref).max()
    np.testing.assert_array_equal(X, X.swapaxes(1, 2))     # L⁻ᵀL⁻¹
    # and what _factorize itself lowers to on this backend (the library
    # pair, through the switch at n <= 16) is the same inverse
    lib = np.asarray(_factorize(jax.tree.map(jnp.asarray, fac),
                                jnp.asarray(rs)))
    assert np.abs(lib - X).max() <= 1e-12 * np.abs(ref).max()


def test_the_switch_keeps_the_library_pair_off_the_tpu(stack_factors,
                                                       monkeypatch):
    """On this backend ``_factorize`` at a width the TPU would unroll IS
    the matmul, ``jnp.linalg.cholesky`` and ``triangular_solve`` pair it
    was, bit for bit: the same call with the switch taken out of the
    way gives an equal inverse."""
    fac = jax.tree.map(jnp.asarray, stack_factors)
    rs = jnp.asarray(np.geomspace(1e-3, 1e3, fac.A_s.shape[0]))
    assert qps._polish_unrollable(fac.A_s)
    through_switch = np.asarray(jax.jit(
        lambda f, r: _factorize(f, r))(fac, rs))
    monkeypatch.setattr(qps, "_tpu_stack_form", lambda A_s: None)
    library_only = np.asarray(jax.jit(
        lambda f, r: _factorize(f, r))(fac, rs))
    np.testing.assert_array_equal(through_switch, library_only)


# ---------------- the rule ---------------------------------------------

# (backend, ndim, dtype, n) -> (f64_refactor_form, _needs_host_factor,
# resolve_mode("auto"), f64_loop_form). "metal" stands for a backend
# nobody measured. The loop's shape is read from ndim, dtype and n
# alone; the backend only takes the answer away where it sends the
# rebuild to the host, so that no program holds one.
_F8, _F4 = "float64", "float32"
_RES, _CND = "resident", "conditional"
_RULE = [
    ("tpu", 3, _F8, 12, "unrolled", False, "fused", _RES),
    ("tpu", 3, _F8, 16, "unrolled", False, "fused", _RES),
    ("tpu", 3, _F8, 17, "blocked", False, "fused", _CND),
    ("tpu", 3, _F8, 384, "blocked", False, "fused", _CND),
    ("tpu", 3, _F8, 768, "blocked", False, "fused", _CND),
    # the hospital's UC-width batch: too large to rebuild on the device
    ("tpu", 3, _F8, 13056, "host", True, "segmented", None),
    ("tpu", 2, _F8, 12, "library", False, "fused", _CND),
    ("tpu", 2, _F8, 768, "library", False, "fused", _CND),
    ("tpu", 3, _F4, 12, None, False, "fused", None),
    ("tpu", 3, _F4, 768, None, False, "fused", None),
    ("tpu", 2, _F4, 16, None, False, "fused", None),
    ("cpu", 3, _F8, 12, "library", False, "fused", _RES),
    ("cpu", 3, _F8, 16, "library", False, "fused", _RES),
    ("cpu", 3, _F8, 17, "library", False, "fused", _CND),
    ("cpu", 3, _F8, 768, "library", False, "fused", _CND),
    ("cpu", 2, _F8, 768, "library", False, "fused", _CND),
    ("cpu", 3, _F4, 12, None, False, "fused", None),
    ("metal", 3, _F8, 12, "host", True, "segmented", None),
    ("metal", 2, _F8, 12, "library", False, "fused", _CND),
]


@pytest.mark.parametrize(
    "backend,ndim,dtype,n,form,host,mode,loop", _RULE,
    ids=[f"{b}-{d}d-{t}-n{n}" for b, d, t, n, *_ in _RULE])
def test_the_rule_by_backend_ndim_dtype_and_n(monkeypatch, backend, ndim,
                                              dtype, n, form, host, mode,
                                              loop):
    """ONE rule, read from shapes, dtype and platform alone: on the TPU
    a per-scenario float64 stack is inverted by the unrolled forms to
    n = 16 and by the blocked ones above (ISSUE 45); the host inverts
    only a stack too large to rebuild on the device (or on a backend
    nobody measured); those and only those solve in host-driven
    segments under ``auto``; and the
    loop that adapts rho inside a program takes its shape from ndim,
    dtype and n (``f64_loop_form``, what the plan's descriptor tells)."""
    assert _POLISH_UNROLL_MAX_N == 16
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    m = max(1, (7 * n) // 12)
    A_s = jax.ShapeDtypeStruct((24, m, n)[3 - ndim:], jnp.dtype(dtype))
    fac = qps.QPFactors(*[None] * len(qps.QPFactors._fields)) \
        ._replace(A_s=A_s)
    assert f64_refactor_form(A_s) == form
    assert _needs_host_factor(fac) is host
    assert kernels.resolve_mode("auto", fac) == mode
    assert kernels.resolve_mode("segmented", fac) == "segmented"
    assert kernels.resolve_mode("fused", fac) == "fused"
    assert qps.f64_loop_form(A_s) == loop
    assert kernels.prepare(fac).descriptor()["f64_loop"] == loop


def test_a_split_matrix_has_no_float64_inverse(monkeypatch):
    split = qps.split_f32(jnp.ones((7, 12)))
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert f64_refactor_form(split) is None
    plan = kernels.KernelPlan(mode="fused").descriptor()
    assert plan["f64_refactor"] is None and plan["f64_loop"] is None


# ---------------- the loop's two shapes (ISSUE 43) ---------------------

@pytest.fixture(scope="module")
def stack_calls():
    """The recorded solves of the full served stack: iter-0's (cold) and
    the hot pass's, as ``(args, kwargs)`` of ``_solve_impl``."""
    from stacked_farmer import record_stacked_farmer_calls
    calls, plan = record_stacked_farmer_calls()
    # what the engine's ``phase_timing()["kernel"]`` says of its loop
    assert plan["f64_loop"] == "resident" and plan["mode"] == "fused"
    return [(a, {k: v for k, v in kw.items() if k != "_segmented_caller"})
            for a, kw in (calls[0], calls[-1])]


def _eps(e):
    return dict(eps_abs=e, eps_rel=e, eps_abs_dua=e, eps_rel_dua=e)


# (recorded call, keywords over the recorded ones, the check of its
# four-check period the solve must END on (None: not asked)). Budgets
# that end mid-period (75, 250), at a period's fourth check with rho
# moving there (400) and the recorded one; tolerances at which the cold
# solve converges at a period's first, second and fourth check (its
# residuals fall 1.2-1.5x a check there, the tolerance between two);
# the stall window on (the cold solve then leaves through it at 1,300
# iterations for 5,000, after twelve rho moves that each reset it);
# and the whole program, polish included.
_LOOP_CASES = [
    (0, dict(max_iter=75), None), (0, dict(max_iter=250), None),
    (0, dict(max_iter=400), 3), (0, {}, None),
    (1, dict(max_iter=75), None), (1, dict(max_iter=250), None),
    (1, dict(max_iter=400), 3), (1, {}, None),
    (0, _eps(2.2e-4), 0), (0, _eps(4.7e-5), 1), (0, _eps(2.4e-5), 3),
    (0, dict(stall_rel=1e-2), None), (1, dict(stall_rel=1e-2), None),
    (1, dict(polish=True), None),
]


@pytest.mark.parametrize(
    "call,over,ends_on", _LOOP_CASES,
    ids=[f"call{c}-" + ("-".join(f"{k}={v}" for k, v in o.items()
                                 if not k.endswith(("_rel", "_dua"))
                                 or k == "stall_rel") or "recorded")
         for c, o, _ in _LOOP_CASES])
def test_the_two_level_loop_equals_the_conditional_loop(
        stack_calls, monkeypatch, call, over, ends_on):
    """The loop that rebuilds the inverse once a period between two
    inner loops (``f64_loop_form`` "resident", what a per-scenario
    float64 stack at n <= 16 traces on every backend) against the
    one-level loop with the rebuild under a ``lax.cond`` (what every
    other factor form keeps, traced here by answering for one): the
    same checks, exits and rebuilds, so every output is equal bit for
    bit."""
    args, kw = stack_calls[call]
    kw = {**kw, "adaptive_rho": True, "polish": False, **over}

    def solve():
        def impl(factors, data, q, state, **k):     # a trace of its own
            return qps._solve_impl(factors, data, q, state, **k)
        fn = jax.jit(impl, static_argnames=qps._SOLVE_STATICS)
        loops = fn.lower(*args, **kw).as_text().count("stablehlo.while")
        return fn(*args, **kw), loops

    assert qps.f64_loop_form(args[0].A_s) == "resident"
    two, loops_two = solve()
    monkeypatch.setattr(qps, "f64_loop_form", lambda A_s: "conditional")
    one, loops_one = solve()
    # the loop, the period inside it (two-level only) and the ADMM scan
    assert loops_two - loops_one == 1
    st2, st1 = two[0], one[0]
    # iters, refactors, L, rho_scale and the iterates among the fields
    for name, a, b in [("x", two[1], one[1]), ("yA", two[2], one[2]),
                       ("yB", two[3], one[3])] + [
            (f, getattr(st2, f), getattr(st1, f)) for f in st2._fields]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    iters, budget = int(st2.iters), kw["max_iter"]
    if "max_iter" in over:
        assert iters == budget
    if ends_on is not None:
        assert (iters // kw["check_every"] - 1) % 4 == ends_on
        if "eps_abs" in over:
            assert iters < budget and int(st2.refactors) > 0
    if over.get("stall_rel") and call == 0:
        # left through the stall window, with rho moves behind it
        assert iters < budget and int(st2.refactors) > 0


# ---------------- a served wheel on the unrolled refactorization -------

def _cell_limits():
    import json
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           "farmer3_serve_c8.json"), encoding="utf-8") as f:
        return json.load(f)["limits"]


def _wait(svc, rid, timeout=300):
    t0 = time.time()
    while time.time() - t0 < timeout:
        rec = svc.result(rid)
        if rec and rec["status"] in ("done", "failed"):
            return rec
        time.sleep(0.05)
    raise TimeoutError(f"{rid}: {svc.result(rid)}")


def _served(tmp_path, tag, costs):
    """One stacked wheel of ``len(costs)`` patched farmers and the solo
    re-send of each, through the manager: (stacked objectives, solo
    objectives, solo outer bounds, the kernel descriptors of the plans
    the engines prepared)."""
    from mpisppy_tpu.serve.manager import ServeService
    from mpisppy_tpu.utils.config import ServeConfig
    plans = []
    prepare = kernels.prepare

    def spy(factors, **kw):
        plan = prepare(factors, **kw)
        plans.append(plan.descriptor())
        return plan
    kernels.prepare = spy
    svc = ServeService(ServeConfig(
        state_dir=str(tmp_path / tag), batch_window=1.0,
        batch_max=len(costs)).validate()).start()
    try:
        pay = [{"model": "farmer", "num_scens": 3,
                "algo": {"max_iterations": 10},
                "patch": {"c": {"DevotedAcreage": list(c)}}}
               for c in costs]
        recs = [_wait(svc, t.id) for t in [svc.submit(p) for p in pay]]
        assert all(r["status"] == "done" for r in recs), recs
        assert {r["result"]["wheel"]["stack"] for r in recs} == {len(costs)}
        solos = [_wait(svc, svc.submit({**p, "batchable": False}).id)
                 for p in pay]
        assert all(r["status"] == "done" for r in solos), solos
    finally:
        kernels.prepare = prepare
        svc.stop()
    return ([r["result"]["objective"] for r in recs],
            [r["result"]["objective"] for r in solos],
            [r["result"]["wheel"]["outer_bound"] for r in solos], plans)


def test_served_wheel_on_the_unrolled_refactorization(tmp_path,
                                                      monkeypatch):
    """The same stacked wheel and solo re-sends twice through the
    manager: on the library pair this backend keeps, and as the TPU
    runs them since ISSUE 42 (the rule answering for a TPU, the
    unrolled inverse in ``_factorize``'s place): ONE fused program a
    solve with rho adapting inside it, no host refactorization. Every
    answer holds the served cell's four limits
    (benchmarks/workloads/farmer3_serve_c8.json) against the HiGHS
    extensive form and against the library path's answer."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "reference"))
    import farmer_ef
    rng = np.random.default_rng(20260927)
    costs = [[float(b * rng.uniform(0.9, 1.1)) for b in (150., 230., 260.)]
             for _ in range(3)]
    lib = _served(tmp_path, "library", costs)
    assert {p["f64_refactor"] for p in lib[3]} == {"library"}
    # the loop's shape is read from the operand alone: the same on both
    assert {p["f64_loop"] for p in lib[3]} == {"resident"}

    jax.clear_caches()      # the traces above hold the library pair
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qps, "_kkt_inverse_library", _kkt_inverse_unrolled)
    obs.configure(out_dir=None)
    try:
        unr = _served(tmp_path, "unrolled", costs)
        assert obs.counter_value("qp.host_rho_refactors") == 0
        assert obs.counter_value("kernel.f64_refactor_unrolled") > 0
        assert obs.counter_value("kernel.f64_loop_resident") > 0
        assert obs.counter_value("kernel.f64_loop_conditional") == 0
        assert obs.counter_value("kernel.factor_prepares") > 0
    finally:
        obs.shutdown()
        monkeypatch.undo()
        jax.clear_caches()  # nor may a later test inherit these traces
    assert unr[3] and all(p["f64_refactor"] == "unrolled"
                          and p["f64_loop"] == "resident"
                          and p["mode"] == "fused" for p in unr[3])

    _LIMITS = _cell_limits()
    for c, obj, solo, outer, obj_l, solo_l, outer_l in zip(
            costs, *unr[:3], *lib[:3]):
        ef = farmer_ef.ef_optimum(np.asarray(c))
        for o in (obj, solo):
            assert -(o - ef) / abs(ef) <= _LIMITS["objective_below_ef"]
            assert (o - ef) / abs(ef) <= _LIMITS["objective_above_ef"]
        assert (outer - ef) / abs(ef) <= _LIMITS["outer_bound_above_ef"]
        lim = _LIMITS["solo_vs_stacked_objective"]
        assert abs(obj - solo) <= lim * abs(solo)
        # against the library path's answers to the same requests
        assert abs(obj - obj_l) <= lim * abs(obj_l)
        assert abs(solo - solo_l) <= lim * abs(solo_l)
        assert abs(outer - outer_l) <= lim * abs(outer_l)
