"""Eight three-scenario farmers with cost patches, stacked as
``serve/batch`` stacks a full wheel of the benchmark's served cell
(24 scenarios, per-scenario (7, 12) float64 matrices), taken through
iter-0 and one hot PH pass on the CPU while recording every call of
the native-f64 solve: the operands ``core/ph`` hands
``qp_solver._solve_impl`` (shared by tests/test_f64_products.py and,
through tests/chip_compile_helpers.py, the three
tests/test_chip_compile_stacked_f64*.py)."""

import contextlib

import jax
import numpy as np
import pytest


@contextlib.contextmanager
def recorded_qp_solves():
    """While open, every call of ``_qp_solve_jit`` /
    ``_qp_solve_jit_donated`` is appended to the yielded list as
    ``(args, kwargs)``, array leaves as numpy."""
    import mpisppy_tpu.ops.qp_solver as qps
    calls = []
    mp = pytest.MonkeyPatch()
    for name in ("_qp_solve_jit", "_qp_solve_jit_donated"):
        def wrapper(*a, _fn=getattr(qps, name), **kw):
            calls.append((jax.tree.map(
                lambda v: np.array(v) if hasattr(v, "shape") else v, a),
                dict(kw)))
            return _fn(*a, **kw)
        mp.setattr(qps, name, wrapper)
    try:
        yield calls
    finally:
        mp.undo()


def record_stacked_farmer_calls(stack=8):
    """([(args, kwargs)] of ``_qp_solve_jit`` / ``_qp_solve_jit_donated``
    with array leaves as numpy, the engine's kernel descriptor)."""
    from mpisppy_tpu.core.ph import PH
    from mpisppy_tpu.serve import batch as sbatch
    from mpisppy_tpu.utils.vanilla import build_batch_for

    payload = {"model": "farmer", "num_scens": 3,
               "algo": {"max_iterations": 10}}
    base = build_batch_for(sbatch.base_runconfig(payload))
    rng = np.random.default_rng(20260927)
    stacked, _blocks = sbatch.stack_instances([
        sbatch.apply_patch(base, {"c": {"DevotedAcreage": [
            float(b * rng.uniform(0.9, 1.1)) for b in (150., 230., 260.)]}})
        for _ in range(stack)])
    with recorded_qp_solves() as calls:
        ph = PH(stacked, options=dict(
            sbatch.request_algo(payload).to_options()))
        ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
        plan = ph.phase_timing(True)["kernel"]
    assert calls and calls[0][0][0].A_s.shape == (3 * stack, 7, 12)
    return calls, plan
