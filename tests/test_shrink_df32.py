"""The df32 compacted gather (ISSUE 17): a df32 compacted wheel keeps
the SplitMatrix layout at the compacted width and reproduces the
full-width df32 trajectory, on one device and on 2/4-device sharded
meshes. Progressive shrinking itself, and the UC batch and options
shared with it: tests/test_shrink.py."""

import numpy as np
import pytest

from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.parallel.mesh import make_mesh

from test_shrink import UC_OPTS, uc_batch
from test_shrink import telemetry  # noqa: F401  (fixture by name)


# Solver-grade df32, NOT the bench recipe's budget caps (tail 100-150,
# stall exit at 1.5e-3): at those caps the df32 solves of this toy sit
# at their ~1e-3 residual floor, and which slots reach the fixer's 1e-2
# consensus band within 10 iterations is decided by f32 rounding order
# — measured under jax 0.9: 2..29 of 36 slots fixed across 1e-7..1e-4
# cost perturbations and across arithmetically equivalent paths (LInv
# vs triangular solves agree per call to the solve tolerance, the PH
# trajectories built on them do not). With the tail given its budget
# (1500, no stall exit) the full-width, compacted and sharded
# trajectories are reproducible to the bands below, so these tests
# exercise the compacted df32 gather, not the rounding of the day.
DF32_OPTS = dict(UC_OPTS, subproblem_precision="df32",
                 subproblem_eps=1e-5, subproblem_eps_hot=1e-4,
                 subproblem_eps_dua_hot=1e-2,
                 subproblem_stall_rel=0.0,
                 subproblem_tail_iter=1500)


def test_df32_compacted_roundtrip_matches_fullwidth(telemetry):
    """ISSUE 17 tentpole: the compacted gather understands the df32
    SplitMatrix layout — a df32 compacted wheel reproduces the
    full-width df32 trajectory (and the certified dual bound through
    the fold) instead of silently falling back to full width or f64."""
    from mpisppy_tpu.ops.qp_solver import SplitMatrix

    rec, tmp = telemetry
    ph0 = PH(uc_batch(6, 3, 6), dict(DF32_OPTS))
    ph0.ph_main()
    o = dict(DF32_OPTS, shrink_compact=True, shrink_buckets="0.1,0.5")
    ph1 = PH(uc_batch(6, 3, 6), o)
    ph1.ph_main()
    st = ph1._shrink_status
    assert st["compactions"] >= 1
    assert st["n_cols"] < ph1.batch.n
    # the compacted factors keep the df32 split layout at the
    # compacted width (the tentpole: no full-width bypass, no f64
    # promotion)
    factors, data = ph1._get_factors(True)
    A = getattr(data.A, "A_s", data.A)   # unwrap the Ruiz ScaledView
    assert isinstance(A, SplitMatrix)
    assert data.lb.shape[-1] == ph1._shrink.n_c < ph1.batch.n
    # trajectory equivalence at the df32 grade: each inexact solve
    # lands O(df32 gate) off per iteration and the compacted system is
    # a different XLA program (different f32 rounding order), so the
    # bands are the df32 suite's, not the f64 round-trip's 1e-8 pins
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar),
                               rtol=1e-2, atol=1e-2)
    assert ph1.Eobjective_value() == pytest.approx(
        ph0.Eobjective_value(), rel=2e-2)
    # certified dual bound through the compacted df32 dual machinery
    # (ScaledView AᵀyA unscaling, sup rows on the shifted compacted
    # bounds, the fold constant). The two engines' prox-off solves
    # land at DIFFERENT dual points — the bound-vs-bound band is
    # convergence quality, not fold arithmetic (the f64 farmer
    # round-trip above pins the fold exactly, with nonzero folded
    # values; this fixture's fixed generators all sit at 0). The
    # assertions here are validity (a true lower bound) and sanity
    # (same order as the full-width reference — a mis-unscaled AᵀyA
    # or dropped rhs-shift lands orders of magnitude off, like the
    # unconverged full-width f64 UC bound at -6.5e7)
    ph0.solve_loop(w_on=True, prox_on=False, update=False)
    ph1.solve_loop(w_on=True, prox_on=False, update=False)
    e0, e1 = ph0.Ebound(), ph1.Ebound()
    obj = ph1.Eobjective_value()
    assert e1 <= obj * (1 + 1e-6)
    assert abs(e1 - e0) <= 0.2 * abs(e0)
    # full-width state for every consumer after the detour
    ph1.solve_loop(w_on=True, prox_on=True)
    assert np.asarray(ph1.x).shape[1] == ph1.batch.n


@pytest.mark.parametrize("ndev", [2, 4])
def test_df32_compacted_sharded_mesh_matches_single_device(ndev):
    """df32 compaction under scenario-axis sharding: the sharded
    compacted df32 wheel tracks the single-device compacted df32 wheel
    (collective reduction reorderings on f32 statistics widen the
    bands versus the f64 sharded test)."""
    opts = dict(DF32_OPTS, shrink_compact=True,
                shrink_buckets="0.1,0.5")
    opts.pop("subproblem_chunk")
    ph0 = PH(uc_batch(8, 3, 6), dict(opts))
    ph0.ph_main()
    ph1 = PH(uc_batch(8, 3, 6), dict(opts), mesh=make_mesh(ndev))
    ph1.ph_main()
    assert ph1._shrink_status["compactions"] >= 1
    assert ph1._shrink_status["n_cols"] \
        == ph0._shrink_status["n_cols"]
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar), atol=5e-2)
    assert ph1.Eobjective_value() == pytest.approx(
        ph0.Eobjective_value(), rel=2e-2)
