"""Golden-trajectory regression: fresh wheel runs must reproduce the
checked-in bound quality and stay inside the wall-clock ceiling.

The reference's analog is its checked-in Quartz full-run logs compared
by eye across pushes (ref. examples/uc/quartz/*.baseline.out); here the
goldens are machine-checked: a bound regression (outer drops / inner
rises past its band) or a cadence collapse (wall past ~2.5x the
recorded run) goes red.

Regenerating after an intentional change: run the two wheels exactly as
below, paste the new bounds into tests/golden/wheels.json, and set the
wall ceilings to ~2.5x the fresh measurement.
"""

import json
import os
import time

import numpy as np
import pytest

from mpisppy_tpu.utils import vanilla
from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig
from mpisppy_tpu.utils.sputils import spin_the_wheel

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "golden", "wheels.json")))


def _run(cfg, gap_marks=None):
    hd, sds = vanilla.wheel_dicts(cfg)
    if gap_marks:
        hd["hub_kwargs"]["options"]["gap_marks"] = gap_marks
    t0 = time.perf_counter()
    res = spin_the_wheel(hd, sds)
    return res, time.perf_counter() - t0


def _check(res, wall, g):
    # bound QUALITY must not regress: the outer bound may only rise,
    # the inner only fall, within the wheel's recorded band — tight
    # where the bounds come from deterministic host solves, the
    # gap-termination envelope where async spoke timing decides which
    # candidate lands last (see golden/wheels.json)
    band = g["band"]
    assert res.best_outer_bound >= g["outer"] - band * abs(g["outer"]), \
        f"outer bound regressed: {res.best_outer_bound} < {g['outer']}"
    assert res.best_inner_bound <= g["inner"] + band * abs(g["inner"]), \
        f"inner bound regressed: {res.best_inner_bound} > {g['inner']}"
    assert np.isfinite(res.best_outer_bound)
    assert np.isfinite(res.best_inner_bound)
    assert wall <= g["max_wall_seconds"], \
        f"wheel cadence regressed: {wall:.1f}s > {g['max_wall_seconds']}s"


def test_farmer_wheel_golden():
    cfg = RunConfig(
        model="farmer", num_scens=3,
        algo=AlgoConfig(default_rho=10.0, max_iterations=200,
                        convthresh=-1.0, subproblem_max_iter=4000),
        spokes=[SpokeConfig(kind="lagrangian"),
                SpokeConfig(kind="xhatshuffle")],
        rel_gap=2e-3)
    res, wall = _run(cfg)
    _check(res, wall, GOLDEN["farmer"])


def _uc10_small_cfg(max_iterations):
    """The round-3 small-instance headline wheel (10 gens x 24 h,
    10 scenarios): pure-f32 PH hub + MIP-tight LP-EF-warm-started
    Lagrangian spoke + dual-purpose host EF-MIP spoke. Kept verbatim
    from the r3 bench (which now benches the reference-scale instance)
    so the certified 0.056%-gap circuit cannot rot unnoticed."""
    fast = {"defaultPHrho": 100.0, "subproblem_max_iter": 2000,
            "subproblem_eps": 1e-4, "subproblem_eps_hot": 1e-3,
            "subproblem_eps_dua_hot": 1e-2, "subproblem_stall_rel": 1e-3,
            "subproblem_segment": 2000, "subproblem_polish_hot": False}
    return RunConfig(
        model="uc", num_scens=10,
        model_kwargs={"num_gens": 10, "num_hours": 24,
                      "relax_integrality": False},
        hub="ph",
        algo=AlgoConfig(default_rho=100.0, max_iterations=max_iterations,
                        convthresh=-1.0, subproblem_max_iter=2000,
                        subproblem_eps=1e-6),
        hub_options={**fast, "dtype": "float32", "iter0_feas_tol": 5e-3},
        spokes=[SpokeConfig(kind="lagrangian",
                            options={"dtype": "float64",
                                     "lagrangian_exact_oracle": True,
                                     "lagrangian_mip_oracle": True,
                                     "lagrangian_mip_time_limit": 10.0,
                                     "lagrangian_mip_gap": 1e-4}),
                SpokeConfig(kind="efmip",
                            options={"dtype": "float64",
                                     "efmip_time_limit": 120.0,
                                     "efmip_gap": 1e-5})],
        rel_gap=5e-5)


@pytest.mark.slow
def test_uc10_wheel_golden():
    """The r3 headline wheel (PH hub + MIP-tight warm-started
    Lagrangian + host EF-MIP incumbent on 10-scenario integer UC): the
    certified 0.056% gap and its cadence must not rot."""
    res, wall = _run(_uc10_small_cfg(max_iterations=250),
                     gap_marks=(0.01, 0.005))
    g = GOLDEN["uc10"]
    _check(res, wall, g)
    # both milestone marks must have been crossed in-run
    assert set(res.hub.gap_mark_times) == {0.01, 0.005}
