"""The sharded chunked df32 hot loop as the four-chip deployment runs it
(``benchmarks`` cell ``uc_s1024_mesh4_hub_hot``), on four virtual CPU
devices at a small seeded UC size: the fused chunk program compiles
ONCE across iter-0 and the hot passes, the consensus reduce is the exact
numpy mean of the gathered rows, the trajectory tracks a single-device
run with the same chunk composition, and the cross-chip combines are
counted beside the seconds with no telemetry session."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.ir.tree import two_stage_tree
from mpisppy_tpu.models import uc
from mpisppy_tpu.parallel.mesh import ShardedScenarioOps, make_mesh

S, NDEV, LC = 16, 4, 2          # shard 4 rows, two chunk solves a pass
# 3 generators x 7 hours: a width no other test of the suite solves, so
# this file's compile log is not emptied by a worker's warm jit cache
INSTANCE = {"num_gens": 3, "num_hours": 7, "relax_integrality": False,
            "min_up_down": True, "ramping": True, "t0_state": True,
            "startup_shutdown_ramps": True}
# the benchmark configuration's recipe (bench.DF32) with a shorter tail
OPTS = {"defaultPHrho": 100.0, "subproblem_precision": "df32",
        "subproblem_max_iter": 400, "subproblem_eps": 1e-5,
        "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
        "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 100,
        "subproblem_segment": 100, "subproblem_segment_lo": 400,
        "subproblem_polish_hot": False, "subproblem_hospital": False}
# the sharded chunk ci holds local rows [ci*LC, (ci+1)*LC) of EVERY
# shard; a single-device run over this order with chunks of NDEV*LC
# contiguous rows solves the same microbatches in the same row order
PERM = np.concatenate([d * (S // NDEV) + ci * LC + np.arange(LC)
                       for ci in range(S // NDEV // LC)
                       for d in range(NDEV)])
HOT = 2
FUSED = "_fused_mixed_impl"


def _batch(order):
    tree = two_stage_tree([f"scen{int(i)}" for i in order],
                          nonant_names=["u", "st"])
    return build_batch(uc.scenario_creator, tree, creator_kwargs=INSTANCE,
                       vector_patch=uc.scenario_vector_patch)


def _hot(ph):
    ph.solve_loop(w_on=True, prox_on=True)
    ph.W = ph.W_new
    jax.block_until_ready(ph.x)


def _trajectory(ph, log=None):
    """iter-0 and HOT hot passes; per hot pass (xbar, W, conv) and the
    compile log's entry names seen so far."""
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    traj = []
    for _ in range(HOT):
        _hot(ph)
        traj.append((np.asarray(ph.xbar).copy(), np.asarray(ph.W).copy(),
                     float(ph.conv), None if log is None else list(log)))
    return traj


def consensus(xn, prob):
    """The plain reference: probability-weighted mean of the scenarios'
    nonants and the expected mean absolute deviation from it, numpy
    float64 (what ``benchmarks/reference/scenario_lp.consensus`` does)."""
    xn, prob = np.asarray(xn, np.float64), np.asarray(prob, np.float64)
    xbar = prob @ xn / prob.sum()
    return xbar, float(prob @ np.abs(xn - xbar).sum(axis=1) / xn.shape[1])


def reduce_err(ph):
    xn = np.asarray(ph.x)[:, np.asarray(ph.nonant_idx)]
    xbar_ref, conv_ref = consensus(xn, np.asarray(ph.prob))
    xbar = np.asarray(ph.xbar)
    return (float(np.abs(xbar - xbar_ref).max()
                  / max(1.0, np.abs(xbar_ref).max())),
            abs(float(ph.conv) - conv_ref) / abs(conv_ref))


@pytest.fixture(scope="module")
def mesh_run():
    """One sharded engine through iter-0 and the hot passes, with every
    backend compile (or cache load) of the process logged by entry
    name: jax's duration event carries ``fun_name``."""
    from jax import monitoring
    log = []

    def on(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            log.append(str(kw.get("fun_name")))

    monitoring.register_event_duration_secs_listener(on)
    try:
        ph = PHBase(_batch(np.arange(S)), dict(OPTS, subproblem_chunk=LC),
                    mesh=make_mesh(NDEV), dtype=jnp.float64)
        traj = _trajectory(ph, log)
    finally:
        monitoring.unregister_event_duration_listener(on)
    return ph, traj


def test_sharded_engine_compiles_the_fused_chunk_program_once(mesh_run):
    """A cold state born replicated made the mesh lower and compile the
    fused program twice, once for iter-0's replicated iterates and once
    for the row-sharded iterates every later pass hands back (at UC
    width 111 s and 83 s on a four-chip v5e host, ~11 GiB of host
    memory each: PERF.md, PR 28). The cold rows are
    sharded now (``PHBase._cold_state``): ONE executable serves iter-0
    and the hot passes, as on one device."""
    ph, traj = mesh_run
    pt = ph.phase_timing(True)
    assert pt["mode"] == "sharded" and pt["devices"] == NDEV
    assert pt["kernel"]["mode"] == "fused"
    fused = [n for n in traj[-1][3] if FUSED in n]
    assert len(fused) == 1, traj[-1][3]
    # and nothing at all compiles in the second hot pass
    assert traj[-1][3] == traj[-2][3]
    # the placement that makes it so: every chunk state's rows sharded,
    # before the first solve as after it
    cold = ph._cold_state(*_chunk0(ph))
    hot = ph._qp_states[("chunks", True)][0]
    for f in ("x", "zA", "pri_rel"):
        a = getattr(cold, f)
        assert a.sharding.is_equivalent_to(getattr(hot, f).sharding, a.ndim)
        assert not a.sharding.is_fully_replicated


def _chunk0(ph):
    factors, data = ph._get_factors(True, False)
    chs = ph._shard_ops.to_chunks(ph._per_scen_operands(data), LC)
    return factors, data._replace(l=chs["l"][0], u=chs["u"][0],
                                  lb=chs["lb"][0], ub=chs["ub"][0])


def test_mesh_reduce_is_the_numpy_consensus_of_the_gathered_rows(mesh_run):
    """x-bar and conv after hot steps on the mesh against the numpy
    float64 mean of all gathered rows: the psum reorders a sum of 16
    float64 terms, so the two agree to a few ulps; 1e-12 leaves three
    digits over that and six under the benchmark's 1e-9 limit."""
    ph, _ = mesh_run
    xbar_err, conv_err = reduce_err(ph)
    assert xbar_err <= 1e-12 and conv_err <= 1e-12


def test_mesh_trajectory_tracks_the_single_device_chunk_composition(
        mesh_run):
    """The same microbatches on one device (rows permuted so that
    contiguous chunks of 8 ARE the mesh's strided chunks). The two
    programs partition their reductions differently, and the f32 bulk's
    chunk-pooled rho adaptation decides on psum'd f32 statistics, so the
    trajectories part at the df32 gate's level (1e-4 relative on values
    of order 1) and compound per pass: 1e-2 per pass is ~100x over that
    noise and ~100x under what a wrong chunk, a dropped row or a stale
    W shows (x-bar entries are 0/1 commitments). W rides rho = 100."""
    ph, traj = mesh_run
    single = PHBase(_batch(PERM), dict(OPTS, subproblem_chunk=NDEV * LC),
                    dtype=jnp.float64)
    assert single.phase_timing(True) is None
    for k, ((xb0, W0, c0, _), (xb1, W1, c1, _)) in enumerate(
            zip(_trajectory(single), traj)):
        tol = 1e-2 * (k + 1)
        np.testing.assert_allclose(xb0, xb1[PERM], atol=tol,
                                   err_msg=f"xbar, hot pass {k}")
        np.testing.assert_allclose(W0, W1[PERM], atol=100.0 * tol,
                                   err_msg=f"W, hot pass {k}")
        assert c1 == pytest.approx(c0, abs=tol), f"conv, hot pass {k}"
    assert single.phase_timing(True)["mode"] == "host"
    # a one-device engine books no cross-chip combine
    assert single.phase_timing(True)["collective"] == {"combines": 0.0,
                                                       "bytes": 0.0}


def test_reduce_that_drops_one_shard_fails_the_exact_check(mesh_run,
                                                           monkeypatch):
    """A psum that leaves one chip out (here: the last shard's rows
    enter the combine with weight zero) moves x-bar by parts in ten,
    nine orders over the 1e-9 the exact recomputation allows."""
    ph, _ = mesh_run
    real = ShardedScenarioOps.combine

    def dropped(self, xn, prob, weights, W, rho, wmask):
        keep = (jnp.arange(xn.shape[0]) < xn.shape[0]
                - self.shard_size).astype(weights.dtype)
        keep = keep if weights.ndim == 1 else keep[:, None]
        return real(self, xn, prob, weights * keep, W, rho, wmask)

    monkeypatch.setattr(ShardedScenarioOps, "combine", dropped)
    _hot(ph)
    xbar_err, _ = reduce_err(ph)
    assert xbar_err > 1e-9
    monkeypatch.undo()
    _hot(ph)
    assert max(reduce_err(ph)) <= 1e-12


def test_collective_counter_needs_no_session_and_resets_with_the_seconds(
        mesh_run):
    from mpisppy_tpu import obs
    ph, _ = mesh_run
    assert not obs.enabled()
    ops = ph._shard_ops
    per_combine = ops.combine_collective_bytes(8, full=True)
    # 3 psum operands (num, den, squares) of (1 node, K nonants) + conv
    K = int(np.asarray(ph.nonant_idx).size)
    assert per_combine == 3 * K * 8 + 8
    ph.reset_phase_timing()
    assert ph.phase_timing(True) is None
    for calls in (1, 2):
        _hot(ph)
        pt = ph.phase_timing(True)
        assert pt["calls"] == calls
        assert pt["collective"] == {"combines": 1.0,
                                    "bytes": float(per_combine)}
    ph.reset_phase_timing()
    _hot(ph)
    assert ph.phase_timing(True)["collective"]["combines"] == 1.0


def test_mesh_spans_lie_inside_their_phases(mesh_run, profiler_capture):
    """``mesh.to_chunks`` under ``ph.assemble``; ``mesh.from_chunks`` and
    the collective combine (``ph.reduce.combine``) under ``ph.reduce``:
    in any profiler capture, with no telemetry session."""
    ph, _ = mesh_run
    with profiler_capture as cap:
        _hot(ph)
    assert cap.inside("mesh.to_chunks", "ph.assemble")
    assert cap.inside("mesh.from_chunks", "ph.reduce")
    assert cap.inside("ph.reduce.combine", "ph.reduce")
    assert len([e for e in cap.events if e[0] == "ph.reduce.combine"]) == 1
