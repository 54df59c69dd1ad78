"""What ``phase_timing`` carries with NO telemetry session, on the
chunked, sharded and un-chunked solve paths: the ADMM iteration counts
beside the seconds they cover, the per-phase contract by layout and
precision, whole runs and explicit-inverse builds. The pipeline itself,
and the UC batch and options shared with it: tests/test_pipeline.py."""

import jax.numpy as jnp
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.parallel.mesh import make_mesh

from test_pipeline import _OPTS, _run, _uc_batch


_DF32_OPTS = {"defaultPHrho": 50.0, "subproblem_precision": "df32",
              "subproblem_max_iter": 400, "subproblem_eps": 1e-5,
              "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
              "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 150,
              "subproblem_segment": 150, "subproblem_polish_hot": False,
              "subproblem_hospital": False, "subproblem_chunk": 2}


@pytest.mark.parametrize("recipe", ["native", "df32", "df32-segmented"])
def test_admm_iteration_counts_beside_the_seconds_without_session(recipe):
    """phase_timing carries the ADMM work of the SAME solve passes its
    seconds cover, with NO telemetry session: bulk + tail per call is
    the sum of the chunk states' iteration counts, a native solve books
    everything as tail, a df32 one splits at the handoff, the reset
    zeroes both, and the gate still costs one D2H per call."""
    assert not obs.enabled()
    opts, S = (_OPTS, 8) if recipe == "native" else (_DF32_OPTS, 4)
    if recipe == "df32-segmented":
        opts = {**opts, "subproblem_kernel_mode": "segmented"}
    ph = _run(lambda: _uc_batch(S), opts, iters=2)
    ph.reset_phase_timing()
    assert ph.phase_timing(True) is None            # counts went with it
    calls = 2
    total = refs = 0
    for _ in range(calls):
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
        states = ph._qp_states[("chunks", True)]
        total += sum(int(st.iters) for st in states)
        refs += sum(int(st.refactors) for st in states)
    pt = ph.phase_timing(True)
    admm = pt["admm_iters_per_call"]
    assert pt["calls"] == calls
    assert admm["bulk"] + admm["tail"] == pytest.approx(total / calls)
    assert admm["refactors"] == pytest.approx(refs / calls)
    n_chunks = len(ph._qp_states[("chunks", True)])
    if recipe == "native":
        assert admm["bulk"] == 0 and admm["tail"] > 0
    else:
        assert admm["bulk"] > 0
        assert 0 <= admm["tail"] <= n_chunks * (
            opts["subproblem_tail_iter"] + opts["subproblem_segment"])
    assert pt["gate_d2h_syncs_per_call"] == 1.0
    assert pt["kernel"]["mode"] == (
        "segmented" if recipe == "df32-segmented" else "fused")
    shape = pt["solve_shape"]
    assert (shape["n"], shape["m"]) == (ph.batch.n, ph.batch.m)
    assert shape["s_chunk"] == opts["subproblem_chunk"]
    assert shape["ir_sweeps"] == 1 and shape["block_dtype"] == "f32"
    # keyword for keyword what the bytes model prices
    from mpisppy_tpu.ops.kernels import est_hbm_bytes_per_iter
    assert est_hbm_bytes_per_iter(**shape)["tail"] > 0


@pytest.mark.parametrize("precision", ["df32", "mixed"])
@pytest.mark.parametrize("layout", ["host-chunked", "sharded-2",
                                    "sharded-4"])
def test_phase_timing_contract(layout, precision):
    """The dictionary the benchmark's ``solve.*`` readers consume
    (``benchmarks/metrics/solve.{chunk_s,bulk_iters,tail_iters,
    fused_mixed_roofline}.py``, printed by ``drivers/ph_hot.py``), in
    the layouts its UC cells run: one device's chunked loop and the
    scenario-sharded one, under the two precision-escalated recipes.
    No kernel option is set, as in every cell: the fused program, f32
    blocks, the explicit inverse left to its rule (off here: a tail of
    10 x 2 rows x 2 applies does not repay an n-column inverse)."""
    from mpisppy_tpu.ops.kernels import est_hbm_bytes_per_iter
    ndev = {"host-chunked": 1, "sharded-2": 2, "sharded-4": 4}[layout]
    opts = {**_DF32_OPTS, "subproblem_precision": precision,
            "subproblem_tail_iter": 10}
    assert not any(k.startswith("subproblem_kernel") for k in opts)
    # >= 6 generators: the analyser finds the per-generator structure,
    # so the df32 operand is packed as the cell's is
    ph = _run(lambda: _uc_batch(8, G=6, T=8, min_up_down=True,
                                ramping=True),
              opts, iters=2, mesh=make_mesh(ndev) if ndev > 1 else None)
    pt = ph.phase_timing(True)
    assert pt["kernel"] == {"mode": "fused", "backend": "reference",
                            "l_inv": False, "block_dtype": "f32",
                            "f64_products": None,
                            # df32's split matrix never polishes; the
                            # mixed recipe's shared float64 matrix
                            # would through the library calls
                            "f64_polish": None if precision == "df32"
                            else "library",
                            # nor is its factor a float64 inverse; the
                            # mixed recipe's shared one is the device
                            # library's on every backend
                            "f64_refactor": None if precision == "df32"
                            else "library",
                            # rebuilt under a ``lax.cond`` in the loop
                            "f64_loop": None if precision == "df32"
                            else "conditional",
                            # one shared matrix: no stack to walk in
                            # blocks of scenarios
                            "f64_stack_block": None}
    assert (pt["mode"], pt["devices"]) == (
        "sharded" if ndev > 1 else "host", ndev)
    shape = pt["solve_shape"]
    assert set(shape) == {"n", "m", "s_chunk", "ir_sweeps",
                          "pk_pass_bytes", "block_dtype"}
    assert (shape["n"], shape["m"]) == (ph.batch.n, ph.batch.m)
    assert shape["s_chunk"] == opts["subproblem_chunk"]   # per device
    assert shape["ir_sweeps"] == 1 and shape["block_dtype"] == "f32"
    if precision == "df32":
        assert 0 < shape["pk_pass_bytes"] < 8 * ph.batch.n * ph.batch.m
    else:
        assert shape["pk_pass_bytes"] is None
    priced = est_hbm_bytes_per_iter(**shape)
    assert priced["tail"] > priced["bulk"] > 0
    admm = pt["admm_iters_per_call"]
    assert set(admm) == {"bulk", "tail", "refactors", "linv_builds",
                         "linv_applies"}
    assert admm["bulk"] > 0 and admm["tail"] >= 0
    assert admm["linv_builds"] == 0        # no LInv, nothing built
    assert set(pt["seconds_per_call"]) == {"assemble", "solve", "gate",
                                           "reduce"}
    assert (pt["collective"]["bytes"] > 0) == (ndev > 1)


def _sslp_batch(S=6):
    from mpisppy_tpu.models import sslp
    return build_batch(
        sslp.scenario_creator, sslp.make_tree(S),
        creator_kwargs=dict(num_servers=3, num_clients=8, overflow=True,
                            server_budget=3, capacity=60.0,
                            demand_is_revenue=True),
        vector_patch=sslp.scenario_vector_patch)


_SSLP_DF32 = {**_DF32_OPTS, "defaultPHrho": 5.0, "subproblem_chunk": 0,
              "subproblem_tail_iter": 30, "iter0_feas_tol": 1.0}


@pytest.mark.parametrize("family", ["uc-chunked", "sslp-unchunked",
                                    "sslp-chunked"])
def test_runs_and_linv_builds_beside_the_seconds(family):
    """``phase_timing()["runs"]`` and ``["admm_iters_per_call"]
    ["linv_builds"]`` (ISSUE 32), with no telemetry session. A UC toy
    in the chunked loop carries no explicit inverse (its rule says
    off, as in the UC cells): 0 builds. An sslp toy has it ON by the same rule (30 tail
    iterations x 2 applies x 6 rows >= n = 30), un-chunked and chunked:
    the wrap of each mode's cold state is one build a run (a chunk
    chain flows ONE factor, so only its first state arrives bare), and
    every refactorization leaves the inverse to be built anew: the
    count is wraps + ``refactors``. Runs are counted by ``run_span``
    and their resets timed."""
    assert not obs.enabled()
    if family == "uc-chunked":
        # a tail of 10 x 2 applies x 2 rows does not repay an inverse
        mk = lambda: _uc_batch(4)
        opts = {**_DF32_OPTS, "subproblem_tail_iter": 10}
    else:
        mk = _sslp_batch
        opts = {**_SSLP_DF32, "subproblem_chunk":
                3 if family == "sslp-chunked" else 0}
    ph = PHBase(mk(), dict(opts), dtype=jnp.float64)
    on = family != "uc-chunked"
    firsts = []
    for _ in range(2):
        with ph.run_span():
            ph.reset_run()
            ph.solve_loop(w_on=False, prox_on=False)
            ph.W = ph.W_new
            before = ph.phase_timing(True)
            ph.solve_loop(w_on=True, prox_on=True)
            ph.W = ph.W_new
            firsts.append(ph.phase_timing(True)["admm_iters_per_call"]
                          ["linv_builds"] * ph.phase_timing(True)["calls"]
                          - (before["admm_iters_per_call"]["linv_builds"]
                             * before["calls"] if before else 0))
            ph.solve_loop(w_on=True, prox_on=True)
            ph.W = ph.W_new
    pt = ph.phase_timing(True)
    assert pt["kernel"]["l_inv"] is on
    assert pt["calls"] == 4
    if on:
        # each run's first hot call wraps its cold state
        assert all(f >= 1 for f in firsts), firsts
        st = ph._qp_states[("chunks", True)][0] \
            if family == "sslp-chunked" else ph._qp_states[True]
        assert type(st.L).__name__ == "LInv"
        total = pt["admm_iters_per_call"]["linv_builds"] * pt["calls"]
        refs = pt["admm_iters_per_call"]["refactors"] * pt["calls"]
        assert round(total) == 2 + round(refs)   # one wrap a run
        assert ph.phase_timing(False)["admm_iters_per_call"][
            "linv_builds"] >= 1
    else:
        assert firsts == [0, 0]
        assert pt["admm_iters_per_call"]["linv_builds"] == 0
    runs = pt["runs"]
    assert runs["count"] == 2
    assert 0 < runs["reset_seconds"] < runs["seconds"]
    assert ph.phase_timing(False)["runs"] == runs    # the engine's, not a mode's
    ph.reset_phase_timing()
    with ph.run_span():
        ph.solve_loop(w_on=False, prox_on=False)
    assert ph.phase_timing(False)["runs"]["count"] == 1
    assert ph.phase_timing(False)["runs"]["reset_seconds"] == 0.0
