"""Pipelined chunk dispatch (core/ph._solve_loop_chunked pipeline mode):
equivalence against the sequential opt-out, fused-gate sync accounting,
recovery behavior under a forced-pathological chunk, donation semantics,
and the SHARDED chunked path (scenario-axis SPMD over the mesh — the
ISSUE 6 replacement of PR 2's round-robin chunk spreading). What
``phase_timing`` carries with no telemetry session:
tests/test_pipeline_timing.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.core.ph import PHBase, _ph_chunk_objs
from mpisppy_tpu.models import uc
from mpisppy_tpu.parallel.mesh import make_mesh


def _uc_batch(S, G=3, T=6, **kw):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T, **kw},
                       vector_patch=uc.scenario_vector_patch)


_OPTS = {"defaultPHrho": 50.0, "subproblem_max_iter": 1200,
         "subproblem_eps": 1e-6, "subproblem_chunk": 3}


def _run(batch_fn, opts, iters=3, mesh=None):
    ph = PHBase(batch_fn(), dict(opts), dtype=jnp.float64, mesh=mesh)
    for it in range(iters):
        ph.solve_loop(w_on=(it > 0), prox_on=(it > 0))
        ph.W = ph.W_new
    return ph


def test_pipelined_matches_sequential_nonsplit():
    """Default pipelined dispatch (pre-assembly + fused gate + donated
    warm starts) must reproduce the sequential opt-out's trajectory: on
    one device the passes run the same programs in the same order, so
    the iterates agree to roundoff, not just tolerance."""
    ph_seq = _run(lambda: _uc_batch(8), {**_OPTS, "subproblem_pipeline": 0})
    ph_pip = _run(lambda: _uc_batch(8), _OPTS)
    np.testing.assert_allclose(np.asarray(ph_pip.xbar),
                               np.asarray(ph_seq.xbar), atol=1e-9)
    np.testing.assert_allclose(np.asarray(ph_pip.W),
                               np.asarray(ph_seq.W), atol=1e-7)
    assert ph_pip.conv == pytest.approx(ph_seq.conv, abs=1e-12)
    # pri_rel-level agreement of the accepted solves (the acceptance
    # tolerance of the equivalence contract)
    pr_s = np.asarray(ph_seq._qp_states[True].pri_rel)
    pr_p = np.asarray(ph_pip._qp_states[True].pri_rel)
    assert np.abs(pr_s - pr_p).max() < 1e-8


def test_pipelined_matches_sequential_df32():
    """Split (df32) mode keeps the sequential factor flow — pipelining
    overlaps assembly only — and must track the sequential trajectory
    within solve tolerance."""
    opts = {"defaultPHrho": 50.0, "subproblem_precision": "df32",
            "subproblem_max_iter": 400, "subproblem_eps": 1e-5,
            "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
            "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 150,
            "subproblem_segment": 150, "subproblem_polish_hot": False,
            "subproblem_hospital": False, "subproblem_chunk": 2}
    ph_seq = _run(lambda: _uc_batch(4), {**opts, "subproblem_pipeline": 0})
    ph_pip = _run(lambda: _uc_batch(4), opts)
    assert ph_pip.conv == pytest.approx(ph_seq.conv, abs=1e-6)
    np.testing.assert_allclose(np.asarray(ph_pip.xbar),
                               np.asarray(ph_seq.xbar), atol=1e-5)
    assert float(np.asarray(ph_pip._qp_states[True].pri_rel).max()) < 1e-2


def test_fused_gate_one_sync_per_iteration():
    """The acceptance criterion's sync accounting: pipelined quality
    gates cost ONE host D2H per PH iteration regardless of chunk count,
    where the sequential loop pays one blocking read per chunk."""
    ph_pip = _run(lambda: _uc_batch(8), _OPTS, iters=2)
    ph_seq = _run(lambda: _uc_batch(8), {**_OPTS, "subproblem_pipeline": 0},
                  iters=2)
    n_chunks = len(ph_seq._chunk_index(3))
    assert n_chunks == 3
    pt_pip = ph_pip.phase_timing(True)
    pt_seq = ph_seq.phase_timing(True)
    assert pt_pip["gate_d2h_syncs_per_call"] == 1.0
    assert pt_seq["gate_d2h_syncs_per_call"] == float(n_chunks)
    # the per-phase anatomy is recorded for every phase (bench/profiling
    # observability satellite)
    for phase in ("assemble", "solve", "gate", "reduce"):
        assert pt_pip["seconds_per_call"][phase] >= 0.0
    assert 0.0 < pt_pip["occupancy"] <= 1.0


def test_pipeline_recovery_matches_sequential_on_pathological_chunk():
    """A chunk whose warm-started rho trajectory is forced pathological
    must be recovered by the fused gate exactly like the sequential
    gate: retried from a reset factorization, and blacklisted the same
    way when incurable."""
    from mpisppy_tpu.ops.qp_solver import _factorize

    def poisoned(pipeline):
        ph = _run(lambda: _uc_batch(8),
                  {**_OPTS, "subproblem_chunk": 4,
                   "subproblem_pipeline": pipeline}, iters=2)
        sts = ph._qp_states[("chunks", True)]
        factors, _ = ph._get_factors(True)
        bad_rho = jnp.full_like(sts[0].rho_scale, 1e-6)
        sts[0] = sts[0]._replace(rho_scale=bad_rho,
                                 L=_factorize(factors, bad_rho))
        ph.solve_loop(w_on=True, prox_on=True)
        return ph

    ph_p = poisoned(1)
    ph_s = poisoned(0)
    pr_p = np.asarray(ph_p._qp_states[True].pri_rel)
    pr_s = np.asarray(ph_s._qp_states[True].pri_rel)
    assert pr_p.max() < 1e-2, f"pipelined recovery missed: {pr_p.max():.1e}"
    assert pr_s.max() < 1e-2
    # identical blacklist outcomes
    assert ph_p._chunk_no_retry.get(True, set()) \
        == ph_s._chunk_no_retry.get(True, set())


def test_sharded_chunked_matches_single_device():
    """The ISSUE 6 tentpole contract (MULTICHIP tier-1): the sharded
    chunked loop — every chunk one SPMD program over the 2-device mesh,
    reductions as psum — must track the single-device chunked
    trajectory. Per-scenario x is compared only at the consensus level
    (x̄): the UC LP relaxation is degenerate, and solves that converge
    to 1e-14 residuals from different chunk compositions legitimately
    land on different optimal vertices."""
    assert len(jax.devices()) >= 2
    opts = {**_OPTS, "subproblem_chunk": 4, "subproblem_max_iter": 6000,
            "subproblem_eps": 1e-8}
    ph_one = _run(lambda: _uc_batch(16), {**opts, "subproblem_pipeline": 0},
                  iters=2)
    # per-device chunk semantics: shard = 8 rows/device, chunk 4 -> the
    # sharded chunked loop really runs (2 chunks of 4 rows per device)
    ph_two = _run(lambda: _uc_batch(16), opts, iters=2, mesh=make_mesh(2))
    pt = ph_two.phase_timing(True)
    assert pt["devices"] == 2 and pt["mode"] == "sharded", \
        "sharded chunked path did not engage"
    np.testing.assert_allclose(np.asarray(ph_two.xbar),
                               np.asarray(ph_one.xbar), atol=5e-3)
    assert ph_two.conv == pytest.approx(ph_one.conv, abs=1e-4)
    # both compositions' solves actually converged (the premise of the
    # consensus-level comparison above)
    for ph in (ph_one, ph_two):
        assert float(np.asarray(ph._qp_states[True].pri_rel).max()) < 1e-6
    # the fused gate still costs one D2H per iteration — not one per
    # chunk, not one per device
    assert pt["gate_d2h_syncs_per_call"] == 1.0


def test_sharded_chunked_zero_device_put_steady_state(tmp_path):
    """Acceptance criterion: the steady-state sharded iteration moves
    ZERO bytes through device_put (chunk staging is a local reshape,
    outputs stay mesh-placed) while the collective combine books
    psum bytes, and gate syncs stay O(1)/iteration — all read from the
    telemetry counters a production run would emit."""
    obs.configure(out_dir=str(tmp_path))
    try:
        ph = _run(lambda: _uc_batch(16), {**_OPTS, "subproblem_chunk": 4},
                  iters=2, mesh=make_mesh(2))
        before = obs.counters_snapshot()
        ph.solve_loop(w_on=True, prox_on=True)   # steady-state iteration
        ph.W = ph.W_new
        after = obs.counters_snapshot()
        delta = lambda k: after.get(k, 0) - before.get(k, 0)
        assert delta("xfer.device_put_bytes") == 0
        assert delta("ph.gate_syncs") == 1
        assert delta("xfer.collective_bytes") > 0
    finally:
        obs.shutdown()


def test_sharded_multistep_with_view_consumers():
    """Multi-iteration sharded run exercising the mesh state view
    (locally-concatenated residual reads between iterations) and the
    donation hand-off on mesh-resident warm starts."""
    ph = _run(lambda: _uc_batch(16), {**_OPTS, "subproblem_chunk": 4},
              iters=3, mesh=make_mesh(2))
    st = ph._qp_states[True]
    pr = np.asarray(st.pri_rel)          # lazy sharded concat
    assert pr.shape == (16,)
    assert np.isfinite(pr).all()
    za = np.asarray(st.zA)               # the big lazy field too
    assert za.shape[0] == 16
    assert np.isfinite(ph.conv)


def test_chunk_idx_cache_invalidation_with_factors():
    """ISSUE 2 satellite: the chunk index cache is keyed by (chunk, S)
    and cleared together with the factor cache on rho reset — a stale
    entry must not survive invalidate_factors nor batch-size changes."""
    ph = _run(lambda: _uc_batch(8), _OPTS, iters=1)
    assert (3, 8) in ph._chunk_idx_cache
    assert True in ph._chunk_donatable or False in ph._chunk_donatable
    ph.invalidate_factors()
    assert ph._chunk_idx_cache == {}
    assert ph._chunk_donatable == set()
    # chunk states for the hot mode were dropped with the factors;
    # the next solve rebuilds and runs (no stale-slice reuse)
    ph.solve_loop(w_on=True, prox_on=True)
    assert np.isfinite(float(np.asarray(
        ph._qp_states[True].pri_rel).max()))


def test_interrupted_donating_pass_recovers_cold():
    """A donating pass that dies between consuming the warm-start
    buffers (pass 1) and storing their successors (pass 3) leaves the
    cached chunk states referencing deleted arrays; the next solve_loop
    must detect the open donation window and rebuild cold instead of
    crashing on the dead warm starts."""
    ph = _run(lambda: _uc_batch(8), _OPTS, iters=3)
    assert True in ph._chunk_donatable
    # simulate the mid-pass crash: window open, states consumed
    sts = ph._qp_states[("chunks", True)]
    for s in sts:
        s.x.delete()
        s.zA.delete()
    ph._chunk_dirty.add(True)
    # ANOTHER mode rebuilding must not transplant from the dirty mode's
    # dead view (cross-mode warm starts read its lazy zA concat)
    ph._qp_states.pop(("chunks", False), None)
    ph._qp_states.pop(False, None)
    ph.solve_loop(w_on=True, prox_on=False, update=False)   # must not raise
    # ...and the dirty mode's own re-run rebuilds cold
    ph.solve_loop(w_on=True, prox_on=True)                  # must not raise
    assert True not in ph._chunk_dirty
    pr = np.asarray(ph._qp_states[True].pri_rel)
    assert np.isfinite(pr).all() and pr.shape == (8,)


def test_donated_solve_matches_copying_solve():
    """qp_solve(donate=True) consumes the input state's buffers (reads
    raise afterwards) and returns the same solution as the copying
    twin — the ownership contract the pipelined driver relies on."""
    from mpisppy_tpu.ops.qp_solver import qp_cold_state, qp_solve

    ph = PHBase(_uc_batch(4), {}, dtype=jnp.float64)
    factors, data = ph._get_factors(False)
    st_a = qp_cold_state(factors, data)
    st_b = qp_cold_state(factors, data)
    q = ph.c
    st1, x1, _, _ = qp_solve(factors, data, q, st_a, max_iter=300,
                             polish=False)
    st2, x2, _, _ = qp_solve(factors, data, q, st_b, max_iter=300,
                             polish=False, donate=True)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(st1.pri_rel),
                               np.asarray(st2.pri_rel), rtol=1e-9)
    # the copying twin leaves its input readable; the donated one does not
    assert np.isfinite(float(st_a.x[0, 0]))
    with pytest.raises(RuntimeError):
        _ = float(st_b.x[0, 0])


# ---- ONE staging program for every chunk's operands (ISSUE 31) ----

_STAGE_LAYOUTS = {
    # S, chunk, devices: host-chunked leaves a RAGGED last chunk (8 rows
    # in chunks of 3: the last is padded by repeating row 7); on the
    # mesh 16 rows are 2 local chunks of 4 on each of 2 devices
    "host-chunked": (8, 3, 1),
    "sharded": (16, 4, 2),
}


@functools.lru_cache(maxsize=None)
def _stage_engine(layout, w_scale):
    """One settled engine per (layout, w_scale): two real iterations
    (nonzero W and x-bar), then a few pinned nonants so the boxes move
    too. The cases below only ever call it with ``update=False`` and a
    stand-in solver, so W / x-bar / rho / the pins stay as they are."""
    S, chunk, ndev = _STAGE_LAYOUTS[layout]
    batch = _uc_batch(S)
    vp = False
    if w_scale:
        rng = np.random.default_rng(5)
        vp = np.asarray(batch.prob)[:, None] \
            * rng.uniform(0.5, 1.5, (batch.S, batch.K))
        vp[0, 0] = 0.0          # a zero-probability entry: no W pressure
    ph = PHBase(batch, {**_OPTS, "subproblem_chunk": chunk},
                dtype=jnp.float64, variable_probability=vp,
                mesh=make_mesh(ndev) if ndev > 1 else None)
    for it in range(2):
        ph.solve_loop(w_on=(it > 0), prox_on=(it > 0))
        ph.W = ph.W_new
    mask = np.zeros((batch.S, batch.K), bool)
    mask[1::3, ::4] = True
    ph.fix_nonants(np.asarray(ph.xbar) + 0.25, mask=mask)
    return ph


_REAL_CHUNK_OBJS = _ph_chunk_objs   # the cases below wrap the module's


def _chunk_operands(ph, monkeypatch, pipeline, w_on, prox_on):
    """What one ``solve_loop(update=False)`` hands each chunk solve
    (l, u, lb, ub, q) and each pass-3 objective call (c, c0, P0, W),
    as host arrays, chunk by chunk; the solver is a stand-in that hands
    the warm state back as solved."""
    from mpisppy_tpu.core import ph as ph_mod
    solves, objs = [], []

    def solver(factors, d, q, st, **kw):
        solves.append([np.asarray(a) for a in (d.l, d.u, d.lb, d.ub, q)])
        return (st._replace(pri_rel=jnp.zeros_like(st.pri_rel)),
                st.x, st.yA, st.yB)

    def chunk_objs(x, yA, yB, d, q, c, c0, P0, idx, W, *, w_on):
        objs.append([np.asarray(a) for a in (c, c0, P0, W)])
        return _REAL_CHUNK_OBJS(x, yA, yB, d, q, c, c0, P0, idx, W,
                                w_on=w_on)

    monkeypatch.setattr(ph_mod, "_solver_call", solver)
    monkeypatch.setattr(ph_mod, "_ph_chunk_objs", chunk_objs)
    ph.options["subproblem_pipeline"] = pipeline
    ph.reset_phase_timing()
    ph.solve_loop(w_on=w_on, prox_on=prox_on, update=False)
    key = bool(prox_on)
    return (solves, objs,
            ph.phase_timing(key)["assemble_programs_per_call"])


@pytest.mark.parametrize("w_on,prox_on", [(True, True), (True, False),
                                          (False, True), (False, False)])
@pytest.mark.parametrize("w_scale", [False, True],
                         ids=["uniform", "w_scale"])
@pytest.mark.parametrize("layout", list(_STAGE_LAYOUTS))
def test_staged_operands_equal_per_chunk_assembly_bit_for_bit(
        layout, w_scale, w_on, prox_on, monkeypatch):
    """The resident pipelined pass stages every chunk's operands with
    ONE device program; the sequential opt-out keeps the per-chunk
    spelling (eager gathers, then ``_ph_assemble``). Same state in,
    same bits out, in every leaf the chunk solves and pass 3 read — no
    tolerance: the staging body IS the per-chunk expression."""
    ph = _stage_engine(layout, w_scale)
    S, chunk, ndev = _STAGE_LAYOUTS[layout]
    n_chunks = -(-(S // ndev) // chunk)
    staged = _chunk_operands(ph, monkeypatch, 1, w_on, prox_on)
    each = _chunk_operands(ph, monkeypatch, 0, w_on, prox_on)
    assert (staged[2], each[2]) == (1.0, float(n_chunks))
    for got, want in zip(staged[:2], each[:2]):
        assert len(got) == len(want) == n_chunks
        for ci in range(n_chunks):
            for a, b in zip(got[ci], want[ci]):
                assert a.dtype == b.dtype and a.shape == b.shape
                assert a.tobytes() == b.tobytes(), f"chunk {ci}"
    # the operands are not trivially equal: W and the pins are in them
    q, lb = staged[0][0][4], staged[0][0][2]
    c = staged[1][0][0]
    assert (np.abs(q - c).max() > 0) == (w_on or prox_on)
    ids0 = np.asarray(
        ph._sharded_chunk_slices(ph._local_chunk(chunk))[0][0]
        if ndev > 1 else ph._chunk_index(chunk)[0][0])
    assert np.abs(lb - np.asarray(ph.qp_data.lb)[ids0]).max() > 0
    if layout == "host-chunked":
        # the ragged last chunk repeats scenario 7
        last = staged[0][-1][4]
        np.testing.assert_array_equal(last[1], last[2])


@pytest.mark.parametrize("source,layout,expect", [
    ("resident", "host-chunked", 1), ("resident", "sharded", 1),
    ("sequential", "host-chunked", "n_chunks"),
    ("sequential", "sharded", "n_chunks"),
    ("streamed", "host-chunked", "n_chunks")])
def test_assemble_programs_per_call(source, layout, expect):
    """``phase_timing()``'s count of the device programs the assemble
    phase launched: ONE where the staging program ran, one per chunk
    where the input keeps the per-chunk path (the sequential opt-out; a
    streamed source, whose double buffer bounds the staged chunks)."""
    S, chunk, ndev = _STAGE_LAYOUTS[layout]
    opts = {**_OPTS, "subproblem_chunk": chunk}
    if source == "sequential":
        opts["subproblem_pipeline"] = 0
    if source == "streamed":
        opts["scenario_source"] = "streamed"
    ph = PHBase(_uc_batch(S), opts, dtype=jnp.float64,
                mesh=make_mesh(ndev) if ndev > 1 else None)
    try:
        for it in range(2):
            ph.solve_loop(w_on=(it > 0), prox_on=(it > 0))
            ph.W = ph.W_new
    finally:
        if source == "streamed":
            ph.close_stream()
    n_chunks = -(-(S // ndev) // chunk)
    want = n_chunks if expect == "n_chunks" else expect
    for key in (True, False):
        assert ph.phase_timing(key)["assemble_programs_per_call"] == want


@pytest.mark.parametrize("layout", list(_STAGE_LAYOUTS))
def test_staging_program_takes_vectors_only(layout, monkeypatch):
    """The guard against handing ``data`` through the jit boundary (XLA
    copies what crosses it: +2.7 GB a chunk measured when a matrix
    did): no operand of the staging program is larger than one
    per-scenario vector block, S x max(n, m) elements, so neither the
    (n, n) factor nor the (m, n) / packed constraint matrix is among
    them."""
    from mpisppy_tpu.core import ph as ph_mod
    from mpisppy_tpu.parallel.mesh import ShardedScenarioOps
    ph = _stage_engine(layout, False)
    ph.options["subproblem_pipeline"] = 1
    calls = []
    if layout == "sharded":
        real = ShardedScenarioOps.map_chunks

        def spy(self, key, fn, tree, lc, *rep):
            calls.append((jax.tree.leaves(tree) + list(rep), None))
            return real(self, key, fn, tree, lc, *rep)

        monkeypatch.setattr(ShardedScenarioOps, "map_chunks", spy)
    else:
        real = ph_mod._ph_stage_chunks

        def spy(*args, **kw):
            calls.append((jax.tree.leaves(args), jax.make_jaxpr(
                functools.partial(real, **kw))(*args)))
            return real(*args, **kw)

        monkeypatch.setattr(ph_mod, "_ph_stage_chunks", spy)
    ph.solve_loop(w_on=True, prox_on=True, update=False)
    (leaves, jaxpr), = calls
    S, n, m = ph.batch.S, ph.batch.n, ph.batch.m
    sizes = [int(np.prod(a.shape)) for a in leaves]
    if jaxpr is not None:
        assert sizes == [int(np.prod(v.shape)) for v in jaxpr.in_avals]
    # at this size the bound does tell a vector block from a matrix
    assert S * max(n, m) < min(n * n, n * m)
    assert max(sizes) <= S * max(n, m), sizes
