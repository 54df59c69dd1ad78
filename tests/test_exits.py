"""How every chunk solve ended (ISSUE 37): ``phase_timing()["exits"]``,
the booking that stands beside the solve seconds and the ADMM counts
(``core/ph._book_exits``). Held here: the host's row classification IS
the loop's own exit test (recomputed in numpy float64 from the returned
iterates, primal and dual apart); the entry exists with no session on
the un-chunked, chunked, dispatch-pass and mesh paths, resets with the
seconds, and its histograms sum to ``admm_iters_per_call`` exactly; a
forced cap reads every solve capped and a loose tolerance none; the
gate still reads the device once and the fused program's lowered text
does not know the booking exists; the ``ph.standing`` note and counter
read what a plain loop over the rows reads."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core import ph as ph_mod
from mpisppy_tpu.core.aph import APH
from mpisppy_tpu.core.ph import (PHBase, _book_exits, _new_phase_entry,
                                 _row_map, _rows_over)
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import sslp, uc
from mpisppy_tpu.ops.qp_solver import (EXIT_ROWS, SplitMatrix,
                                       qp_cold_state, qp_solve)
from mpisppy_tpu.parallel.mesh import make_mesh


# same toy shapes as tests/test_pipeline.py so the UC programs compile
# once per suite run
def _uc_batch(S, G=3, T=6):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": G, "num_hours": T},
                       vector_patch=uc.scenario_vector_patch)


def _sslp_batch(S):
    return build_batch(
        sslp.scenario_creator, sslp.make_tree(S),
        creator_kwargs=dict(num_servers=3, num_clients=6, overflow=True,
                            server_budget=3, capacity=30.0,
                            demand_is_revenue=True),
        vector_patch=sslp.scenario_vector_patch)


_BATCH = {"uc": _uc_batch, "sslp": _sslp_batch}
_DF32 = {"defaultPHrho": 50.0, "subproblem_precision": "df32",
         "subproblem_max_iter": 400, "subproblem_eps": 1e-5,
         "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
         "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 100,
         "subproblem_polish_hot": False, "subproblem_hospital": False}
# a tolerance no row meets: every tail runs its budget, every row is over
_TIGHT = {"subproblem_eps_hot": 1e-12, "subproblem_eps_dua_hot": 1e-12,
          "subproblem_stall_rel": 0.0, "subproblem_tail_iter": 25}


def _hot(ph, iters):
    for it in range(iters):
        ph.solve_loop(w_on=(it > 0), prox_on=(it > 0))
        ph.W = ph.W_new
    return ph


# ---------------- (a) the host's classification is the loop's ----------------

def _np_exit_test(factors, data, q, st):
    """``qp_solver._unscaled_residuals`` and the scales of ``conv_ok``
    in numpy float64, from the returned (scaled) iterates of a
    shared-structure solve: per row, primal residual / (1 + pri_sc) and
    dual residual / (1 + dua_sc), i.e. the smallest ``eps_abs =
    eps_rel`` each test would pass at."""
    f8 = lambda a: np.asarray(a, np.float64)
    _, D, E, Eb, cs, A_s, P_s, _, _ = factors
    A = f8(A_s.hi) + f8(A_s.lo) if isinstance(A_s, SplitMatrix) else f8(A_s)
    D, E, Eb, cs, P_s = f8(D), f8(E), f8(Eb), f8(cs), f8(P_s)
    x, yA, yB, zA, zB = (f8(getattr(st, k)) for k in
                         ("x", "yA", "yB", "zA", "zB"))
    g, q_s = Eb * D, cs * D * f8(q)
    Ax, Aty = x @ A.T, yA @ A
    mx = lambda a: np.abs(a).max(axis=1)
    pri = np.maximum(mx((Ax - zA) / E), mx(D * x - zB / Eb))
    dinv = 1.0 / (D * cs)
    dua = mx(dinv * (P_s * x + q_s + Aty + g * yB))
    pri_sc = np.maximum.reduce([mx(Ax / E), mx(zA / E), mx(D * x),
                                mx(zB / Eb), np.full(len(x), 1e-6)])
    dua_sc = np.maximum.reduce([mx(dinv * P_s * x), mx(dinv * q_s),
                                mx(dinv * Aty), mx(dinv * g * yB),
                                np.full(len(x), 1e-6)])
    return pri / (1.0 + pri_sc), dua / (1.0 + dua_sc)


def _split_at(need):
    """A tolerance some rows meet and some do not: the middle of the
    widest relative gap among the sorted per-row needs, so that no row
    sits at the tolerance itself."""
    v = np.sort(need)
    k = int(np.argmax(v[1:] / v[:-1]))
    assert v[k + 1] / v[k] > 1.05, "no gap to put a tolerance in"
    return float(np.sqrt(v[k] * v[k + 1]))


@pytest.mark.parametrize("model", ["uc", "sslp"])
@pytest.mark.parametrize("path", ["qp_solve", "fused"])
def test_host_classification_is_the_loops(model, path, monkeypatch):
    """Run the solve with a cap that some rows meet and some do not;
    the booked over-rows equal ``~conv_ok`` recomputed in numpy float64
    from the returned iterates, row for row, primal and dual apart."""
    S, cap = 8, 50
    if path == "qp_solve":
        ph = PHBase(_BATCH[model](S), {"defaultPHrho": 50.0},
                    dtype=jnp.float64)
        factors, data = ph._get_factors(False)
        q = ph.c

        def solve(e_pri, e_dua):
            st, *_ = qp_solve(factors, data, q,
                              qp_cold_state(factors, data), max_iter=cap,
                              eps_abs=e_pri, eps_rel=e_pri,
                              eps_abs_dua=e_dua, eps_rel_dua=e_dua,
                              polish=False)
            return st
        tests = lambda e_pri, e_dua: (e_pri, e_dua, 0, cap)
    else:
        # the operands of an engine's own hot call of the fused df32
        # program, then the program itself at this test's tolerances
        from mpisppy_tpu.ops import kernels
        seen = {}
        real = kernels.fused_mixed_solve

        def record(*a, **kw):
            seen["call"] = (a, kw)
            return real(*a, **kw)
        monkeypatch.setattr(kernels, "fused_mixed_solve", record)
        ph = _hot(PHBase(_BATCH[model](S), dict(_DF32), dtype=jnp.float64),
                  2)
        assert ph.phase_timing(True)["kernel"]["mode"] == "fused"
        (factors, A_lo, data, q, st0), kw = seen["call"]

        def solve(e_pri, e_dua):
            st, *_ = real(factors, A_lo, data, q, st0, **dict(
                kw, bulk_iter=cap, tail_iter=cap, eps_abs=e_pri,
                eps_rel=e_pri, eps_abs_dua=e_dua, eps_rel_dua=e_dua,
                stall_rel=0.0))
            return st
        tests = lambda e_pri, e_dua: (e_pri, e_dua, cap, cap)
    # first against a tolerance nobody meets: what each row would need
    need_p, need_d = _np_exit_test(factors, data, q, solve(1e-14, 1e-14))
    e_pri, e_dua = _split_at(need_p), _split_at(need_d)
    st = solve(e_pri, e_dua)
    assert int(st.iters) - int(st.iters_lo) == cap     # the tail capped
    need_p, need_d = _np_exit_test(factors, data, q, st)
    want_p, want_d = need_p > e_pri, need_d > e_dua
    # rows within a hair of a tolerance may round either way between
    # the program's residual arithmetic and numpy's
    clear = (np.abs(need_p / e_pri - 1) > 1e-3) \
        & (np.abs(need_d / e_dua - 1) > 1e-3)
    assert clear.sum() >= S - 1
    assert 0 < want_p.sum() < S or 0 < want_d.sum() < S
    res = np.stack([np.asarray(getattr(st, f), np.float64)
                    for f in EXIT_ROWS])
    got_p, got_d, _, _ = _rows_over(res, e_pri, e_dua)
    np.testing.assert_array_equal(got_p[clear], want_p[clear])
    np.testing.assert_array_equal(got_d[clear], want_d[clear])
    # and as booked: counts by test, the tally by scenario id
    ex = _new_phase_entry()["exits"]
    ex["tally"] = np.zeros(S, np.int64)
    ids, live = _row_map(np.arange(S)[None, :], [S], S)
    rec = _book_exits(ex, [(int(st.iters), int(st.iters_lo))],
                      tests(e_pri, e_dua), 0.5, res[:, None, :], ids, live)
    assert ex["solves"] == ex["tail_capped"] == ex["rows_read"] == 1
    assert ex["capped_calls"] == 1 and ex["capped_solve_seconds"] == 0.5
    assert ex["rows_over_pri_only"] == (got_p & ~got_d).sum()
    assert ex["rows_over_dua_only"] == (got_d & ~got_p).sum()
    assert ex["rows_over_both"] == (got_p & got_d).sum()
    np.testing.assert_array_equal(ex["tally"], (got_p | got_d).astype(int))
    assert rec == [[cap], int((got_p | got_d).sum())] == ex["per_call"][0]
    assert ex["worst_pri"] == pytest.approx(need_p.max() / e_pri, rel=1e-3)
    assert ex["worst_dua"] == pytest.approx(need_d.max() / e_dua, rel=1e-3)


def test_rows_over_edge_rows():
    """A zero residual passes under any scale, a NaN row is over by
    both tests, an infinite one (a cold state's) too."""
    res = np.array([[0.0, np.nan, np.inf, 2e-3],      # pri_rel
                    [0.0, np.nan, np.inf, 2e-3],      # pri_res (scale 1)
                    [0.0, np.nan, np.inf, 1e-3],      # dua_res
                    [0.0, np.nan, np.inf, 1e-3]])     # dua_rel (scale 1)
    over_p, over_d, by_p, by_d = _rows_over(res, 1e-4, 1e-2)
    assert over_p.tolist() == [False, True, True, True]
    assert over_d.tolist() == [False, True, True, False]
    assert by_p[3] == pytest.approx(2e-3 / 2e-4)
    assert by_d[3] == pytest.approx(1e-3 / 2e-2)


# ---------------- (b) the entry on every solve path ----------------

def _check_sums(pt):
    """What holds on every path: the histograms count the solves, and
    their iterations ARE the ADMM totals booked beside the seconds."""
    ex, admm, calls = pt["exits"], pt["admm_iters_per_call"], pt["calls"]
    assert sum(ex["tail_hist"].values()) == ex["solves"] \
        == sum(ex["bulk_hist"].values())
    assert sum(k * v for k, v in ex["bulk_hist"].items()) \
        == admm["bulk"] * calls
    assert sum(k * v for k, v in ex["tail_hist"].items()) \
        == admm["tail"] * calls
    assert len(ex["per_call"]) == calls
    assert sum(len(t) for t, _ in ex["per_call"]) == ex["solves"]
    assert sum(n for _, n in ex["per_call"]) == ex["rows_over"]
    assert ex["rows_over"] == ex["rows_over_pri_only"] \
        + ex["rows_over_dua_only"] + ex["rows_over_both"]
    assert ex["scenarios_over"] >= len(ex["top"])
    json.dumps(ex)                      # plain host values throughout
    return ex


@pytest.mark.parametrize("path", ["unchunked", "chunked", "mesh",
                                  "mesh-unchunked"])
def test_exits_with_no_session_on_the_full_pass_paths(path):
    """Against a tolerance no row meets every solve runs its tail to
    the cap and every LIVE row is over: the counts are exact, chunk
    pads (S = 8 in chunks of 3) and zero-probability mesh pads (S = 6
    over four devices) are in none of them, and ``top`` names global
    scenario ids."""
    assert not obs.enabled()
    S, chunk, mesh = {"unchunked": (8, 0, None), "chunked": (8, 3, None),
                      "mesh": (6, 1, make_mesh(4)),
                      "mesh-unchunked": (6, 0, make_mesh(4))}[path]
    opts = dict(_DF32, **_TIGHT, subproblem_chunk=chunk)
    ph = _hot(PHBase(_uc_batch(S), opts, dtype=jnp.float64, mesh=mesh), 2)
    if mesh is not None:
        assert ph.batch.S == 8 and ph._S_orig == 6      # two pad rows
    ph.reset_phase_timing()
    assert ph.phase_timing(True) is None            # exits went with it
    calls = 3
    for _ in range(calls):
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
    pt = ph.phase_timing(True)
    assert pt["mode"] == ("sharded" if mesh is not None else "host")
    ex = _check_sums(pt)
    n_chunks = {"unchunked": 1, "chunked": 3, "mesh": 2,
                "mesh-unchunked": 1}[path]
    assert ex["solves"] == calls * n_chunks == ex["tail_capped"] \
        == ex["rows_read"]
    assert ex["tail_hist"] == {25: calls * n_chunks}
    assert ex["rows_over"] == calls * S                 # pads in no count
    assert ex["rows_over_uncapped"] == 0
    assert ex["scenarios_over"] == S
    assert ex["top"] == [[g, calls * 1] for g in range(8)][:min(S, 8)]
    assert ex["capped_calls"] == calls
    assert ex["capped_solve_seconds"] == pytest.approx(
        pt["seconds_per_call"]["solve"] * calls)
    assert ex["worst_pri"] > 1 and ex["worst_dua"] > 1
    assert ex["per_call"][0] == [[25] * n_chunks, S]
    if "unchunked" not in path:
        assert pt["gate_d2h_syncs_per_call"] >= 1.0
    # iter-0's mode booked its own entry, and the reset took both
    ph.reset_phase_timing()
    assert ph.phase_timing(False) is None


def test_exits_of_dispatch_passes_name_the_scenarios_they_solved():
    """APH φ-dispatch (S = 16, chunk 4, frac 0.25: one chunk solve of
    four rows a pass): against a tolerance no row meets, the tally is
    exactly how often each scenario was dispatched."""
    assert not obs.enabled()
    opts = dict(_DF32, **_TIGHT, subproblem_chunk=4, dispatch_frac=0.25,
                APHnu=1.0, APHgamma=1.0, convthresh=-1.0, PHIterLimit=8,
                iter0_feas_tol=1.0)
    aph = APH(_uc_batch(16), opts, dtype=jnp.float64)
    aph.solve_loop(w_on=False, prox_on=False)
    aph.Update_W()
    aph.iterate(1)                      # the forced full pass
    full = _check_sums(aph.phase_timing(True))
    assert full["solves"] == 4 and full["rows_over"] == 16
    aph.reset_phase_timing()
    times = np.zeros(16, int)
    for it in (2, 3, 4, 5):
        aph.iterate(it)
        solved = np.flatnonzero(np.asarray(aph._dispatched))
        assert solved.size == 4
        times[solved] += 1
    pt = aph.phase_timing(True)
    assert pt["dispatch"]["passes"] == pt["calls"] == 4
    assert pt["aph"]["gate_syncs"] == 4
    ex = _check_sums(pt)
    assert ex["solves"] == ex["tail_capped"] == 4
    assert ex["rows_over"] == 16 and ex["per_call"] == [[[25], 4]] * 4
    assert ex["scenarios_over"] == np.count_nonzero(times)
    order = sorted(np.flatnonzero(times), key=lambda g: (-times[g], g))
    assert ex["top"] == [[int(g), int(times[g])] for g in order[:8]]


def test_per_call_keeps_the_first_256_calls():
    ex = _new_phase_entry()["exits"]
    ex["tally"] = np.zeros(2, np.int64)
    for _ in range(ph_mod._PER_CALL_KEPT + 5):
        _book_exits(ex, [(50, 25)], (1e-4, 1e-2, 400, 100), 0.1)
    assert len(ex["per_call"]) == ph_mod._PER_CALL_KEPT
    assert ex["solves"] == ph_mod._PER_CALL_KEPT + 5
    assert ex["tail_capped"] == ex["bulk_capped"] == ex["capped_calls"] == 0


# ---------------- (c) a forced cap, a loose tolerance ----------------

@pytest.mark.parametrize("kind", ["tight", "loose", "native", "segmented"])
def test_capped_share_against_the_tolerance(kind):
    """``subproblem_tail_iter`` 25 against a tolerance nobody meets:
    every solve tail-capped. A tolerance everybody meets at the first
    check: none, and no row over. A native solve has no bulk phase: its
    one loop is booked as tail against ``subproblem_max_iter``. The
    host-segmented df32 driver books the same phase ends from the
    counts it holds (chunked, the gate's read carries its rows too)."""
    opts = dict(_DF32, subproblem_chunk=3)
    if kind in ("tight", "segmented"):
        opts.update(_TIGHT)
    if kind == "segmented":
        opts.update(subproblem_kernel_mode="segmented",
                    subproblem_segment=25)
    if kind == "loose":
        opts.update(subproblem_eps_hot=0.5, subproblem_eps_dua_hot=10.0)
    if kind == "native":
        opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 50,
                "subproblem_eps": 1e-12, "subproblem_chunk": 3,
                "subproblem_hospital": False}
    ph = _hot(PHBase(_uc_batch(8), opts, dtype=jnp.float64), 1)
    ph.reset_phase_timing()
    ph.solve_loop(w_on=True, prox_on=True)
    ex = _check_sums(ph.phase_timing(True))
    assert ex["solves"] == 3
    if kind == "loose":
        assert ex["tail_capped"] == ex["bulk_capped"] == 0
        assert ex["tail_hist"] == {25: 3} and max(ex["bulk_hist"]) < 400
        assert ex["rows_over"] == ex["rows_over_uncapped"] == 0
        assert ex["capped_calls"] == 0 and ex["top"] == []
    elif kind == "native":
        assert ex["bulk_hist"] == {0: 3} and ex["bulk_capped"] == 0
        assert ex["tail_hist"] == {50: 3} and ex["tail_capped"] == 3
        assert ex["rows_over"] == 8
    else:
        assert ex["tail_capped"] == 3 == ex["rows_read"]
        assert ex["rows_over"] == 8 and ex["scenarios_over"] == 8


def test_unchunked_segmented_path_books_the_ends_without_the_rows():
    """The host-driven segmented drivers on the un-chunked body (the
    served farmer's path): the phase ends come from the counts the
    driver holds; its residual rows are not on the host and are not
    fetched."""
    opts = dict(_DF32, **_TIGHT, subproblem_kernel_mode="segmented",
                subproblem_segment=25)
    ph = _hot(PHBase(_uc_batch(8), opts, dtype=jnp.float64), 2)
    pt = ph.phase_timing(True)
    assert pt["kernel"]["mode"] == "segmented"
    ex = _check_sums(pt)
    assert ex["solves"] == ex["tail_capped"] == 1
    assert ex["rows_read"] == ex["rows_over"] == 0 and ex["top"] == []


# ---------------- (d) what it must not cost ----------------

def test_one_gate_read_and_the_fused_program_does_not_know(monkeypatch):
    """The chunked pass still reads the device once at the gate, and
    the fused df32 program lowered from an engine with the booking in
    the path is, character for character, the one lowered with the
    booking's call taken out."""
    import mpisppy_tpu.ops.kernels.reference as ref
    opts = dict(_DF32, subproblem_tail_iter=150, subproblem_chunk=2)
    fn = ref._fused_mixed_jit_donated

    def lowered(book):
        calls = {}

        def record(*a, **kw):
            calls.setdefault("args", (a, kw))
            return fn(*a, **kw)
        monkeypatch.setattr(ref, "_fused_mixed_jit_donated", record)
        if not book:
            monkeypatch.setattr(PHBase, "_book_call_exits",
                                lambda self, *a, **kw: None)
        ph = _hot(PHBase(_uc_batch(4), dict(opts), dtype=jnp.float64), 3)
        ph.reset_phase_timing()
        _hot(ph, 3)
        pt = ph.phase_timing(True)
        assert pt["calls"] == 2 and pt["gate_d2h_syncs_per_call"] == 1.0
        assert (pt["exits"]["solves"] == 4) == book
        a, kw = calls["args"]
        return fn.lower(*a, **kw).as_text()

    with_booking = lowered(True)
    assert lowered(False) == with_booking


# ---------------- (e) the standing rows, folded in ----------------

def test_standing_note_and_counter_read_what_a_loop_over_the_rows_reads(
        tmp_path):
    """Rows still above the recovery gate after passes 2 / 2b: the
    ``ph.standing`` event, the ``ph.standing_rows`` counter and
    ``exits["rows_over_gate"]`` come from one mask over the rows, and
    read what the parent's loop read: every live row not at or under
    the gate, the worst of them named by its global id."""
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 25,
            "subproblem_eps": 1e-9, "subproblem_chunk": 3,
            "subproblem_hospital": False}
    ph = _hot(PHBase(_uc_batch(8), opts, dtype=jnp.float64), 1)
    # no retry can cure a row: every chunk is blacklisted
    ph._chunk_no_retry[True] = {0, 1, 2}
    obs.configure(out_dir=str(tmp_path))
    try:
        before = obs.counters_snapshot()
        ph.reset_phase_timing()
        ph.solve_loop(w_on=True, prox_on=True)
        after = obs.counters_snapshot()
        rec = ph.iteration_record(1, 0.0, {}, before)
    finally:
        obs.shutdown()
    pri = np.asarray(ph._qp_states[True].pri_rel)[:8]
    thr = 1e-2
    standing = [(g, float(pri[g])) for g in range(8) if not pri[g] <= thr]
    assert standing, "the toy must leave rows above the gate"
    ex = ph.phase_timing(True)["exits"]
    assert ex["rows_over_gate"] == len(standing)
    assert after.get("ph.standing_rows", 0) \
        - before.get("ph.standing_rows", 0) == len(standing)
    events = [json.loads(ln) for ln in
              open(tmp_path / "events.jsonl") if ln.strip()]
    note, = [e for e in events if e.get("type") == "ph.standing"]
    g_w, pr_w = max(standing, key=lambda t: t[1])
    assert (note["rows"], note["gate"], note["worst_scenario"]) == \
        (len(standing), thr, g_w)
    assert note["worst_pri_rel"] == pr_w
    # in a session the per-call record rides ph.iteration, and the two
    # counters stand beside kernel.bulk_iters
    assert rec["exits"] == {"tail_iters": ex["per_call"][-1][0],
                            "rows_over": ex["per_call"][-1][1]}
    assert after.get("kernel.tail_capped", 0) \
        - before.get("kernel.tail_capped", 0) == ex["tail_capped"] == 3
    assert after.get("kernel.rows_over", 0) \
        - before.get("kernel.rows_over", 0) == ex["rows_over"] == 8
    assert rec["counter_deltas"]["ph.standing_rows"] == len(standing)
