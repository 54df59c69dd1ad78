"""The APH engine's own step (``APH.iterate``, ISSUE 34) against the
benchmark's plain reference (``benchmarks/reference/aph_step.py``: one
iteration's outer mathematics and the dispatch selection in numpy
float64; it imports nothing of the program), on a toy UC instance
under φ-dispatch through the chunked-skip path: S = 16, chunk 4, frac
0.25, so every partial pass is ONE chunk solve of four rows, as the
cell ``uc_s256_aph_hot`` makes one of 64. Also: the step driven from
outside against ``APH_main``, the one-chip pool as a rank's share of a
per-rank pool, and the seconds and counts ``phase_timing`` carries
with no telemetry session. Since ISSUE 35 the step's device work is ONE
program (``core/aph._aph_step``): held here to the sequence of programs
it replaced bit for bit, to one compile per static shape, and to the
device twins of the host's mask and stamps."""

import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.aph import APH, _aph_step, _aph_update
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import uc
from mpisppy_tpu.ops.dispatch import (GATE_HEAD, dispatch_gate,
                                      dispatch_select, gather_chunks,
                                      place_chunks, scalar_gate)

S, CHUNK, FRAC, ITERS = 16, 4, 0.25, 7
SOLVED = int(np.ceil(FRAC * S))
OPTS = {"defaultPHrho": 50.0, "subproblem_max_iter": 1200,
        "subproblem_eps": 1e-6, "subproblem_chunk": CHUNK,
        "dispatch_frac": FRAC, "APHnu": 1.0, "APHgamma": 1.0,
        "convthresh": -1.0, "PHIterLimit": ITERS,
        # APH_main's iter-0 abort must not read the budget's end as
        # infeasibility (one toy row stops at 1.3e-3)
        "iter0_feas_tol": 1e-2}


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference",
        "aph_step.py")
    spec = importlib.util.spec_from_file_location("bench_reference_aph",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def engine(**options):
    batch = build_batch(uc.scenario_creator, uc.make_tree(S),
                        creator_kwargs={"num_gens": 3, "num_hours": 6},
                        vector_patch=uc.scenario_vector_patch)
    return APH(batch, dict(OPTS, **options), dtype=jnp.float64)


def iter0(aph):
    """What ``APH_main`` does before its first ``iterate``."""
    aph.solve_loop(w_on=False, prox_on=False)
    aph.Update_W()


def before(aph):
    """Host copies of everything one iteration reads and may move."""
    host = {k: np.asarray(getattr(aph, k)).copy()
            for k in ("x", "yA", "yB", "W", "z", "y_aph", "prob", "rho")}
    host["xn"] = np.asarray(aph.nonants_of(aph.x))
    host["dispatched"] = np.asarray(aph._dispatched).copy()
    host["last"] = np.asarray(aph._last_dispatch).copy()
    return host


@pytest.fixture(scope="module")
def stepped(ref):
    """One engine stepped ITERS times from outside; per iteration the
    state before it, the reference's answer and the engine's."""
    assert jax.config.jax_enable_x64 and not obs.enabled()
    aph = engine()
    iter0(aph)
    trail = []
    for it in range(1, ITERS + 1):
        b = before(aph)
        assert aph.iterate(it) is True
        want = ref.aph_step(b["xn"], b["W"], b["z"], b["y_aph"],
                            b["prob"], b["rho"], b["dispatched"],
                            b["last"], aph.nu, aph.gamma, it, FRAC)
        trail.append((it, b, want, before(aph),
                      {k: getattr(aph, k)
                       for k in ("tau", "phi", "theta", "conv")},
                      dict(aph._aph_status),
                      np.asarray(aph.phis).copy()))
    return aph, trail


@pytest.fixture
def compile_log():
    """{iteration: entry names of the backend compiles made since the
    engine's iter-0} of an engine of its own (jax's duration event
    carries ``fun_name``), stepped with the traces of the three programs
    the count is of dropped first: what the worker compiled before, in
    this file or in another, is not in the count."""
    from jax import monitoring
    compiled = []

    def on(name, _secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kw.get("fun_name")))

    aph = engine()
    iter0(aph)
    for program in (_aph_step, gather_chunks, place_chunks):
        program.clear_cache()
    logs = {}
    monitoring.register_event_duration_secs_listener(on)
    try:
        for it in range(1, ITERS + 1):
            assert aph.iterate(it) is True
            logs[it] = list(compiled)
    finally:
        monitoring.unregister_event_duration_listener(on)
    return logs


def rel(got, want):
    got, want = np.asarray(got, float), np.asarray(want, float)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-300))


def test_engine_against_the_plain_reference(stepped):
    _aph, trail = stepped
    assert len(trail) >= 6
    for it, b, want, after, scalars, status, phis in trail:
        # float64 against float64: the limit is the order of the sums
        assert rel(after["W"], want["W"]) <= 1e-12, it
        assert rel(after["z"], want["z"]) <= 1e-12, it
        assert rel(after["y_aph"], want["y"]) <= 1e-12, it
        for k in ("tau", "phi", "theta", "conv"):
            assert scalars[k] == pytest.approx(want[k], rel=1e-12,
                                               abs=1e-300), (it, k)
        assert rel(phis, want["phis"]) <= 1e-9, it
        # the selection is the reference's, exactly
        assert after["dispatched"].tolist() == want["mask"].tolist(), it
        n = int(want["mask"].sum())
        assert n == (S if it == 1 else SOLVED) == status["dispatched"]
        assert status["solve_path"] == \
            ("full" if it == 1 else "chunked-skip")
        assert (after["last"][want["mask"]] == it).all()
        # an undispatched scenario is carried unchanged, bit for bit
        keep = ~want["mask"]
        for f in ("x", "yA", "yB"):
            np.testing.assert_array_equal(after[f][keep], b[f][keep])
            if it > 1:
                assert (after[f][want["mask"]]
                        != b[f][want["mask"]]).any(), (it, f)
        assert (after["last"][keep] == b["last"][keep]).all()
    # φ decides, not the order of the rows: some pass leaves row 0 out
    assert any(not t[2]["mask"][:SOLVED].all() for t in trail[1:])


def test_the_iteration_number_is_an_operand(compile_log):
    """``it`` reaches the step program as a traced scalar (it stamps
    the dispatched rows): the step compiles for iteration 1 (every row,
    z := x̄) and for the first partial pass, and iterations 3 .. ITERS
    compile nothing at all, the store's gather and placement included."""
    assert ITERS >= 7
    by2 = compile_log[2]
    assert sum("_aph_step" in n for n in by2) == 2, by2
    for name in ("gather_chunks", "place_chunks"):
        assert sum(name in n for n in by2) == 1, by2
    assert compile_log[ITERS] == by2, compile_log[ITERS][len(by2):]


def test_iterate_from_outside_is_aph_main(stepped):
    aph, _trail = stepped
    main = engine()
    main.APH_main(finalize=False)
    assert main._iter == aph._iter == ITERS
    for f in ("x", "W", "z", "y_aph", "yA", "yB", "phis", "xbar"):
        np.testing.assert_array_equal(np.asarray(getattr(main, f)),
                                      np.asarray(getattr(aph, f)), f)
    assert (main.tau, main.phi, main.theta, main.conv) == \
        (aph.tau, aph.phi, aph.theta, aph.conv)
    assert main._dispatched.tolist() == aph._dispatched.tolist()
    assert main._last_dispatch.tolist() == aph._last_dispatch.tolist()


def rank_pool_mask(phis, last, scnt):
    """``APH._dispatch_mask`` on a rank's own rows, as ``aph_shard``'s
    engine calls it on its local batch: the real host code on a bare
    namespace."""
    n = len(phis)
    ns = SimpleNamespace(batch=SimpleNamespace(S=n), _S_orig=n, phis=phis,
                         _last_dispatch=last)
    return APH._dispatch_mask(ns, 0, (scnt - 0.5) / n)


@pytest.mark.parametrize("rank", range(4))
def test_one_chip_pool_is_a_ranks_share(stepped, ref, rank):
    """Upstream dispatches ``dispatch_frac`` of EACH rank's scenarios.
    Rank r of four holds rows 4r .. 4r+3 of the 16 and picks one of
    THOSE; a one-chip engine whose whole pool is those rows (the device
    selection at S = 4) picks the same one, so a chip's cell is an
    honest share of the deployment and every rank solves the same
    count."""
    _aph, trail = stepped
    rows = slice(4 * rank, 4 * rank + 4)
    for it, b, _want, _after, _sc, _st, phis in trail[1:]:
        # what this iteration's selection read: its post-step φ and
        # the stamps from before its pass
        p, last = phis[rows], b["last"][rows]
        host = rank_pool_mask(p, last, 1)
        dev = np.asarray(dispatch_select(jnp.asarray(p), jnp.asarray(last),
                                         scnt=1, S_real=4))
        assert host.tolist() == dev.tolist() == \
            ref.select(p, last, 1).tolist(), (it, rank)
        assert host.sum() == 1


def test_phase_timing_carries_dispatch_and_aph_with_no_session():
    assert not obs.enabled()
    aph = engine()
    iter0(aph)
    aph.iterate(1)                      # the forced full pass
    full = aph.phase_timing(True)
    assert full["dispatch"]["passes"] == 0 and full["calls"] == 1
    assert full["aph"]["iterations"] == full["aph"]["gate_syncs"] == 1
    aph.reset_phase_timing()
    assert aph.phase_timing(True) is None
    for it in (2, 3, 4):
        aph.iterate(it)
    pt = aph.phase_timing(True)
    d = pt["dispatch"]
    assert (d["passes"], d["chunks"], d["solved"], d["skipped"]) == \
        (3, 3, 3 * SOLVED, 3 * (S - SOLVED))
    assert d["gather_seconds"] > 0 and d["scatter_seconds"] > 0
    assert d["bucket_compiles"] <= 1
    # ONE device program each way a pass, whatever it moves
    assert d["gather_programs"] == d["scatter_programs"] == d["passes"]
    # the gather is assembly, the scatter-back is part of the reduce
    sec = pt["seconds_per_call"]
    assert sec["assemble"] * 3 >= d["gather_seconds"]
    assert sec["reduce"] * 3 >= d["scatter_seconds"]
    a = pt["aph"]
    assert a["iterations"] == a["gate_syncs"] == 3
    assert a["project_seconds"] > 0 and a["gate_seconds"] > 0
    # one step program an iteration, fed from the device: the mask and
    # the stamps went up once, at iteration 1, before the reset
    assert a["project_programs"] == 3 and a["twin_seeds"] == 0
    assert full["aph"]["project_programs"] == 1 == full["aph"]["twin_seeds"]
    assert full["dispatch"]["gather_programs"] == 0
    # a dispatch pass's chunk solve is counted as a full pass's are
    assert pt["calls"] == 3 and pt["assemble_programs_per_call"] == 1
    admm = pt["admm_iters_per_call"]
    assert admm["bulk"] + admm["tail"] > 0
    aph.reset_phase_timing()
    aph.iterate(5)
    again = aph.phase_timing(True)
    assert again["dispatch"]["passes"] == 1 == again["aph"]["iterations"]
    assert again["dispatch"]["bucket_compiles"] == 0
    assert again["dispatch"]["gather_programs"] \
        == again["dispatch"]["scatter_programs"] \
        == again["aph"]["project_programs"] == 1


def test_the_four_spans_are_in_a_profiler_capture(profiler_capture):
    assert not obs.enabled()
    aph = engine()
    iter0(aph)
    aph.iterate(1)
    with profiler_capture as cap:
        aph.iterate(2)
    names = [e[0] for e in cap.spans(("ph.", "aph."))]
    for name in ("aph.project", "aph.gate", "ph.dispatch.gather",
                 "ph.dispatch.scatter"):
        assert names.count(name) == 1, (name, names)
    assert cap.inside("ph.dispatch.scatter", "ph.reduce")
    first = {n: min(e[2] for e in cap.events if e[0] == n)
             for n in ("aph.project", "aph.gate", "ph.dispatch.gather",
                       "ph.solve.chunk", "ph.dispatch.scatter")}
    assert list(first) == sorted(first, key=first.get)
    assert names.count("ph.solve.chunk") == 1


# ---------------- the step as ONE program (ISSUE 35) ----------------

@jax.jit
def update_y(W_y, rho, xn, z_y, mask, y):
    """Update_y as one piece of the sequence: in one loop the CPU
    contracts its multiply-add (one rounding where three eager programs
    made two), in the step program as here."""
    return jnp.where(mask[:, None], W_y + rho * (xn - z_y), y)


def eager_step(aph, it):
    """What ``APH.iterate`` launched before its gate read until ISSUE
    35, program by program, from the engine's state: the gather, the
    y-update with an UPLOADED mask, three eager ``compute_xbar``s,
    ``_aph_update`` and a gate program with UPLOADED stamps."""
    S_real = aph._S_orig
    scnt = S_real if it == 1 else \
        max(1, int(np.ceil(S_real * aph.dispatch_frac)))
    xn = aph.nonants_of(aph.x)
    y = aph.y_aph
    if it > 1:
        W_y, z_y = (aph._W_lag, aph._z_lag) if aph.use_lag \
            else (aph.W, aph.z)
        y = update_y(W_y, aph.rho, xn, z_y, jnp.asarray(aph._dispatched),
                     y)
    xbar, xsqbar, ybar = (aph.compute_xbar(v) for v in (xn, xn * xn, y))
    W, z, tau, phi, theta, conv, phis = _aph_update(
        xn, aph.W, y, aph.z, aph.rho, aph.prob, xbar, ybar, aph.nu,
        aph.gamma, iter1=(it == 1))[:7]
    if scnt >= S_real:
        gate = scalar_gate(tau, phi, theta, conv, phis, S_real=S_real)
    else:
        gate = dispatch_gate(tau, phi, theta, conv, phis,
                             jnp.asarray(aph._last_dispatch), scnt=scnt,
                             S_real=S_real)
    return {"W": W, "z": z, "xbar": xbar, "xsqbar": xsqbar, "ybar": ybar,
            "y_aph": y, "phis": phis}, np.asarray(gate)


@pytest.mark.parametrize("frac,lag", [(FRAC, False), (FRAC, True),
                                      (1.0, False), (1.0, True)])
def test_one_program_step_is_the_eager_sequence_bit_for_bit(frac, lag):
    """Every array the step hands on and every number of its gate row,
    over ITERS iterations (the forced full pass, then partial passes or
    full ones), with and without the lagged (W, z); and after every
    pass the device's mask and stamps are the host's, uploaded once."""
    aph = engine(dispatch_frac=frac, aph_use_lag=lag)
    iter0(aph)
    for it in range(1, ITERS + 1):
        if lag and it == 1:
            aph._W_lag, aph._z_lag = aph.W, aph.z   # iterate(1) does
        want, gate = eager_step(aph, it)
        assert aph.iterate(it) is True
        for f, v in want.items():
            np.testing.assert_array_equal(np.asarray(getattr(aph, f)),
                                          np.asarray(v), f"{f} at {it}")
        assert (aph.tau, aph.phi, aph.theta, aph.conv) \
            == tuple(gate[:4].tolist()), it
        if frac < 1.0 and it > 1:
            assert aph._dispatched.tolist() \
                == (gate[GATE_HEAD:] != 0).tolist(), it
            assert aph._dispatched.sum() == SOLVED
        else:
            assert aph._dispatched.all() and gate.size == GATE_HEAD
        _mask, _stamps, mask_dev, stamps_dev = aph._twins
        assert np.asarray(mask_dev).tolist() == aph._dispatched.tolist()
        assert np.asarray(stamps_dev).tolist() \
            == aph._last_dispatch.tolist(), it
    assert aph.phase_timing(True)["aph"]["twin_seeds"] == 1
    assert aph.phase_timing(True)["aph"]["project_programs"] == ITERS


@pytest.mark.parametrize("route", ["install_aph_state", "assignment"])
def test_a_host_write_reseeds_the_device_twins(route):
    """The checkpoint route (``install_aph_state``) and a caller's
    assignment (``APHShard``, tests) change the host's mask and stamps
    behind the step program's back: the next step takes THEM, not what
    the last step left on the device."""
    aph = engine()
    iter0(aph)
    for it in (1, 2, 3):
        aph.iterate(it)
    seeds = aph._aph_times["twin_seeds"]
    assert seeds == 1
    stamps = np.roll(aph._last_dispatch, 5) + 7
    mask = np.roll(aph._dispatched, 3)
    assert mask.sum() == SOLVED and (mask != aph._dispatched).any()
    if route == "install_aph_state":
        state = aph.aph_state_arrays()
        state.update(aph_last_dispatch=stamps,
                     aph_dispatched=mask.astype(np.int64))
        aph.install_aph_state(state)
    else:
        aph._last_dispatch = stamps.copy()
        aph._dispatched = mask.copy()
        aph.phis = np.zeros(S)      # the step overwrites it, unread
    y_before = np.asarray(aph.y_aph).copy()
    want, gate = eager_step(aph, 4)
    aph.iterate(4)
    assert aph._aph_times["twin_seeds"] == seeds + 1
    # Update_y ran on the rows the HOST named ...
    np.testing.assert_array_equal(np.asarray(aph.y_aph),
                                  np.asarray(want["y_aph"]))
    moved = (np.asarray(aph.y_aph) != y_before).any(axis=1)
    assert moved[mask].all() and not moved[~mask].any()
    # ... the selection read the host's stamps, and the device's new
    # ones are the host's, rows this pass left alone included
    new = gate[GATE_HEAD:] != 0
    assert aph._dispatched.tolist() == new.tolist()
    expect = np.where(new, 4, stamps)
    assert aph._last_dispatch.tolist() == expect.tolist()
    assert np.asarray(aph._twins[3]).tolist() == expect.tolist()
    aph.iterate(5)
    assert aph._aph_times["twin_seeds"] == seeds + 1
