"""The in-process wheel (PH hub + Lagrangian outer spoke + x-hat pool
spoke through ``wheel_dicts`` / ``spin_the_wheel``) held to the plain
reference ``benchmarks/reference/wheel_bounds.py`` on a toy UC instance,
and the two things ISSUE 39 added under it: the arbiter that admits the
cylinders' chunk solves to the one device queue, and what the engines
of one wheel share, keep private and free (doc/cylinders.md)."""

import os
import sys
import threading
import time

import numpy as np
import pytest

REF = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference")
if REF not in sys.path:
    sys.path.insert(0, REF)

import wheel_bounds as ref  # noqa: E402

from mpisppy_tpu.utils import sputils  # noqa: E402
from mpisppy_tpu.utils.config import (AlgoConfig, RunConfig,  # noqa: E402
                                      SpokeConfig)
from mpisppy_tpu.utils.runtime import (WheelArbiter,  # noqa: E402
                                       wheel_host_section)
from mpisppy_tpu.utils.vanilla import wheel_dicts  # noqa: E402

S, CHUNK = 4, 2
TOY = dict(num_gens=3, num_hours=6)
DIVE = dict(xhat_pin_vars=["u"], incumbent_pool_thresholds=[0.5],
            incumbent_pool_flips=0, incumbent_pool_random=0)


def toy_config(spokes=True, iters=12):
    opts = dict(subproblem_chunk=CHUNK)
    return RunConfig(
        model="uc", num_scens=S, model_kwargs=dict(TOY),
        algo=AlgoConfig(default_rho=100.0, max_iterations=iters,
                        convthresh=-1.0),
        hub_options=dict(opts),
        spokes=[SpokeConfig("lagrangian", dict(opts)),
                SpokeConfig("dive", dict(opts, **DIVE))] if spokes else [],
        incumbent_mode="device")


@pytest.fixture(scope="module")
def wheel():
    hub_d, spoke_ds = wheel_dicts(toy_config())
    w = sputils.spin_the_wheel(hub_d, spoke_ds)
    b = w.hub.opt.batch
    data = dict(A=ref.sparse(b.A), c=np.asarray(b.c), c0=np.asarray(b.c0),
                l=np.asarray(b.l), u=np.asarray(b.u), lb=np.asarray(b.lb),
                ub=np.asarray(b.ub), prob=np.asarray(b.prob),
                idx=np.asarray(b.nonant_idx),
                integer=np.asarray(b.integer, bool))
    return w, data


def scen(d, s):
    return (d["A"], d["c"][s], d["c0"][s], d["l"][s], d["u"][s],
            d["lb"][s], d["ub"][s])


# ---- the wheel against the reference ----
@pytest.mark.parametrize("integer", [False, True],
                         ids=["lp_relaxation", "mip"])
def test_outer_le_zstar_le_inner(wheel, integer):
    w, d = wheel
    z = ref.extensive_form(d["A"], d["c"], d["c0"], d["l"], d["u"],
                           d["lb"], d["ub"], d["prob"], d["idx"],
                           integer=d["integer"] if integer else None)
    tol = 1e-6 * abs(z)
    assert np.isfinite(w.BestOuterBound) and np.isfinite(w.BestInnerBound)
    # the Lagrangian bound is the LP relaxation's; the incumbent is a
    # plan with integral commitments, feasible in every scenario
    assert w.BestOuterBound <= z + tol
    if integer:
        assert z <= w.BestInnerBound + 1e-4 * abs(z)


@pytest.mark.parametrize("s", range(S))
def test_outer_row_is_under_its_lp_value(wheel, s):
    """(i): every scenario value behind the outer spoke's last bound is
    certified: at most the exact LP value of f_s + W_s . x."""
    w, d = wheel
    lag = w.spokes[0]
    lb = lag.last_bound
    v = ref.lagrangian_value(*scen(d, s), np.asarray(lb["W"])[s], d["idx"])
    row = float(np.asarray(lb["rows"])[s])
    assert row <= v + 1e-7 * abs(v)
    assert row >= v - 0.25 * abs(v)          # and not -inf under it


def test_outer_bound_is_its_rows_expectation_on_the_manifold(wheel):
    w, d = wheel
    lb = w.spokes[0].last_bound
    assert lb["source"] >= 1                  # made from a hub W
    assert ref.w_is_dual_feasible(lb["W"], d["prob"], tol=1e-12)
    assert float(d["prob"] @ np.asarray(lb["rows"])) \
        == pytest.approx(lb["value"], rel=1e-12)
    # off the manifold the test must say so
    W_off = np.asarray(lb["W"]).copy()
    W_off[0, 0] += 1.0
    assert not ref.w_is_dual_feasible(W_off, d["prob"])


@pytest.mark.parametrize("s", range(S))
def test_inner_row_is_over_its_recourse_value(wheel, s):
    """(ii): the published plan is feasible in every scenario and the
    spoke's scenario value is not under the cheapest recourse to it."""
    w, d = wheel
    xh = w.spokes[1]
    assert xh.best_xhat is not None and xh.best_xhat_rows is not None
    pin = np.asarray(xh._pin_mask, bool)
    v, ok = ref.recourse_value(*scen(d, s), np.asarray(xh.best_xhat)[pin],
                               d["idx"][pin])
    assert ok
    row = float(np.asarray(xh.best_xhat_rows)[s])
    assert row >= v - 2e-3 * abs(v)
    assert row <= v + 1e-2 * abs(v)
    assert float(d["prob"] @ np.asarray(xh.best_xhat_rows)) \
        == pytest.approx(xh.bound, rel=1e-12)


def test_last_screen_rows_are_over_their_recourse_values(wheel):
    """What the pool's last completed round SCREENED: every candidate
    it judged feasible is a feasible plan, its per-row screen value is
    not under the cheapest recourse to it, and the verdict is the rows'
    expectation."""
    w, d = wheel
    xh = w.spokes[1]
    scr = xh.last_screen
    assert scr is not None and scr["source"] >= 1
    P = len(scr["objs"])
    rows = np.asarray(scr["rows"], float).reshape(P, -1)[:, :S]
    pin = np.asarray(xh._pin_mask, bool)
    ok_c = np.flatnonzero(scr["feas"] & np.isfinite(scr["objs"]))
    assert ok_c.size >= 1
    for c in ok_c:
        plan = np.where(d["integer"][d["idx"]], np.round(scr["pool"][c]),
                        scr["pool"][c])
        for s in range(S):
            v, ok = ref.recourse_value(*scen(d, s), plan[pin],
                                       d["idx"][pin])
            assert ok
            assert rows[c, s] >= v - 2e-3 * abs(v)
            assert rows[c, s] <= v + 0.25 * abs(v)
        assert float(d["prob"] @ rows[c]) \
            == pytest.approx(scr["objs"][c], rel=1e-9)


def test_the_pool_screen_books_how_its_solves_ended(wheel):
    """``phase_timing(("pool", False))``: the screen's chunk solves
    book their ADMM counts and exits like every other solve path."""
    w, _d = wheel
    xh = w.spokes[1]
    pt = xh.opt.phase_timing(("pool", False))
    rounds = xh._rounds
    assert pt["calls"] == rounds >= 1
    ex = pt["exits"]
    P = len(xh.last_screen["objs"])
    assert ex["solves"] == rounds * (P * S // CHUNK)
    assert 0 <= ex["tail_capped"] <= ex["solves"]
    assert sum(ex["tail_hist"].values()) == ex["solves"]
    its = pt["admm_iters_per_call"]
    assert its["bulk"] + its["tail"] > 0
    # the Lagrangian's pass books under its own mode, as before
    assert w.spokes[0].opt.phase_timing(False)["exits"]["solves"] > 0


@pytest.mark.parametrize("precision, given, want", [
    ("df32", None, 1e-3),         # the default lies under df32's floor
    ("df32", 1e-4, None),         # a user's value stands, df32 or not
    ("df32", 5e-3, None),
    ("native", None, None)])
def test_pool_screen_tolerance_defaults_to_the_df32_floor(precision, given,
                                                          want):
    from mpisppy_tpu.cylinders.xhat_bounders import DiveInnerBound
    from mpisppy_tpu.core.ph import PHBase

    hub_d, _ = wheel_dicts(toy_config(spokes=False, iters=1))
    kw = dict(hub_d["opt_kwargs"])
    kw["options"] = dict(kw["options"], subproblem_precision=precision)
    opts = dict(DIVE)
    if given is not None:
        opts["xhat_feas_tol"] = given
    sp = DiveInnerBound(PHBase(**kw), options=opts)
    assert sp._screen_kw == ({} if want is None else {"feas_tol": want})


def test_recourse_reference_says_infeasible(wheel):
    _w, d = wheel
    pin = d["idx"]
    # nothing committed, ever: the demand cannot be met
    v, ok = ref.recourse_value(*scen(d, 0), np.zeros(pin.size), pin)
    assert not ok and v == np.inf


def test_wheel_timing_reads_the_wheel(wheel):
    w, _d = wheel
    wt = w.hub.wheel_timing()
    cyl = wt["cylinders"]
    assert set(cyl) == {"hub", "spoke0", "spoke1"}
    # the hub: iter-0 and 12 hot passes of S / CHUNK chunk solves
    assert cyl["hub"]["turns"] == 13 * (S // CHUNK)
    assert cyl["hub"]["rows"] == 13 * S
    for name in ("spoke0", "spoke1"):
        assert cyl[name]["turns"] > 0 and cyl[name]["device_s"] > 0
    sync = wt["sync"]
    assert sync["syncs"] == 13 and sync["seconds"] > 0
    K = w.hub.opt.batch.K
    assert sync["bytes_read"] == 13 * 2 * S * K * 8
    lag, xh = wt["spokes"]["spoke0"], wt["spokes"]["spoke1"]
    assert lag["char"] == "L" and xh["char"] == "D"
    assert lag["published"] == lag["accepted"] + lag["rejected"] > 0
    assert len(lag["lag_iters"]) > 0 and min(lag["lag_iters"]) >= 0
    assert xh["own"]["rounds"]["rounds"] >= 1
    assert xh["own"]["rounds"]["verifications"] >= 1
    w.hub.reset_wheel_timing()
    again = w.hub.wheel_timing()
    assert again["sync"]["syncs"] == 0
    assert again["cylinders"]["hub"]["turns"] == 0
    assert again["spokes"]["spoke1"]["own"]["rounds"]["rounds"] == 0


# ---- the arbiter ----
def drive(n_cyl, turns, idle=None, in_pass=()):
    """``n_cyl`` threads take ``turns`` turns each. A turn is held
    until every other cylinder that still has turns to take (and is not
    idling on its host, ``idle``) is WAITING: who is ready is then the
    test's to say, not the scheduler's."""
    arb = WheelArbiter(["hub"] + [f"spoke{i}" for i in range(n_cyl - 1)])
    idle = idle or {}
    left = {n: turns for n in arb.names}
    away = set()                # idling on its host: not ready
    live, peak = [], [0]

    def others_waiting(me):
        want = {i for i, n in enumerate(arb.names)
                if n != me and left[n] > 0 and n not in away}
        return want <= arb._waiting

    def work(name):
        port = arb.port(name)
        if name in in_pass:
            port.begin_pass()
        while left[name]:
            with port(3):
                live.append(name)
                peak[0] = max(peak[0], len(live))
                left[name] -= 1
                t_end = time.monotonic() + 10.0
                while not others_waiting(name) \
                        and time.monotonic() < t_end:
                    time.sleep(0.0005)
                live.remove(name)
                if name in idle:
                    away.add(name)
            if name in idle:
                time.sleep(idle[name])
                away.discard(name)
        port.end_pass()

    ts = [threading.Thread(target=work, args=(n,)) for n in arb.names]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return arb, peak[0]


@pytest.mark.parametrize("n_cyl", [2, 3, 4])
def test_arbiter_fixed_order_among_ready_cylinders(n_cyl):
    arb, peak = drive(n_cyl, turns=6)
    order = [name for name, _rows, _a, _d in arb.turn_log()]
    # every cylinder always has a solve ready: the fixed cycle, from
    # the hub on
    assert order == list(arb.names) * 6
    assert peak == 1            # ONE solve in flight, ever
    tot = arb.totals()
    for name in arb.names:
        assert tot[name]["turns"] == 6 and tot[name]["rows"] == 18


@pytest.mark.parametrize("slow", ["hub", "spoke0", "spoke1"])
def test_arbiter_passes_over_a_cylinder_with_nothing_ready(slow):
    """A cylinder busy on its host is passed over and loses no turn of
    its own; one that waits is never skipped."""
    arb, _ = drive(3, turns=5, idle={slow: 0.02})
    order = [name for name, *_ in arb.turn_log()]
    assert sorted(order) == sorted(list(arb.names) * 5)
    fast = [n for n in arb.names if n != slow]
    # while both fast cylinders have turns left they alternate, with
    # at most the slow one between them: neither is ever skipped
    both = order[:max(i for i, n in enumerate(order) if n in fast
                      and order[:i + 1].count(n) == 5) + 1]
    seq = [n for n in both if n in fast]
    head = seq[:2 * min(seq.count(f) for f in fast)]
    assert all(a != b for a, b in zip(head, head[1:])), order
    # and the slow one never takes two turns running while they wait
    assert all(not (a == b == slow) for a, b in zip(both, both[1:])), order


@pytest.mark.parametrize("slow", ["hub", "spoke0", "spoke1"])
def test_arbiter_keeps_the_place_of_a_cylinder_in_a_pass(slow):
    """Busy on its host BETWEEN the solves of one pass, a cylinder is
    waited for: the order does not depend on how long its host work
    takes, and when it ends its pass the others run on."""
    arb, _ = drive(3, turns=5, idle={slow: 0.02}, in_pass={slow})
    order = [name for name, *_ in arb.turn_log()]
    assert order == list(arb.names) * 5


@pytest.mark.parametrize("slow", ["spoke0", "spoke1"])
def test_a_host_section_gives_up_the_place_and_takes_it_back(slow):
    """A spoke in a pass that enters a host-only section (an oracle's
    MILPs, the float64 certification) is passed over for its length:
    the other cylinders' turns do not wait for it; afterwards it is in
    its pass again."""
    arb = WheelArbiter(["hub", "spoke0", "spoke1"])
    ports = {n: arb.port(n) for n in arb.names}
    eng = type("E", (), {"_wheel_port": ports[slow]})()
    ports[slow].begin_pass()
    inside, go_on = threading.Event(), threading.Event()

    def host_work():
        with wheel_host_section(eng):
            assert not ports[slow].in_pass()
            inside.set()
            assert go_on.wait(30)
        assert ports[slow].in_pass()

    t = threading.Thread(target=host_work)
    t.start()
    assert inside.wait(30)
    # the two others take turns while the slow one is on its host: were
    # it still in its pass, the second turn would wait for ever
    others = [n for n in arb.names if n != slow]
    for name in others * 3:
        with ports[name](1):
            pass
    go_on.set()
    t.join(30)
    assert [n for n, *_ in arb.turn_log()] == others * 3
    with ports[slow](1):        # and it still gets its own
        pass
    ports[slow].end_pass()


def test_a_host_section_outside_a_pass_or_a_wheel_is_nothing():
    arb = WheelArbiter(["hub", "spoke0"])
    port = arb.port("spoke0")
    eng = type("E", (), {"_wheel_port": port})()
    with wheel_host_section(eng):           # not in a pass: stays out
        assert not port.in_pass()
    assert not port.in_pass()
    with wheel_host_section(object()):      # no port at all
        pass


def test_arbiter_books_waits_and_resets():
    arb, _ = drive(3, turns=4)
    tot = arb.totals()
    assert all(tot[n]["queue_wait_s"] > 0 for n in arb.names)
    assert all(tot[n]["device_s"] > 0 for n in arb.names)
    log = arb.turn_log()
    assert all(b[2] >= a[3] for a, b in zip(log, log[1:]))  # one at a time
    arb.reset()
    assert arb.totals()["hub"] == {"turns": 0, "rows": 0,
                                   "queue_wait_s": 0.0, "device_s": 0.0}


def test_a_hub_only_wheel_never_meets_the_arbiter():
    hub_d, spoke_ds = wheel_dicts(toy_config(spokes=False, iters=2))
    w = sputils.spin_the_wheel(hub_d, spoke_ds)
    assert w.hub.arbiter is None
    assert w.hub.opt._wheel_port is None
    assert w.hub.wheel_timing()["cylinders"] is None
    pt = w.hub.opt.phase_timing(True)
    assert "residency" not in pt["kernel"]
    # and keeps its iter-0 mode (nothing shares its memory)
    assert ("chunks", False) in w.hub.opt._qp_states


# ---- three engines in one memory ----
def test_shared_operands_are_one_buffer_in_every_engine(wheel):
    w, _d = wheel
    engines = [w.hub.opt] + [sp.opt for sp in w.spokes]
    first = engines[0].qp_data
    for e in engines[1:]:
        for f in ("l", "u", "lb", "ub"):
            assert getattr(e.qp_data, f) is getattr(first, f), f
        assert e.qp_data.A is first.A
    for e in engines:
        res = e.phase_timing(next(iter(e._phase_times)))["kernel"][
            "residency"]
        assert {"l", "u", "lb", "ub"} <= set(res["shared"])


def test_the_hub_frees_its_iter0_mode_and_says_so(wheel):
    w, _d = wheel
    hub = w.hub.opt
    assert ("chunks", False) not in hub._qp_states
    assert False not in hub._qp_states
    res = hub.residency()
    assert res["freed"] == ["w0-p0"] or len(res["freed"]) == 1
    assert len(res["private"]) == 1           # the hot mode alone
    # the spokes keep theirs: the Lagrangian's prox-off mode is its own
    assert ("chunks", False) in w.spokes[0].opt._qp_states
    assert w.spokes[0].opt.residency()["freed"] == []


# the benchmark's df32 recipe (bench.DF32) with its cells' iter-0 gate
DF32 = dict(subproblem_precision="df32", subproblem_max_iter=400,
            subproblem_eps=1e-5, subproblem_eps_hot=1e-4,
            subproblem_eps_dua_hot=1e-2, subproblem_stall_rel=1.5e-3,
            subproblem_tail_iter=100, subproblem_hospital=False,
            iter0_feas_tol=1e-2)


@pytest.mark.parametrize("recipe", [{}, DF32], ids=["native", "df32"])
def test_engines_of_a_wheel_equal_unshared_engines_bit_for_bit(recipe):
    """The same hub iterations from an engine of a wheel (shared
    operands, freed iter-0 mode, the inherited factor let go, every
    solve a turn) and from a lone engine over a batch of its own."""
    from mpisppy_tpu.core.ph import PH

    def run(in_wheel):
        hub_d, _ = wheel_dicts(toy_config(spokes=False, iters=3))
        hub_d["opt_kwargs"]["options"] = dict(
            hub_d["opt_kwargs"]["options"], **recipe)
        ph = PH(**hub_d["opt_kwargs"])
        if in_wheel:
            other = PH(**hub_d["opt_kwargs"])   # shares the batch's cache
            other.solve_loop(w_on=False, prox_on=False, update=False)
            ph._wheel_port = WheelArbiter(["hub"]).port("hub")
        ph.ph_main(finalize=False)
        return ph

    solo, shared = run(False), run(True)
    assert ("chunks", False) in solo._qp_states
    assert ("chunks", False) not in shared._qp_states
    for f in ("W", "xbar", "x"):
        a, b = np.asarray(getattr(solo, f)), np.asarray(getattr(shared, f))
        assert np.array_equal(a, b), f
    assert solo.conv == shared.conv


@pytest.mark.parametrize("in_wheel", [True, False],
                         ids=["engine of a wheel", "lone engine"])
def test_a_donating_df32_pass_lets_the_inherited_factor_go(monkeypatch,
                                                          in_wheel):
    """Two (n, n) factors an engine through a pass are one too many
    where three engines share one HBM: in a wheel the chunk states give
    up the factor they share between passes when a donating pass
    starts (its first solve takes it, the flow re-attaches the last);
    a lone engine keeps its states whole."""
    from mpisppy_tpu.core import ph as ph_mod

    hub_d, _ = wheel_dicts(toy_config(spokes=False, iters=2))
    hub_d["opt_kwargs"]["options"] = dict(
        hub_d["opt_kwargs"]["options"], **DF32)
    eng = ph_mod.PH(**hub_d["opt_kwargs"])
    if in_wheel:
        eng._wheel_port = WheelArbiter(["hub"]).port("hub")
    eng.ph_main(finalize=False)     # its hot states are private now
    held, real = [], ph_mod._solver_call

    def spy(factors, d, q, st, **kw):
        states = eng._qp_states[("chunks", True)]
        held.append((kw.get("donate"), np.ndim(st.L.tri
                                               if hasattr(st.L, "tri")
                                               else st.L),
                     [np.ndim(getattr(s.L, "tri", s.L)) for s in states]))
        return real(factors, d, q, st, **kw)

    monkeypatch.setattr(ph_mod, "_solver_call", spy)
    eng.solve_loop(w_on=True, prox_on=True, update=False)
    assert len(held) == S // CHUNK and all(h[0] for h in held)
    # every solve is handed a whole factor
    assert all(h[1] == 2 for h in held)
    for _don, _in, left in held:
        # what the not-yet-solved chunks' states keep meanwhile
        assert set(left) == ({0} if in_wheel else {2})
    after = eng._qp_states[("chunks", True)]
    assert all(np.ndim(getattr(s.L, "tri", s.L)) == 2 for s in after)
