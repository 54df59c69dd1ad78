"""Driver ``wheel_hot``: the hub of an in-process WHEEL iterating back
to back beside its busy spokes (PH hub + the configuration's bound
spokes as threads of one ``spin_the_wheel``, sharing one chip's memory
and one device queue).

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios            S of the run (the chip's share of the deployment)
  scenario_base        must be 0: the wheel is built by the program's own
                       builders (``utils/vanilla.wheel_dicts``), whose
                       scenarios are ids 0 .. S-1 in that order
  subproblem_chunk     rows per device call in ALL cylinders; left out,
                       the configuration's
  warm_hot_iterations  hub iterations that must have run before the
                       window may open
  ph_iter_range        K: ``ph_iter_s`` is the mean over the window's
                       first K HUB iterations
  reference_sample     hub iter-0 rows checked against the plain LP
  lagrangian_sample    rows of the outer spoke's last bound checked
                       against ``wheel_bounds.lagrangian_value``
  incumbent_sample     rows of the published incumbent checked against
                       ``wheel_bounds.recourse_value``
  trace_seconds        seconds of one extra hub iteration the profiler
                       records (--trace 1), after the window
  setup_deadline_s     give up (the run fails, loudly) when the window
                       has not opened this long after the hub started
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

The wheel is ``wheel_dicts(RunConfig)`` + ``spin_the_wheel``, the path
``python -m mpisppy_tpu uc --with-lagrangian --with-dive`` takes; the
driver hangs two of the program's own user hooks on the hub engine (the
dict schema's ``opt_kwargs``): an ``Extension`` that keeps iter-0's
solution, and a ``converger`` that is called once per hub iteration,
after its exchange: it opens the window, stamps iterations, and ends
the run when ``--seconds`` have passed. Nothing else stops the hub (no
gap, no iteration limit, no convergence threshold).

Set-up: host build, the three engines, iter-0, ``warm_hot_iterations``
hub iterations, and the window does not open before the outer spoke
has published a bound made from a hub W and the x-hat spoke has
finished a whole round with a verification (between them every program
the window runs has compiled or loaded) and one more hub iteration has
run after that; then the device's memory is compacted once
(``compact_hbm``). Window: hub iterations back to
back, both spokes running. ``ph_iter_s`` = wall seconds of the window's
first K hub iterations / K (an iteration ends after its exchange);
``solves_per_s`` = scenario rows solved inside the window by ALL
cylinders (``Hub.wheel_timing()["cylinders"]``: every admitted chunk
solve's real rows) / window seconds. The two together keep a change
from buying hub pace with spoke work or the reverse.

``correct`` compares what the window itself produced, snapshotted when
it closes, against ``reference/scenario_lp.py`` and
``reference/wheel_bounds.py``: the hub's state; the W and per-scenario
values of the outer spoke's LAST bound; the per-row values of every
candidate that the pool's last completed SCREEN judged feasible (a
screen must have ended inside the window). The published incumbent and
its per-scenario values are checked too, but they may date from
set-up: the first verified round is what opens the window, and a
later round publishes only where it improves on it. ``--seed`` draws
the sampled rows; the instance and its order are the same for every
seed.

``run.variant`` (tests and control runs only): ``instance`` /
``recipe`` as in ``ph_hot``; ``control`` = ``"uncertified_bound"`` (the
outer spoke publishes its PRIMAL objective, which no dual certifies,
with the df32 gate's tolerance as the unconverged excess: must fail
``outer_over_lp``) or ``"unverified_incumbent"`` (the x-hat spoke
publishes the pool screen's verdict with no verification solve: must
fail ``inner_publishes_verified``).
"""

import threading
import time

import numpy as np

import harness

_ph_hot = harness.load_module("drivers", "ph_hot")
sample_rows = _ph_hot.sample_rows
# what the uncertified control's spoke adds to the value it publishes:
# the df32 engines' own feasibility gate (incumbent_publish_feas_tol,
# 5e-3), what a solve stopped there may be off by
CONTROL_EXCESS = 5e-3


def _require_wheel_support():
    """A program without the wheel's arbiter and ``Hub.wheel_timing``
    cannot run this cell: say so and end at once, with no result."""
    try:
        from mpisppy_tpu.cylinders.hub import Hub
        from mpisppy_tpu.utils.runtime import WheelArbiter  # noqa: F401
        if not hasattr(Hub, "wheel_timing"):
            raise ImportError("Hub.wheel_timing")
    except ImportError as e:
        raise SystemExit(
            "benchmark: this checkout's program cannot run a wheel cell "
            f"(no {e}): the wheel_hot driver needs utils/runtime."
            "WheelArbiter and Hub.wheel_timing") from None


_HBM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
             "largest_free_block_bytes", "bytes_limit")


def _hbm():
    """The device allocator's numbers as the runtime tells them
    (``bytes_reserved`` lies outside ``bytes_in_use`` and its peak);
    empty where the backend keeps none (the CPU)."""
    import jax
    mem = jax.local_devices()[0].memory_stats() or {}
    return {k: mem[k] for k in _HBM_KEYS if k in mem}


def compact_hbm(port):
    """Set-up's last step: ask the allocator ONCE, inside a turn of the
    hub's (no chunk solve of any cylinder is in flight), for nearly all
    of the memory that is free, and give it back. Set-up leaves the
    free bytes in holes (three engines' iter-0 states, one-off
    constants, a fresh 0.68 GB factor per chunk solve: the largest free
    block reads 1 - 2 GB of 4.8 GB free, another in every run), so the
    runtime defragments to serve the request (libtpu:
    ``ExecutePrepareWithOomRetries attempting to defragment and
    retry``; 29 ms) and every run's window opens on the same layout
    (``bytes_in_use`` at the close is the same to the byte). It does
    NOT cure the turn that runs 0.5 - 2.9 s long once in ~320 s of
    window: that one is not the allocator's (PERF.md section 6, PR 39,
    third round). Returns what was asked and seen, for the run's log;
    None where the allocator keeps no numbers."""
    import jax.numpy as jnp
    before = _hbm()
    if "largest_free_block_bytes" not in before:
        return None
    free = before["bytes_limit"] - before.get("bytes_reserved", 0) \
        - before["bytes_in_use"]
    ask = free - 2 ** 29    # what the other threads' small puts may need
    out = {"before": before, "asked": int(ask)}
    with port(0):
        t = time.perf_counter()
        try:
            # (rows, 1024) float32: no padding under the (8, 128) tile
            a = jnp.zeros((ask // 4096, 1024), jnp.float32)
            a.block_until_ready()
            a.delete()
        except Exception as e:
            # RESOURCE_EXHAUSTED after the runtime's own retry: it has
            # defragmented by then, which is all that was wanted
            out["error"] = f"{type(e).__name__}: {str(e)[:120]}"
        out["seconds"] = time.perf_counter() - t
        out["after"] = _hbm()
    return out


def build_wheel(run, S, chunk):
    """(hub_dict, spoke_dicts) through the program's own builders."""
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig
    from mpisppy_tpu.utils.vanilla import wheel_dicts

    cfg = run.config
    recipe = dict(cfg["recipe"], **run.variant.get("recipe", {}),
                  subproblem_chunk=chunk,
                  iter0_feas_tol=cfg["iter0_feas_tol"])
    rc = RunConfig(
        model="uc", num_scens=S,
        model_kwargs=dict(cfg["instance"],
                          **run.variant.get("instance", {})),
        hub=cfg["hub"],
        algo=AlgoConfig(default_rho=recipe["defaultPHrho"],
                        max_iterations=10 ** 6, convthresh=-1.0),
        hub_options=dict(recipe, dtype=cfg["outer_dtype"]),
        spokes=[SpokeConfig(sp["kind"],
                            dict(recipe, dtype=cfg["outer_dtype"],
                                 **sp["options"]))
                for sp in cfg["spokes"]],
        incumbent_mode=cfg["incumbent_mode"])
    t = time.perf_counter()
    hub_d, spoke_ds = wheel_dicts(rc)
    run.span("host_build", t)
    return hub_d, spoke_ds


class _Control:
    """The window's clockwork, driven from the hub thread once per hub
    iteration (the converger hook) and once after iter-0 (the
    extension hook)."""

    def __init__(self, run):
        self.run = run
        self.p = run.params
        self.hub = None
        self.phase = "warm"
        self.warm_at = None     # hub iteration at which the spokes were warm
        self.t_hub = self.t_iter0 = None
        self.x0 = self.obj0 = self.iter0_pri = None
        self.ends, self.pri_max, self.convs = [], [], []
        self.snap = None
        self.xbar_before = None
        self._timer = None
        self.compaction = None

    # ---- the spokes, by what they give the hub ----
    def spoke(self, kind):
        from mpisppy_tpu.cylinders.spoke import ConvergerSpokeType as T
        want = T.OUTER_BOUND if kind == "outer" else T.INNER_BOUND
        return next(sp for sp in self.hub.spokes
                    if want in sp.converger_spoke_types)

    def spokes_warm(self):
        lag, xh = self.spoke("outer"), self.spoke("inner")
        lb = getattr(lag, "last_bound", None)
        from_hub_w = lb is not None and lb["source"] >= 1
        rounds = xh.wheel_totals().get("rounds") or {}
        return from_hub_w and rounds.get("verifications", 0) >= 1

    def spoke_exits(self):
        """{spoke: [chunk solves, tail-capped, bulk-capped]} booked so
        far by each spoke's engine (``phase_timing(key)["exits"]``)
        over the solve modes a spoke runs: the Lagrangian's prox-off
        pass, the pool's screen, the winner's verification. Totals since
        the engines were built: the window's are a difference."""
        out = {}
        for i, sp in enumerate(self.hub.spokes):
            tot = np.zeros(3, int)
            for key in (False, ("pool", False), ("fixed", False)):
                pt = None
                for _ in range(3):
                    try:
                        pt = sp.opt.phase_timing(key)
                        break
                    except RuntimeError:    # its thread booked meanwhile
                        continue
                ex = (pt or {}).get("exits")
                if ex:
                    tot += (ex["solves"], ex["tail_capped"],
                            ex["bulk_capped"])
            out[f"spoke{i}"] = tot
        return out

    # ---- hooks ----
    def post_iter0(self, opt):
        self.t_iter0 = time.perf_counter()
        self.run.spans["iter0"] = self.t_iter0 - self.t_hub
        self.x0 = opt.x
        self.obj0 = opt._last_solved_obj
        self.iter0_pri = opt.residual_summary(False)["pri_rel_max"]

    def tick(self, opt):
        """After hub iteration ``opt._iter``'s exchange. True ends the
        hub's loop."""
        now = time.perf_counter()
        run, p = self.run, self.p
        if self.phase == "warm":
            if self.warm_at is None \
                    and opt._iter >= int(p["warm_hot_iterations"]) \
                    and self.spokes_warm():
                self.warm_at = opt._iter
            # one whole hub iteration AFTER every cylinder has run each
            # of its programs: what follows the pool's first verified
            # round once (the hub's first incumbent, the pool's first
            # donated pass) stays in set-up
            if self.warm_at is not None and opt._iter > self.warm_at:
                self.compaction = compact_hbm(opt._wheel_port)
                run.spans["warm_hot"] = time.perf_counter() - self.t_iter0
                self.xbar_before = opt.xbar
                opt.reset_phase_timing()
                self.hub.reset_wheel_timing()
                self.exits_open = self.spoke_exits()
                self.phase = "window"
                run.open_window()
            elif now - self.t_hub > float(p["setup_deadline_s"]):
                lag, xh = self.spoke("outer"), self.spoke("inner")
                raise RuntimeError(
                    f"the window did not open within "
                    f"{p['setup_deadline_s']} s of the hub's start: hub "
                    f"iteration {opt._iter}, outer spoke's last bound "
                    f"{getattr(lag, 'last_bound', None) and lag.last_bound['source']}, "
                    f"x-hat spoke {xh.wheel_totals().get('rounds')}")
            return False
        if self.phase == "window":
            self.ends.append(now)
            self.pri_max.append(opt.residual_summary(True)["pri_rel_max"])
            self.convs.append(float(opt.conv))
            if now - run.t_open < run.seconds:
                return False
            run.close_window()
            self.take_snapshot(opt)
            if not run.trace:
                return True
            # one more hub iteration, its first trace_seconds recorded
            self.phase = "trace"
            run.trace_start()
            self._timer = threading.Timer(float(p["trace_seconds"]),
                                          run.trace_stop)
            self._timer.start()
            self._ann = run.annotate("bench.wheel_iter")
            self._ann.__enter__()
            return False
        self._ann.__exit__(None, None, None)
        self._timer.join()
        run.trace_stop()
        return True

    def take_snapshot(self, opt):
        """What the window produced, held by reference (device arrays
        are immutable) and read after the wheel has ended."""
        hub = self.hub
        lag, xh = self.spoke("outer"), self.spoke("inner")
        closed = self.spoke_exits()
        self.snap = {
            # the device allocator at the window's close
            "hbm": _hbm(),
            "spoke_exits": {
                n: dict(zip(("solves", "tail_capped", "bulk_capped"),
                            (closed[n] - self.exits_open[n]).tolist()))
                for n in closed},
            "phase": opt.phase_timing(True),
            "wheel": hub.wheel_timing(),
            "turn_log": hub.arbiter.turn_log(),
            "x": opt.x, "xbar": opt.xbar, "conv": float(opt.conv),
            "outer": hub.BestOuterBound, "inner": hub.BestInnerBound,
            "ob_char": hub.latest_ob_char, "ib_char": hub.latest_ib_char,
            "trivial": hub._trivial_seed,
            "outer_sent": [b for _t, b in lag._trace],
            "inner_sent": [b for _t, b in xh._trace],
            "lag_bound": lag.last_bound,
            "xhat": None if xh.best_xhat is None
            else np.asarray(xh.best_xhat, float),
            "xhat_rows": xh.best_xhat_rows,
            "xhat_value": xh.bound,
            "pin_mask": getattr(xh, "_pin_mask", None),
            # every publish is preceded by a verification SOLVE: a pass
            # of the engine's fixed-nonant mode (calculate_incumbent),
            # counted since the engine was built
            "verification_solves": (xh.opt.phase_timing(("fixed", False))
                                    or {"calls": 0})["calls"],
            # the last round the pool SCREENED to its end (the window's
            # own, where its stamp lies inside the window)
            "screen": xh.last_screen,
        }


def _install_control(run, hub_d, spoke_ds, ctl):
    """The driver's two hooks on the hub engine, and a control run's
    alteration of a spoke (``run.variant["control"]``)."""
    from mpisppy_tpu.extensions.extension import Extension

    class KeepIter0(Extension):
        def post_iter0(self, opt):
            ctl.post_iter0(opt)

    class WindowClock:          # the converger protocol: one method
        def __init__(self, opt):
            self.opt = opt

        def is_converged(self):
            return ctl.tick(self.opt)

    hub_d["opt_kwargs"]["extensions"] = KeepIter0()
    hub_d["opt_kwargs"]["converger"] = WindowClock
    control = run.variant.get("control")
    if control is None:
        return
    from mpisppy_tpu.core.ph import PHBase
    if control == "uncertified_bound":
        class Uncertified(PHBase):
            # the PRIMAL objective of an inexact solve, which no dual
            # certifies (PHBase.Ebound's docstring): above the LP value
            # by what the solve left unconverged, here the recipe's
            # stall tolerance (a toy CPU solve converges to rounding,
            # so the control states the excess instead of hoping for it)
            def Ebound(self):
                rows = self._last_solved_obj
                self._last_dual_obj = rows + CONTROL_EXCESS * abs(rows)
                return float(self.Eobjective(self._last_dual_obj))
        spoke_ds[0]["opt_class"] = Uncertified
    elif control == "unverified_incumbent":
        class Unverified(PHBase):
            # the pool screen's verdict published as it stands: the
            # winner's screen rows, and no verification solve
            def evaluate_incumbent_pool(self, pool, **kw):
                objs, feas = super().evaluate_incumbent_pool(pool, **kw)
                good = np.flatnonzero(feas & np.isfinite(objs))
                if good.size:
                    b = int(good[np.argmin(objs[good])])
                    S = self.batch.S
                    self._screen_rows = self._pool_obj_rows[
                        b * S:(b + 1) * S]
                return objs, feas

            def calculate_incumbent(self, xhat_vals, **kw):
                self._incumbent_rows = self._screen_rows
                return float(self.Eobjective(self._screen_rows))
        spoke_ds[1]["opt_class"] = Unverified
    else:
        raise ValueError(f"unknown control {control!r}")


def _odd_turns(log, t_open, t_close):
    """The window's admitted chunk solves by cylinder: how many, their
    median seconds, and every one that took over 1.3 times the median
    (seconds into the window, seconds), plus every gap between two
    turns of over 0.25 s: where a stall of the host or a foreign
    program on the device shows, turn by turn."""
    log = [t for t in log if t_open <= t[2] and t[3] <= t_close]
    out = {}
    for name in sorted({t[0] for t in log}):
        secs = [t[3] - t[2] for t in log if t[0] == name]
        med = float(np.median(secs))
        out[name] = {"n": len(secs), "median_s": round(med, 4),
                     "odd": [(round(t[2] - t_open, 2), round(t[3] - t[2], 3))
                             for t in log if t[0] == name
                             and t[3] - t[2] > 1.3 * med]}
    out["gaps"] = [(round(a[3] - t_open, 2), a[0], b[0],
                    round(b[2] - a[3], 3))
                   for a, b in zip(log, log[1:]) if b[2] - a[3] > 0.25]
    return out


def run(run):
    _require_wheel_support()
    import jax

    import scenario_lp as lpref
    import wheel_bounds as ref
    from mpisppy_tpu.utils.sputils import spin_the_wheel

    p, lim, cfg = run.params, run.limits, run.config
    S = int(p["scenarios"])
    if int(p["scenario_base"]) != 0:
        raise ValueError("wheel_hot runs scenarios 0 .. S-1 (the "
                         "program's own builders name them)")
    chunk = int(p.get("subproblem_chunk", cfg["subproblem_chunk"]))
    hub_d, spoke_ds = build_wheel(run, S, chunk)
    batch = hub_d["opt_kwargs"]["batch"]
    shape = cfg["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m) == (shape["n"], shape["m"]), \
            f"width was cut: n={batch.n} m={batch.m}"
    ctl = _Control(run)
    _install_control(run, hub_d, spoke_ds, ctl)

    def register(hub):
        ctl.hub = hub
        ctl.t_hub = time.perf_counter()

    wheel = spin_the_wheel(hub_d, spoke_ds, register_hub=register)
    run.trace_stop()
    hub, ph, snap = wheel.hub, wheel.hub.opt, ctl.snap
    if snap is None:
        raise RuntimeError("the hub ended before the window closed")
    assert all(r is not None for r in wheel.spoke_results), \
        f"a spoke did not exit at the join: {wheel.spoke_results}"
    t_open, ends = run.t_open, ctl.ends
    elapsed = run.t_close - t_open
    iters = np.diff([t_open] + ends)
    k = min(int(p["ph_iter_range"]), len(ends))
    ph_iter_s = (ends[k - 1] - t_open) / k
    phase, wt = snap["phase"], snap["wheel"]
    cyl = wt["cylinders"]
    rows_solved = sum(v["rows"] for v in cyl.values())

    # ---- correct, family 1: the hub, as the hub-only cells check it ----
    gate = float(cfg["guarantees"]["pri_rel_gate"])
    run.check("window_pri_rel_max", max(ctl.pri_max), gate)
    run.check("window_conv_finite",
              float(np.isfinite(ctl.convs).all()), 1.0, how="==")
    x = np.asarray(snap["x"])[:S]
    prob = np.asarray(ph.prob)[:S]
    idx = np.asarray(ph.nonant_idx)
    xbar_ref, conv_ref = lpref.consensus(x[:, idx], prob)
    xbar = np.asarray(snap["xbar"])
    run.check("reduce_xbar_err",
              float(np.abs(xbar - xbar_ref).max()
                    / max(1.0, np.abs(xbar_ref).max())),
              lim["reduce_xbar_err"])
    run.check("reduce_conv_err",
              abs(snap["conv"] - conv_ref) / abs(conv_ref),
              lim["reduce_conv_err"])
    run.check("window_xbar_move",
              float(np.abs(xbar[0] - np.asarray(ctl.xbar_before)[0]).max()),
              lim["window_xbar_move_min"], how=">=")
    A = lpref.sparse(batch.A)
    box = (batch.l[:S], batch.u[:S], batch.lb[:S], batch.ub[:S])
    x0, obj0 = np.asarray(ctl.x0)[:S], np.asarray(ctl.obj0)
    viol0 = lpref.primal_violation(A, x0, *box)
    viol_hot = lpref.primal_violation(A, x, *box)
    t = time.perf_counter()
    rows = sample_rows(run.seed, S, p["reference_sample"])
    gaps = []
    for r in rows:
        lp = lpref.solve_lp(A, batch.c[r], batch.c0[r],
                            *(b[r] for b in box))
        gaps.append(abs(float(obj0[r]) - lp) / abs(lp))
    run.check("iter0_obj_gap", max(gaps), lim["iter0_obj_gap"])
    run.check("iter0_primal_violation", float(viol0.max()),
              lim["iter0_primal_violation"])
    run.check("hot_primal_violation", float(viol_hot.max()),
              lim["hot_primal_violation"])
    run.check("hot_violation_q1", float(np.quantile(viol_hot, 0.25)),
              lim["hot_violation_q1"])

    # ---- family 2: the outer spoke's LAST bound of the window ----
    lb = snap["lag_bound"]
    W = np.asarray(lb["W"], float)[:S]
    lag_rows = np.asarray(lb["rows"], float)[:S]
    run.check("w_dual_feasible_err", ref.w_dual_feasible_err(W, prob),
              lim["w_dual_feasible_err"])
    over, slack, lag_lp = [], [], {}
    for r in sample_rows(run.seed + 17, S, p["lagrangian_sample"]):
        v = ref.lagrangian_value(A, batch.c[r], batch.c0[r],
                                 *(b[r] for b in box), W[r], idx)
        lag_lp[r] = v
        over.append((lag_rows[r] - v) / abs(v))
        slack.append((v - lag_rows[r]) / abs(v))
    # a certified scenario value lies UNDER the exact LP value (up to
    # rounding), and not far under it (-inf is a valid bound too)
    run.check("outer_over_lp", max(over), lim["outer_over_lp"])
    run.check("outer_slack", max(slack), lim["outer_slack"])
    run.check("outer_value_err",
              abs(float(prob @ lag_rows) - lb["value"])
              / abs(lb["value"]), lim["outer_value_err"])

    # ---- family 3: the published incumbent ----
    xhat, xh_rows = snap["xhat"], snap["xhat_rows"]
    have = xhat is not None and xh_rows is not None
    run.check("incumbent_published", float(have), 1.0, how="==")
    under, slack_in, feas = [np.inf], [np.inf], [False]
    if have:
        pin = snap["pin_mask"]
        pin = np.ones(idx.size, bool) if pin is None \
            else np.asarray(pin, bool)
        xh_rows = np.asarray(xh_rows, float)[:S]
        under, slack_in, feas = [], [], []
        for r in sample_rows(run.seed + 29, S, p["incumbent_sample"]):
            v, ok = ref.recourse_value(A, batch.c[r], batch.c0[r],
                                       *(b[r] for b in box),
                                       xhat[pin], idx[pin])
            feas.append(ok)
            # the spoke may not be optimistic: its scenario value may
            # not lie under the cheapest recourse to its own plan
            under.append((v - xh_rows[r]) / abs(v) if ok else np.inf)
            # nor far above it: the pool screen's loose values (shared
            # budget, fixed rho) are what the verification replaces
            slack_in.append((xh_rows[r] - v) / abs(v) if ok else np.inf)
        run.check("inner_value_err",
                  abs(float(prob @ xh_rows) - snap["xhat_value"])
                  / abs(snap["xhat_value"]), lim["inner_value_err"])
    run.check("inner_publishes_verified",
              float(snap["verification_solves"] >= len(snap["inner_sent"])
                    > 0), 1.0, how="==")
    run.check("xhat_feasible", float(all(feas)), 1.0, how="==")
    run.check("inner_under_lp", max(under), lim["inner_under_lp"])
    run.check("inner_slack", max(slack_in), lim["inner_slack"])

    # ---- family 3b: what the pool solved INSIDE the window ----
    # the incumbent above may date from set-up (the first verified
    # round is what opens the window); the pool's work of the window is
    # its last completed SCREEN: the per-row values of every candidate
    # it judged feasible, against the exact recourse LP of that plan
    scr = snap["screen"]
    inside = scr is not None and t_open <= scr["at"] <= run.t_close
    run.check("screen_in_window", float(inside), 1.0, how="==")
    n_ok, s_under, s_slack, s_feas = 0, [np.inf], [np.inf], [False]
    if inside and scr["rows"] is not None:
        P = len(scr["objs"])
        scr_rows = np.asarray(scr["rows"], float).reshape(P, -1)[:, :S]
        ok_c = np.flatnonzero(np.asarray(scr["feas"], bool)
                              & np.isfinite(scr["objs"]))
        n_ok = int(ok_c.size)
        imask = np.asarray(ph.nonant_integer_mask, bool)
        pin = snap["pin_mask"]
        pin = np.ones(idx.size, bool) if pin is None \
            else np.asarray(pin, bool)
        if n_ok:
            s_under, s_slack, s_feas = [], [], []
        for c in ok_c:
            plan = np.where(imask, np.round(scr["pool"][c]), scr["pool"][c])
            for r in sample_rows(run.seed + 41 + int(c), S,
                                 p["incumbent_sample"]):
                v, ok = ref.recourse_value(A, batch.c[r], batch.c0[r],
                                           *(b[r] for b in box),
                                           plan[pin], idx[pin])
                s_feas.append(ok)
                s_under.append((v - scr_rows[c, r]) / abs(v)
                               if ok else np.inf)
                s_slack.append((scr_rows[c, r] - v) / abs(v)
                               if ok else np.inf)
    # a round always screens the max-commitment anchor, which every
    # scenario can run: a screen with no feasible candidate is broken
    run.check("screen_feasible_candidates", n_ok, 1, how=">=")
    run.check("screen_plans_feasible", float(all(s_feas)), 1.0, how="==")
    run.check("screen_under_lp", max(s_under), lim["screen_under_lp"])
    run.check("screen_slack", max(s_slack), lim["screen_slack"])

    # ---- family 4: the hub's bounds and the spokes' share ----
    outer, inner = snap["outer"], snap["inner"]
    run.check("outer_le_inner", float(outer <= inner), 1.0, how="==")
    sent_o = snap["outer_sent"] + ([snap["trivial"]]
                                   if snap["trivial"] is not None else [])
    # the hub may lag its spokes by an exchange, so membership, not the
    # best: the window carries float64 unchanged
    run.check("hub_bounds_published",
              float(outer in sent_o and inner in snap["inner_sent"]),
              1.0, how="==")
    sp_o = next(v for v in wt["spokes"].values()
                if v["spoke"] == type(ctl.spoke("outer")).__name__)
    sp_i = next(v for v in wt["spokes"].values()
                if v["spoke"] == type(ctl.spoke("inner")).__name__)
    rounds = (sp_i["own"] or {}).get("rounds") or {}
    # a starved spoke is a failure, not a faster hub (a pool round of
    # 24 chunk solves need not END inside a window: its turns count)
    run.check("outer_updates", sp_o["accepted"], lim["outer_updates_min"],
              how=">=")
    xh_name = next(n for n, v in wt["spokes"].items() if v is sp_i)
    run.check("inner_turns", cyl[xh_name]["turns"], lim["inner_turns_min"],
              how=">=")

    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} + {len(over)} + {len(under)} + "
          f"{len(s_under) if n_ok else 0} scenario "
          f"LPs by HiGHS in {time.perf_counter() - t:.1f} s; iter-0 "
          f"pri_rel_max {ctl.iter0_pri:.3g}; violation over all {S} rows "
          f"(min, quartiles, max): iter-0 {quart(viol0)}, after the "
          f"window {quart(viol_hot)}; outer {outer:.6g} "
          f"[{snap['ob_char']}] inner {inner:.6g} [{snap['ib_char']}]",
          flush=True)
    print(f"window: {len(ends)} hub iterations in {elapsed:.2f} s: "
          f"{[float(f'{v:.3f}') for v in iters]}; ph_iter_s "
          f"{ph_iter_s:.4f} = mean of the first {k}; rows solved "
          f"{ {n: v['rows'] for n, v in cyl.items()} }; turns "
          f"{ {n: v['turns'] for n, v in cyl.items()} }; device s "
          f"{ {n: round(v['device_s'], 2) for n, v in cyl.items()} }; "
          f"queue wait s "
          f"{ {n: round(v['queue_wait_s'], 2) for n, v in cyl.items()} }; "
          f"sync {wt['sync']}; outer bounds accepted {sp_o['accepted']} "
          f"lag {sp_o['lag_iters']}; x-hat rounds {rounds.get('rounds')} "
          f"(verifications {rounds.get('verifications')}) lag "
          f"{sp_i['lag_iters']}; spokes' chunk solves and how many ran "
          f"their tail / bulk budget out {snap['spoke_exits']}; device "
          f"memory at the close {snap['hbm']}, compacted before the "
          f"window opened {ctl.compaction}; hub phases/iter "
          f"{phase['seconds_per_call']}; kernel {phase['kernel']}",
          flush=True)
    print(f"turns: {_odd_turns(snap['turn_log'], t_open, run.t_close)}",
          flush=True)
    over_gate = sum(v > gate for v in ctl.pri_max)
    return {"attempted": int(rows_solved),
            "failed": over_gate * S,
            "end_to_end": {"ph_iter_s": ph_iter_s,
                           "solves_per_s": rows_solved / elapsed},
            "observations": {
                "spans": dict(run.spans), "phase": phase,
                "iter_median_s": float(np.median(iters)),
                "chunk_solves_per_iteration": -(-S // chunk),
                "hub_iterations": len(ends),
                "spoke_exits": snap["spoke_exits"],
                "wheel": wt}}
