"""Driver ``fwph_hot``: outer iterations of the Frank-Wolfe PH cylinder,
back to back, each ``FW_iter_limit`` SDM passes (a linearized prox-off
solve of every scenario, one column, one simplex QP) and the PH update
from the QP's solutions.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios              S of the run: one chip's share of the cylinder
  scenario_base          must be 0: the engine is built by the program's
                         own builders (``utils/vanilla.wheel_dicts``),
                         whose scenarios are ids 0 .. S-1 in that order
  subproblem_chunk       rows per device call; left out, the
                         configuration's
  warm_outer_iterations  outer iterations run as warm-up after iter-0
  ph_iter_range          K: ``ph_iter_s`` is the wall time of the
                         window's first K OUTER iterations / K
  reference_sample       scenarios whose certified value at the last
                         timed first pass is held against the exact LP
  qp_sample              scenarios whose last weight QP is held against
                         the reference minimiser
  trace_seconds          seconds the profiler records (--trace 1), from
                         the end of the first linearized solve of one
                         more outer iteration after the window and the
                         checks: the column step, the QP and the head
                         of the next pass's first chunk solve
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

Set-up: host build and the engine through ``wheel_dicts(RunConfig(
spokes=[SpokeConfig("fwph", ...)]))`` -> the spoke's ``opt_class(
**opt_kwargs)`` (float64 outer arithmetic, the configuration's recipe
and its four FWPH options; no stop rule: ``convthresh`` -1), then what
``FWPH.fwph_main`` does: ``iter0()`` and the warm-up's ``iterate(it)``,
which between them compile or load every program the window runs.
Window: ``FWPH.iterate(it)``, again and again until ``--seconds`` have
passed; the iteration in flight is finished and counted (it ends with
the engine's own read of conv). ``solves_per_s`` = rows of every
linearized chunk solve of every pass in the window / its seconds;
``ph_iter_s`` = the mean over the window's first K outer iterations;
``failed`` = the rows of passes that put a row over the gate.

The driver hangs ONE of the program's own user hooks on the engine
(the dict schema's ``opt_kwargs["extensions"]``): ``post_solve``, which
the engine calls after every ``solve_loop``. In the window it keeps
REFERENCES to the immutable device arrays a pass starts from and its
solve ends with (nothing is read back that the engine does not read
itself), and in the traced iteration it starts the profiler. One small
program of the driver's own runs between two iterations: a (C,)
checksum of the pool's slots, read after the window (``pool_slot_ok``).
``correct`` replays the window's LAST pass, QP and outer update with
``reference/fwph_step.py`` (numpy float64, HiGHS) from those arrays.
The HiGHS LPs run after the window, outside ``setup_s``.

``--seed`` draws the sampled rows; the instance and its order are the
same for every seed.
"""

import collections
import threading
import time

import numpy as np

import harness

_hot = harness.load_module("drivers", "ph_hot")
sample_rows, chunk_rows = _hot.sample_rows, _hot.chunk_rows
rel_err = harness.load_module("drivers", "aph_hot").rel_err

FW_KEYS = ("FW_iter_limit", "FW_conv_thresh", "fwph_max_columns",
           "fwph_qp_iters")


def _require_fwph_step():
    """A program whose FWPH has no step of its own cannot run this
    cell: say so and end at once, before the minute of host build."""
    from mpisppy_tpu.core.fwph import FWPH
    if not (hasattr(FWPH, "iter0") and hasattr(FWPH, "iterate")):
        raise SystemExit("benchmark: this program's FWPH has no iter0() / "
                         "iterate(); the fwph_hot driver cannot run on it")


def make_watch():
    from mpisppy_tpu.extensions.extension import Extension

    class Watch(Extension):
        """After every ``solve_loop`` of the engine: while ``on``, keep
        what the pass started from and what its solve ended with, by
        reference; once, when ``on_first`` is set, call it (the traced
        iteration's profiler start)."""

        def __init__(self):
            super().__init__()
            self.on = False
            self.on_first = None
            self.last = collections.deque(maxlen=4)
            self.pri = []           # each pass's (S,) pri_rel, on device

        def post_solve(self, opt):
            if self.on_first is not None:
                hook, self.on_first = self.on_first, None
                hook()
            if not self.on:
                return
            self.last.append({
                "k": opt._sdm_k, "ptr": opt._col_ptr, "a": opt._a,
                "base": opt._base, "xn_t": opt._xn_t, "w_t": opt._w_t,
                "x_star": opt.x, "dual": opt._last_dual_obj})
            self.pri.append(opt._qp_states[False].pri_rel)

    return Watch()


def build_engine(run, S, watch):
    """The FWPH cylinder's engine as ``spin_the_wheel`` would get it:
    ``wheel_dicts`` -> the fwph spoke's ``opt_class(**opt_kwargs)``."""
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig
    from mpisppy_tpu.utils.vanilla import wheel_dicts

    cfg = run.config
    recipe = dict(cfg["recipe"], **run.variant.get("recipe", {}),
                  subproblem_chunk=chunk_rows(run))
    rc = RunConfig(
        model="uc", num_scens=S,
        model_kwargs=dict(cfg["instance"],
                          **run.variant.get("instance", {})),
        hub="ph",
        algo=AlgoConfig(default_rho=recipe["defaultPHrho"],
                        max_iterations=10 ** 6, convthresh=-1.0),
        hub_options=dict(recipe, dtype=cfg["outer_dtype"]),
        spokes=[SpokeConfig(cfg["cylinder"],
                            dict(recipe, dtype=cfg["outer_dtype"],
                                 **{k: cfg[k] for k in FW_KEYS}))])
    t = time.perf_counter()
    _hub_d, spoke_ds = wheel_dicts(rc)
    run.span("host_build", t)
    sd = spoke_ds[0]
    sd["opt_kwargs"]["extensions"] = watch
    return sd["opt_class"](**sd["opt_kwargs"])


def pool_sums_fn():
    """(C,) checksum of the pool's slots: each slot's entries against a
    fixed weight a column, summed. Two launches on equal slots give
    equal bits."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pool_sums(columns):
        wts = 1.0 + jnp.arange(columns.shape[-1], dtype=columns.dtype) \
            / columns.shape[-1]
        return jnp.sum(columns * wts, axis=(0, 2))

    return pool_sums


def run(run):
    import jax

    import fwph_step as fw_ref
    import scenario_lp as ref

    _require_fwph_step()
    p, lim, cfg = run.params, run.limits, run.config
    S = int(p["scenarios"])
    assert int(p["scenario_base"]) == 0, "wheel_dicts builds ids 0 .. S-1"
    watch = make_watch()
    fw = build_engine(run, S, watch)
    batch = fw.batch
    shape = cfg["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m) == (shape["n"], shape["m"]), \
            f"width was cut: n={batch.n} m={batch.m}"
    pool_sums = pool_sums_fn()

    def iterate(it):
        if fw.iterate(it) is not True:
            raise RuntimeError(f"FWPH iteration {it} ended the run")

    # ---- set-up: iter-0 and the warm-up iterations ----
    t = time.perf_counter()
    fw.iter0()
    jax.block_until_ready(fw.W)
    run.span("iter0", t)
    t = time.perf_counter()
    it = 0
    for _ in range(int(p["warm_outer_iterations"])):
        it += 1
        iterate(it)
    jax.block_until_ready(pool_sums(fw.columns))
    run.span("warm_outer", t)
    xbar_before = fw.xbar
    fw.reset_phase_timing()

    # ---- the window ----
    gate = float(cfg["guarantees"]["pri_rel_gate"])
    ends, convs, bounds = [], [], []
    watch.on = True
    t_open = run.open_window()
    while True:
        it += 1
        pre = {"W": fw.W, "xbar": fw.xbar, "sums": pool_sums(fw.columns)}
        iterate(it)
        ends.append(time.perf_counter())
        convs.append(fw.conv)
        bounds.append(fw._local_bound)
        if ends[-1] - t_open >= run.seconds:
            break
    t_close = run.close_window()
    watch.on = False
    elapsed = t_close - t_open
    phase = fw.phase_timing(False)
    fwt = phase["fwph"]
    iters = np.diff([t_open] + ends)
    k = min(int(p["ph_iter_range"]), len(ends))
    ph_iter_s = (ends[k - 1] - t_open) / k
    passes = len(watch.pri)
    pri_max = [float(np.asarray(v)[:S].max()) for v in watch.pri]

    # ---- correct: the window's own numbers ----
    run.check("window_pri_rel_max", max(pri_max), gate)
    run.check("window_conv_finite", float(np.isfinite(convs).all()), 1.0,
              how="==")
    run.check("window_xbar_move",
              float(np.abs(np.asarray(fw.xbar)[0]
                           - np.asarray(xbar_before)[0]).max()),
              lim["window_xbar_move_min"], how=">=")
    trail = [fw.trivial_bound] + bounds
    run.check("bound_monotone",
              float(all(b >= a for a, b in zip(trail, trail[1:]))), 1.0,
              how="==")
    run.check("bound_over_trivial",
              (bounds[-1] - fw.trivial_bound) / abs(fw.trivial_bound), 0.0,
              how=">=")
    run.check("bounds_dropped", fwt["bounds_dropped"], 0, how="==")

    # ---- the last pass, the last QP and the outer update, replayed
    # by the plain reference from the arrays the engine held ----
    f = lambda v: np.asarray(v, float)[:S]
    nidx = np.asarray(fw.nonant_idx)
    prob, rho = f(fw.prob), f(fw.rho)
    c, c0 = f(fw.c), f(fw.c0)
    C = fw.max_columns
    last = watch.last[-1]
    W, xbar = f(pre["W"]), f(pre["xbar"])
    want = fw_ref.sdm_pass(W, rho, xbar, f(last["xn_t"]), f(last["a"]),
                           f(last["base"]), c, c0, f(last["x_star"]), nidx,
                           prob, last["ptr"], C)
    run.check("w_t_err", rel_err(f(last["w_t"]), want["w_t"]), 1e-12)
    # the last pass's own row, as the engine read it
    row = fw._sdm_row
    run.check("gamma_err",
              abs(row["gamma"] - want["gamma"])
              / max(abs(want["E_lin_t"]), 1.0), lim["gamma_err"])
    # every pass of the last iteration left its x_star in its slot, to
    # the last bit, and no other slot moved
    pool = fw.columns
    n_last = next(i for i, r in enumerate(reversed(watch.last), 1)
                  if r["k"] == 0)
    wrote = {}
    for r in list(watch.last)[-n_last:]:
        wrote[r["ptr"] % C] = bool(
            (np.asarray(pool[:S, r["ptr"] % C]) == f(r["x_star"])).all())
    moved = np.flatnonzero(np.asarray(pool_sums(pool))
                           != np.asarray(pre["sums"]))
    run.check("pool_slot_ok",
              float(all(wrote.values())
                    and set(moved.tolist()) <= set(wrote)), 1.0, how="==")
    # the QP: a . G against the engine's xn_t (all rows), then the
    # sampled rows against the reference minimiser
    G, base, a = f(fw._G), f(fw._base), f(fw._a)
    xn_t = f(fw._xn_t)
    run.check("xn_err", rel_err(xn_t, np.einsum("sc,sck->sk", a, G)),
              lim["xn_err"])
    run.check("qp_feas_err",
              float(max(np.abs(a.sum(axis=1) - 1).max(), -a.min(), 0.0)),
              lim["qp_feas_err"])
    t = time.perf_counter()
    qrows = sample_rows(run.seed + 1, S, p["qp_sample"])
    gaps, kkts = [], []
    for r in qrows:
        best = fw_ref.simplex_qp_reference(G[r], base[r], W[r], rho[r],
                                           xbar[r])
        got = fw_ref.qp_value(G[r], base[r], W[r], rho[r], xbar[r], a[r])
        gaps.append((got - best["value"]) / abs(best["value"]))
        kkts.append(best["kkt"])
    print(f"reference: {len(qrows)} weight QPs in "
          f"{time.perf_counter() - t:.1f} s; the reference's own KKT "
          f"residual max {max(kkts):.3g}; gaps by row "
          f"{dict(zip(qrows, (float(f'{g:.3g}') for g in gaps)))}",
          flush=True)
    run.check("qp_reference_kkt", max(kkts), 1e-9)
    run.check("qp_obj_gap", max(gaps), lim["qp_obj_gap"])
    up = fw_ref.outer_update(xn_t, prob, W, rho)
    run.check("reduce_xbar_err", rel_err(f(fw.xbar)[0], up["xbar"]),
              lim["reduce_xbar_err"])
    run.check("update_w_err", rel_err(f(fw.W), up["W"]),
              lim["update_w_err"])
    run.check("update_conv_err", abs(fw.conv - up["conv"]) / up["conv"],
              lim["update_conv_err"])

    # ---- the bound: the certified values of the LAST timed first
    # pass against the exact LP at the same w_t ----
    first = next(r for r in reversed(watch.last) if r["k"] == 0)
    w_first, dual = f(first["w_t"]), f(first["dual"])
    run.check("w_manifold_err", fw_ref.w_manifold_err(w_first, prob),
              lim["w_manifold_err"])
    # what that pass offered for publication IS their expectation
    run.check("bound_value_err",
              abs(fw._pass_bound - float(prob @ dual))
              / abs(float(prob @ dual)), 1e-9)
    A = ref.sparse(batch.A)
    box = (batch.l[:S], batch.u[:S], batch.lb[:S], batch.ub[:S])
    rows = sample_rows(run.seed, S, p["reference_sample"])
    t = time.perf_counter()
    over, under = [], []
    for r in rows:
        lp = fw_ref.lagrangian_value(A, batch.c[r], batch.c0[r],
                                     *(b[r] for b in box), w_first[r], nidx)
        over.append((dual[r] - lp) / abs(lp))
        under.append((lp - dual[r]) / abs(lp))
    viol = ref.primal_violation(A, f(last["x_star"]), *box)
    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} Lagrangian LPs by HiGHS in "
          f"{time.perf_counter() - t:.1f} s; certified value under its "
          f"LP by row {dict(zip(rows, (float(f'{v:.3g}') for v in under)))}"
          f"; violation of the last pass's x_star over all {S} rows (min, "
          f"quartiles, max) {quart(viol)}", flush=True)
    run.check("bound_above_lp", max(over), lim["bound_above_lp"])
    run.check("bound_under_lp", max(under), lim["bound_under_lp"])
    run.check("hot_primal_violation", float(viol.max()),
              lim["hot_primal_violation"])
    run.check("hot_violation_q1", float(np.quantile(viol, 0.25)),
              lim["hot_violation_q1"])

    print(f"window: {len(ends)} outer iterations ({it - len(ends) + 1} .. "
          f"{it}), {passes} passes in {elapsed:.2f} s: "
          f"{[float(f'{v:.3f}') for v in iters]}; ph_iter_s "
          f"{ph_iter_s:.4f} = mean of the first {k}; median "
          f"{np.median(iters):.3f}; conv {convs[0]:.5f} -> "
          f"{convs[-1]:.5f}; bound {fw.trivial_bound:.6g} (trivial) -> "
          f"{bounds[-1]:.6g}; last pass's row {row}; fwph {fwt}; phases/pass "
          f"{phase['seconds_per_call']}; admm/pass "
          f"{phase['admm_iters_per_call']}; kernel {phase['kernel']}",
          flush=True)
    obs_out = {"spans": dict(run.spans), "phase": phase,
               "iter_median_s": float(np.median(iters)),
               "chunk_solves_per_iteration": -(-S // chunk_rows(run)),
               "fwph_bound_gain": (bounds[-1] - fw.trivial_bound)
               / abs(fw.trivial_bound),
               "fwph_qp_shape": {"rows": S, "slots": C, "nonants": nidx.size,
                                 "iters": fw.qp_iters,
                                 "itemsize": fw._G.dtype.itemsize}}
    if run.trace:
        traced_iteration(run, fw, watch, it + 1)
    return {"attempted": passes * S,
            "failed": S * sum(v > gate for v in pri_max),
            "end_to_end": {"ph_iter_s": ph_iter_s,
                           "solves_per_s": passes * S / elapsed},
            "observations": obs_out}


def traced_iteration(run, fw, watch, it):
    """One more outer iteration after the window; the profiler starts
    when its FIRST linearized solve has ended and records
    ``trace_seconds``: one whole column step, one whole QP program and
    the head of the second pass's first chunk solve."""
    timer = threading.Timer(float(run.params["trace_seconds"]),
                            run.trace_stop)
    ann = run.annotate("bench.fwph_pass")

    def start():
        run.trace_start()
        timer.start()
        ann.__enter__()

    watch.on_first = start
    if fw.iterate(it) is not True:
        raise RuntimeError(f"FWPH iteration {it} ended the run")
    ann.__exit__(None, None, None)
    timer.join()
    run.trace_stop()
