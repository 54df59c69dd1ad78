"""Driver ``ph_runs``: whole hub-only PH runs from a cold W, back to back.

For instances whose PH iteration is tens of milliseconds: a window of
back-to-back hot iterations would sit at PH's fixed point for all but
its first second, which no user pays for. The normal path stops at an
iteration budget (``ph_main``: ``max_iterations``), so the window holds
whole RUNS of that budget, closed loop: the next run starts when the
last one's x is ready.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios            S of the run
  scenario_base        the run's scenarios are ids base .. base+S-1,
                       in that order, for every seed
  run_hot_iterations   H: hot iterations of one run, after its iter-0
                       (the configuration's ``max_iterations``; a fixed
                       number, never a stop rule evaluated in the window)
  warm_runs            whole runs made as warm-up (they compile or load
                       every program the window runs)
  ph_iter_range        K <= H: ``ph_iter_s`` is the wall time from the
                       end of the window's FIRST run's iter-0 to the end
                       of its K-th hot iteration / K (a fixed range of
                       the same work in every run of the cell)
  reference_sample     scenarios whose iter-0 solve is checked against
                       the plain reference LP (S: all of them)
  trace_seconds        seconds of one extra run the profiler records
                       (--trace 1), after the window and the checks
  subproblem_chunk     rows per device call; left out, the
                       configuration's (0: one call for all S rows)
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

Set-up: host build of the instance (the configuration's ``model`` of
``mpisppy_tpu.models``, its ``instance`` as creator kwargs, one shared
matrix through the model's vector patch), the engine (hub-only
``PHBase``, float64 outer arithmetic, the configuration's recipe,
kernel mode and explicit inverse left at ``auto``), ``warm_runs`` runs.
One run, warm-up or window: ``with ph.run_span()``: ``reset_run()``;
iter-0 (``solve_loop(w_on=False, prox_on=False)``; ``W = W_new``;
``block_until_ready(x)``; the trivial bound, as ``ph_main`` takes it);
then H times the step ``ph_hot`` times (``solve_loop(w_on=True,
prox_on=True)``; ``W = W_new``; ``block_until_ready(x)``). The run in
flight at ``--seconds`` is finished and counted. ``solves_per_s`` =
scenario solves (iter-0's count) / elapsed over all of the window.

Nothing is read back inside a run that the normal path does not read
(conv, the trivial bound): the window keeps REFERENCES to each
iteration's ``pri_rel`` and each run's final x-bar on the device and
looks at them after it has closed.

``--seed`` draws only the rows checked against the reference when
``reference_sample`` < S; the instance and its order are the same for
every seed.
"""

import threading
import time
import types

import numpy as np

import harness


# the step, the chunk rule and the seeded row sample are ``ph_hot``'s own
_hot = harness.load_module("drivers", "ph_hot")
hot_iteration, chunk_rows, sample_rows = (
    _hot.hot_iteration, _hot.chunk_rows, _hot.sample_rows)


def instance_of(run):
    return dict(run.config["instance"], **run.variant.get("instance", {}))


def build_engine(run, ids):
    """The program's own entry points: the model's creator and vector
    patch through ``build_batch``, then ``PHBase``."""
    import jax.numpy as jnp
    from mpisppy_tpu import models
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree

    cfg = run.config
    mod = getattr(models, cfg["model"])
    t = time.perf_counter()
    tree = two_stage_tree([f"Scenario{int(i)}" for i in ids],
                          nonant_names=list(cfg["nonant_names"]))
    batch = build_batch(mod.scenario_creator, tree,
                        creator_kwargs=instance_of(run),
                        vector_patch=mod.scenario_vector_patch)
    run.span("host_build", t)
    opts = dict(cfg["recipe"], **run.variant.get("recipe", {}),
                subproblem_chunk=chunk_rows(run))
    dtype = {"float32": jnp.float32, "float64": jnp.float64}[
        cfg["outer_dtype"]]
    return batch, PHBase(batch, opts, dtype=dtype)


def one_run(ph, hot_iterations):
    """reset, iter-0, ``hot_iterations`` hot iterations: one PH run as
    ``ph_main`` makes it to a fixed budget. Returns what the run leaves
    for the checks, as references to device arrays, not copies."""
    import jax
    log = types.SimpleNamespace(ends=[], pri_rel=[], convs=[])
    with ph.run_span():
        ph.reset_run()
        log.obj0 = ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        jax.block_until_ready(ph.x)
        log.trivial_bound = ph.Ebound()
        log.t_iter0 = time.perf_counter()   # iter-0 ends here
        log.x0, log.xbar0 = ph.x, ph.xbar
        log.pri_rel0 = ph._qp_states[False].pri_rel
        log.convs.append(ph.conv)
        for _ in range(hot_iterations):
            hot_iteration(ph)
            log.ends.append(time.perf_counter())
            log.pri_rel.append(ph._qp_states[True].pri_rel)
            log.convs.append(ph.conv)
    log.xbar = ph.xbar
    return log


def run(run):
    import sslp_lp as ref

    p, lim, cfg = run.params, run.limits, run.config
    S, H = int(p["scenarios"]), int(p["run_hot_iterations"])
    K = min(int(p["ph_iter_range"]), H)
    ids = int(p["scenario_base"]) + np.arange(S)
    batch, ph = build_engine(run, ids)
    shape = cfg["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m, batch.K) == (
            shape["n"], shape["m"], shape["binary_nonants"]) \
            and S == int(cfg["scenarios"]), \
            f"width was cut: n={batch.n} m={batch.m} K={batch.K} S={S}"
    assert batch.shared_A, "one matrix for every scenario"

    # ---- set-up: whole runs (they compile or load every program) ----
    t = time.perf_counter()
    for _ in range(int(p["warm_runs"])):
        one_run(ph, H)
    run.span("warm_runs", t)
    ph.reset_phase_timing()

    # ---- the window ----
    logs = []
    t_open = run.open_window()
    while True:
        logs.append(one_run(ph, H))
        if logs[-1].ends[-1] - t_open >= run.seconds:
            break
    t_close = run.close_window()
    elapsed = t_close - t_open
    phase = ph.phase_timing(True)
    phase0 = ph.phase_timing(False)
    first, last = logs[0], logs[-1]
    ph_iter_s = (first.ends[K - 1] - first.t_iter0) / K
    iters = np.concatenate([np.diff([lg.t_iter0] + lg.ends) for lg in logs])

    # ---- correct: the window's own numbers ----
    gate = float(cfg["guarantees"]["pri_rel_gate"])
    pri_max = [float(np.asarray(a)[:S].max())
               for lg in logs for a in lg.pri_rel]
    pri0_max = [float(np.asarray(lg.pri_rel0)[:S].max()) for lg in logs]
    run.check("window_pri_rel_max", max(pri_max + pri0_max), gate)
    convs = np.array([lg.convs for lg in logs], float)
    run.check("window_conv_finite", float(np.isfinite(convs).all()), 1.0,
              how="==")
    # the reset is complete: every run of the window ends where the
    # first one did, bit for bit
    xbars = [np.asarray(lg.xbar) for lg in logs]
    same = all(np.array_equal(xb, xbars[0]) for xb in xbars) \
        and bool((convs == convs[0]).all())
    run.check("window_runs_identical", float(same), 1.0, how="==")
    # ---- the consensus reduce, exactly, from the gathered state ----
    x = np.asarray(ph.x)[:S]
    prob = np.asarray(ph.prob)[:S]
    xbar_ref, conv_ref = ref.consensus(x[:, np.asarray(ph.nonant_idx)],
                                       prob)
    xbar = xbars[-1]
    run.check("reduce_xbar_err",
              float(np.abs(xbar - xbar_ref).max()
                    / max(1.0, np.abs(xbar_ref).max())),
              lim["reduce_xbar_err"])
    run.check("reduce_conv_err",
              abs(float(ph.conv) - conv_ref) / abs(conv_ref),
              lim["reduce_conv_err"])
    # a step that hands its state back unchanged moves nothing
    run.check("window_xbar_move",
              float(np.abs(xbar[0] - np.asarray(last.xbar0)[0]).max()),
              lim["window_xbar_move_min"], how=">=")
    if "recipe" not in run.variant and "kernel" in cfg:
        # the plan the code chose by itself is the one the
        # configuration states (a control below the recipe's precision
        # changes the choice, and fails by the numbers below)
        want = cfg["kernel"]
        got = phase["kernel"] or {}
        run.check("kernel_as_stated",
                  float(all(got.get(k) == v for k, v in want.items())),
                  1.0, how="==")
    # ---- the scenario solves against the plain reference, built from
    # the instance's numbers and never from the program's batch ----
    ikw = instance_of(run)
    inst = ref.instance(ikw["num_servers"], ikw["num_clients"],
                        ikw.get("base_seed", 1), ikw["capacity"],
                        ikw["server_budget"], cfg["overflow_penalty"])
    A, _c, lb, ub, l0, u0 = ref.matrices(inst)
    hs = np.stack([ref.presence(i, inst["n"], ikw.get("presence_prob", 0.5))
                   for i in ids])
    lu = [ref.rows(inst, h, l0, u0) for h in hs]
    box = (np.stack([l for l, _ in lu]), np.stack([u for _, u in lu]),
           np.broadcast_to(lb, (S, lb.size)),
           np.broadcast_to(ub, (S, ub.size)))
    viol0 = ref.primal_violation(A, np.asarray(last.x0)[:S], *box)
    viol_hot = ref.primal_violation(A, x, *box)
    rows = sample_rows(run.seed, S, p["reference_sample"])
    t = time.perf_counter()
    lps = ref.scenario_lps(inst, hs[rows])
    t_ref = time.perf_counter() - t
    obj0 = np.asarray(last.obj0)[:S][rows]
    gaps = np.abs(obj0 - lps) / np.abs(lps)
    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} scenario LPs by HiGHS in {t_ref:.1f} s; "
          f"iter-0 objective gaps (min, quartiles, max) {quart(gaps)}, "
          f"largest at row {rows[int(gaps.argmax())]}; violation over all "
          f"{S} rows: iter-0 {quart(viol0)}, after the last run "
          f"{quart(viol_hot)}", flush=True)
    # held against a part of the batch left unsolved, or solved as
    # another problem
    run.check("iter0_obj_gap", float(gaps.max()), lim["iter0_obj_gap"])
    if len(rows) == S:
        # the engine's trivial bound certifies sum_s p_s LP_s from below
        ws = ref.wait_and_see(lps, prob / prob.sum())
        run.check("trivial_bound_gap",
                  abs(last.trivial_bound - ws) / abs(ws),
                  lim["trivial_bound_gap"])
        run.check("trivial_bound_below_lp",
                  (last.trivial_bound - ws) / abs(ws),
                  lim["trivial_bound_below_lp"])
    run.check("iter0_primal_violation", float(viol0.max()),
              lim["iter0_primal_violation"])
    run.check("hot_primal_violation", float(viol_hot.max()),
              lim["hot_primal_violation"])
    # the number the recipe's precision moves (see ph_hot)
    run.check("hot_violation_q1", float(np.quantile(viol_hot, 0.25)),
              lim["hot_violation_q1"])

    runs = phase["runs"] if phase and "runs" in phase else None
    print(f"window: {len(logs)} runs of 1 + {H} iterations in "
          f"{elapsed:.2f} s; ph_iter_s {ph_iter_s:.5f} = mean of the first "
          f"run's first {K}; hot median {np.median(iters):.5f}, max "
          f"{iters.max():.5f}; first run's hot iterations "
          f"{[float(f'{v:.4f}') for v in np.diff([first.t_iter0] + first.ends)]}"
          f"; conv over the first run {[float(f'{v:.4g}') for v in convs[0]]}"
          f"; trivial bound {last.trivial_bound:.6f}; runs {runs}; "
          f"phases/hot call {phase['seconds_per_call']}, iter-0 call "
          f"{phase0['seconds_per_call']}; ADMM/hot call "
          f"{phase['admm_iters_per_call']}, iter-0 "
          f"{phase0['admm_iters_per_call']}; mode {phase['mode']} on "
          f"{phase['devices']} device(s); kernel {phase['kernel']}",
          flush=True)
    chunk = chunk_rows(run)
    obs_out = {"spans": dict(run.spans), "phase": phase, "phase_iter0": phase0,
               "iter_median_s": float(np.median(iters)),
               "chunk_solves_per_iteration":
                   -(-S // chunk) if 0 < chunk < S else 1}
    if run.trace:
        traced_run(run, ph, H)
    over_gate = sum(v > gate for v in pri_max + pri0_max)
    solves = len(logs) * (1 + H) * S
    return {"attempted": solves,
            # every solve of an iteration that averaged in a scenario
            # over the gate
            "failed": over_gate * S,
            "end_to_end": {"ph_iter_s": ph_iter_s,
                           "solves_per_s": solves / elapsed},
            "observations": obs_out}


def traced_run(run, ph, hot_iterations):
    """One more run after the window, its first ``trace_seconds`` under
    the profiler."""
    run.trace_start()
    timer = threading.Timer(float(run.params["trace_seconds"]),
                            run.trace_stop)
    timer.start()
    with run.annotate("bench.ph_run"):
        one_run(ph, hot_iterations)
    timer.join()
    run.trace_stop()
