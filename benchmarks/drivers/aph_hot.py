"""Driver ``aph_hot``: hot APH iterations of the hub under φ-dispatch,
back to back, each a partial pass of the chunked-skip path.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios                S of the run: ONE rank's pool (upstream
                           dispatches ``dispatch_frac`` of each rank's
                           own scenarios)
  scenario_base            the run's scenarios are ids base .. base+S-1,
                           in that order, for every seed
  warm_partial_iterations  partial passes run as warm-up after APH
                           iteration 1 (the forced full pass)
  ph_iter_range            K: ``ph_iter_s`` is the wall time of the
                           window's first K iterations / K
  reference_sample         scenarios whose iter-0 solve is checked
                           against the plain reference LP
  trace_seconds            seconds the profiler records (--trace 1) from
                           the START of one more iteration after the
                           window and the checks: the projective step,
                           the gate, the gathers and the head of the
                           chunk solve lie at its front
  subproblem_chunk         rows per device call; left out, the
                           configuration's
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

Set-up: host build of the instance, the engine (hub-only ``APH``,
float64 outer arithmetic, the configuration's recipe and its ``hub``
keys ``dispatch_frac`` / ``APHnu`` / ``APHgamma`` / ``aph_use_lag``;
no stop rule: ``convthresh`` -1), then what ``APH_main`` does: iter-0
(``solve_loop(w_on=False, prox_on=False)``), ``Update_W()``,
``iterate(1)`` (every scenario, forced: the staged full pass), and the
warm-up's partial passes, which between them compile or load every
program the window runs. Window: ``APH.iterate(it)``;
``block_until_ready(x)``, again and again until ``--seconds`` have
passed; the iteration in flight is finished and counted. Every window
iteration is a partial pass of the same fixed instance: the
``ceil(frac S)`` rows the device selection names, in
``ceil(ceil(frac S) / chunk)`` chunk solves. ``solves_per_s`` counts the
scenarios actually SOLVED (pad rows of a chunk not counted) over all of
the window; ``ph_iter_s`` is the mean over the window's first K
iterations.

Nothing is read back inside the window that the engine does not read
itself (its ONE gate row an iteration): the driver keeps REFERENCES to
the immutable device arrays each iteration starts from and ends with,
and a copy of the S last-dispatch stamps, and looks at them after the
window has closed. ``correct`` replays the window's LAST iteration with
``reference/aph_step.py`` (numpy float64) from the state the engine
held before it.

``--seed`` draws the scenarios checked against the reference LP; the
instance and its order are the same for every seed.
"""

import threading
import time

import numpy as np

import harness

# the row order, the chunk rule and the seeded row sample are ``ph_hot``'s
_hot = harness.load_module("drivers", "ph_hot")
scenario_ids, chunk_rows, sample_rows = (
    _hot.scenario_ids, _hot.chunk_rows, _hot.sample_rows)

HUB_KEYS = ("dispatch_frac", "APHnu", "APHgamma", "aph_use_lag")


def build_engine(run, ids):
    """The program's own entry points, as ``utils/vanilla`` builds the
    APH hub's engine: ``build_batch`` and ``APH``."""
    import jax.numpy as jnp
    from mpisppy_tpu.core.aph import APH
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree
    from mpisppy_tpu.models import uc

    cfg = run.config
    assert cfg["hub"] == "aph", cfg["hub"]
    t = time.perf_counter()
    tree = two_stage_tree([f"scen{int(i)}" for i in ids],
                          nonant_names=["u", "st"])
    batch = build_batch(uc.scenario_creator, tree,
                        creator_kwargs=dict(cfg["instance"],
                                            **run.variant.get("instance",
                                                              {})),
                        vector_patch=uc.scenario_vector_patch)
    run.span("host_build", t)
    opts = dict(cfg["recipe"], **{k: cfg[k] for k in HUB_KEYS},
                **run.variant.get("recipe", {}), convthresh=-1.0,
                subproblem_chunk=chunk_rows(run))
    dtype = {"float32": jnp.float32, "float64": jnp.float64}[
        cfg["outer_dtype"]]
    return batch, APH(batch, opts, dtype=dtype)


def held(aph):
    """What one iteration starts from, or ends with: references to the
    engine's device arrays (jax arrays are immutable; the engine rebinds
    its names), its host mask (rebound each pass) and a COPY of the
    stamps (written in place)."""
    return {"x": aph.x, "yA": aph.yA, "yB": aph.yB, "W": aph.W,
            "z": aph.z, "y": aph.y_aph, "dispatched": aph._dispatched,
            "last": aph._last_dispatch.copy(),
            "scalars": (aph.tau, aph.phi, aph.theta, aph.conv)}


def iterate(aph, it):
    import jax
    if aph.iterate(it) is not True:
        raise RuntimeError(f"APH iteration {it} ended the run")
    jax.block_until_ready(aph.x)


def rel_err(got, want):
    got, want = np.asarray(got, float), np.asarray(want, float)
    return float(np.abs(got - want).max()
                 / max(float(np.abs(want).max()), 1e-300))


def run(run):
    import jax

    import aph_step
    import scenario_lp as ref
    from mpisppy_tpu.core.aph import APH

    if not hasattr(APH, "iterate"):
        # a program from before the engine had its own step: nothing to
        # drive, said before the minute of host build
        raise SystemExit("benchmark: this program's APH has no iterate(); "
                         "the aph_hot driver cannot run on it")
    p, lim, cfg = run.params, run.limits, run.config
    S = int(p["scenarios"])
    ids = scenario_ids(S, p["scenario_base"])
    batch, aph = build_engine(run, ids)
    shape = cfg["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m) == (shape["n"], shape["m"]), \
            f"width was cut: n={batch.n} m={batch.m}"
    chunk = chunk_rows(run)
    frac = float(cfg["dispatch_frac"])
    scnt = max(1, int(np.ceil(S * frac)))

    # ---- set-up: iter-0, Update_W, the full pass, the warm-up ----
    t = time.perf_counter()
    obj0 = np.asarray(aph.solve_loop(w_on=False, prox_on=False))
    aph.Update_W()
    jax.block_until_ready(aph.x)
    run.span("iter0", t)
    x0 = np.asarray(aph.x)[:S]
    iter0_pri = aph.residual_summary(False)["pri_rel_max"]
    t = time.perf_counter()
    iterate(aph, 1)
    run.span("full_pass", t)
    full_pri = aph.residual_summary(True)["pri_rel_max"]
    t = time.perf_counter()
    it = 1
    for _ in range(int(p["warm_partial_iterations"])):
        it += 1
        iterate(aph, it)
    run.span("warm_partial", t)
    z_before = aph.z
    aph.reset_phase_timing()

    # ---- the window ----
    gate = float(cfg["guarantees"]["pri_rel_gate"])
    ends, passes, convs = [], [], []
    t_open = run.open_window()
    while True:
        it += 1
        pre = held(aph)
        iterate(aph, it)
        ends.append(time.perf_counter())
        passes.append((aph._dispatched, aph._qp_states[True].pri_rel))
        convs.append(aph.conv)
        if ends[-1] - t_open >= run.seconds:
            break
    t_close = run.close_window()
    post = held(aph)
    elapsed = t_close - t_open
    phase = aph.phase_timing(True)
    status = dict(aph._aph_status)
    iters = np.diff([t_open] + ends)
    k = min(int(p["ph_iter_range"]), len(ends))
    ph_iter_s = (ends[k - 1] - t_open) / k
    # each pass's largest pri_rel over the rows IT solved, and its count
    pri_max = [float(np.asarray(pri)[mask].max()) for mask, pri in passes]
    solved = [int(mask.sum()) for mask, _ in passes]

    # ---- correct: the window's own numbers ----
    run.check("window_pri_rel_max", max(pri_max), gate)
    run.check("window_conv_finite", float(np.isfinite(convs).all()), 1.0,
              how="==")
    # a step that hands its state back unchanged moves nothing
    run.check("window_z_move",
              float(np.abs(np.asarray(post["z"])
                           - np.asarray(z_before)).max()),
              lim["window_z_move_min"], how=">=")
    # ---- the last iteration, replayed by the plain reference ----
    nidx = np.asarray(aph.nonant_idx)
    h = {f: np.asarray(pre[f])[:S] for f in ("x", "yA", "yB", "W", "z",
                                             "y")}
    g = {f: np.asarray(post[f])[:S] for f in ("x", "yA", "yB", "W", "z")}
    prob = np.asarray(aph.prob)[:S]
    want = aph_step.aph_step(
        h["x"][:, nidx], h["W"], h["z"], h["y"], prob,
        np.asarray(aph.rho)[:S], pre["dispatched"][:S], pre["last"][:S],
        float(cfg["APHnu"]), float(cfg["APHgamma"]), it, frac)
    mask = np.asarray(post["dispatched"])[:S]
    run.check("aph_w_err", rel_err(g["W"], want["W"]), lim["aph_w_err"])
    run.check("aph_z_err", rel_err(g["z"], want["z"]), lim["aph_z_err"])
    run.check("aph_scalars_err",
              max(rel_err(got, want[k]) for got, k in
                  zip(post["scalars"], ("tau", "phi", "theta", "conv"))),
              lim["aph_scalars_err"])
    run.check("dispatch_mask_mismatch",
              int((mask != want["mask"]).sum()), 0, how="==")
    # the selection alone, on the φ the engine itself holds: the
    # device's sorts against the reference's, bit for bit
    run.check("dispatch_select_mismatch",
              int((mask != aph_step.select(np.asarray(aph.phis)[:S],
                                           pre["last"][:S], scnt)).sum()),
              0, how="==")
    run.check("dispatched_rows", int(mask.sum()), scnt, how="==")
    run.check("undispatched_rows_changed",
              int(sum((g[f][~mask] != h[f][~mask]).any(axis=1).sum()
                      for f in ("x", "yA", "yB"))), 0, how="==")
    # ---- the scenario solves against the plain reference ----
    A = ref.sparse(batch.A)
    box = (batch.l[:S], batch.u[:S], batch.lb[:S], batch.ub[:S])
    viol0 = ref.primal_violation(A, x0, *box)
    viol_hot = ref.primal_violation(A, g["x"], *box)
    rows = sample_rows(run.seed, S, p["reference_sample"])
    t = time.perf_counter()
    gaps = []
    for r in rows:
        lp = ref.solve_lp(A, batch.c[r], batch.c0[r], *(b[r] for b in box))
        gaps.append(abs(float(obj0[r]) - lp) / abs(lp))
    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} scenario LPs by HiGHS in "
          f"{time.perf_counter() - t:.1f} s; program pri_rel_max iter-0 "
          f"{iter0_pri:.3g}, full pass {full_pri:.3g}; iter-0 objective "
          f"gaps by row "
          f"{dict(zip(rows, (float(f'{v:.3g}') for v in gaps)))}; "
          f"violation (min, quartiles, max) over all {S} rows: iter-0 "
          f"{quart(viol0)}, after the window {quart(viol_hot)}; over the "
          f"{int(mask.sum())} rows of the last pass {quart(viol_hot[mask])}",
          flush=True)
    run.check("iter0_obj_gap", max(gaps), lim["iter0_obj_gap"])
    run.check("iter0_primal_violation", float(viol0.max()),
              lim["iter0_primal_violation"])
    # every row was solved hot at least once (iteration 1); the rows of
    # the last pass alone are the freshest quarter
    run.check("hot_primal_violation", float(viol_hot.max()),
              lim["hot_primal_violation"])
    run.check("hot_violation_q1", float(np.quantile(viol_hot, 0.25)),
              lim["hot_violation_q1"])
    run.check("last_pass_violation_q1",
              float(np.quantile(viol_hot[mask], 0.25)),
              lim["last_pass_violation_q1"])

    print(f"window: {len(ends)} APH iterations ({it - len(ends) + 1} .. "
          f"{it}) in {elapsed:.2f} s: "
          f"{[float(f'{v:.3f}') for v in iters]}; ph_iter_s "
          f"{ph_iter_s:.4f} = mean of the first {k}; median "
          f"{np.median(iters):.3f}; conv {convs[0]:.5f} -> "
          f"{convs[-1]:.5f}; last pass {status}; phases/pass "
          f"{phase['seconds_per_call']}; admm/pass "
          f"{phase['admm_iters_per_call']}; dispatch "
          f"{phase.get('dispatch')}; aph {phase.get('aph')}; "
          f"kernel {phase['kernel']}", flush=True)
    obs_out = {"spans": dict(run.spans), "phase": phase,
               "iter_median_s": float(np.median(iters)),
               "chunk_solves_per_iteration": -(-scnt // chunk)}
    if run.trace:
        traced_iteration(run, aph, it + 1)
    return {"attempted": sum(solved),
            # the solves of a pass that put a row over the gate
            "failed": sum(n for n, v in zip(solved, pri_max) if v > gate),
            "end_to_end": {"ph_iter_s": ph_iter_s,
                           "solves_per_s": sum(solved) / elapsed},
            "observations": obs_out}


def traced_iteration(run, aph, it):
    """One more iteration after the window, its first ``trace_seconds``
    under the profiler."""
    run.trace_start()
    timer = threading.Timer(float(run.params["trace_seconds"]),
                            run.trace_stop)
    timer.start()
    with run.annotate("bench.aph_iter"):
        iterate(aph, it)
    timer.join()
    run.trace_stop()
