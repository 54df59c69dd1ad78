"""Driver ``ph_hot``: hot PH iterations of the hub, back to back.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios            S of the run (all chips together)
  subproblem_chunk     rows per device call (per device on a mesh);
                       left out, the configuration's
  warm_hot_iterations  hot iterations run as warm-up after iter-0
  ph_iter_range        K: ``ph_iter_s`` is the mean over the window's
                       first K iterations (a fixed range of PH
                       iterations, so the same work in every run)
  reference_sample     scenarios whose iter-0 solve is checked against
                       the plain reference LP
  trace_seconds        seconds of one extra hot iteration the profiler
                       records (--trace 1), after the window
  scenario_base        the run's scenarios are ids base .. base+S-1,
                       in that order
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

Set-up: host build of the instance, the engine (hub-only ``PHBase``,
float64 outer arithmetic, the configuration's recipe; a mesh over the
cell's chips when it has more than one), iter-0 (``w_on=False,
prox_on=False``) and the warm-up iterations, which between them compile
or load every program the window runs. Window:
``solve_loop(w_on=True, prox_on=True)``; ``W = W_new``;
``block_until_ready(x)``, again and again until ``--seconds`` have
passed; the iteration in flight is finished and counted.
``solves_per_s`` is taken over all of the window. ``ph_iter_s`` is the
wall time of the window's first K iterations / K: the window always
opens at the same PH iteration of the same instance, so that range is
the same work in every run, however many iterations a faster or slower
program fits into ``--seconds`` (hot iterations are not alike: 4.6 to
6.8 s in one window, my chip run, PR 25). With ``--trace 1`` one more
iteration follows the window and the checks, whose first
``trace_seconds`` the profiler records (a whole iteration is 5.6
million device events: the profiler's buffer drops them after ~6 s and
writing them takes minutes).

``--seed`` draws the scenarios that are checked against the reference
LP; the instance and its order are the same for every seed (see
``scenario_ids``).
"""

import threading
import time

import numpy as np


def scenario_ids(S, base):
    """The run's scenarios: ids base .. base+S-1 in that order, for
    every seed. Which scenarios share a chunk decides the work: the same
    256 scenarios in six seeded orders took 3.69 .. 6.70 s a hot iteration
    (window seconds / iterations; chunk-pooled rho adaptation at the
    recipe's residual floor; each order repeats to 0.2%; my chip runs,
    PR 25). So the instance AND its
    order are fixed, and ``--seed`` draws only the rows that are checked
    against the reference."""
    return int(base) + np.arange(S)


def sample_rows(seed, S, k):
    """k scenario rows to check, the first and the last among them (on
    a mesh and in a chunked loop they sit in different shards/chunks)."""
    rng = np.random.default_rng(int(seed) + 1)
    k = min(int(k), S)
    rest = rng.choice(np.arange(1, S - 1), size=max(k - 2, 0),
                      replace=False) if S > 2 else []
    return sorted({0, S - 1, *(int(i) for i in rest)})


def build_engine(run, ids):
    """The program's own entry points, as ``bench.bench_1024`` and
    ``chip_smoke.mesh_leg`` call them."""
    import jax.numpy as jnp
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree
    from mpisppy_tpu.models import uc
    from mpisppy_tpu.parallel.mesh import make_mesh

    cfg, p = run.config, run.params
    t = time.perf_counter()
    tree = two_stage_tree([f"scen{int(i)}" for i in ids],
                          nonant_names=["u", "st"])
    batch = build_batch(uc.scenario_creator, tree,
                        creator_kwargs=dict(cfg["instance"],
                                            **run.variant.get("instance",
                                                              {})),
                        vector_patch=uc.scenario_vector_patch)
    run.span("host_build", t)
    mesh = make_mesh(devices=list(run.devices)) \
        if len(run.devices) > 1 else None
    opts = dict(cfg["recipe"], **run.variant.get("recipe", {}),
                subproblem_chunk=chunk_rows(run))
    dtype = {"float32": jnp.float32, "float64": jnp.float64}[
        cfg["outer_dtype"]]
    return batch, PHBase(batch, opts, mesh=mesh, dtype=dtype)


def chunk_rows(run):
    return int(run.params.get("subproblem_chunk",
                              run.config["subproblem_chunk"]))


def hot_iteration(ph):
    import jax
    ph.solve_loop(w_on=True, prox_on=True)
    ph.W = ph.W_new
    jax.block_until_ready(ph.x)


def run(run):
    import jax

    import scenario_lp as ref

    p, lim = run.params, run.limits
    S = int(p["scenarios"])
    ids = scenario_ids(S, p["scenario_base"])
    batch, ph = build_engine(run, ids)
    shape = run.config["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m) == (shape["n"], shape["m"]), \
            f"width was cut: n={batch.n} m={batch.m}"

    # ---- set-up: iter-0 and the warm-up iterations ----
    t = time.perf_counter()
    obj0 = np.asarray(ph.solve_loop(w_on=False, prox_on=False))
    ph.W = ph.W_new
    jax.block_until_ready(ph.x)
    run.span("iter0", t)
    x0 = np.asarray(ph.x)[:S]
    iter0_pri = ph.residual_summary(False)["pri_rel_max"]
    t = time.perf_counter()
    for _ in range(int(p["warm_hot_iterations"])):
        hot_iteration(ph)
    run.span("warm_hot", t)
    xbar_before = np.asarray(ph.xbar).copy()
    ph.reset_phase_timing()

    # ---- the window ----
    gate = float(run.config["guarantees"]["pri_rel_gate"])
    ends, pri_max, convs = [], [], []
    t_open = run.open_window()
    while True:
        hot_iteration(ph)
        ends.append(time.perf_counter())
        pri_max.append(ph.residual_summary(True)["pri_rel_max"])
        convs.append(float(ph.conv))
        if ends[-1] - t_open >= run.seconds:
            break
    t_close = run.close_window()
    elapsed = t_close - t_open
    phase = ph.phase_timing(True)
    iters = np.diff([t_open] + ends)    # each holds its residual read
    k = min(int(p["ph_iter_range"]), len(ends))
    ph_iter_s = (ends[k - 1] - t_open) / k

    # ---- correct: the window's own numbers ----
    run.check("window_pri_rel_max", max(pri_max), gate)
    run.check("window_conv_finite", float(np.isfinite(convs).all()), 1.0,
              how="==")
    # ---- the consensus reduce, exactly, from the gathered state ----
    x = np.asarray(ph.x)[:S]
    prob = np.asarray(ph.prob)[:S]
    xn = x[:, np.asarray(ph.nonant_idx)]
    xbar_ref, conv_ref = ref.consensus(xn, prob)
    xbar = np.asarray(ph.xbar)
    run.check("reduce_xbar_err",
              float(np.abs(xbar - xbar_ref).max()
                    / max(1.0, np.abs(xbar_ref).max())),
              lim["reduce_xbar_err"])
    run.check("reduce_conv_err",
              abs(float(ph.conv) - conv_ref) / abs(conv_ref),
              lim["reduce_conv_err"])
    # a step that hands its state back unchanged moves nothing
    run.check("window_xbar_move",
              float(np.abs(xbar[0] - xbar_before[0]).max()),
              lim["window_xbar_move_min"], how=">=")
    # ---- the scenario solves against the plain reference ----
    A = ref.sparse(batch.A)
    box = (batch.l[:S], batch.u[:S], batch.lb[:S], batch.ub[:S])
    viol0 = ref.primal_violation(A, x0, *box)
    viol_hot = ref.primal_violation(A, x, *box)
    rows = sample_rows(run.seed, S, p["reference_sample"])
    t = time.perf_counter()
    gaps = []
    for r in rows:
        lp = ref.solve_lp(A, batch.c[r], batch.c0[r], *(b[r] for b in box))
        gaps.append(abs(float(obj0[r]) - lp) / abs(lp))
    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} scenario LPs by HiGHS in "
          f"{time.perf_counter() - t:.1f} s; iter-0 program pri_rel_max "
          f"{iter0_pri:.3g}; iter-0 objective gaps by row "
          f"{dict(zip(rows, (float(f'{g:.3g}') for g in gaps)))}; "
          f"violation over all {S} rows (min, quartiles, max): iter-0 "
          f"{quart(viol0)}, after the window {quart(viol_hot)}",
          flush=True)
    # held against a part of the batch left unsolved, or solved as
    # another problem
    run.check("iter0_obj_gap", max(gaps), lim["iter0_obj_gap"])
    run.check("iter0_primal_violation", float(viol0.max()),
              lim["iter0_primal_violation"])
    run.check("hot_primal_violation", float(viol_hot.max()),
              lim["hot_primal_violation"])
    # the number the recipe's precision moves: without the split-f32
    # refinement tail every scenario stops at the f32 noise floor, which
    # the best-converged quarter of the scenarios shows most plainly
    run.check("hot_violation_q1", float(np.quantile(viol_hot, 0.25)),
              lim["hot_violation_q1"])

    print(f"window: {len(ends)} hot iterations in {elapsed:.2f} s: "
          f"{[float(f'{v:.3f}') for v in iters]}; ph_iter_s "
          f"{ph_iter_s:.4f} = mean of the first {k}; median "
          f"{np.median(iters):.3f}; conv {convs[0]:.5f} -> "
          f"{convs[-1]:.5f}; phases/iter {phase['seconds_per_call']}; "
          f"mode {phase['mode']} on {phase['devices']} device(s); "
          f"kernel {phase['kernel']}", flush=True)
    obs_out = {"spans": dict(run.spans), "phase": phase,
               "iter_median_s": float(np.median(iters)),
               "chunk_solves_per_iteration":
                   -(-(S // len(run.devices)) // chunk_rows(run))}
    if run.trace:
        traced_iteration(run, ph)
    over_gate = sum(v > gate for v in pri_max)
    return {"attempted": len(ends) * S,
            # every solve of an iteration that averaged in a scenario
            # over the gate
            "failed": over_gate * S,
            "end_to_end": {"ph_iter_s": ph_iter_s,
                           "solves_per_s": len(ends) * S / elapsed},
            "observations": obs_out}


def traced_iteration(run, ph):
    """One hot iteration after the window, its first ``trace_seconds``
    under the profiler."""
    run.trace_start()
    timer = threading.Timer(float(run.params["trace_seconds"]),
                            run.trace_stop)
    timer.start()
    with run.annotate("bench.ph_iter"):
        hot_iteration(ph)
    timer.join()
    run.trace_stop()
