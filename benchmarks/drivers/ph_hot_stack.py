"""Driver ``ph_hot_stack``: ``ph_hot``'s traffic for an instance whose
scenarios each carry their own constraint matrix (the yields of the
farmer sit IN the matrix), so the engine's factor is a per-scenario
stack: (S, m, n) matrices and (S, n, n) float64 KKT inverses, rebuilt
where the rho adaptation moves a scenario.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  scenarios                 S of the run, scenarios ``scen<base> ..`` in
                            that order for every seed
  scenario_base             first scenario number
  warm_hot_iterations       hot iterations run as warm-up after iter-0
  ph_iter_range             K: ``ph_iter_s`` is the mean over the
                            window's first K iterations
  reference_sample          scenarios whose iter-0 objective is checked
                            against the plain reference LP (S: all)
  reference_sample_factors  seeded rows whose resident KKT inverse is
                            held against numpy after the window
  trace_seconds             seconds of one extra hot iteration the
                            profiler records (--trace 1)
  subproblem_chunk          rows per device call; left out, the
                            configuration's (0: one call for all rows)
Limits of the compared numbers: ``workloads/<cell>.json`` -> ``limits``.

Set-up: host build through the model's ``scenario_creator`` (no vector
patch: the matrices differ), the engine (hub-only ``PHBase``, the
configuration's recipe, both kernel options left at ``auto``). BEFORE
iter-0, from the stack's shape alone, the program's kernel rules are
held to the configuration's ``kernel`` block, and the run ends at once,
non-zero, if they differ: a program whose rules send this stack's
factors to the host or its polish to the library's row loops does not
reach the end of iter-0 in any set-up. Then iter-0
(``solve_loop(w_on=False, prox_on=False)``; the trivial bound) and the
warm-up iterations, which between them compile or load every program
the window runs. Window: ``ph_hot``'s step, closed loop, the iteration
in flight at ``--seconds`` finished and counted.

Nothing is read back inside the window that the normal path does not
read (conv): each iteration's ``pri_rel`` is kept as a REFERENCE to the
device array and looked at after the window has closed.

``--seed`` draws the rows checked against the reference where a sample
is smaller than S (the factors' rows); the instance and its order are
the same for every seed.
"""

import time

import numpy as np

import harness

# the step, the fixed order and the seeded row sample are ``ph_hot``'s own
_hot = harness.load_module("drivers", "ph_hot")
hot_iteration, scenario_ids, sample_rows, chunk_rows, traced_iteration = (
    _hot.hot_iteration, _hot.scenario_ids, _hot.sample_rows,
    _hot.chunk_rows, _hot.traced_iteration)


def instance_of(run):
    return dict(run.config["instance"], **run.variant.get("instance", {}))


def outer_dtype(run):
    import jax.numpy as jnp
    name = run.variant.get("outer_dtype", run.config["outer_dtype"])
    return {"float32": jnp.float32, "float64": jnp.float64}[name]


def build_engine(run, ids):
    """The program's own entry points: the model's creator through
    ``build_batch`` (every scenario its own matrix), then ``PHBase``."""
    from mpisppy_tpu import models
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree

    cfg = run.config
    mod = getattr(models, cfg["model"])
    t = time.perf_counter()
    tree = two_stage_tree([f"scen{int(i)}" for i in ids],
                          nonant_names=list(cfg["nonant_names"]))
    batch = build_batch(mod.scenario_creator, tree,
                        creator_kwargs=instance_of(run))
    run.span("host_build", t)
    opts = dict(cfg["recipe"], **run.variant.get("recipe", {}),
                subproblem_chunk=chunk_rows(run))
    return batch, PHBase(batch, opts, dtype=outer_dtype(run))


def kernel_by_rule(S, m, n):
    """What the program's rules answer for a (S, m, n) float64 stack
    with both kernel options at ``auto``: the plan's descriptor, from
    shapes alone (nothing is built, nothing runs)."""
    import jax
    import jax.numpy as jnp
    from mpisppy_tpu.ops import kernels, qp_solver
    fac = qp_solver.QPFactors(*[None] * len(qp_solver.QPFactors._fields)) \
        ._replace(A_s=jax.ShapeDtypeStruct((S, m, n), jnp.float64))
    return kernels.prepare(fac).descriptor()


def as_stated(want, got):
    return all((got or {}).get(k) == v for k, v in want.items())


def kkt_inverse_err(ph, rows):
    """|M M^-1 - I|max over ``rows`` of the hot mode's RESIDENT inverse:
    M = diag(P_s) + sigma I + A_s' diag(rho_A) A_s + diag(g^2 rho_b) at
    the state's own rho, rebuilt here in numpy float64 from the engine's
    scaled factors (a few rows' gather, after the window)."""
    fac, _ = ph._get_factors(True)
    st = ph._qp_states[True]
    idx = np.asarray(rows)
    take = lambda a: np.asarray(a[idx], np.float64)
    A_s, P_s = take(fac.A_s), take(fac.P_s)
    g = take(fac.Eb) * take(fac.D)
    rs = take(st.rho_scale)[:, None]
    M = np.einsum("smi,sm,smj->sij", A_s, take(fac.rho_A) * rs, A_s)
    k = np.arange(A_s.shape[-1])
    M[:, k, k] += P_s + float(fac.sigma) + g * g * take(fac.rho_b) * rs
    inv = take(st.L)
    return float(np.abs(np.einsum("sij,sjk->sik", M, inv)
                        - np.eye(k.size)).max())


def run(run):
    import jax

    import farmer_cm_lp as ref

    p, lim, cfg = run.params, run.limits, run.config
    S = int(p["scenarios"])
    ids = scenario_ids(S, p["scenario_base"])
    batch, ph = build_engine(run, ids)
    shape = cfg["shape"]
    if run.on_chip or "instance" not in run.variant:
        assert (batch.n, batch.m, batch.K) == (
            shape["n"], shape["m"], shape["nonants"]) \
            and S == int(cfg["scenarios"]), \
            f"width was cut: n={batch.n} m={batch.m} K={batch.K} S={S}"
    assert not batch.shared_A, "every scenario its own matrix"

    # ---- the program's rules against the configuration, before any
    # solve: the stated forms are what makes iter-0 end ----
    stated = "recipe" not in run.variant and "outer_dtype" not in run.variant
    if stated:
        by_rule = kernel_by_rule(S, batch.m, batch.n)
        if not as_stated(cfg["kernel"], by_rule):
            print(f"benchmark: for a ({S}, {batch.m}, {batch.n}) float64 "
                  f"stack this program's rules choose {by_rule}, the "
                  f"configuration states {cfg['kernel']}: the run ends "
                  "here, before iter-0", flush=True)
            raise SystemExit(4)

    # ---- set-up: iter-0 and the warm-up iterations ----
    t = time.perf_counter()
    obj0 = np.asarray(ph.solve_loop(w_on=False, prox_on=False))[:S]
    ph.W = ph.W_new
    jax.block_until_ready(ph.x)
    trivial_bound = ph.Ebound()
    run.span("iter0", t)
    x0 = np.asarray(ph.x)[:S]
    pri0 = float(np.asarray(ph._qp_states[False].pri_rel)[:S].max())
    phase0 = ph.phase_timing(False)
    t = time.perf_counter()
    for _ in range(int(p["warm_hot_iterations"])):
        hot_iteration(ph)
    run.span("warm_hot", t)
    xbar_before = np.asarray(ph.xbar).copy()
    ph.reset_phase_timing()

    # ---- the window ----
    gate = float(cfg["guarantees"]["pri_rel_gate"])
    ends, pri_rel, convs = [], [], []
    t_open = run.open_window()
    while True:
        hot_iteration(ph)
        ends.append(time.perf_counter())
        pri_rel.append(ph._qp_states[True].pri_rel)
        convs.append(ph.conv)
        if ends[-1] - t_open >= run.seconds:
            break
    t_close = run.close_window()
    elapsed = t_close - t_open
    phase = ph.phase_timing(True)
    iters = np.diff([t_open] + ends)
    k = min(int(p["ph_iter_range"]), len(ends))
    ph_iter_s = (ends[k - 1] - t_open) / k

    # ---- correct: the window's own numbers ----
    pri_max = [float(np.asarray(a)[:S].max()) for a in pri_rel]
    run.check("window_pri_rel_max", max(pri_max + [pri0]), gate)
    run.check("window_conv_finite", float(np.isfinite(convs).all()), 1.0,
              how="==")
    # ---- the consensus reduce, exactly, from the gathered state ----
    x = np.asarray(ph.x)[:S]
    prob = np.asarray(ph.prob)[:S]
    xbar_ref, conv_ref = ref.consensus(x[:, np.asarray(ph.nonant_idx)],
                                       prob)
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
    kernel = phase["kernel"] or {}
    if stated:
        run.check("kernel_as_stated",
                  float(as_stated(cfg["kernel"], kernel)), 1.0, how="==")
    # ---- every factor the window's last solve applied, on a seeded
    # sample of rows, against numpy ----
    if kernel.get("f64_refactor") is not None:
        frows = sample_rows(run.seed, S, p["reference_sample_factors"])
        run.check("kkt_inverse_err", kkt_inverse_err(ph, frows),
                  lim["kkt_inverse_err"])
    # ---- the scenario solves against the plain reference, built from
    # the book's numbers and never from the program's batch ----
    cm = int(instance_of(run)["crops_multiplier"])
    viol0 = ref.primal_violation(ids, cm, x0)
    viol_hot = ref.primal_violation(ids, cm, x)
    rows = sample_rows(run.seed, S, p["reference_sample"])
    t = time.perf_counter()
    lps = ref.scenario_lps(ids[rows], cm)
    t_ref = time.perf_counter() - t
    gaps = np.abs(obj0[rows] - lps) / np.abs(lps)
    quart = lambda v: [float(f"{q:.3g}") for q in
                       np.quantile(v, (0, .25, .5, .75, 1))]
    print(f"reference: {len(rows)} scenario LPs by HiGHS in {t_ref:.1f} s; "
          f"iter-0 pri_rel_max {pri0:.3g}; iter-0 objective gaps (min, "
          f"quartiles, max) {quart(gaps)}, largest at row "
          f"{rows[int(gaps.argmax())]}; violation over all {S} rows: "
          f"iter-0 {quart(viol0)}, after the window {quart(viol_hot)}",
          flush=True)
    # held against a part of the batch left unsolved, or solved as
    # another problem
    run.check("iter0_obj_gap", float(gaps.max()), lim["iter0_obj_gap"])
    if len(rows) == S:
        # the engine's trivial bound certifies sum_s p_s LP_s from below
        ws = ref.wait_and_see(lps, prob / prob.sum())
        run.check("trivial_bound_gap", abs(trivial_bound - ws) / abs(ws),
                  lim["trivial_bound_gap"])
        run.check("trivial_bound_below_lp", (trivial_bound - ws) / abs(ws),
                  lim["trivial_bound_below_lp"])
    run.check("iter0_primal_violation", float(viol0.max()),
              lim["iter0_primal_violation"])
    run.check("hot_primal_violation", float(viol_hot.max()),
              lim["hot_primal_violation"])
    # the number the solve's precision moves: a lower-precision loop
    # stops every scenario at its noise floor, which the best-converged
    # quarter of the scenarios shows most plainly
    run.check("hot_violation_q1", float(np.quantile(viol_hot, 0.25)),
              lim["hot_violation_q1"])

    print(f"window: {len(ends)} hot iterations in {elapsed:.2f} s: "
          f"{[float(f'{v:.3f}') for v in iters]}; ph_iter_s "
          f"{ph_iter_s:.4f} = mean of the first {k}; median "
          f"{np.median(iters):.3f}; conv {convs[0]:.5f} -> "
          f"{convs[-1]:.5f}; trivial bound {trivial_bound:.4f}; "
          f"phases/iter {phase['seconds_per_call']}, iter-0 "
          f"{phase0['seconds_per_call']}; ADMM/hot call "
          f"{phase['admm_iters_per_call']}, iter-0 "
          f"{phase0['admm_iters_per_call']}; mode {phase['mode']} on "
          f"{phase['devices']} device(s); kernel {kernel}; spans "
          f"{ {k_: float(f'{v:.2f}') for k_, v in run.spans.items()} }",
          flush=True)
    obs_out = {"spans": dict(run.spans), "phase": phase,
               "phase_iter0": phase0,
               "iter_median_s": float(np.median(iters)),
               "chunk_solves_per_iteration": 1}
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

