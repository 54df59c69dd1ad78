"""chip_smoke.py — the quickest proof that the system still starts on
the chip: the flagship wheel at full width, through the entry points a
user calls, on the attached TPU.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the sharded step on a 4-chip host

One chip, two legs in ONE process (a chip belongs to one process; this
script starts no child that imports jax):

 1. the reference-scale stochastic unit-commitment wheel (the
    ``instance`` of ``benchmarks/configs/uc90x48_df32.json`` — 90 gens
    x 48 h, n = 13,056, m = 26,016, integrality on) under its df32
    ``recipe``: the deployment the benchmark's cells state, read from
    the same file (``deployment()`` below), entered through
    ``mpisppy_tpu.__main__.run(RunConfig)``: PH hub + Lagrangian outer
    spoke + x̂ inner spoke as threads of one wheel, incumbents on the
    device, ``subproblem_chunk`` < S so the chunked loop iterates. Width
    and recipe are never cut; the counts are (S, CHUNK, MAX_HOT_ITERS
    and the x̂ spoke's candidate pool below): the hub iterates until
    the first incumbent has landed, so the bound sandwich is checked
    on every run. The three engines share the chip's memory and its
    one device queue under the wheel's arbiter (doc/cylinders.md): the
    leg's line carries ``wheel_timing`` (turns, device seconds and
    queue-wait seconds by cylinder, the exchange's seconds and bytes),
    so the spokes are timed beside the hub. The cell ``uc_s256_wheel``
    (``benchmarks/drivers/wheel_hot.py``) is this path at the
    deployment's rows.
 2. the serving layer, started in-process the way ``serve_main`` does:
    one farmer request, then the same shape with a ``patch`` — the
    second must be a warm-cache hit with zero new XLA compiles.

``--chips 4`` runs ONLY the sharded chunked df32 PH step (the path
``__graft_entry__.dryrun_multichip`` rehearses on virtual CPU devices)
at full UC width on a 4-device mesh — its cross-chip reduce checked
EXACTLY against a host recomputation from the gathered shards — and the
same S on one device, and compares x̄ and conv.

Every line of stdout is one JSON object; the LAST is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check, any exception in any leg, or a non-TPU backend ends
the run with ``"ok": false`` and a non-zero exit code. The script sets
no JAX_PLATFORMS and forces no platform.
"""

import argparse
import functools
import json
import os
import resource
import sys
import tempfile
import time
import traceback
import urllib.request

S, CHUNK, MAX_HOT_ITERS = 16, 8, 60     # one-chip wheel: the only cuts
# the hub stops at the first termination check that sees BOTH bounds
# (any finite gap passes), i.e. once the x̂ spoke's first incumbent has
# landed — or at MAX_HOT_ITERS / the wheel deadline, which fails the run
FIRST_INCUMBENT_REL_GAP = 10.0
MESH_S, MESH_CHUNK = 8, 1               # --chips 4: 2 scen/device, 2 chunks
# the cross-chip reduce is checked exactly: x̄ and conv recomputed on the
# host (numpy, f64) from the gathered x and prob must equal the sharded
# engine's to rounding
MESH_REDUCE_RTOL = 1e-9
# sharded vs single device is a comparison of two SOLVER runs: both are
# the df32 recipe's budget-capped df32 solves of a degenerate LP
# relaxation (accepted at a 1e-2 pri_rel gate, doc/tpu_numerics.md), so
# two correct runs land on different points of the optimal face and x̄
# agrees in the mean, not slot by slot (rehearsed on 4 virtual CPU
# devices at 20 gens x 24 h: mean |Δx̄| 0.050, conv 28% apart). This
# band only catches a mesh run that solved something else.
MESH_XBAR_MEAN_ATOL, MESH_CONV_RTOL = 0.1, 0.5
# |M·M⁻¹ − I|max a trusted f64 device inverse must meet at every probed
# width (the ADMM runs at 1e-4..1e-6)
F64_RESID_BOUND = 1e-8
# the same residual for an inverse rebuilt INSIDE a solve program when
# rho moves (the served stack's, doc/kernels.md §3f): the host path's
F64_REFACTOR_BOUND = 1e-9
V5E_ROW = (197e12, 819.0)

HERE = os.path.dirname(os.path.abspath(__file__))
# the run's event stream + metrics come back with the chip tool's
# output directory (git-ignored)
TELEMETRY_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke_telemetry")
_T0 = time.perf_counter()


# stdout carries JSON lines only: the program's own screen trace
# (global_toc) is sent to stderr for the run (see main)
_OUT = sys.stdout


def emit(stage, **fields):
    # every line carries the process's peak HOST memory so far: a
    # UC-width XLA compile takes ~10 GB of it, and the wheel's threads
    # compile concurrently
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(json.dumps({"stage": stage,
                      "t": round(time.perf_counter() - _T0, 2),
                      "host_peak_rss_gib": round(rss, 1), **fields},
                     default=str), file=_OUT, flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


@functools.cache
def deployment():
    """(instance, recipe, n, m) of the UC deployment, from the file the
    benchmark's cells read: the bring-up check and the yardstick run
    ONE stated deployment. Callers copy the dicts before adding keys."""
    with open(os.path.join(HERE, "benchmarks", "configs",
                           "uc90x48_df32.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    return (cfg["instance"], cfg["recipe"],
            cfg["shape"]["n"], cfg["shape"]["m"])


def _events():
    with open(os.path.join(TELEMETRY_DIR, "events.jsonl"),
              encoding="utf-8") as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _cache_entries(path):
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def _compiles(obs):
    """(count, seconds) of backend XLA compiles so far this session."""
    h = obs.histogram_snapshot("jax.compile_seconds") or {}
    return int(obs.counter_value("jax.compiles")), float(h.get("sum", 0.0))


def _tpu_devices(x):
    devs = sorted(x.devices(), key=lambda d: d.id)
    check(all(d.platform == "tpu" for d in devs),
          f"engine arrays are not on TPU devices: {devs}")
    return devs


def _peak_hbm():
    import jax

    out = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        out[str(d.id)] = st.get("peak_bytes_in_use")
    return out


def f64_linalg_probe(num_gens, num_hours, batch=4):
    """What ops/qp_solver._device_f64_linalg_trusted asserts, measured
    on THIS device through the program's own routine: |M·M⁻¹ − I|max of
    ``_factorize``'s f64 explicit inverse for REAL UC KKT matrices in
    the scenario hospital's spelling (the shared A broadcast to a
    (batch, m, n) non-shared block, qp_setup's own scaling and
    eq-boosted rho) and for the single shared factor, beside numpy on
    the same matrix. The shared factor always runs on the device, so
    it must meet the bound; the batched inverse must meet it only
    where the rule trusts the backend with it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpisppy_tpu.core.spbase import SPBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import uc
    from mpisppy_tpu.ops.qp_solver import (QPData,
                                           _device_f64_linalg_trusted,
                                           _factorize, qp_setup)

    b = build_batch(uc.scenario_creator, uc.make_tree(batch),
                    creator_kwargs=dict(deployment()[0], num_gens=num_gens,
                                        num_hours=num_hours),
                    vector_patch=uc.scenario_vector_patch)
    sp = SPBase(b, {}, dtype=jnp.float64)
    d, n = sp.qp_data, b.n
    d_b = QPData(jnp.broadcast_to(d.P_diag, (batch, n)),
                 jnp.broadcast_to(d.A, (batch,) + d.A.shape),
                 d.l, d.u, d.lb, d.ub)
    fac_b, fac_s = qp_setup(d_b, q_ref=sp.c), qp_setup(d, q_ref=sp.c)

    def kkt(f, pick):
        A, g = np.asarray(pick(f.A_s)), np.asarray(pick(f.Eb * f.D))
        M = A.T @ (np.asarray(pick(f.rho_A))[:, None] * A)
        M[np.diag_indices(n)] += np.asarray(pick(f.P_s)) \
            + float(f.sigma) + g * g * np.asarray(pick(f.rho_b))
        return M

    def resid(M, inv):
        return float(np.abs(M @ np.asarray(inv) - np.eye(n)).max())

    inv_b = jax.jit(_factorize)(fac_b, jnp.ones(batch))
    inv_s = jax.jit(_factorize)(fac_s, jnp.asarray(1.0))
    M_b, M_s = kkt(fac_b, lambda a: a[batch - 1]), kkt(fac_s, lambda a: a)
    ev = np.linalg.eigvalsh(M_b)
    out = {"n": n, "m": b.m, "batch": batch, "cond": float(ev[-1] / ev[0]),
           "batched_device_resid": resid(M_b, inv_b[batch - 1]),
           "shared_device_resid": resid(M_s, inv_s),
           "numpy_resid": resid(M_b, np.linalg.inv(M_b))}
    out["batched_trusted_by_rule"] = _device_f64_linalg_trusted()
    check(out["shared_device_resid"] <= F64_RESID_BOUND,
          f"shared f64 device factor misses {F64_RESID_BOUND}: {out}")
    check(not out["batched_trusted_by_rule"]
          or out["batched_device_resid"] <= F64_RESID_BOUND,
          f"the rule trusts a batched f64 inverse that misses "
          f"{F64_RESID_BOUND}: {out}")
    return out


def f64_refactor_probe(rho_scale, stack=8):
    """What ops/qp_solver.f64_refactor_form asserts for the served
    stack, measured on THIS device through the program's own routine:
    |M·M⁻¹ − I|max of ``_factorize``'s explicit inverse of a full
    wheel's per-scenario float64 KKTs (``stack`` three-scenario farmers
    with cost patches, stacked as serve/batch stacks them: (24, 12, 12))
    at ``rho_scale``, beside numpy on the same matrices. Where the rule
    keeps the refactorization on the device ("unrolled" on the TPU,
    "library" on a backend with trusted f64 linalg) it must meet the
    host path's bar at every rho the adaptation's clip allows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpisppy_tpu.core.spbase import SPBase
    from mpisppy_tpu.ops.qp_solver import (_factorize, _kkt_host,
                                           f64_refactor_form, qp_setup)
    from mpisppy_tpu.serve import batch as sbatch
    from mpisppy_tpu.utils.vanilla import build_batch_for

    payload = {"model": "farmer", "num_scens": 3}
    base = build_batch_for(sbatch.base_runconfig(payload))
    rng = np.random.default_rng(20260927)
    stacked, _ = sbatch.stack_instances([
        sbatch.apply_patch(base, {"c": {"DevotedAcreage": [
            float(b * rng.uniform(0.9, 1.1)) for b in (150., 230., 260.)]}})
        for _ in range(stack)])
    sp = SPBase(stacked, {}, dtype=jnp.float64)
    fac = qp_setup(sp.qp_data, q_ref=sp.c)
    S, _m, n = fac.A_s.shape
    rs = np.full((S,), float(rho_scale))
    inv = np.asarray(jax.jit(_factorize)(fac, jnp.asarray(rs)))
    M = _kkt_host(fac, rs)
    out = {"case": "farmer_stack", "shape": [S, n, n],
           "rho_scale": float(rho_scale),
           "cond": float(np.linalg.cond(M).max()),
           "form_by_rule": f64_refactor_form(fac.A_s),
           "batched_device_resid": float(np.abs(M @ inv - np.eye(n)).max()),
           "numpy_resid": float(np.abs(M @ np.linalg.inv(M)
                                       - np.eye(n)).max())}
    check(out["form_by_rule"] == "host"
          or out["batched_device_resid"] <= F64_REFACTOR_BOUND,
          f"the rule keeps a float64 refactorization on the device that "
          f"misses {F64_REFACTOR_BOUND}: {out}")
    return out


# ---------------- leg 1: the UC df32 wheel ----------------

def uc_wheel_leg():
    import threading

    import numpy as np

    import mpisppy_tpu.utils.sputils as sputils
    from mpisppy_tpu import obs
    from mpisppy_tpu.__main__ import run
    from mpisppy_tpu.obs import profile
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig

    instance, df32, n_scen, m_scen = deployment()
    recipe = dict(df32, subproblem_chunk=CHUNK, iter0_feas_tol=5e-3,
                  display_timing=False)
    cfg = RunConfig(
        model="uc", num_scens=S, model_kwargs=dict(instance),
        algo=AlgoConfig(default_rho=df32["defaultPHrho"],
                        max_iterations=MAX_HOT_ITERS, convthresh=-1.0),
        # the recipe rides hub_options / the spokes' own options: the
        # CLI has no precision flag
        hub_options=dict(recipe),
        # the x̂ spoke is the device candidate-pool spoke with the
        # smallest pool: one vote threshold + the builder's two slam
        # and two bound rows = 5 candidates x S scenarios per round (a
        # count, like S)
        spokes=[SpokeConfig("lagrangian", dict(recipe)),
                SpokeConfig("dive",
                            dict(recipe, xhat_pin_vars=["u"],
                                 incumbent_pool_thresholds=[0.5],
                                 incumbent_pool_flips=0,
                                 incumbent_pool_random=0))],
        incumbent_mode="device", rel_gap=FIRST_INCUMBENT_REL_GAP,
        wheel_deadline=900.0)

    # run() returns the bounds only; the engine the checks below read
    # rides the WheelResult run() gets from spin_the_wheel
    seen = {}
    spin = sputils.spin_the_wheel

    def spin_and_keep(*a, **kw):
        seen["wheel"] = spin(*a, **kw)
        return seen["wheel"]

    sputils.spin_the_wheel = spin_and_keep
    compiles0 = _compiles(obs)
    t0 = time.perf_counter()
    try:
        result = run(cfg)
    finally:
        sputils.spin_the_wheel = spin
    wall = time.perf_counter() - t0
    compiles1 = _compiles(obs)
    wheel = seen["wheel"]
    ph = wheel.hub.opt

    check(ph.batch.n == n_scen and ph.batch.m == m_scen,
          f"width was cut: n={ph.batch.n} m={ph.batch.m}")
    check(int(ph._iter) >= 2,
          f"only {ph._iter} hot PH iterations completed")
    # every cylinder finished: no spoke was declared stuck at the join
    # (its result would be None and its thread still running into the
    # serve leg), and the x̂ spoke landed an incumbent
    check(all(r is not None for r in wheel.spoke_results),
          f"a spoke did not exit at the join: {wheel.spoke_results}")
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith("spoke")]
    check(not alive, f"spoke threads outlived the wheel: {alive}")
    conv = float(ph.conv)
    xbar = np.asarray(ph.xbar)
    outer, inner = result["outer_bound"], result["inner_bound"]
    check(np.isfinite(conv), f"conv not finite: {conv}")
    check(xbar.shape[-1] == ph.batch.K and np.isfinite(xbar).all(),
          f"xbar not finite / wrong shape {xbar.shape}")
    check(outer is not None and np.isfinite(outer),
          f"outer bound not finite: {outer}")
    check(inner is not None and np.isfinite(inner),
          f"no incumbent landed within {ph._iter} iterations: {inner}")
    check(outer <= inner + 1e-6 * (1.0 + abs(inner)),
          f"bounds crossed: outer {outer} > inner {inner}")
    devs = _tpu_devices(ph.x)
    pt_hot, pt_0 = ph.phase_timing(True), ph.phase_timing(False)
    check(pt_hot is not None and pt_0 is not None,
          "iter-0 / hot solve loops never ran")
    check(pt_hot["kernel"]["mode"] == "fused",
          f"kernel plan is not the fused program: {pt_hot['kernel']}")
    pk = profile.peaks()
    check(pk[2] == "table" and (pk[0], pk[1]) == V5E_ROW,
          f"device peaks did not resolve to the v5e table row: {pk}")
    emit("uc_wheel", S=S, chunk=CHUNK, hot_iters=int(ph._iter),
         n=ph.batch.n, m=ph.batch.m, K=ph.batch.K, conv=conv,
         xbar_min=float(xbar.min()), xbar_max=float(xbar.max()),
         outer_bound=outer, inner_bound=inner,
         rel_gap=(inner - outer) / abs(inner),
         devices=[str(d) for d in devs],
         kernel_plan=pt_hot["kernel"],
         phase_seconds_iter0=pt_0["seconds_per_call"],
         phase_seconds_hot=pt_hot["seconds_per_call"],
         hot_calls=pt_hot["calls"], mode=pt_hot["mode"],
         donated_passes=int(obs.counter_value("qp.donated_passes")),
         wall_seconds=round(wall, 1),
         xla_compiles=compiles1[0] - compiles0[0],
         xla_compile_seconds=round(compiles1[1] - compiles0[1], 1),
         peak_hbm_bytes=_peak_hbm(),
         wheel_timing={k: v for k, v in wheel.hub.wheel_timing().items()
                       if k != "spokes"},
         peaks={"flops": pk[0], "hbm_gbps": pk[1], "source": pk[2],
                "device_kind": pk[3]})
    # where the cold run's seconds went, from the event stream's clock
    obs.flush()
    ev = _events()
    # this wheel's events only (the serve leg's wheels come later, and
    # a reused output directory may hold an earlier run's)
    ev = ev[max(i for i, e in enumerate(ev)
                if e["type"] == "wheel.build"):]
    t_of = {e["type"]: e["t"] for e in ev
            if e["type"] in ("wheel.build", "batch.build", "ph.iter0")}
    # the compiles that cost minutes and GiBs of host memory each: the
    # wheel's three engines must share ONE fused chunk-solve program
    # (none when the machine's compile cache already held it)
    big = [{"entry": e["entry"], "seconds": round(e["seconds"], 1)}
           for e in ev if e["type"] == "jax.compile"
           and e["seconds"] >= 20.0]
    fused = [c for c in big if "_fused_mixed_impl" in c["entry"]]
    check(len(fused) <= 1,
          f"the wheel compiled {len(fused)} fused chunk-solve programs")
    emit("uc_wheel_stages",
         host_build_seconds=round(t_of["batch.build"]
                                  - t_of["wheel.build"], 1),
         setup_compile_iter0_seconds=round(t_of["ph.iter0"]
                                           - t_of["batch.build"], 1),
         hot_iteration_seconds=[round(e["seconds"], 1) for e in ev
                                if e["type"] == "ph.iteration"],
         compiles_over_20s=big)


# ---------------- leg 2: the serving layer ----------------

def _http(url, obj=None):
    req = urllib.request.Request(
        url, data=None if obj is None else json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _wait_done(base, rid, timeout):
    t0 = time.time()
    while time.time() - t0 < timeout:
        rec = _http(f"{base}/result/{rid}")
        if rec["status"] in ("done", "failed"):
            return rec
        time.sleep(0.2)
    raise TimeoutError(f"serve request {rid} still {rec['status']}")


def serve_leg():
    from mpisppy_tpu.serve.http import ServeHTTPServer
    from mpisppy_tpu.serve.manager import ServeService
    from mpisppy_tpu.utils.config import ServeConfig

    farmer = {"model": "farmer", "num_scens": 3,
              "algo": {"max_iterations": 10}}
    patch = {"c": {"DevotedAcreage": [160.0, 235.0, 250.0]}}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_serve_") as state:
        service = ServeService(ServeConfig(state_dir=state).validate())
        service.start()
        server = ServeHTTPServer(service, 0).start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            r1 = _wait_done(
                base, _http(f"{base}/solve", farmer)["request_id"], 600)
            check(r1["status"] == "done", f"first request: {r1}")
            r2 = _wait_done(
                base, _http(f"{base}/solve",
                            {**farmer, "patch": patch,
                             "batchable": False})["request_id"], 300)
            check(r2["status"] == "done", f"second request: {r2}")
        finally:
            server.stop()
            service.stop(join_timeout=30.0)
    w1, w2 = r1["result"]["wheel"], r2["result"]["wheel"]
    check(w1["xla_compiles_delta"] > 0,
          "the first request booked no compile: the counter is dead")
    check(w2["cache_hit"] is True, f"second request missed the cache: {w2}")
    check(w2["xla_compiles_delta"] == 0,
          f"second request recompiled: {w2['xla_compiles_delta']}")
    emit("serve", first={k: w1[k] for k in ("cache_hit",
                                            "xla_compiles_delta",
                                            "seconds")},
         second={k: w2[k] for k in ("cache_hit", "xla_compiles_delta",
                                    "seconds")},
         objectives=[r1["result"]["objective"], r2["result"]["objective"]])


# ---------------- --chips 4: the sharded step ----------------

def mesh_leg(n_chips):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree
    from mpisppy_tpu.models import uc
    from mpisppy_tpu.parallel.mesh import make_mesh

    instance, df32 = deployment()[:2]

    def build(order):
        t = time.perf_counter()
        tree = two_stage_tree([f"scen{i}" for i in order],
                              nonant_names=["u", "st"])
        b = build_batch(uc.scenario_creator, tree,
                        creator_kwargs=dict(instance),
                        vector_patch=uc.scenario_vector_patch)
        emit("mesh_host_build", seconds=round(time.perf_counter() - t, 1),
             order=[int(i) for i in order], n=b.n, m=b.m)
        return b

    def two_steps(batch, mesh, chunk):
        opts = dict(df32, subproblem_chunk=chunk, iter0_feas_tol=5e-3,
                    display_timing=False)
        t = time.perf_counter()
        ph = PHBase(batch, opts, mesh=mesh, dtype=jnp.float64)
        ph.solve_loop(w_on=False, prox_on=False)     # iter 0
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)       # one hot step
        jax.block_until_ready(ph.xbar)
        return ph, time.perf_counter() - t

    batch = build(range(MESH_S))
    ph_m, sec_m = two_steps(batch, make_mesh(n_chips), MESH_CHUNK)
    pt = ph_m.phase_timing(True)
    check(pt["mode"] == "sharded" and pt["devices"] == n_chips,
          f"the sharded chunked path did not engage: {pt}")
    devs = _tpu_devices(ph_m.x)
    check(len(devs) == n_chips and len({d.id for d in devs}) == n_chips,
          f"ph.x is not spread over {n_chips} distinct TPU devices: {devs}")
    check(len(ph_m.x.sharding.device_set) == n_chips,
          "ph.x sharding does not span the mesh")
    # the shared operands must be REPLICATED over the mesh, not parked
    # on device 0
    A = getattr(ph_m.qp_data.A, "A_s", ph_m.qp_data.A)
    for name, arr in (("A.hi", A.hi), ("A.lo", A.lo)):
        check(len(arr.sharding.device_set) == n_chips
              and arr.sharding.is_fully_replicated,
              f"shared operand {name} is not replicated over the mesh: "
              f"{arr.sharding}")
    xbar_m, conv_m = np.asarray(ph_m.xbar)[0], float(ph_m.conv)
    obj_m = ph_m.Eobjective_value()
    # the cross-chip reduce, EXACTLY: gather every shard's x and prob to
    # the host and redo Compute_Xbar and the convergence metric there
    # (numpy, f64). A reduce that drops or mis-weights a shard moves x̄
    # by percents; rounding moves it by ~1e-15.
    prob = np.asarray(ph_m.prob)
    xn = np.asarray(ph_m.x)[:, np.asarray(ph_m.nonant_idx)]
    xbar_host = prob @ xn / prob.sum()
    conv_host = float(prob @ np.abs(xn - xbar_host).sum(axis=1)
                      / xn.shape[1])
    reduce_err = {
        "xbar_max_abs_err": float(np.abs(xbar_m - xbar_host).max()),
        "conv_rel_err": abs(conv_m - conv_host) / abs(conv_host)}
    check(np.abs(np.asarray(ph_m.xbar) - xbar_m).max() == 0.0,
          "the shards disagree on x̄")
    check(reduce_err["xbar_max_abs_err"]
          <= MESH_REDUCE_RTOL * max(1.0, float(np.abs(xbar_host).max()))
          and reduce_err["conv_rel_err"] <= MESH_REDUCE_RTOL,
          f"the sharded reduce differs from the host recomputation: "
          f"{reduce_err}")
    # the sharded loop's chunk ci holds local rows [ci*lc, (ci+1)*lc) of
    # EVERY device's shard — strided global ids
    ops = ph_m._shard_ops
    n_chunks = (MESH_S // n_chips) // MESH_CHUNK
    order = np.concatenate([ops.chunk_global_index(ci, MESH_CHUNK)
                            for ci in range(n_chunks)])
    emit("mesh_sharded", seconds=round(sec_m, 1), mode=pt["mode"],
         devices=[str(d) for d in devs], conv=conv_m,
         shard_rows=MESH_S // n_chips, chunk_rows_per_device=MESH_CHUNK,
         kernel_plan=pt["kernel"],
         phase_seconds_hot=pt["seconds_per_call"],
         reduce_vs_host=reduce_err, reduce_rtol=MESH_REDUCE_RTOL,
         peak_hbm_bytes=_peak_hbm())
    # free the mesh run's device arrays (the batch caches the shipped
    # operands) before the one-device run allocates its own
    del ph_m, A, arr, xn
    if getattr(batch, "_dev_cache", None):
        batch._dev_cache.clear()

    # the comparison: the same scenarios on ONE device, listed in the
    # sharded run's chunk order, so both runs solve the same chunks in
    # the same sequence (shared rho adaptation pools a chunk's rows and
    # the df32 factor flows chunk to chunk — composition matters) and
    # differ by the collectives' reduction order only; x̄ is the
    # probability-weighted mean, invariant to the listing order
    ph_1, sec_1 = two_steps(build(order), None, MESH_CHUNK * n_chips)
    check(ph_1.phase_timing(True)["mode"] == "host",
          "the comparison run was not single-device")
    xbar_1, conv_1 = np.asarray(ph_1.xbar)[0], float(ph_1.conv)
    obj_1 = ph_1.Eobjective_value()
    check(np.isfinite(xbar_m).all() and np.isfinite(xbar_1).all()
          and np.isfinite(conv_m) and np.isfinite(conv_1),
          "non-finite xbar/conv")
    dx = np.abs(xbar_m - xbar_1)
    emit("mesh_vs_single", seconds_single=round(sec_1, 1),
         conv_sharded=conv_m, conv_single=conv_1,
         xbar_max_abs_diff=float(dx.max()),
         xbar_mean_abs_diff=float(dx.mean()),
         eobj_sharded=obj_m, eobj_single=obj_1,
         xbar_mean_atol=MESH_XBAR_MEAN_ATOL, conv_rtol=MESH_CONV_RTOL)
    check(dx.mean() <= MESH_XBAR_MEAN_ATOL,
          f"sharded xbar differs from single-device by {dx.mean()} "
          "in the mean")
    check(abs(conv_m - conv_1) <= MESH_CONV_RTOL * abs(conv_1),
          f"sharded conv {conv_m} vs single-device {conv_1}")


# ---------------- driver ----------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default): the UC wheel + serve legs on one "
                         "chip; 4: ONLY the sharded step vs one device")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    import numpy as np

    device = None
    sys.stdout = sys.stderr
    try:
        dev0 = jax.devices()[0]
        device = {"platform": dev0.platform, "kind": dev0.device_kind,
                  "count": len(jax.devices())}
        check(dev0.platform == "tpu",
              f"no TPU: jax found {device} (this script does not time "
              "the CPU)")
        check(len(jax.devices()) == args.chips,
              f"--chips {args.chips} needs exactly {args.chips} TPU "
              f"device(s); jax sees {len(jax.devices())}")

        from mpisppy_tpu import obs
        from mpisppy_tpu.utils.runtime import (compile_cache_dir,
                                               setup_jax_runtime)
        setup_jax_runtime()
        cache = compile_cache_dir()
        try:
            import libtpu
            libtpu_version = getattr(libtpu, "__version__", "?")
        except ImportError:
            libtpu_version = None
        emit("start", device=device, jax=jax.__version__,
             jaxlib=jaxlib.__version__, libtpu=libtpu_version,
             numpy=np.__version__, compile_cache_dir=cache,
             cache_entries_before=_cache_entries(cache))
        # the compile/ledger counters both legs assert on only count
        # while a telemetry session is active
        obs.configure(out_dir=TELEMETRY_DIR, config={"chip_smoke": True})
        if args.chips == 1:
            for gens, hours in ((3, 12), (10, 24)):
                emit("f64_linalg", **f64_linalg_probe(gens, hours))
            for rho_scale in (1.0, 1e6):
                emit("f64_linalg", **f64_refactor_probe(rho_scale))
            uc_wheel_leg()
            serve_leg()
        else:
            mesh_leg(args.chips)
        emit("end", cache_entries_after=_cache_entries(cache),
             seconds=round(time.perf_counter() - _T0, 1))
        obs.shutdown()
    except BaseException as e:   # report, then fail: never exit 0
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"ok": False, "error": repr(e)[:500],
                          "device": device}), file=_OUT, flush=True)
        return 1
    finally:
        sys.stdout = _OUT
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
