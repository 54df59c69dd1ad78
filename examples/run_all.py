"""Run the full example sweep — every model family through a
representative engine/wheel — and exit nonzero listing the bad guys.

The analog of the reference's ``examples/run_all.py`` (ref.
examples/run_all.py:59-61: a shell loop of `mpiexec -np N python -m
mpi4py xxx_cylinders.py` drives accumulating a ``badguys`` dict). Here
each entry is an in-process wheel/engine drive through the typed
config layer plus two CLI subprocess drives (the `python -m
mpisppy_tpu ...` surface users actually invoke). ``examples/afew.py``
is the quick after-install smoke; this is the long tier (the
reference runs it weekly).

    python examples/run_all.py           # ~10-15 min on CPU

A CPU tool: this process and the CLI children it starts are pinned to
the CPU backend (a correctness sweep, not a measurement; the chip's
entry point is chip_smoke.py).
"""

import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np

from mpisppy_tpu.core.ef import ExtensiveForm
from mpisppy_tpu.utils.config import AlgoConfig, RunConfig, SpokeConfig
from mpisppy_tpu.utils.sputils import spin_the_wheel
from mpisppy_tpu.utils.vanilla import build_batch_for, wheel_dicts

badguys = {}


def check(name, ok, detail=""):
    print(f"{name}: {'OK' if ok else 'FAIL'} {detail}")
    if not ok:
        badguys[name] = detail


def sandwich(name, wheel, slack=1e-5):
    # slack scales with |inner| SIGN-SAFELY (inn*(1+slack) would be
    # stricter than equality for negative objectives); 1e-5 relative
    # absorbs the ADMM-tolerance crossings observed on farmer
    out, inn = wheel.best_outer_bound, wheel.best_inner_bound
    ok = np.isfinite(out) and out <= inn + slack * (1 + abs(inn))
    check(name, ok, f"outer {out:.2f} inner {inn:.2f}")


def wheel_of(model, spokes, hub="ph", num_scens=3, model_kwargs=None,
             iters=60, rho=1.0, rel_gap=5e-3, hub_options=None):
    cfg = RunConfig(
        model=model, num_scens=num_scens, model_kwargs=model_kwargs or {},
        hub=hub,
        algo=AlgoConfig(default_rho=rho, max_iterations=iters,
                        convthresh=-1.0, subproblem_max_iter=4000),
        hub_options=hub_options or {},
        spokes=[SpokeConfig(kind=k) if isinstance(k, str) else k
                for k in spokes],
        rel_gap=rel_gap)
    return spin_the_wheel(*wheel_dicts(cfg))


def main():
    # 1. farmer: PH + lagrangian + xhatshuffle (golden EF -108390)
    w = wheel_of("farmer", ["lagrangian", "xhatshuffle"])
    check("farmer wheel", w.best_outer_bound <= -108389.0
          and w.best_inner_bound >= -108391.0,
          f"outer {w.best_outer_bound:.1f} inner {w.best_inner_bound:.1f}")

    # 2. sizes: PH + lagrangian + xhatlooper
    sandwich("sizes wheel",
             wheel_of("sizes", ["lagrangian", "xhatlooper"],
                      model_kwargs={"scenario_count": 3}, rho=5.0))

    # 3. sslp: EF engine
    obj, _ = ExtensiveForm(build_batch_for(RunConfig(
        model="sslp", num_scens=4,
        model_kwargs={"num_servers": 3, "num_clients": 8}))
    ).solve_extensive_form()
    check("sslp EF", np.isfinite(obj), f"obj {obj:.2f}")

    # 4. netdes: PH + cross-scenario cuts
    sandwich("netdes wheel (cross-scenario)",
             wheel_of("netdes", ["lagrangian", "cross_scenario",
                                 "xhatshuffle"],
                      num_scens=4, model_kwargs={"num_nodes": 5},
                      rho=10.0))

    # 5. hydro (3-stage): PH + lagrangian + xhatspecific
    sandwich("hydro wheel (3-stage)",
             wheel_of("hydro", ["lagrangian", "xhatspecific"],
                      num_scens=9,
                      model_kwargs={"branching_factors": (3, 3)},
                      iters=50, rel_gap=2e-2))

    # 6. uc (integer, r5 constraint families): PH + lagrangian + xhatshuffle
    sandwich("uc wheel (T0 + su/sd ramps)",
             wheel_of("uc", ["lagrangian", "xhatshuffle"],
                      num_scens=5,
                      model_kwargs={"num_gens": 6, "num_hours": 8,
                                    "relax_integrality": False,
                                    "min_up_down": True, "ramping": True,
                                    "t0_state": True,
                                    "startup_shutdown_ramps": True,
                                    "quick_start": True},
                      rho=100.0, iters=80, rel_gap=1e-2))

    # 7. battery: EF
    obj, _ = ExtensiveForm(build_batch_for(RunConfig(
        model="battery", num_scens=3, model_kwargs={"T": 12}))
    ).solve_extensive_form()
    check("battery EF", np.isfinite(obj), f"obj {obj:.2f}")

    # 8. ccopf (4-stage quadratic): PH main
    from mpisppy_tpu.core.ph import PH
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import ccopf
    batch = build_batch(ccopf.scenario_creator,
                        ccopf.make_tree((2, 2, 2)),
                        creator_kwargs={"branching": (2, 2, 2)})
    ph = PH(batch, {"defaultPHrho": 1.0, "PHIterLimit": 20,
                    "convthresh": 1e-5, "subproblem_max_iter": 3000})
    conv, eobj, trivial = ph.ph_main()
    check("ccopf PH (4-stage)", np.isfinite(trivial),
          f"trivial {trivial:.2f} conv {conv:.2e}")

    # 9. aph hub on farmer
    sandwich("farmer APH wheel",
             wheel_of("farmer", ["lagrangian", "xhatshuffle"], hub="aph",
                      iters=100))

    # 10. lshaped hub on farmer + xhatlshaped
    sandwich("farmer L-shaped wheel",
             wheel_of("farmer", ["xhatlshaped"], hub="lshaped", iters=40))

    # 11-12. the CLI surface itself (subprocess, like the reference's
    # shell drives)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name, args in (
            ("CLI farmer EF", ["farmer", "--num-scens", "3", "--EF"]),
            ("CLI uc wheel", ["uc", "--num-scens", "3",
                              "--with-lagrangian", "--with-xhatshuffle",
                              "--max-iterations", "30"])):
        r = subprocess.run([sys.executable, "-m", "mpisppy_tpu"] + args,
                           cwd=root, env=env, capture_output=True,
                           text=True, timeout=900)
        check(name, r.returncode == 0, (r.stderr or "")[-200:])

    if badguys:
        print("badguys:", badguys)
        sys.exit(1)
    print("all good")
    sys.exit(0)


if __name__ == "__main__":
    main()
