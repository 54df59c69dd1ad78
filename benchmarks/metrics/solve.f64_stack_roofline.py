"""Share of the chip's HBM bandwidth the native-float64 solve over a
per-scenario stack reaches: 100 x bytes its ADMM iterations stream /
(solve seconds x HBM bytes per second of
``harness.peaks_for(device_kind)``). Bytes = ADMM iterations per
``solve_loop`` call x ``f64_stack_model.admm_iteration_bytes`` at the
program's own ``solve_shape`` (rows per device call, m, n): the matrix
twice and the inverse once an iteration, and the vectors. Counts and
seconds come from ONE ``phase_timing`` entry: the same solves, the same
reset. The seconds are the HOST's solve-phase seconds (the launch and
the wait for the packed exit included; the in-program rebuilds of the
inverse too, whose bytes are not counted), so the share errs low and
cannot pass 100. Rated against HBM: the products are float64
multiply-and-sum fusions with one multiply-add an entry read
(doc/kernels.md section 3d), and ``peaks.json`` rates no float64 work
against a FLOP peak. ``None`` off the TPU, and where the timed solves'
factor is no per-scenario float64 inverse. Moves ``ph_iter_s``."""

import f64_stack_model
import harness


def read(obs):
    ph = obs.get("phase") or {}
    admm, shape = ph.get("admm_iters_per_call"), ph.get("solve_shape")
    kernel = ph.get("kernel") or {}
    if not admm or not shape or obs.get("platform") != "tpu" \
            or kernel.get("f64_products") is None \
            or kernel.get("f64_refactor") is None:
        return None
    moved = (admm["bulk"] + admm["tail"]) \
        * f64_stack_model.admm_iteration_bytes(
            rows=shape["s_chunk"], m=shape["m"], n=shape["n"])
    peak = harness.peaks_for(obs["device_kind"])["hbm_gbps"] * 1e9
    return 100.0 * moved / (ph["seconds_per_call"]["solve"] * peak)
