"""Share of the chip's HBM bandwidth ONE eager build of the
per-scenario float64 inverse stack reaches: 100 x
``f64_stack_model.refactor_build_bytes(rows, m, n)`` / (build seconds x
HBM bytes per second of ``harness.peaks_for(device_kind)``), the seconds
those of ``solve.f64_refactor_build_s`` (span
``qp.f64_refactor_build``), rows and n the build's own, m the solve's.

Rated against HBM because ``peaks.json`` rates no float64 work against
a FLOP peak (the chip has no float64 datapath: the build is soft-float
on the vector unit). The bytes are a FLOOR (the matrix read once, the
inverse written once) whatever form implements the build, and the build
is COMPUTE-bound: about 3 n^3 + m n^2 float64 multiply-adds a scenario.
So the share errs low, cannot pass 100, and reads a few percent: it
says how far the build is from a copy, not how well it uses the chip.
``None`` where ``solve.f64_refactor_build_s`` is. Moves ``setup_s``."""

import f64_stack_model
import harness

_build = harness.load_module("metrics", "solve.f64_refactor_build_s")


def read(obs):
    rec = _build.entry(obs)
    shape = (obs.get("phase") or {}).get("solve_shape")
    if rec is None or not rec["seconds"] or not shape:
        return None
    peak = harness.peaks_for(obs["device_kind"])["hbm_gbps"] * 1e9
    moved = f64_stack_model.refactor_build_bytes(
        rows=rec["rows"], m=shape["m"], n=rec["n"])
    return 100.0 * moved / (rec["seconds"] / rec["builds"] * peak)
