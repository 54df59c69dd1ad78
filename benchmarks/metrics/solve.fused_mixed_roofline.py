"""Share of the chip's HBM bandwidth the fused mixed/df32 chunk solve
reaches: 100 x bytes its ADMM iterations stream / (solve seconds x HBM
bytes per second of ``harness.peaks_for(device_kind)``). Bounded by
HBM: the loop is f32/df32 and never rated against a FLOP peak.

Bytes = bulk iterations x B_bulk + tail iterations x B_tail per
``solve_loop`` call, B from ``bytes_model.hbm_bytes_per_iter`` priced at
the program's own ``solve_shape`` (n, m, rows per device call, IR
sweeps, packed operand bytes, block dtype). Counts and seconds come
from ONE ``phase_timing`` entry: the same pass-1 chunk solves, the same
reset. The seconds are the HOST's solve-phase seconds (launch gaps and
the wait for the last chunk included), not device seconds from the
trace, which holds no whole chunk solve (PERF.md section 3): the share
errs low. ``None`` off the TPU. Moves ``ph_iter_s``."""

import bytes_model
import harness


def read(obs):
    ph = obs.get("phase") or {}
    admm, shape = ph.get("admm_iters_per_call"), ph.get("solve_shape")
    if not admm or not shape or obs.get("platform") != "tpu":
        return None
    per_iter = bytes_model.hbm_bytes_per_iter(**shape)
    moved = sum(admm[k] * per_iter[k] for k in ("bulk", "tail"))
    peak = harness.peaks_for(obs["device_kind"])["hbm_gbps"] * 1e9
    return 100.0 * moved / (ph["seconds_per_call"]["solve"] * peak)
