"""Share of its roofline the weight QP's program reaches: 100 x the
least seconds the chip could take for the QP's work
(``fwph_qp_model.floor_seconds`` at the run's own (S, C, K, trips)
against ``harness.peaks_for(device_kind)``: the larger of bytes over
the HBM's rate and operations over the peak rate; bytes bind) / the
DEVICE seconds of one execution of the ``simplex_qp_solve`` program in
the traced slice (``trace.modules``). ``None`` without a trace, off the
TPU, or where the slice holds no whole execution of it. Moves
``ph_iter_s``."""

import fwph_qp_model
import harness

PROGRAM = "jit_simplex_qp_solve"


def read(obs):
    tr, shape = obs.get("trace"), obs.get("fwph_qp_shape")
    if not tr or not shape or obs.get("platform") != "tpu":
        return None
    runs = [v for name, v in (tr.get("modules") or {}).items()
            if name.split("(")[0] == PROGRAM]
    secs, count = (sum(v[i] for v in runs) for i in (0, 1))
    if not count or not secs:
        return None
    floor, _bound = fwph_qp_model.floor_seconds(
        shape, harness.peaks_for(obs["device_kind"]))
    return 100.0 * floor / (secs / count)
