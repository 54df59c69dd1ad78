"""Share of the chip's HBM bandwidth ONE eager build of the explicit
inverse reaches: 100 x ``linv_bytes_model.linv_build_bytes(n)`` /
(build seconds x HBM bytes per second of
``harness.peaks_for(device_kind)``), the seconds those of
``solve.linv_build_s`` (span ``qp.l_inv_build``), n the build's own.

Rated against HBM because ``peaks.json`` rates no f32 loop against a
FLOP peak. The bytes are a FLOOR (the factor's half read once, the
inverse's written once), and the build is SUBSTITUTION-bound: a blocked
forward substitution on the identity, whose sequential block rows
re-read the panel built so far and multiply in f32 at six bf16 passes.
So the share errs low, cannot pass 100, and reads a fraction of a
percent at UC width: it says how far the build is from a copy, not how
well it uses the chip. ``None`` where ``solve.linv_build_s`` is. Moves
``setup_s``."""

import harness
import linv_bytes_model

_build = harness.load_module("metrics", "solve.linv_build_s")


def read(obs):
    rec = _build.entry(obs)
    if rec is None or not rec["seconds"]:
        return None
    peak = harness.peaks_for(obs["device_kind"])["hbm_gbps"] * 1e9
    moved = linv_bytes_model.linv_build_bytes(n=rec["n"])
    return 100.0 * moved / (rec["seconds"] / rec["builds"] * peak)
