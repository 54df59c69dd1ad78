"""Scenario rows still over tolerance when a chunk solve's tail ran to
its cap, a mean over the window's tail-capped exits: ``rows_over`` /
``rows_read`` of ``phase_timing(True)["exits"]`` (``rows_read`` = the
tail-capped exits whose residual rows the host had: every one of them
on the chunked paths and the un-chunked fused body, so ``tail_capped``
in every listed cell). A row is over when it still fails the loop's
own ``conv_ok`` on the loop's own last residuals; chunk pads and mesh
pads are in no count. Of 64 rows a solve in the UC cells (4 x 64 on
the mesh), of 2000 in the sslp cell: one row holding a solve at the
cap asks for another cure than forty. From the chip only; ``None``
without the entry, 0 in a window with no capped exit. Moves
``ph_iter_s``."""

import harness

_tail = harness.load_module("metrics", "solve.tail_capped_share")


def read(obs):
    ex = _tail.entry(obs)
    if not ex:
        return None
    return ex["rows_over"] / ex["rows_read"] if ex["rows_read"] else 0.0
