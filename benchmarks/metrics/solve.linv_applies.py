"""L^-1 products per chunk solve, a mean over ALL the window's hot
solve calls: ``PHBase.phase_timing(True)["admm_iters_per_call"]
["linv_applies"]`` / chunk solves per iteration. Derived by the program
on the host from counts it already holds (no readback of its own):
tail iterations x (1 + IR sweeps) solves x 2 products where the timed
mode's plan carries the explicit inverse (``kernel.l_inv``), 0 where
the x-update substitutes through the prepared factor. So it says WHICH
x-update the timed solves ran (at the recipe's one sweep: 4 x
``solve.tail_iters``, or 0), and a later change to the rule
(``l_inv_profitable``) cannot flip a cell's form unseen. ``better`` is
nominal: the number is a form, not a cost. ``None`` off the TPU or on
a program with no such counter. Moves ``ph_iter_s``."""


def read(obs):
    admm = (obs.get("phase") or {}).get("admm_iters_per_call")
    if not admm or "linv_applies" not in admm \
            or obs.get("platform") != "tpu":
        return None
    return admm["linv_applies"] / obs["chunk_solves_per_iteration"]
