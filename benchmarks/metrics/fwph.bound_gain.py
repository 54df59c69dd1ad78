"""How far the published Lagrangian bound stands over the iter-0
trivial bound at the window's end, as a share of the trivial bound's
magnitude, in percent. It guards the bound's progress and moves no
end-to-end metric by itself (listed under ``solves_per_s``: the passes
are what buy it)."""


def read(obs):
    g = obs.get("fwph_bound_gain")
    return None if g is None else 100.0 * g
