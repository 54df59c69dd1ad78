"""``wheel.spoke_capped_share``: % of the SPOKES' chunk solves of the
window whose accurate tail ran its whole budget (the spokes' engines'
``phase_timing(key)["exits"]``: ``tail_capped`` / ``solves`` over the
Lagrangian's prox-off pass, the pool's screen and the winner's
verification, the window's share of each by difference). The spokes
hold most of a wheel's device seconds, and a solve that runs to its cap
costs four times one that converges at the first check: what
``solve.tail_capped_share`` is to the hub. Counted by the program.
``None`` where the program books no exits for its spokes. Moves
``solves_per_s``."""


def read(obs):
    ex = obs.get("spoke_exits")
    if not ex:
        return None
    solves = sum(v["solves"] for v in ex.values())
    return 100.0 * sum(v["tail_capped"] for v in ex.values()) / solves \
        if solves else None
