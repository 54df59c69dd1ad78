"""Work of FWPH's weight QP over a pool of C columns, S scenarios, K
nonants: the benchmark's own model, kept here so that the yardstick
does not move with the program, and written from what the ALGORITHM has
to touch, not from the form that implements it.

    min_a  b.a + w.(aG) + sum_k rho_k/2 (aG - xbar)_k^2  over the simplex,
    `iters` accelerated projected-gradient trips

    bytes : the (S, C, K) nonant block read twice (once for the Hessian
            G diag(rho) G' and the linear term G (w - rho xbar), which
            one sweep can share, and once for xn = a G, which needs the
            trips' result), the four (S, K) vectors (w, rho, xbar read,
            xn written), the (S, C, C) Hessian written once, and per
            trip four (S, C) vectors (a, y, the gradient, the projected
            point). The Hessian is NOT counted per trip: (C, C) a
            scenario can stay on the chip.
    multiply-adds : S C C K for the Hessian, S C K each for the linear
            term and xn, S C C a trip for the gradient.

Both are FLOORS (a form that reads G once more, or spills the Hessian,
moves more), so a share made from them errs low and cannot pass 100.
"""


def qp_bytes(*, rows, slots, nonants, iters, itemsize=8):
    S, C, K, b = int(rows), int(slots), int(nonants), int(itemsize)
    return b * (2 * S * C * K + 4 * S * K + S * C * C
                + int(iters) * 4 * S * C)


def qp_multiply_adds(*, rows, slots, nonants, iters, itemsize=8):
    S, C, K = int(rows), int(slots), int(nonants)
    return S * C * C * K + 2 * S * C * K + int(iters) * S * C * C


def floor_seconds(shape, peaks):
    """(seconds, which bound): the larger of bytes over the HBM's rate
    and two operations a multiply-add over the chip's peak rate."""
    by_bytes = qp_bytes(**shape) / (peaks["hbm_gbps"] * 1e9)
    by_flops = 2 * qp_multiply_adds(**shape) / peaks["bf16_flops"]
    return max(by_bytes, by_flops), \
        "hbm" if by_bytes >= by_flops else "flops"
