"""HBM bytes of the native-float64 solve over a per-scenario stack: S
scenarios, each with its own scaled (m, n) constraint matrix and its own
explicit (n, n) float64 KKT inverse. The benchmark's own model, kept
here so that the yardstick does not move with the program, and written
from what the ALGORITHM has to touch, not from the form that implements
it (blocked, unrolled, a library call: all read and write at least
this).

    an ADMM iteration : the matrix twice (A'(rho z - y) and A x~) and
                        the inverse once, 8 B an entry, plus ~6 sweeps
                        over the (S, m) / (S, n) vectors (rhs assembly,
                        the two projections, the two dual updates):
                        8 S (2 m n + n^2) + 6 x 8 S (m + n);
    a build           : the matrix read once, the inverse written once,
                        the two rho vectors read: 8 S (m n + n^2 + m + n).

The build's bytes are a FLOOR: a Cholesky factor, its triangular
inverse and their product each pass over (S, n, n) several times, and
the work is n^3 float64 multiply-adds a scenario that the chip runs as
soft-float, so a share made from this number errs low and cannot pass
100.
"""


def admm_iteration_bytes(*, rows, m, n):
    return 8 * int(rows) * (2 * int(m) * int(n) + int(n) * int(n)) \
        + 6 * 8 * int(rows) * (int(m) + int(n))


def refactor_build_bytes(*, rows, m, n):
    return 8 * int(rows) * (int(m) * int(n) + int(n) * int(n)
                            + int(m) + int(n))
