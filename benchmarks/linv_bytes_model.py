"""HBM bytes of ONE build of the explicit inverse of the shared f32
factor (``qp_solver.LInv``): the benchmark's own copy of
``mpisppy_tpu/ops/kernels.est_l_inv_build_bytes``, kept here so that the
yardstick does not move with the program; ``benchmarks/tests`` checks
that the two still agree.

    factor : the computed (lower) half of L read once, n^2 / 2 x 4 B;
    inverse: the lower half of L^-1 written once,      n^2 / 2 x 4 B.

A FLOOR of the bytes: the build is a blocked forward substitution on
the identity, which reads the rows of the inverse it has so far at
every block row (about n^3 / 3 x 4 B / 128 in all at a 128-row block:
23 GB at n = 13,056) and is bound by the substitution's sequential
block steps and their f32 products, not by these bytes. A share made
from this number errs low and cannot pass 100.
"""


def linv_build_bytes(*, n, factor_bytes=4):
    return int(n) * int(n) * int(factor_bytes)
