"""HBM bytes of one ADMM iteration of the fused df32 chunk solve: the
benchmark's own copy of ``mpisppy_tpu/ops/kernels.est_hbm_bytes_per_iter``
(the traffic model of ``doc/roofline.md``). The yardstick lives here so
that it does not move with the program; ``benchmarks/tests`` checks that
the two still agree.

    factor applies : 2 triangle passes x (1 seed + ir_sweeps IR solves)
                     x n^2 x 4 B;
    A passes       : (2 + 2 ir_sweeps) packed split passes over the
                     hi+lo operand bytes (dense m n 8 when unpacked);
    vectors        : ~6 (S, m)/(S, n) sweeps.

``bulk`` (the f32 phase) reads the factor once per triangle, the hi
operand only, and f32 vectors: it is the SMALLER of the two, so pricing
every iteration at it gives a lower bound of the bytes moved.
"""


def hbm_bytes_per_iter(*, n, m, s_chunk, pk_pass_bytes=None, ir_sweeps=1,
                       block_dtype="f32", factor_bytes=4, vec_bytes=8):
    a_pass = pk_pass_bytes if pk_pass_bytes is not None else m * n * 8
    tail_factor = 2 * (1 + int(ir_sweeps)) * n * n * factor_bytes
    tail_a = (2 + 2 * int(ir_sweeps)) * a_pass
    tail_vec = 6 * (m + n) * s_chunk * vec_bytes
    bulk_a_pass = a_pass / 2
    if block_dtype == "bf16":
        bulk_a_pass /= 2
    bulk = int(2 * n * n * factor_bytes + 2 * bulk_a_pass
               + 6 * (m + n) * s_chunk * 4)
    return {"tail": int(tail_factor + tail_a + tail_vec), "bulk": bulk}
