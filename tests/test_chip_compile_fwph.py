"""Compile for the described v5e the two programs of an FWPH pass at
the cell's shape (``uc_s256_fwph_hot``: 256 scenarios, a pool of 16
columns at UC width, n = 13,056): the column step and the weight QP.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures and why they are fixtures: tests/chip_compile_helpers.py.
"""

import jax
import jax.numpy as jnp

from chip_compile_helpers import _UC, _hlo_lines
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


# ---------------- the FWPH pass's two programs at the cell's shape (PR 48)

_FW = dict(S=256, C=16, n=_UC["n"], K=_UC["K"])


def _fw_shape(one_chip):
    return lambda *s, dt=jnp.float64: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)


def test_fwph_column_step_aliases_the_pool_for_v5e(one_chip,
                                                   no_persistent_cache):
    """``core/fwph._column_step`` at (256, 16, 13056) / (256, 16, 8640)
    float64: the pool and its nonant block come back in the buffers
    they came in (the whole 711 MB of them is aliased), so no second
    pool exists at the peak, and what is not aliased is the 32 KB of
    base costs and the pass's row of four scalars. The temporaries
    (672 MB read here) are the compiler's own: the v5e holds float64
    as pairs of 32-bit halves and splits a float64 parameter where the
    program starts (doc/fwph.md section 3)."""
    from mpisppy_tpu.core.fwph import _column_step
    S, C, n, K = (_FW[k] for k in "SCnK")
    f = _fw_shape(one_chip)
    compiled = _column_step.lower(
        f(S, C, n), f(S, C, K), f(S, C), f(S, C), f(S, K), f(S, K),
        f(S, n), f(S), f(S, n), f(S), f(S), f(S), (f(S, 1),),
        f(K, dt=jnp.int32), f(dt=jnp.int32),
        slot_slices=((0, K),)).compile()
    mem = compiled.memory_analysis()
    pool = 8 * S * C * (n + K)
    assert mem.alias_size_in_bytes == pool == 710_934_528
    assert mem.output_size_in_bytes - pool < 64 * 1024
    assert mem.temp_size_in_bytes < pool


def test_fwph_weight_qp_compiles_at_the_cell_shape_for_v5e(
        one_chip, no_persistent_cache):
    """``ops/simplex_qp.simplex_qp_solve`` at (256, 16, 8640) float64,
    400 trips: ONE loop (the trips' scan) and no other: the products
    over the pool lower as multiply-and-sum fusions, not as the loop
    nests of an emulated float64 ``dot_general`` (doc/kernels.md
    section 3d), and the program fits beside the engine (arguments
    0.34 GB, temporaries 1.0 GB read here)."""
    from mpisppy_tpu.ops.simplex_qp import simplex_qp_solve
    S, C, K = _FW["S"], _FW["C"], _FW["K"]
    f = _fw_shape(one_chip)
    compiled = simplex_qp_solve.lower(
        f(S, C, K), f(S, C), f(S, K), f(S, K), f(S, K), f(S, C),
        iters=400).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1.6e9
    hlo = compiled.as_text()
    assert f"f64[{S},{C},{K}]" in hlo             # the real size
    assert len(_hlo_lines(hlo, "while")) == 1
