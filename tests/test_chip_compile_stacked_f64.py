"""Compile for the described v5e the stacked native-f64 programs of the
served cell at its own shapes: the segment's products (and the emulated
dot they replaced) and the polish's three scans. The blocked forms above
the unrolled width: tests/test_chip_compile_stacked_f64_blocked.py; the
loop that adapts rho, carries its matrices and walks a wide stack in
blocks: tests/test_chip_compile_stacked_f64_loop.py.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures (the recorded segment among them) and why they are fixtures:
tests/chip_compile_helpers.py.
"""

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (_hlo_lines, _polish_loops, _product_loops,
                                  _widened)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, stacked_farmer_segment, topo)


# ---------------- the segment's products (ISSUE 38) --------------------

# (S, scale): the served stack, a solo wheel, and the largest shape the
# chip sweep timed ((24, 700, 1200): ``crops_multiplier`` 100)
@pytest.mark.parametrize("S,scale", [(24, 1), (3, 1), (24, 100)])
def test_stacked_f64_segment_has_no_emulated_dot_loops_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, S, scale):
    """The rule answers "reduce" for every per-scenario float64 matrix
    (doc/kernels.md §3d: the sweep found no shape where the emulated
    dot wins), and the segment program the v5e compiler makes of it
    holds the solve's own two loops and nothing of the dot emulation:
    no ``while`` under ``qp.Ax`` / ``qp.ATy`` / ``qp.kkt_solve``, no
    ``dynamic-update-slice`` (at (24, 7, 12): 34 loops and 74
    update-slices before ISSUE 38, 14 of the loops in the ADMM scan
    body). One answer, held by a compile at each pinned shape."""
    import mpisppy_tpu.ops.qp_solver as qps
    fn, args, kw = stacked_farmer_segment
    wide = _widened(args, S, scale, one_chip)
    hlo = fn.lower(*wide, **kw).compile().as_text()
    assert f"f64[{S},{7 * scale},{12 * scale}]" in hlo
    assert not _product_loops(hlo)
    # the widest shape is over the budget of ISSUE 46's blocks (18 MB a
    # scenario: three rows a block), so its scan sits in one more loop,
    # whose results are placed by the only update-slices
    blocks = qps.f64_stack_block_rows(wide[0].A_s)
    assert blocks == (3 if scale == 100 else None)
    assert len(_hlo_lines(hlo, "while")) == (3 if blocks else 2)
    if not blocks:
        assert not _hlo_lines(hlo, "dynamic-update-slice")


def test_the_emulated_dot_is_a_loop_nest_on_v5e(one_chip,
                                                no_persistent_cache):
    """What the reduction replaced, so that a compiler that learns to
    multiply float64 batches shows up here: one batched float64
    ``einsum`` at the stacked inverse's shape compiles to ``while``
    loops over f32 limbs with ``dynamic-update-slice`` in them, the
    reduction of the same product to neither."""
    from mpisppy_tpu.ops.qp_solver import _matvec_dot, _matvec_reduce
    F = jax.ShapeDtypeStruct((24, 12, 12), jnp.float64, sharding=one_chip)
    b = jax.ShapeDtypeStruct((24, 12), jnp.float64, sharding=one_chip)
    dot = jax.jit(_matvec_dot).lower(F, b).compile().as_text()
    assert _hlo_lines(dot, "while") \
        and _hlo_lines(dot, "dynamic-update-slice")
    red = jax.jit(_matvec_reduce).lower(F, b).compile().as_text()
    assert not _hlo_lines(red, "while") \
        and not _hlo_lines(red, "dynamic-update-slice")


# ---------------- the polish of the stacked native-f64 solve (ISSUE 40) -

# (S, scale): the served stack and a solo wheel, at n = 12
@pytest.mark.parametrize("S,scale", [(24, 1), (3, 1)])
def test_stacked_f64_polish_has_only_its_three_scans_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, S, scale):
    """The polish program the chip's segmented driver launches last
    (``max_iter=0``, ``polish=True``) at n = 12, where the rule answers
    "unrolled" (doc/kernels.md §3e): the v5e compiler's program holds
    the polish's own three scans as ``while``s under ``qp.polish`` and
    nothing of the library expansions: no loop of a ``cholesky``, a
    ``triangular_solve`` or the Gram ``dot_general``, no
    ``dynamic-update-slice`` (at (24, 7, 12): 115 loops and 262
    update-slices under ``qp.polish`` before ISSUE 40)."""
    fn, args, kw = stacked_farmer_segment
    kw = dict(kw, max_iter=0, polish=True)
    hlo = fn.lower(*_widened(args, S, scale, one_chip), **kw).compile() \
        .as_text()
    assert f"f64[{S},{7 * scale},{12 * scale}]" in hlo
    loops, expansions = _polish_loops(hlo)
    assert len(loops) == 3 and not expansions
    # with the solve's own two (never entered at max_iter 0)
    assert len(_hlo_lines(hlo, "while")) == 5
    assert not _hlo_lines(hlo, "dynamic-update-slice")
