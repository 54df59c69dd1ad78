"""Compile for the described v5e the stacked native-f64 programs ABOVE
the unrolled width (n > 16): the polish and the in-program
refactorization in their blocked forms, and the size of the
refactorization's program under its static groups. The served shapes:
tests/test_chip_compile_stacked_f64.py.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures (the recorded segment among them) and why they are fixtures:
tests/chip_compile_helpers.py.
"""

import re

import pytest

from chip_compile_helpers import (_hlo_lines, _polish_loops,
                                  _refactor_loops, _widened)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, stacked_farmer_segment, topo)


# ---------------- the blocked polish (ISSUE 45) ------------------------

def test_the_polish_takes_the_blocked_forms_above_the_width_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache):
    """Above ``_POLISH_UNROLL_MAX_N`` (here n = 24) the polish lowers
    the BLOCKED forms since ISSUE 45 (doc/kernels.md §3h): under
    ``qp.polish`` the program holds its own three scans and the block
    rows' ``fori_loop``s of the three factorizations (since ISSUE 47
    ONE Cholesky loop, and a U⁻¹ loop a static group of block rows),
    and nothing of the library: no loop of a ``cholesky``, a
    ``triangular_solve`` or the Gram ``dot_general``. Until then the
    library path was what was lowered there (the compiler's row loops,
    PR 40)."""
    import mpisppy_tpu.ops.qp_solver as qps
    fn, args, kw = stacked_farmer_segment
    kw = dict(kw, max_iter=0, polish=True)
    assert 12 * 2 > qps._POLISH_UNROLL_MAX_N
    hlo = fn.lower(*_widened(args, 3, 2, one_chip), **kw).compile() \
        .as_text()
    loops, expansions = _polish_loops(hlo)
    # three scans, and a factorization's one Cholesky loop and a U^-1
    # loop a group of block rows (n = 24 pads to two block rows: two
    # groups); the compiler merges the first and the third
    # factorization: the same active set
    per = 1 + len(qps._block_row_groups(32))
    assert 3 + 2 * per <= len(loops) <= 3 + 3 * per and not expansions
    assert not _hlo_lines(hlo, "cholesky")
    assert not _hlo_lines(hlo, "triangular-solve")


# ---------------- the blocked refactorization (ISSUEs 45, 47) ----------

def test_the_refactorization_takes_the_blocked_forms_above_the_width_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache):
    """Above ``_POLISH_UNROLL_MAX_N`` (n = 24) ``_factorize`` lowers
    the BLOCKED inverse since ISSUE 45 (doc/kernels.md §3h), under the
    loop's ``conditional``: the block rows' ``fori_loop``s under
    ``qp.refactor`` (since ISSUE 47 ONE Cholesky loop, the static
    extents of its product under a ``lax.switch`` on the block row's
    group, and a U⁻¹ loop a group; the Gram matrix and the product
    W Wᵀ are strips with no loop), and no loop of a ``cholesky``, a
    ``triangular_solve`` or a batched ``dot_general``. Until then the
    library pair was what was lowered there, and the rule sent such
    factors to the host."""
    import mpisppy_tpu.ops.qp_solver as qps
    fn, args, kw = stacked_farmer_segment
    assert 12 * 2 > qps._POLISH_UNROLL_MAX_N
    hlo = fn.lower(*_widened(args, 3, 2, one_chip),
                   **dict(kw, adaptive_rho=True)).compile().as_text()
    loops = _refactor_loops(hlo)
    assert len(loops) == 1 + len(qps._block_row_groups(32)) == 3
    assert not any(k in ln for ln in loops
                   for k in ("cholesky", "triangular_solve", "dot_general"))
    assert not _hlo_lines(hlo, "cholesky")
    assert not _hlo_lines(hlo, "triangular-solve")
    # the loop's one ``conditional`` (the rebuild's), and under it the
    # Cholesky's ``lax.switch`` on the block row's group
    conds = _hlo_lines(hlo, "conditional")
    assert len([ln for ln in conds if "qp.refactor/" not in ln]) == 1
    assert len(conds) == 2


def _instructions(hlo):
    return [ln for ln in hlo.splitlines()
            if re.match(r"^\s*(ROOT )?%?[\w.\-]+ = ", ln)]


# the program of PR 46 (one loop a stage whatever n): instructions of
# the whole compiled solve, and of them under ``qp.refactor``, at
# n = 24 and at n = 96 (described v5e, this repo's installation)
_PARENT_REFACTOR_SIZE = {2: (29697, 14894), 8: (29458, 14695)}


@pytest.mark.parametrize("scale", [2, 8])
def test_static_groups_hold_the_refactorization_programs_size_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, scale):
    """The unrolled (16, 16) diagonal factor is ~14,000 of the parent's
    ~14,900 instructions under ``qp.refactor``, and compile seconds
    follow the instruction count: it stays in the program ONCE (one
    Cholesky loop whatever the group count: only the small bodies of
    the substitution and the strips are copied a group). The solve
    program with its rebuild stays under 1.25 times the parent's, a
    literal that does not follow ``_F64_GROUPS``, at n = 24 (two block
    rows) and at n = 96 (six): a second copy of the factor's body, or
    a group count that grows the program, fails here until someone
    measures again (doc/kernels.md §3h: device seconds and compile
    seconds by form; 30,759 and 31,562 instructions at PR 47)."""
    fn, args, kw = stacked_farmer_segment
    hlo = fn.lower(*_widened(args, 3, scale, one_chip),
                   **dict(kw, adaptive_rho=True)).compile().as_text()
    whole, refactor = _PARENT_REFACTOR_SIZE[scale]
    ins = _instructions(hlo)
    under = [ln for ln in ins if "qp.refactor/" in ln]
    assert refactor <= len(under) <= 1.25 * refactor
    assert len(ins) <= 1.25 * whole
