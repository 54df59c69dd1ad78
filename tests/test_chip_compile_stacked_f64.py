"""Compile for the described v5e the stacked native-f64 programs of the
served cell: the segment's products, the polish, the in-program
refactorization and the loop that carries their matrices.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures and why they are fixtures: tests/chip_compile_helpers.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_compile_helpers import _hlo_lines
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


# ---------------- the stacked native-f64 solve (ISSUE 38) --------------

@pytest.fixture(scope="module")
def stacked_farmer_segment():
    """The served cell's segment program as the chip's plan runs it
    (``_needs_host_factor``: ``polish=False``, ``adaptive_rho=False``,
    segments of 500) at a full stack's operands, recorded from a CPU
    pass of eight stacked three-scenario farmers: A_s (24, 7, 12)
    float64, the factor the explicit (24, 12, 12) float64 inverse."""
    import mpisppy_tpu.ops.qp_solver as qps
    from stacked_farmer import record_stacked_farmer_calls
    calls, _plan = record_stacked_farmer_calls()
    args, kw = calls[-1]
    assert args[0].A_s.shape == (24, 7, 12) \
        and args[0].A_s.dtype == np.float64
    assert args[3].L.shape == (24, 12, 12) and args[3].L.dtype == np.float64
    kw = {k: v for k, v in kw.items() if k != "_segmented_caller"}
    kw.update(max_iter=500, polish=False, adaptive_rho=False)
    fn = jax.jit(qps._solve_impl, static_argnames=qps._SOLVE_STATICS)
    return fn, args, kw


_PRODUCT_SCOPES = ("qp.Ax", "qp.ATy", "qp.kkt_solve")


def _product_loops(hlo):
    """The ``while`` instructions whose ``op_name`` lies under one of
    the three product scopes: the compiler's emulation of a batched
    float64 ``dot_general`` (eight f32 limbs, nested loops)."""
    return [ln for ln in _hlo_lines(hlo, "while")
            if any(s + "/" in ln for s in _PRODUCT_SCOPES)]


def _resized(tree, dims, sharding):
    """Recorded operands as shapes on the described chip, every axis
    of length d at ``dims[d]``."""
    def leaf(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        return jax.ShapeDtypeStruct(tuple(dims[d] for d in a.shape),
                                    a.dtype, sharding=sharding)
    return jax.tree.map(leaf, tree)


def _widened(tree, S, scale, sharding):
    """The recorded (24, 7, 12) operands: the scenario axis at ``S``
    rows, m and n times ``scale``."""
    return _resized(tree, {24: S, 7: 7 * scale, 12: 12 * scale}, sharding)


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

def _polish_loops(hlo):
    """The ``while`` instructions under ``qp.polish``, and those of
    them that are the compiler's expansion of a batched float64
    ``cholesky`` / ``triangular_solve`` / Gram ``dot_general``."""
    loops = [ln for ln in _hlo_lines(hlo, "while") if "qp.polish/" in ln]
    return loops, [ln for ln in loops
                   if any(k in ln for k in ("cholesky", "triangular_solve",
                                            "dot_general"))]


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


# ---------------- the in-program refactorization (ISSUE 42) ------------

def _refactor_loops(hlo):
    """The ``while`` instructions under ``qp.refactor`` (the rebuild of
    the explicit float64 inverse inside ``qp.rho_adapt``): the
    compiler's expansions of the batched float64 ``cholesky`` /
    ``triangular_solve`` pair and of the product in front of them."""
    return [ln for ln in _hlo_lines(hlo, "while") if "qp.refactor/" in ln]


def _while_bodies(hlo):
    """{body name: text} of every ``while`` of a compiled module."""
    out = {}
    for body in re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo):
        out[body] = re.search(r"\n%?" + re.escape(body) + r" \(.*?\n\}",
                              hlo, re.S).group(0)
    return out


def _loops_carrying_halves(hlo, S):
    """For every ``while`` of the compiled program whose body reads an
    f32[S,7,12] / f32[S,12,12] array out of its carry (the two f32
    halves of the float64 matrix and of the explicit inverse): how many
    such reads the body holds, and how many of them the compiler placed
    in ``S(1)`` (VMEM), as ``(reads, resident)`` pairs."""
    out = []
    for text in _while_bodies(hlo).values():
        reads = [ln for ln in text.splitlines()
                 if "get-tuple-element(" in ln
                 and re.search(rf"f32\[{S},(7|12),12\]", ln)]
        if reads:
            out.append((len(reads), sum("S(1)" in ln for ln in reads)))
    return out


# (S, scale): the served stack and a solo wheel, at n = 12
@pytest.mark.parametrize("S,scale", [(24, 1), (3, 1)])
def test_stacked_f64_loop_adapts_rho_without_library_linalg_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, S, scale):
    """The solve's loop as the chip's plan runs it since ISSUE 42
    (``adaptive_rho=True``: the rule keeps the refactorization of a
    per-scenario float64 stack with n <= 16 inside the program,
    doc/kernels.md §3f), in the shape it has since ISSUE 43 (§3g): the
    v5e compiler's program holds the solve's own three loops (the
    periods, the checks of a period, the ADMM scan) and nothing else:
    no loop of a ``cholesky``, a ``triangular_solve`` or a batched
    ``dot_general`` under ``qp.refactor``, no ``dynamic-update-slice``;
    no ``conditional``, and every loop that carries the f32 halves of
    the matrix and of the inverse carries all four in VMEM."""
    fn, args, kw = stacked_farmer_segment
    kw = dict(kw, adaptive_rho=True)
    hlo = fn.lower(*_widened(args, S, scale, one_chip), **kw).compile() \
        .as_text()
    assert f"f64[{S},{7 * scale},{12 * scale}]" in hlo
    assert "qp.refactor" in hlo
    assert not _refactor_loops(hlo) and not _product_loops(hlo)
    assert len(_hlo_lines(hlo, "while")) == 3
    assert not _hlo_lines(hlo, "dynamic-update-slice")
    assert not _hlo_lines(hlo, "conditional")
    carrying = _loops_carrying_halves(hlo, S)
    assert len(carrying) == 3
    assert all(reads >= 4 and resident == reads
               for reads, resident in carrying), carrying


@pytest.mark.parametrize("S,scale", [(24, 1), (3, 1)])
def test_a_conditional_in_the_loop_keeps_its_matrices_in_hbm_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, monkeypatch,
        S, scale):
    """What the two-level loop replaced, so that a compiler which learns
    to keep operands resident across a ``conditional`` shows up here:
    the same solve with the rebuild under a ``lax.cond`` in the loop's
    one body (the shape every other factor form keeps, traced here by
    answering for one; the rebuild itself stays the unrolled one)
    compiles to a ``conditional``, and not one of the four halves is in
    VMEM in either loop."""
    import mpisppy_tpu.ops.qp_solver as qps
    _fn, args, kw = stacked_farmer_segment
    monkeypatch.setattr(qps, "f64_loop_form", lambda A_s: "conditional")

    def impl(factors, data, q, state, **k):         # a trace of its own
        return qps._solve_impl(factors, data, q, state, **k)
    fn = jax.jit(impl, static_argnames=qps._SOLVE_STATICS)
    hlo = fn.lower(*_widened(args, S, scale, one_chip),
                   **dict(kw, adaptive_rho=True)).compile().as_text()
    assert not _refactor_loops(hlo) and not _product_loops(hlo)
    assert len(_hlo_lines(hlo, "while")) == 2
    assert len(_hlo_lines(hlo, "conditional")) == 1
    carrying = _loops_carrying_halves(hlo, S)
    assert len(carrying) == 2
    assert all(resident == 0 for _reads, resident in carrying), carrying


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


# ---------------- the ADMM scan of a WIDE stack, in blocks (ISSUE 46) --

@pytest.fixture(scope="module")
def wide_stack_hot_solve():
    """The stack cell's hot solve (``farmer_cm32_s1024_hub_hot``: one
    native-f64 fused call of all rows, rho adapted in the program) as
    the engine calls it, recorded from a CPU pass of the farmer at
    ``crops_multiplier`` 2 over 8 scenarios ((8, 13, 24) float64) under
    the cell's recipe: ``(fn, args, kw)`` of the last, hot call."""
    import mpisppy_tpu.ops.qp_solver as qps
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import farmer
    from stacked_farmer import recorded_qp_solves
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(8),
                        creator_kwargs={"crops_multiplier": 2})
    ph = PHBase(batch, {"subproblem_precision": "native",
                        "defaultPHrho": 1.0, "subproblem_eps_hot": 1e-4,
                        "subproblem_eps_dua_hot": 1e-2,
                        "subproblem_polish_hot": False}, dtype=jnp.float64)
    with recorded_qp_solves() as calls:
        ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
    args, kw = calls[-1]
    assert args[0].A_s.shape == (8, 13, 24) and not kw["polish"] \
        and kw["adaptive_rho"]

    def impl(factors, data, q, state, **k):         # a trace of its own
        return qps._solve_impl(factors, data, q, state, **k)
    return jax.jit(impl, static_argnames=qps._SOLVE_STATICS), args, kw


def test_a_wide_stacks_hot_program_scans_block_by_block_on_v5e(
        wide_stack_hot_solve, one_chip, no_persistent_cache):
    """The cell's hot program at its own operands ((1024, 193, 384)
    float64 and the (1024, 384, 384) inverse) compiles for the v5e, and
    its ADMM scan runs a block of ``f64_stack_block_rows`` scenarios at
    a time (doc/kernels.md §3i): the loop over the blocks holds the
    scan and nothing else that loops or branches; the scan's body holds
    no ``conditional`` and no ``while`` (none of the dot emulation under
    the three product scopes either), and reads the block's matrices,
    the f32 halves of (B, 193, 384) and (B, 384, 384), out of its carry;
    the one ``conditional`` of the solve stays the rebuild's, in the
    outer loop's body (the rebuild's own, the Cholesky's ``lax.switch``,
    is under ``qp.refactor``)."""
    import mpisppy_tpu.ops.qp_solver as qps
    fn, args, kw = wide_stack_hot_solve
    wide = _resized(args, {8: 1024, 13: 193, 24: 384}, one_chip)
    B = qps.f64_stack_block_rows(wide[0].A_s)
    assert B and 1024 % B == 0
    hlo = fn.lower(*wide, **kw).compile().as_text()
    assert "f64[1024,193,384]" in hlo
    assert not _product_loops(hlo)
    assert len([ln for ln in _hlo_lines(hlo, "conditional")
                if "qp.refactor/" not in ln]) == 1
    halves = re.compile(rf"f32\[{B},(193|384),384\]")
    scans = {name: text for name, text in _while_bodies(hlo).items()
             if any("get-tuple-element(" in ln and halves.search(ln)
                    for ln in text.splitlines())}
    # the scan over a block's iterations, and the loop over the blocks
    # around it (which hands the scan its block)
    inner = [t for t in scans.values() if not _hlo_lines(t, "while")]
    outer = [t for t in scans.values() if _hlo_lines(t, "while")]
    assert len(inner) == 1 and len(outer) == 1, sorted(scans)
    assert not _hlo_lines(inner[0], "conditional")
    assert all(s in inner[0] for s in _PRODUCT_SCOPES)
    assert len(_hlo_lines(outer[0], "while")) == 1 \
        and not _hlo_lines(outer[0], "conditional")


def test_the_served_stack_keeps_one_scan_over_all_rows_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache, monkeypatch):
    """The rule leaves the served (24, 7, 12) stack whole: its solve
    program, lowered for the v5e, is text-equal to the one traced with
    the rule answering None for every operand (the parent's single
    scan), and holds the three loops of doc/kernels.md §3g."""
    import mpisppy_tpu.ops.qp_solver as qps
    _fn, args, kw = stacked_farmer_segment
    kw = dict(kw, adaptive_rho=True)
    assert qps.f64_stack_block_rows(args[0].A_s) is None

    def lowered():
        def impl(factors, data, q, state, **k):     # a trace of its own
            return qps._solve_impl(factors, data, q, state, **k)
        return jax.jit(impl, static_argnames=qps._SOLVE_STATICS).lower(
            *_widened(args, 24, 1, one_chip), **kw)
    mine = lowered()
    monkeypatch.setattr(qps, "f64_stack_block_rows", lambda A_s: None)
    assert mine.as_text() == lowered().as_text()
    assert len(_hlo_lines(mine.compile().as_text(), "while")) == 3
