"""Compile for the described v5e the LOOP of the stacked native-f64
solve: the in-program refactorization that adapts rho (ISSUE 42), the
two-level loop that carries its matrices in VMEM (ISSUE 43) and the scan
that walks a wide stack a block of scenarios at a time (ISSUE 46). The
served shapes' products and polish: tests/test_chip_compile_stacked_f64.py.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures (the recorded segment among them) and why they are fixtures:
tests/chip_compile_helpers.py.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (_PRODUCT_SCOPES, _hlo_lines,
                                  _product_loops, _refactor_loops, _resized,
                                  _widened)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, stacked_farmer_segment, topo)


# ---------------- the in-program refactorization (ISSUEs 42, 43) -------

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
