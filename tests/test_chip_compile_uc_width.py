"""Compile for the described v5e the two programs at the UC cells' OWN
width (n = 13,056): the explicit inverse's build in column panels and
the fused chunk solve at the deployment's 128 rows a device call (a
compile of some minutes and ~11 GiB of host memory: this file's floor).

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures and why they are fixtures: tests/chip_compile_helpers.py.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (_UC, _assert_matvecs_place_by_gather,
                                  _at_rows, _hlo_lines)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


# ---------------- the explicit inverse at UC width (ISSUE 41) ----------

@pytest.mark.parametrize("container", ["bare", "prepared"])
def test_l_inv_build_compiles_at_uc_width_for_v5e(one_chip,
                                                  no_persistent_cache,
                                                  container):
    """``jit(_make_l_inv)`` at (13056, 13056) f32, as the eager wrap
    hands it a bare factor and the fused program's handoff and in-loop
    refactorization a prepared one. As ONE n-RHS ``triangular_solve``
    against ``eye(n)`` the v5e compiler was asked for 32.65 GB (chip
    run, PR 25) and every path that built an inverse died there; in
    column panels (``qp_solver._l_inv_by_panels``) the output (the
    inverse and the factor riding along: 2 x 0.68 GB) and the
    temporaries stay under 2.5 GB, and the program is one loop a panel,
    not 102 unrolled block steps a panel."""
    import mpisppy_tpu.ops.qp_solver as qs
    n = _UC["n"]
    L = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    arg = L
    if container == "prepared":
        nb = -(-n // qs._TRI_BLOCK)
        arg = qs.PreparedFactor(L, jax.ShapeDtypeStruct(
            (nb, qs._TRI_BLOCK, qs._TRI_BLOCK), jnp.float32,
            sharding=one_chip))
    compiled = jax.jit(qs._make_l_inv).lower(arg).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 2.5e9
    assert qs.l_inv_panels(n) == 6
    assert len(_hlo_lines(compiled.as_text(), "while")) == 6


@pytest.fixture(scope="module")
def uc_width_call():
    """The fused df32 chunk solve's operands at the UC cells' OWN
    widths (n = 13,056, m = 26,016: the benchmark's configuration
    built for two scenarios on the CPU, ~40 s), recorded at the first
    call and never run, in the form the program's rule picks for 128
    rows a device call."""
    import json

    import mpisppy_tpu.core.ph as phmod
    import mpisppy_tpu.ops.kernels.reference as ref
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.ir.tree import two_stage_tree
    from mpisppy_tpu.models import uc

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "uc90x48_df32_chunk128.json")) as f:
        cfg = json.load(f)
    rows = cfg["subproblem_chunk"]
    recipe = cfg["recipe"]
    form = ref.l_inv_profitable(_UC["n"], rows,
                                recipe["subproblem_tail_iter"], 1)
    assert {"mode": "fused", "l_inv": form} == cfg["kernel"]

    class Recorded(Exception):
        pass

    seen = {}

    def grab(*a, **kw):
        seen["call"] = (a, kw)
        raise Recorded

    mp = pytest.MonkeyPatch()
    real = ref._fused_mixed_jit_donated
    mp.setattr(ref, "_fused_mixed_jit_donated", grab)
    try:
        batch = build_batch(
            uc.scenario_creator,
            two_stage_tree(["scen0", "scen1"], nonant_names=["u", "st"]),
            creator_kwargs=dict(cfg["instance"]),
            vector_patch=uc.scenario_vector_patch)
        assert (batch.n, batch.m) == (_UC["n"], _UC["m"])
        ph = phmod.PHBase(
            batch, dict(recipe, subproblem_chunk=2,
                        subproblem_kernel_l_inv="on" if form else "off"),
            dtype=jnp.float64)
        with pytest.raises(Recorded):
            ph.solve_loop(w_on=False, prox_on=False)
    finally:
        mp.undo()
    return real, seen["call"], rows


def test_fused_chunk_solve_at_128_rows_of_uc_width_compiles_for_v5e(
        uc_width_call, one_chip, no_persistent_cache):
    """ISSUE 41: the deployment's own chunk. On the parent the rule
    turned the explicit inverse on at 66 rows and over, and the program
    died in the compiler on the inverse's build (32.65 GB); the form
    the measured rule picks (the prepared substitution) compiles with
    arguments + outputs + temporaries + code well inside the chip's 16
    GB (6.6 GB read here; a compile of some minutes and ~11 GiB of host
    memory, the one UC-width program this file compiles)."""
    fn, (args, kw), rows = uc_width_call
    assert kw["l_inv"] is False and (kw["bulk_iter"], kw["tail_iter"]) \
        == (400, 100)
    compiled = fn.lower(*_at_rows(args, 2, rows, one_chip), **kw).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert need < 9e9, need       # of one v5e chip's 16 GB
    hlo = compiled.as_text()
    assert f"f64[{rows},{_UC['n']}]" in hlo       # the real size
    _assert_matvecs_place_by_gather(hlo)
    assert not _hlo_lines(hlo, "all-reduce")


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
