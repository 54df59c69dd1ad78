"""Compile the main path's step programs for the chip — from a
sandbox that has none (on-chip-measurement guide §2.3): the UC df32
chunk solve, the consensus reduce, the sharded solve, the chunk
staging programs and the explicit inverse's build in column panels at
the UC cells' OWN width (n = 13,056).

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
from jax.sharding import NamedSharding, PartitionSpec

from chip_compile_helpers import (_UC, _assert_matvecs_place_by_gather,
                                  _hlo_lines, _on, _stage_operands)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


# ---------------- the df32 chunk solve and the consensus reduce --------

@pytest.fixture(scope="module")
def uc_calls():
    """One chunked df32 PH pass of a mid-width UC on the CPU, recording
    the arguments core/ph hands the fused df32 chunk solve
    (ops/kernels/reference) and the consensus reduce (_ph_chunk_objs +
    _ph_combine) — the programs chip_smoke.py runs at full width."""
    import mpisppy_tpu.core.ph as phmod
    import mpisppy_tpu.ops.kernels.reference as ref
    import mpisppy_tpu.ops.qp_solver as qps
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import uc

    calls = {}
    mp = pytest.MonkeyPatch()

    def record(mod, name):
        fn = getattr(mod, name)

        def wrapper(*a, **kw):
            calls.setdefault(name, (fn, a, kw))
            return fn(*a, **kw)
        mp.setattr(mod, name, wrapper)

    record(qps, "_cold_state_jit")
    record(ref, "_fused_mixed_jit_donated")
    record(phmod, "_ph_chunk_objs")
    record(phmod, "_ph_combine")
    try:
        batch = build_batch(
            uc.scenario_creator, uc.make_tree(8),
            creator_kwargs=dict(num_gens=8, num_hours=8,
                                min_up_down=True, ramping=True,
                                t0_state=True,
                                startup_shutdown_ramps=True,
                                relax_integrality=False),
            vector_patch=uc.scenario_vector_patch)
        ph = phmod.PHBase(
            batch, {"defaultPHrho": 100.0,
                    "subproblem_precision": "df32",
                    "subproblem_max_iter": 50, "subproblem_eps": 1e-5,
                    "subproblem_tail_iter": 25,
                    "subproblem_hospital": False,
                    "subproblem_chunk": 4, "iter0_feas_tol": 1.0},
            dtype=jnp.float64)
        ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
    finally:
        mp.undo()
    return calls


@pytest.mark.parametrize("name", ["_cold_state_jit",
                                  "_fused_mixed_jit_donated",
                                  "_ph_chunk_objs", "_ph_combine"])
def test_uc_df32_step_programs_compile_for_v5e(uc_calls, one_chip,
                                               no_persistent_cache, name):
    fn, args, kw = uc_calls[name]
    compiled = fn.lower(*_on(args, lambda a: one_chip), **kw).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert need < 16e9        # one v5e chip's HBM
    if name == "_fused_mixed_jit_donated":
        hlo = compiled.as_text()
        _assert_matvecs_place_by_gather(hlo)
        assert not _hlo_lines(hlo, "all-reduce")


def test_sharded_df32_chunk_solve_compiles_over_four_chips(
        uc_calls, topo, no_persistent_cache):
    """The same chunk solve as ONE program over a 4-chip mesh of the
    described devices, scenario rows sharded and shared operands
    replicated the way core/spbase places them: the compiler must put
    the termination tests' cross-shard reduction in as a collective,
    and nothing else: a few all-reduces of scalars (at the UC cell's
    recipe six, three in each phase's per-check body: PERF.md §5). The
    placement gathers run along the unsharded column axis of the local
    block through a replicated index and need none."""
    from jax.sharding import Mesh

    from mpisppy_tpu.parallel.mesh import SCEN_AXIS

    fn, args, kw = uc_calls["_fused_mixed_jit_donated"]
    mesh = Mesh(np.asarray(topo.devices[:4]), (SCEN_AXIS,))
    rows = args[4][0].shape[0]           # iterates[0] = x: (chunk, n)

    def place(a):
        lead = a.ndim >= 1 and a.shape[0] == rows
        spec = PartitionSpec(SCEN_AXIS, *([None] * (a.ndim - 1))) \
            if lead else PartitionSpec()
        return NamedSharding(mesh, spec)

    hlo = fn.lower(*_on(args, place), **kw).compile().as_text()
    _assert_matvecs_place_by_gather(hlo)
    reduces = _hlo_lines(hlo, "all-reduce")
    assert 1 <= len(reduces) <= 6
    for ln in reduces:           # scalars, or the f64 pair's four slots
        assert re.search(r"= \(?(u32|f32)\[4?\]", ln), ln
    for other in ("all-gather", "reduce-scatter", "collective-permute",
                  "all-to-all", "all-reduce-start", "all-gather-start",
                  "collective-permute-start"):
        assert not _hlo_lines(hlo, other), other


# ---------------- the chunk staging program (ISSUE 31) -----------------

def test_chunk_staging_program_compiles_for_v5e(one_chip,
                                                no_persistent_cache):
    """Cell 1's ASSEMBLE as the one program it is since ISSUE 31: 256
    rows in four chunks of 64, ids as an operand. Its arguments and
    results are vectors (~0.2 GB each way), nowhere near a factor."""
    from mpisppy_tpu.core.ph import _ph_stage_chunks
    per = _stage_operands(_UC["S"], lambda nd: one_chip)
    idx = jax.ShapeDtypeStruct((_UC["K"],), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((4, _UC["chunk"]), jnp.int32,
                               sharding=one_chip)
    mem = _ph_stage_chunks.lower(per, idx, ids, w_on=True, prox_on=True) \
        .compile().memory_analysis()
    assert mem.argument_size_in_bytes < 0.3e9
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 0.6e9


def test_sharded_chunk_staging_is_local_over_four_chips(
        topo, no_persistent_cache):
    """The mesh cell's ASSEMBLE (S = 1024 over four chips, four local
    chunks of 64 rows a chip) as ONE shard_map program: a local reshape,
    local slices and the element-wise assembly, so the compiler puts in
    no collective at all and every result comes out row-sharded."""
    from functools import partial

    from jax.sharding import Mesh

    from mpisppy_tpu.core.ph import _stage_chunk
    from mpisppy_tpu.parallel.mesh import SCEN_AXIS, ShardedScenarioOps

    mesh = Mesh(np.asarray(topo.devices[:4]), (SCEN_AXIS,))
    # the staging builder reads the mesh and the shard size alone (the
    # constructor would device_put the tree's node ids: no device here)
    ops = object.__new__(ShardedScenarioOps)
    ops.mesh, ops.n_devices, ops._fns = mesh, 4, {}
    ops.S, ops.shard_size = 4 * _UC["S"], _UC["S"]
    rows = lambda nd: NamedSharding(
        mesh, PartitionSpec(SCEN_AXIS, *([None] * (nd - 1))))
    per = _stage_operands(ops.S, rows)
    leaves, treedef = jax.tree.flatten(per)
    idx = jax.ShapeDtypeStruct((_UC["K"],), jnp.int32,
                               sharding=NamedSharding(mesh,
                                                      PartitionSpec()))
    prog = ops._map_chunks_fn(
        "ph.stage", partial(_stage_chunk, w_on=True, prox_on=True),
        treedef, tuple(v.ndim for v in leaves), _UC["chunk"], 1)
    compiled = prog.lower(*leaves, idx).compile()
    hlo = compiled.as_text()
    for coll in ("all-reduce", "all-gather", "reduce-scatter",
                 "collective-permute", "all-to-all"):
        assert not _hlo_lines(hlo, coll), coll
    outs = jax.tree.leaves(compiled.output_shardings)
    assert len(outs) == 4 * 9           # l u lb ub q c c0 P0 W, a chunk
    assert all(s.spec == PartitionSpec(SCEN_AXIS) for s in outs)


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
