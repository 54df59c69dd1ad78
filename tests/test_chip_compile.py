"""Compile the main path's step programs for the chip —
from a sandbox that has none (on-chip-measurement guide §2.3).

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture that skips
when it cannot be — never at import, never in a ``skipif`` /
``parametrize`` argument, never in conftest.py: only one process may
load the TPU library, so only the xdist worker that is handed THIS file
may touch it (all of these tests live in this one file for the same
reason), and every compile happens in the test's own process. The
persistent compile cache is switched off around them: an entry compiled
for a described chip cannot be read back without one.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(tree, sharding_of):
    """Every array leaf -> a ShapeDtypeStruct placed by ``sharding_of``
    (shapes only: there is no device to hold an array)."""
    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=sharding_of(a))
        return a
    return jax.tree.map(leaf, tree)


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


def _hlo_lines(hlo, opcode):
    """The instructions of a compiled module's text with this opcode."""
    return [ln for ln in hlo.splitlines()
            if re.search(rf"\s{re.escape(opcode)}\(", ln)]


def _assert_matvecs_place_by_gather(hlo):
    """ISSUE 29: no packed matvec of the fused program scatters (on the
    chip the df32 tail's was a serial variadic scatter over the
    emulated f64's (hi, lo) pair, 73 ns an index); each places its
    result with a gather through the inverse index."""
    scatters = _hlo_lines(hlo, "scatter")
    assert not [ln for ln in scatters if "qp.Ax" in ln or "qp.ATy" in ln]
    gathers = _hlo_lines(hlo, "gather")
    assert [ln for ln in gathers if "qp.tail" in ln and "qp.Ax" in ln]
    assert [ln for ln in gathers if "qp.tail" in ln and "qp.ATy" in ln]
    assert [ln for ln in gathers if "qp.bulk" in ln and "qp.Ax" in ln]


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

# the UC cells' widths (benchmarks/configs/uc90x48_df32.json): what ONE
# staging call moves at S = 256 a chip in four chunks of 64
_UC = dict(S=256, n=13056, m=26016, K=8640, chunk=64)


def _stage_operands(S, place):
    """``PHBase._per_scen_operands``' vectors at the cell's widths, as
    shapes (float64 outer arithmetic, no shrink plan, no w_scale)."""
    n, m, K = _UC["n"], _UC["m"], _UC["K"]
    f8 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float64,
                                          sharding=place(len(sh)))
    return {"l": f8(S, m), "u": f8(S, m), "lb": f8(S, n), "ub": f8(S, n),
            "c0": f8(S), "P0": f8(S, n), "c": f8(S, n), "W": f8(S, K),
            "xbar": f8(S, K), "rho": f8(S, K), "fv": f8(S, K),
            "fm": jax.ShapeDtypeStruct((S, K), jnp.bool_,
                                       sharding=place(2))}


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


# ---------------- the un-chunked sslp solve (ISSUE 32) -----------------

# benchmarks/configs/sslp_10_50_df32.json: SIPLIB's sslp_10_50, all of
# its 2000 scenarios in ONE call of the fused df32 program
# rows: enough that the packing rule answers at the rehearsal's row
# count what it answers at 2000 (dense: ops/packed.pack_profitable)
_SSLP = dict(S=2000, n=520, m=61, rows=24)


@pytest.fixture(scope="module")
def sslp_calls():
    """Two PH passes (iter-0, one hot) of the published sslp_10_50 on
    the CPU at 24 rows, un-chunked, under the cell's recipe with a short
    budget: every call core/ph makes of the fused df32 program and of
    the eager explicit-inverse build."""
    import mpisppy_tpu.core.ph as phmod
    import mpisppy_tpu.ops.kernels.reference as ref
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import sslp

    calls = {"_fused_mixed_jit_donated": [], "make_l_inv": []}
    mp = pytest.MonkeyPatch()
    for name in calls:
        fn = getattr(ref, name)

        def wrapper(*a, _fn=fn, _name=name, **kw):
            calls[_name].append((_fn, a, kw))
            return _fn(*a, **kw)
        mp.setattr(ref, name, wrapper)
    try:
        batch = build_batch(
            sslp.scenario_creator, sslp.make_tree(_SSLP["rows"]),
            creator_kwargs=dict(num_servers=10, num_clients=50,
                                overflow=True, server_budget=10,
                                capacity=188.0, demand_is_revenue=True),
            vector_patch=sslp.scenario_vector_patch)
        assert (batch.n, batch.m) == (_SSLP["n"], _SSLP["m"])
        ph = phmod.PHBase(
            batch, {"defaultPHrho": 1.0, "subproblem_precision": "df32",
                    "subproblem_max_iter": 50, "subproblem_eps": 1e-5,
                    "subproblem_eps_hot": 1e-4,
                    "subproblem_eps_dua_hot": 1e-2,
                    "subproblem_stall_rel": 1.5e-3,
                    "subproblem_tail_iter": 100,
                    "subproblem_polish_hot": False,
                    "subproblem_hospital": False, "subproblem_chunk": 0},
            dtype=jnp.float64)
        ph.solve_loop(w_on=False, prox_on=False)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
        ph.W = ph.W_new
        ph.solve_loop(w_on=True, prox_on=True)
        calls["plan"] = ph.phase_timing(True)["kernel"]
    finally:
        mp.undo()
    return calls


def _at_rows(tree, rows, S, sharding):
    """The recorded operands as shapes on the described chip, their
    scenario axis (leading, ``rows`` long) widened to ``S``."""
    def leaf(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        shape = tuple(a.shape)
        if shape and shape[0] == rows:
            shape = (S,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sharding)
    return jax.tree.map(leaf, tree)


def test_unchunked_sslp_df32_solve_compiles_for_v5e(sslp_calls, one_chip,
                                                    no_persistent_cache):
    """(S, n, m) = (2000, 520, 61): ONE ``jit(_fused_mixed_impl)``
    serves iter-0 and the hot passes (the same statics, the same
    operand structure with the explicit inverse in the state: a second
    signature would be a second compile inside a run), one eager
    ``make_l_inv`` a mode's cold state, and the program the v5e
    compiler accepts holds no float64 batched linear algebra (the
    factor is the shared f32 one; the float64 is element-wise outer
    arithmetic and the split matvecs' accumulation)."""
    assert sslp_calls["plan"] == {"mode": "fused", "backend": "reference",
                                  "l_inv": True, "block_dtype": "f32",
                                  "f64_products": None,
                                  "f64_polish": None,
                                  "f64_refactor": None,
                                  "f64_loop": None}
    solves = sslp_calls["_fused_mixed_jit_donated"]
    assert len(solves) == 3
    assert len(sslp_calls["make_l_inv"]) == 2      # iter-0's and hot's
    rows, S = _SSLP["rows"], _SSLP["S"]
    sigs = set()
    for _fn, args, kw in solves:
        # what jit keys an executable on: shapes and dtypes (a Python
        # scalar, e.g. a tolerance, is a weak-typed operand whatever
        # its value)
        avals = jax.tree.map(
            lambda a: (tuple(a.shape), str(a.dtype))
            if hasattr(a, "shape") else type(a).__name__, args)
        leaves, treedef = jax.tree.flatten(avals, is_leaf=lambda v:
                                           isinstance(v, tuple))
        sigs.add((str(treedef), tuple(map(str, leaves)),
                  tuple(sorted(kw.items()))))
    assert len(sigs) == 1, "iter-0 and hot passes share one executable"
    fn, args, kw = solves[-1]
    # the form the cell's 2000 rows get: dense split matvecs, the bulk's
    # operand the plain f32 hi (ISSUE 33: at this shape the packed form
    # saves 0.2 MB a pass and gathers every vector through 51 blocks)
    from mpisppy_tpu.ops.packed import pack_profitable
    elems = 51 * 1 * 10 + 10 * _SSLP["n"]
    assert not pack_profitable(_SSLP["m"], _SSLP["n"], elems, rows) \
        and not pack_profitable(_SSLP["m"], _SSLP["n"], elems, S)
    assert args[0].A_s.pk_hi is None and args[0].A_s.struct is not None
    assert args[1] is args[0].A_s.hi
    compiled = fn.lower(*_at_rows(args, rows, S, one_chip), **kw).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert need < 1e9         # ISSUE 32: well under 1 GB of the 16
    hlo = compiled.as_text()
    assert f"f64[{S},{_SSLP['n']}]" in hlo        # the real size
    for op in ("cholesky", "triangular-solve"):
        assert not [ln for ln in _hlo_lines(hlo, op) if "f64[" in ln], op
    # no batched (per-scenario) factor of any dtype: the one factor is
    # (n, n), shared by all 2000 rows
    n = _SSLP["n"]
    assert not re.search(rf"f(32|64)\[{S},{n},{n}\]", hlo)
    assert not _hlo_lines(hlo, "all-reduce")
    fn, args, kw = sslp_calls["make_l_inv"][0]
    inv = fn.lower(*_at_rows(args, rows, S, one_chip), **kw).compile()
    assert inv.memory_analysis().temp_size_in_bytes < 64e6


# ---------------- the APH cell's own programs (ISSUE 34) ---------------

def test_aph_step_and_dispatch_programs_compile_for_v5e(
        one_chip, no_persistent_cache):
    """The pieces of ``uc_s256_aph_hot``'s pass beside the chunk solve,
    each as a program of its own (as the cell ran them until ISSUE 35,
    and as the tests still compare the one-program forms below with),
    at the cell's widths and in float64 (x64 is on: the outer
    arithmetic is): the projective update, the stacked gate whose
    selection SORTS 256 float64 φ (the v5e compiler takes the float64
    key apart into a (hi, lo) pair of f32 and sorts on both), the
    staging program at ONE chunk of 64 ids, and one field's gather and
    placement."""
    from mpisppy_tpu.core.aph import _aph_update
    from mpisppy_tpu.core.ph import _ph_stage_chunks
    from mpisppy_tpu.ops.dispatch import (dispatch_gate, gather_rows,
                                          scatter_rows)
    S, K, m, chunk = _UC["S"], _UC["K"], _UC["m"], _UC["chunk"]
    f8 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float64,
                                          sharding=one_chip)
    i4 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=one_chip)
    stamps = jax.ShapeDtypeStruct((S,), jnp.int64, sharding=one_chip)
    gate = dispatch_gate.lower(f8(), f8(), f8(), f8(), f8(S), stamps,
                               scnt=chunk, S_real=S).compile()
    sorts = _hlo_lines(gate.as_text(), "sort")
    assert len(sorts) == 3
    assert [ln for ln in sorts if ln.count(f"f32[{S}]") >= 2], sorts
    assert not [ln for ln in sorts if "f64[" in ln], sorts
    step = _aph_update.lower(*(f8(S, K),) * 5, f8(S), f8(S, K), f8(S, K),
                             1.0, 1.0, iter1=False).compile()
    assert step.memory_analysis().temp_size_in_bytes < 0.2e9
    per = _stage_operands(S, lambda nd: one_chip)
    stage = _ph_stage_chunks.lower(per, i4(K), i4(1, chunk), w_on=True,
                                   prox_on=True).compile()
    mem = stage.memory_analysis()
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 0.2e9
    gather_rows.lower(f8(S, m), i4(chunk)).compile()
    # the scatter-back of the widest store field (zA / yA): as
    # ``full.at[idx].set(rows)`` the compiler refused it (20.7 MB of
    # scoped VMEM for the row window, limit 16)
    ids = jax.ShapeDtypeStruct((chunk,), jnp.int64, sharding=one_chip)
    back = scatter_rows.lower(f8(S, m), ids, f8(chunk, m)).compile()
    wide = [ln for ln in _hlo_lines(back.as_text(), "scatter")
            if f"[{S},{m}]" in ln]
    assert not wide, wide
    scatter_rows.lower(f8(S), ids, f8(chunk)).compile()


def test_one_program_each_way_compiles_for_v5e(one_chip,
                                               no_persistent_cache):
    """ISSUE 35's three programs at ``uc_s256_aph_hot``'s widths: the
    step (gather, y-update, three means, ``_aph_update``, the sorting
    gate and the next stamps in one), the store's gather at ONE chunk
    of 64 ids, and the placement of all fifteen fields, which must stay
    under the scoped-VMEM limit that refused the wide scatter of the
    (256, 26,016) float64 store in PR 34: every scatter it holds is
    (S,) wide."""
    from mpisppy_tpu.core.aph import _aph_step
    from mpisppy_tpu.ops.dispatch import (dispatch_gate, gather_chunks,
                                          place_chunks)
    S, n, m, K, chunk = (_UC[k] for k in ("S", "n", "m", "K", "chunk"))
    sds = lambda dt, *sh: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    f8 = partial(sds, jnp.float64)
    i4 = partial(sds, jnp.int32)
    step = _aph_step.lower(
        f8(S, n), f8(S, K), f8(S, K), f8(S, K), None, f8(S, K), f8(S),
        i4(K), f8(S), (f8(S, 1),), sds(jnp.bool_, S), sds(jnp.int64, S),
        7, 1.0, 1.0, gate=dispatch_gate, xbar_fn=None,
        slot_slices=((0, K),), iter1=False, full=False, scnt=chunk,
        S_real=S).compile()
    mem = step.memory_analysis()
    assert mem.temp_size_in_bytes < 0.3e9
    assert len(_hlo_lines(step.as_text(), "sort")) == 3
    ids = sds(jnp.int64, 1, chunk)
    store = (f8(S, n), f8(S, m), f8(S, n), f8(S, m), f8(S, n),
             f8(S), f8(S), f8(S), f8(S))
    gather_chunks.lower(store, ids).compile()
    fulls = store + (f8(S, n), f8(S, m), f8(S, n), f8(S), f8(S), f8(S))
    rows = tuple((f8(chunk, *f.shape[1:]),) for f in fulls)
    back = place_chunks.lower(fulls, ids, rows).compile()
    scatters = _hlo_lines(back.as_text(), "scatter")
    assert not [ln for ln in scatters if f"[{S},{m}]" in ln
                or f"[{S},{n}]" in ln], scatters
    mem = back.memory_analysis()
    # all fifteen results at once (the store's 0.19 GB, the engine's
    # 0.11 GB) and less than that again in temporaries
    assert mem.output_size_in_bytes < 0.35e9
    assert mem.temp_size_in_bytes < 0.2e9


def test_the_gates_four_field_stack_compiles_for_v5e(topo, one_chip,
                                                     no_persistent_cache):
    """The chunked loop's ONE gate read stacks four residual rows of
    every chunk state since ISSUE 37 (``qp_solver.EXIT_ROWS``), where it
    stacked ``pri_rel`` alone: at the UC cells' shapes, float64, that is
    sixteen (64,) rows on one chip and sixteen row-sharded (256,) rows
    over the 2x2 mesh, whose stack stays sharded (no collective: the
    host's read gathers it)."""
    from jax.sharding import Mesh
    from mpisppy_tpu.ops.qp_solver import EXIT_ROWS
    from mpisppy_tpu.parallel.mesh import SCEN_AXIS
    chunk, n_chunks = _UC["chunk"], _UC["S"] // _UC["chunk"]
    stack = jax.jit(lambda *rows: jnp.stack(rows))
    rows = [jax.ShapeDtypeStruct((chunk,), jnp.float64, sharding=one_chip)
            ] * (len(EXIT_ROWS) * n_chunks)
    stack.lower(*rows).compile()
    mesh = Mesh(np.asarray(topo.devices[:4]), (SCEN_AXIS,))
    sharded = NamedSharding(mesh, PartitionSpec(SCEN_AXIS))
    rows = [jax.ShapeDtypeStruct((4 * chunk,), jnp.float64,
                                 sharding=sharded)] * len(rows)
    hlo = stack.lower(*rows).compile().as_text()
    assert not _hlo_lines(hlo, "all-gather") \
        and not _hlo_lines(hlo, "all-reduce")


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


def _widened(tree, S, scale, sharding):
    """The recorded (24, 7, 12) operands as shapes on the described
    chip: the scenario axis at ``S`` rows, m and n times ``scale``."""
    dims = {24: S, 7: 7 * scale, 12: 12 * scale}

    def leaf(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        return jax.ShapeDtypeStruct(tuple(dims[d] for d in a.shape),
                                    a.dtype, sharding=sharding)
    return jax.tree.map(leaf, tree)


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
    fn, args, kw = stacked_farmer_segment
    hlo = fn.lower(*_widened(args, S, scale, one_chip), **kw).compile() \
        .as_text()
    assert f"f64[{S},{7 * scale},{12 * scale}]" in hlo
    assert not _product_loops(hlo)
    assert len(_hlo_lines(hlo, "while")) == 2
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


def test_the_polish_keeps_the_library_calls_above_the_width_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache):
    """Above ``_POLISH_UNROLL_MAX_N`` (here n = 24: the unrolled
    program's compile seconds turn between 16 and 24) the library path
    is still what is lowered: the compiler's loops of the batched
    float64 ``cholesky`` and ``triangular_solve`` are there. So a
    compiler that learns float64 linalg, or a width that moves, shows
    up here."""
    from mpisppy_tpu.ops.qp_solver import _POLISH_UNROLL_MAX_N
    fn, args, kw = stacked_farmer_segment
    kw = dict(kw, max_iter=0, polish=True)
    assert 12 * 2 > _POLISH_UNROLL_MAX_N
    hlo = fn.lower(*_widened(args, 3, 2, one_chip), **kw).compile() \
        .as_text()
    loops, expansions = _polish_loops(hlo)
    assert len(loops) > 3
    assert any("cholesky" in ln for ln in expansions)
    assert any("triangular_solve" in ln for ln in expansions)


# ---------------- the in-program refactorization (ISSUE 42) ------------

def _refactor_loops(hlo):
    """The ``while`` instructions under ``qp.refactor`` (the rebuild of
    the explicit float64 inverse inside ``qp.rho_adapt``): the
    compiler's expansions of the batched float64 ``cholesky`` /
    ``triangular_solve`` pair and of the product in front of them."""
    return [ln for ln in _hlo_lines(hlo, "while") if "qp.refactor/" in ln]


def _loops_carrying_halves(hlo, S):
    """For every ``while`` of the compiled program whose body reads an
    f32[S,7,12] / f32[S,12,12] array out of its carry (the two f32
    halves of the float64 matrix and of the explicit inverse): how many
    such reads the body holds, and how many of them the compiler placed
    in ``S(1)`` (VMEM), as ``(reads, resident)`` pairs."""
    out = []
    for body in re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo):
        text = re.search(r"\n%?" + re.escape(body) + r" \(.*?\n\}", hlo,
                         re.S).group(0)
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


def test_the_refactorization_keeps_the_library_pair_above_the_width_on_v5e(
        stacked_farmer_segment, one_chip, no_persistent_cache):
    """Above ``_POLISH_UNROLL_MAX_N`` (n = 24) ``_factorize`` lowers the
    library pair, and the compiler's loops of it are there: what the
    rule keeps away from the TPU by sending such factors to the host
    (``_needs_host_factor``; this program is never launched there)."""
    from mpisppy_tpu.ops.qp_solver import _POLISH_UNROLL_MAX_N
    fn, args, kw = stacked_farmer_segment
    assert 12 * 2 > _POLISH_UNROLL_MAX_N
    hlo = fn.lower(*_widened(args, 3, 2, one_chip),
                   **dict(kw, adaptive_rho=True)).compile().as_text()
    loops = _refactor_loops(hlo)
    assert any("cholesky" in ln for ln in loops)
    assert any("triangular_solve" in ln for ln in loops)


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
