"""Compile for the described v5e what the sslp and APH cells run: the
un-chunked sslp df32 solve with its explicit-inverse build, the APH
step, the dispatch store's programs and the chunked loop's gate stack.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures and why they are fixtures: tests/chip_compile_helpers.py.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from chip_compile_helpers import _UC, _at_rows, _hlo_lines, _stage_operands
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


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
                                  "f64_loop": None,
                                  "f64_stack_block": None}
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
