"""Compile for the described v5e the fused chunk solve of the UC
deployment at the UC cells' OWN width (n = 13,056) and its 128 rows a
device call, from the deployment's own configuration file (recipe,
form, rows). This file holds that one fixture of minutes and its one
test and nothing else (ROADMAP.md C13): the width costs ~170 s of
compile, ~1,000 CPU-seconds and ~13 GiB of host memory alone on an idle
machine at ANY row count (149, 150, 165, 171 s at 2, 8, 32, 128 rows;
builder, PR 49), so no smaller row count buys a tier-1 case of seconds
and the test is the stated exception to the suite's rule, kept as it
is. The explicit inverse's panel builds at UC width are in
tests/test_chip_compile.py, the FWPH pass's two programs in
tests/test_chip_compile_fwph.py.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: what it refuses here, the chip's
compiler refuses there. Nothing runs, so these tests say nothing about
results or times; a compile that passes is not a chip run. The shared
fixtures and why they are fixtures: tests/chip_compile_helpers.py.
"""

import os

import jax.numpy as jnp
import pytest

from chip_compile_helpers import (_UC, _assert_matvecs_place_by_gather,
                                  _at_rows, _hlo_lines)
from chip_compile_helpers import (  # noqa: F401  (fixtures by name)
    no_persistent_cache, one_chip, topo)


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
    GB (6.6 GB read here; a compile of some minutes and ~13 GiB of host
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
