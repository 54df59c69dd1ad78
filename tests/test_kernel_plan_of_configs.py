"""Every PH-family configuration of the benchmark against the kernel
plan the program's own rules choose at its stated shape (ISSUE 41).

A configuration states (n, m), the rows a device call solves and the
recipe; ``ops/kernels.prepare`` decides from exactly those (through a
factors stand-in that carries the SHAPE of the split matrix and no
array), with ``subproblem_kernel_mode`` and ``subproblem_kernel_l_inv``
left at ``auto``. A file with a ``kernel`` block states the plan; the
UC files without one run the fused program with the explicit inverse
off (``PERF.md`` section 4). So a change to ``l_inv_profitable`` that
flips a cell's x-update fails here before any chip is asked.
"""

import glob
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from mpisppy_tpu.ops import kernels
from mpisppy_tpu.ops.qp_solver import SplitMatrix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _configs():
    out = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmarks",
                                              "configs", "*.json"))):
        with open(path, encoding="utf-8") as f:
            cfg = json.load(f)
        if "recipe" in cfg:         # the served farmer states no recipe
            out[cfg["name"]] = cfg
    return out


CONFIGS = _configs()
# a per-scenario float64 stack: its ``kernel`` block names the forms,
# which the platform chooses (held below, with the TPU's answers)
STACKS = {n: c for n, c in CONFIGS.items()
          if c["recipe"]["subproblem_precision"] == "native"}
CONFIGS = {n: c for n, c in CONFIGS.items() if n not in STACKS}
# rows per device call and the plan, as PERF.md section 4 states them
STATED = {"uc90x48_df32": (64, False), "uc90x48_df32_mesh4": (64, False),
          "uc90x48_df32_aph": (64, False),
          "uc90x48_df32_wheel": (64, False),
          "uc90x48_df32_fwph": (64, False),
          "uc90x48_df32_chunk128": (128, False),
          "sslp_10_50_df32": (2000, True)}


def test_every_ph_configuration_is_held():
    assert set(CONFIGS) == set(STATED)


@pytest.mark.parametrize("name", sorted(STATED))
def test_plan_at_the_stated_shape_is_the_stated_one(name):
    cfg = CONFIGS[name]
    rows, l_inv = STATED[name]
    # un-chunked (``subproblem_chunk`` 0): one call solves every row
    assert rows == (cfg["subproblem_chunk"] or cfg["scenarios"])
    if "dispatch_frac" in cfg:      # APH: a pass is ONE full chunk
        assert cfg["dispatch_frac"] * cfg["scenarios_per_chip"] == rows
    recipe, shape = cfg["recipe"], cfg["shape"]
    assert recipe["subproblem_precision"] == "df32"
    assert not any(k.startswith("subproblem_kernel") for k in recipe)
    mat = jax.ShapeDtypeStruct((shape["m"], shape["n"]), jnp.float32)
    plan = kernels.prepare(
        SimpleNamespace(A_s=SplitMatrix(mat, mat)), mode="auto",
        l_inv="auto", precision="df32",
        tail_iter=recipe["subproblem_tail_iter"], ir_sweeps=1,
        s_chunk=rows)
    want = cfg.get("kernel", {"mode": "fused", "l_inv": False})
    assert want == {"mode": "fused", "l_inv": l_inv}
    got = plan.descriptor()
    assert {k: got[k] for k in want} == want
    assert plan.A_lo is mat         # the bulk's operand: the f32 hi half


def test_every_native_configuration_is_held():
    assert set(STACKS) == {"farmer_cm32_f64"}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_a_stacks_kernel_block_is_what_the_rules_choose_on_the_tpu(
        name, monkeypatch):
    """A configuration whose scenarios carry their own matrices states
    the mode AND the float64 forms (ISSUE 45): what ``prepare`` answers
    on the TPU for a (scenarios, m, n) float64 stack with both kernel
    options at ``auto``, and what the cell's driver holds the program
    to before iter-0. On this backend the same shape keeps the library
    calls, in the same one fused program."""
    from mpisppy_tpu.ops import qp_solver
    cfg = STACKS[name]
    recipe, shape = cfg["recipe"], cfg["shape"]
    assert cfg["subproblem_chunk"] == 0 and cfg["outer_dtype"] == "float64"
    assert not any(k.startswith("subproblem_kernel") for k in recipe)
    fac = qp_solver.QPFactors(*[None] * len(qp_solver.QPFactors._fields)) \
        ._replace(A_s=jax.ShapeDtypeStruct(
            (cfg["scenarios"], shape["m"], shape["n"]), jnp.float64))
    want = cfg["kernel"]
    assert set(want) == {"mode", "f64_products", "f64_polish",
                         "f64_refactor", "f64_loop"}
    assert want["mode"] == "fused" and not (
        {"host", "library"} & set(want.values()))
    here = kernels.prepare(fac).descriptor()
    assert (here["mode"], here["f64_refactor"], here["f64_polish"]) \
        == ("fused", "library", "library")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = kernels.prepare(fac).descriptor()
    assert {k: got[k] for k in want} == want
