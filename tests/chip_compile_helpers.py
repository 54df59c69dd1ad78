"""What the ``tests/test_chip_compile*.py`` files share: the described
``v5e:2x2`` (on-chip-measurement guide §2.3), the switch that keeps
the persistent compile cache away from it, shapes-for-arrays helpers,
readers of a compiled module's text, and the recorded segment program
of the stacked native-f64 solve (the three ``_stacked_f64*`` files
build it once each).

The topology is described inside a module-scoped fixture that skips
when it cannot be — never at import, never in a ``skipif`` /
``parametrize`` argument, never in conftest.py (it would be built for
every test file): each test file imports the fixtures by name, so only
an xdist worker that is handed one of THOSE files touches the TPU
library, and every compile happens in the test's own process. The
files are split by fixture family so that ``--dist loadfile`` spreads
their compile minutes over the workers; the driver's command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, which lets several workers hold the
library at once (without it, in several processes at once, only the
first describes the chip and the other files SKIP: run them one
process at a time then). The persistent compile cache is switched off
around the compiles: an entry compiled for a described chip cannot be
read back without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


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


def _polish_loops(hlo):
    """The ``while`` instructions under ``qp.polish``, and those of
    them that are the compiler's expansion of a batched float64
    ``cholesky`` / ``triangular_solve`` / Gram ``dot_general``."""
    loops = [ln for ln in _hlo_lines(hlo, "while") if "qp.polish/" in ln]
    return loops, [ln for ln in loops
                   if any(k in ln for k in ("cholesky", "triangular_solve",
                                            "dot_general"))]


def _refactor_loops(hlo):
    """The ``while`` instructions under ``qp.refactor`` (the rebuild of
    the explicit float64 inverse inside ``qp.rho_adapt``): the
    compiler's expansions of the batched float64 ``cholesky`` /
    ``triangular_solve`` pair and of the product in front of them."""
    return [ln for ln in _hlo_lines(hlo, "while") if "qp.refactor/" in ln]
