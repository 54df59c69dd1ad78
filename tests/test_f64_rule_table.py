"""The ONE table of the float64 forms (ROADMAP C14; ISSUE 45): what
``ops/qp_solver``'s five rule functions and ``ops/kernels``'s mode
resolution answer for a float64 matrix, by backend name, ``ndim`` and
n, and for nothing else: shapes stand in for arrays, and no option,
flag, environment variable or model name is read.
"""

import jax
import jax.numpy as jnp
import pytest

import mpisppy_tpu.ops.qp_solver as qps
from mpisppy_tpu.ops import kernels

# (backend, ndim, n) -> (products, polish, refactor, loop, mode under
# ``auto``). "metal" stands for a backend nobody measured.
_RES, _CND = "resident", "conditional"
TABLE = {
    # the TPU, a per-scenario stack: its own spellings at every width
    ("tpu", 3, 12): ("reduce", "unrolled", "unrolled", _RES, "fused"),
    ("tpu", 3, 16): ("reduce", "unrolled", "unrolled", _RES, "fused"),
    ("tpu", 3, 17): ("reduce", "blocked", "blocked", _CND, "fused"),
    ("tpu", 3, 384): ("reduce", "blocked", "blocked", _CND, "fused"),
    # the TPU, one shared matrix: one unbatched library factor
    ("tpu", 2, 12): (None, "library", "library", _CND, "fused"),
    ("tpu", 2, 16): (None, "library", "library", _CND, "fused"),
    ("tpu", 2, 17): (None, "library", "library", _CND, "fused"),
    ("tpu", 2, 384): (None, "library", "library", _CND, "fused"),
    # backends with native float64 linalg keep the library, bit for bit
    **{(b, 3, n): ("dot", "library", "library",
                   _RES if n <= 16 else _CND, "fused")
       for b in ("cpu", "gpu") for n in (12, 16, 17, 384)},
    **{(b, 2, n): (None, "library", "library", _CND, "fused")
       for b in ("cpu", "gpu") for n in (12, 16, 17, 384)},
    # nobody measured its batched float64 linalg: numpy, between calls
    **{("metal", 3, n): ("dot", "library", "host", None, "segmented")
       for n in (12, 16, 17, 384)},
    **{("metal", 2, n): (None, "library", "library", _CND, "fused")
       for n in (12, 16, 17, 384)},
}


@pytest.mark.parametrize("backend,ndim,n", sorted(TABLE),
                         ids=[f"{b}-{d}d-n{n}" for b, d, n in sorted(TABLE)])
def test_the_forms_by_backend_ndim_and_n(monkeypatch, backend, ndim, n):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    m = max(1, n // 2 + 1)
    A_s = jax.ShapeDtypeStruct((1024, m, n)[3 - ndim:], jnp.float64)
    products, polish, refactor, loop, mode = TABLE[backend, ndim, n]
    assert qps.f64_product_form(A_s) == products
    assert qps.f64_polish_form(A_s) == polish
    assert qps.f64_refactor_form(A_s) == refactor
    assert qps.f64_loop_form(A_s) == loop
    fac = qps.QPFactors(*[None] * len(qps.QPFactors._fields)) \
        ._replace(A_s=A_s)
    assert qps._needs_host_factor(fac) is (refactor == "host")
    assert kernels.resolve_mode("auto", fac) == mode
    got = kernels.prepare(fac).descriptor()
    assert (got["mode"], got["f64_products"], got["f64_polish"],
            got["f64_refactor"], got["f64_loop"]) \
        == (mode, products, polish, refactor, loop)
    if refactor == "host":
        with pytest.raises(ValueError, match="host"):
            kernels.prepare(fac, mode="fused")
    else:
        assert kernels.prepare(fac, mode="fused").mode == "fused"


@pytest.mark.parametrize("backend", ["tpu", "cpu", "metal"])
def test_what_is_no_float64_matrix_has_no_form(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    f32 = jax.ShapeDtypeStruct((24, 193, 384), jnp.float32)
    split = qps.split_f32(jnp.ones((7, 12)))
    for A_s in (f32, split):
        assert qps.f64_product_form(A_s) is None
        assert qps.f64_polish_form(A_s) is None
        assert qps.f64_refactor_form(A_s) is None
        assert qps.f64_loop_form(A_s) is None


def test_a_stack_too_large_to_rebuild_on_the_device_stays_the_hosts(
        monkeypatch):
    """The scenario hospital's UC-width batches ((4, 26016, 13056): 5.5
    GB an (S, n, n) array) and a farmer stack of 2048 at n = 384 (2.4
    GB): the explicit inverse stays numpy's between device calls, the
    polish the library's, the solve segmented (ROADMAP C11: what still
    reaches ``_factorize_host`` / ``_host_adapt_rho``)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for shape in ((4, 26016, 13056), (2048, 193, 384)):
        A_s = jax.ShapeDtypeStruct(shape, jnp.float64)
        assert 8 * shape[0] * shape[2] ** 2 > qps._F64_BLOCKED_MAX_BYTES
        assert qps.f64_refactor_form(A_s) == "host"
        assert qps.f64_polish_form(A_s) == "library"
        assert qps.f64_loop_form(A_s) is None
        assert qps.f64_product_form(A_s) == "reduce"


# ---- the rows of a block in the ADMM scan of a wide stack (ISSUE 46) --
# what ``f64_stack_block_rows`` answers, from the operand alone: the
# shape, and the module's one budget constant
_ROW = 8 * (193 * 384 + 384 * 384)          # bytes a cm32 scenario holds


# case -> (shape of a float64 matrix, "fit" standing for the rows the
# budget holds; None: a split matrix), the block: "fit" or None
BLOCK_CASES = {
    # the stack cell's operand: the block the budget holds
    "cell-1024x193x384": ((1024, 193, 384), "fit"),
    # half as many rows: still over the budget
    "half-512x193x384": ((512, 193, 384), "fit"),
    # the served stack (n <= 16: resident loop, operands in VMEM already)
    "served-24x7x12": ((24, 7, 12), None),
    "unrolled-1024x9x16": ((1024, 9, 16), None),
    # one shared matrix, plain or split: no per-scenario stack
    "shared-2d": ((193, 384), None),
    "split": (None, None),
    # a wide stack that fits the budget whole
    "under-budget-16x193x384": ((16, 193, 384), None),
    "one-block-exactly": (("fit", 193, 384), None),
    # S prime: its one divisor under the budget is a row at a time
    "prime-1021x193x384": ((1021, 193, 384), None),
    # S = 2 x prime: blocks of two rows fill under half the budget
    "no-usable-divisor-1042x193x384": ((1042, 193, 384), None),
    # a stack too large to rebuild on the device is the host's (segmented)
    "hosts-2048x193x384": ((2048, 193, 384), None),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_the_rows_of_a_block_by_shape_alone(monkeypatch, backend, case):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    fit = qps._F64_LOOP_BLOCK_BYTES // _ROW
    assert 8 <= fit < 512, "the budget holds a block of the cell's stack"
    shape, want = BLOCK_CASES[case]
    if shape is None:
        A_s = qps.split_f32(jnp.ones((7, 12)))
    else:
        shape = tuple(fit if d == "fit" else d for d in shape)
        A_s = jax.ShapeDtypeStruct(shape, jnp.float64)
        assert qps.f64_stack_block_rows(
            jax.ShapeDtypeStruct(shape, jnp.float32)) is None
    if want == "fit":
        # the largest divisor of S the budget holds (S a power of two:
        # the power of two at or under ``fit``)
        want = 1 << (fit.bit_length() - 1)
        assert shape[0] % want == 0 and want * _ROW \
            <= qps._F64_LOOP_BLOCK_BYTES < 2 * want * _ROW
    assert qps.f64_stack_block_rows(A_s) == want
    fac = qps.QPFactors(*[None] * len(qps.QPFactors._fields)) \
        ._replace(A_s=A_s)
    assert kernels.prepare(fac, mode="segmented") \
        .descriptor()["f64_stack_block"] == want


def test_the_block_follows_the_budget_and_nothing_else(monkeypatch):
    """One named constant: a budget of B rows of the operand gives
    blocks of B rows (the largest divisor of S under it), whatever the
    backend; a row that alone is over the budget leaves the stack
    whole."""
    A_s = jax.ShapeDtypeStruct((1024, 193, 384), jnp.float64)
    for rows, want in ((1, 1), (2, 2), (8, 8), (100, 64), (1023, 512),
                      (1024, None), (5000, None)):
        monkeypatch.setattr(qps, "_F64_LOOP_BLOCK_BYTES", rows * _ROW)
        assert qps.f64_stack_block_rows(A_s) == want, rows
    monkeypatch.setattr(qps, "_F64_LOOP_BLOCK_BYTES", _ROW - 1)
    assert qps.f64_stack_block_rows(A_s) is None
