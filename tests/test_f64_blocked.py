"""The BLOCKED float64 forms of a per-scenario stack wider than 16
(ISSUE 45; since ISSUE 47 every n^3 product summed over rows and cut to
the extents that are not stored zeros; ``ops/qp_solver.py``:
``_block_row_groups``, ``_blocked_cholesky``, ``_blocked_uinv``,
``_spd_inverse_from_uinv``, ``_uinv_pair_solve``), called directly and
held against numpy: what the TPU lowering of a wide stack's explicit KKT
inverse and of its polish runs, run here on the CPU (the forms are plain
jax; only the switch that picks them is per platform).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qps

# in block rows of 16: 2 (17 pads to two: fewer than the groups), 2, 3,
# 6, and 7 and 8 (which the group count does not divide)
WIDTHS = (17, 24, 40, 96, 112, 128)
STACKS = (1, 5)
# SPD stacks of two makes: a Gram matrix on a heavy diagonal (cond ~10),
# and the polish's own make, a penalty matrix rho_big A'A + sigma I
# with half the rows active (cond ~1e11)
CONDS = ("well", "ill")


def spd_stack(S, n, cond, seed=0):
    rng = np.random.default_rng(seed + 1000 * n + S)
    if cond == "well":
        B = rng.standard_normal((S, n, n))
        return B @ B.transpose(0, 2, 1) / n + np.eye(n)
    m = max(2, n // 2)
    A = rng.standard_normal((S, m, n)) * (rng.random((S, m, n)) < 0.3)
    act = (rng.random((S, m)) < 0.5) * 1e5
    M = np.einsum("smi,sm,smj->sij", A, act, A)
    return M + np.eye(n) * 1e-6 + np.eye(n) * (rng.random((S, 1, n)) < 0.3) \
        * 1e5


@functools.lru_cache(maxsize=None)
def _forms(S, n):
    """ONE compiled program a shape: the factor, its inverse, the
    explicit inverse and a pair solve."""
    del S, n    # the cache key; jit keys on the operands' shapes itself

    def all_forms(M, b):
        # the factorization reads M's upper triangle of blocks: what
        # lies below must not matter
        blk = jnp.arange(M.shape[-1]) // qps._F64_BLOCK
        M = jnp.where(blk[:, None] > blk[None, :], jnp.nan, M)
        U, Dinv = qps._blocked_cholesky(qps._pad_spd(M, qps._F64_BLOCK))
        W = qps._blocked_uinv(U, Dinv)
        k = M.shape[-1]
        U, W = U[:, :k, :k], W[:, :k, :k]
        return (U, W, qps._spd_inverse_from_uinv(W),
                qps._uinv_pair_solve(W, b))
    return jax.jit(all_forms)


@functools.lru_cache(maxsize=None)
def forms_of(S, n, cond):
    M = spd_stack(S, n, cond)
    b = np.random.default_rng(5).standard_normal((S, n))
    out = _forms(S, n)(jnp.asarray(M), jnp.asarray(b))
    return (M, b) + tuple(np.asarray(o) for o in out)


@pytest.mark.parametrize("cond", CONDS)
@pytest.mark.parametrize("S", STACKS)
@pytest.mark.parametrize("n", WIDTHS)
def test_blocked_cholesky_is_numpys_factor(n, S, cond):
    M, _b, U, *_ = forms_of(S, n, cond)
    L = np.linalg.cholesky(M)
    assert np.array_equal(np.triu(U), U)        # exact zeros below
    # the forward error of a factor scales with cond(M); the backward
    # error below does not
    assert np.abs(U.transpose(0, 2, 1) - L).max() \
        <= {"well": 1e-12, "ill": 1e-7}[cond] * np.abs(L).max()
    back = np.einsum("ski,skj->sij", U, U)
    assert np.abs(back - M).max() <= 1e-14 * np.abs(M).max()


@pytest.mark.parametrize("cond", CONDS)
@pytest.mark.parametrize("S", STACKS)
@pytest.mark.parametrize("n", WIDTHS)
def test_blocked_uinv_inverts_the_factor(n, S, cond):
    _M, _b, U, W, *_ = forms_of(S, n, cond)
    assert np.array_equal(np.triu(W), W)
    off = np.abs(np.einsum("sik,skj->sij", U, W) - np.eye(n)).max()
    # |U W - I| carries cond(U) = sqrt(cond(M)), 3e5 on the polish's
    # make: what the pair solve's residual (below) and the polish's two
    # refinement sweeps are there for
    assert off <= {"well": 1e-13, "ill": 1e-2}[cond]


@pytest.mark.parametrize("cond", CONDS)
@pytest.mark.parametrize("S", STACKS)
@pytest.mark.parametrize("n", WIDTHS)
def test_explicit_inverse_against_numpy(n, S, cond):
    """|M M^-1 - I|max, the cell's ``kkt_inverse_err``: 1e-13 where the
    ADMM's inverse lives (the cell's limit is 1e-10); on the polish's
    make (cond 1e12) no explicit inverse is usable, numpy's neither
    (4e-5 there), which is why the polish solves with the triangular
    pair: held to cond x 1e-14 only."""
    M, _b, _U, _W, Minv, _x = forms_of(S, n, cond)
    off = np.abs(M @ Minv - np.eye(n)).max(axis=(1, 2))
    room = 1e-13 if cond == "well" else 1e-14 * np.linalg.cond(M)
    assert (off <= room).all()
    # one triangle of blocks is computed and the other is its mirror
    # image: symmetric to the last bit
    np.testing.assert_array_equal(Minv, Minv.transpose(0, 2, 1))


@pytest.mark.parametrize("cond", CONDS)
@pytest.mark.parametrize("S", STACKS)
@pytest.mark.parametrize("n", WIDTHS)
def test_pair_solve_residual(n, S, cond):
    """x = W (W' b): the normwise residual |M x - b| / (|M| |x|) of ONE
    solve sits at float64's level on either make (what the polish's two
    refinement sweeps start from, doc/kernels.md section 3e)."""
    M, b, _U, _W, _Minv, x = forms_of(S, n, cond)
    res = np.abs(np.einsum("sij,sj->si", M, x) - b).max(-1)
    scale = np.abs(M).sum(-1).max(-1) * np.abs(x).max(-1)
    assert (res / scale).max() <= 1e-13


@pytest.mark.parametrize("n,groups", [
    (17, [(0, 16), (16, 17)]), (32, [(0, 16), (16, 32)]),
    (40, [(0, 16), (16, 32), (32, 40)]),
    (112, [(0, 32), (32, 64), (64, 112)]),
    (384, [(0, 128), (128, 256), (256, 384)])])
def test_the_block_rows_static_groups(n, groups):
    """The extents the builds' loops and strips are cut to: whole block
    rows, ``_F64_GROUPS`` groups or a block row each where there are
    fewer, none empty, the last one ending at n."""
    assert qps._F64_GROUPS == 3
    assert qps._block_row_groups(n) == groups


@pytest.mark.parametrize("S", STACKS)
@pytest.mark.parametrize("n", WIDTHS)
def test_the_whole_build_from_its_operands_against_numpy(n, S):
    """``_kkt_inverse_blocked`` and ``_penalty_factor_blocked`` from
    (A, r, d), the Gram matrix included (``_penalty_matrix``, the square:
    Step 0 of ISSUE 47 read no gain in its upper triangle alone): M M⁻¹
    = I to rounding, M⁻¹ symmetric to the last bit, and W = U⁻¹ upper
    with W Wᵀ the same inverse."""
    rng = np.random.default_rng(n + S)
    m = n // 2 + 1
    A = jnp.asarray(rng.standard_normal((S, m, n)))
    r = jnp.asarray(rng.random((S, m)) + 0.1)
    d = jnp.asarray(rng.random((S, n)) + 0.5)
    M = np.asarray(jax.jit(qps._penalty_matrix)(A, r, d))
    Minv = np.asarray(jax.jit(
        lambda A, r, d: qps._kkt_inverse_blocked(A, r, 0.0, d))(A, r, d))
    W = np.asarray(jax.jit(qps._penalty_factor_blocked)(A, r, d))
    assert np.abs(M @ Minv - np.eye(n)).max() <= 1e-12
    np.testing.assert_array_equal(Minv, Minv.transpose(0, 2, 1))
    assert (np.tril(W, -1) == 0.0).all()
    assert np.abs(W @ W.transpose(0, 2, 1) - Minv).max() \
        <= 1e-13 * np.abs(Minv).max()


@pytest.mark.parametrize("n", [8, 12, 16])
def test_a_stack_of_one_block_is_the_unrolled_recurrences(n):
    """At n <= 16 the blocked forms ARE the unrolled recurrences (one
    diagonal block, nothing off it): at n = 16 the factor's inverse
    equals ``_unrolled_linv(_unrolled_cholesky(M))`` transposed bit for
    bit; a narrower stack is padded to the block and its sums run over
    the pad's zeros in another order (an ulp). The PROGRAM never takes
    them there: at n <= 16 the rule keeps the unrolled forms themselves
    (``test_the_tpu_forms_by_width``)."""
    M = jnp.asarray(spd_stack(3, n, "well"))
    W = np.asarray(jax.jit(qps._blocked_factor_inverse)(M))
    Linv = np.asarray(jax.jit(
        lambda M: qps._unrolled_linv(qps._unrolled_cholesky(M)))(M))
    if n == qps._F64_BLOCK:
        np.testing.assert_array_equal(W, Linv.transpose(0, 2, 1))
    assert np.abs(W - Linv.transpose(0, 2, 1)).max() \
        <= 4e-16 * np.abs(Linv).max()
    a = np.asarray(jax.jit(qps._spd_inverse_from_uinv)(jnp.asarray(W)))
    b = np.einsum("spi,spj->sij", Linv, Linv)
    assert np.abs(a - b).max() <= 1e-15 * np.abs(b).max()


def test_the_tpu_forms_by_width():
    """The one shape test and the two tables it indexes: unrolled
    to 16, blocked above, nothing for what is no per-scenario float64
    stack or is too large to rebuild on the device."""
    f8 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float64)
    assert qps._tpu_stack_form(f8(24, 7, 12)) == "unrolled"
    assert qps._tpu_stack_form(f8(24, 9, 16)) == "unrolled"
    assert qps._tpu_stack_form(f8(24, 10, 17)) == "blocked"
    assert qps._tpu_stack_form(f8(1024, 193, 384)) == "blocked"
    assert qps._tpu_stack_form(f8(2048, 193, 384)) is None
    assert qps._tpu_stack_form(f8(4, 26016, 13056)) is None
    assert qps._tpu_stack_form(f8(7, 12)) is None
    assert qps._tpu_stack_form(
        jax.ShapeDtypeStruct((24, 7, 12), jnp.float32)) is None
    assert qps._tpu_stack_form(qps.split_f32(jnp.ones((7, 12)))) is None
    assert qps._TPU_KKT_INVERSE == {
        "unrolled": qps._kkt_inverse_unrolled,
        "blocked": qps._kkt_inverse_blocked}
    assert qps._TPU_PENALTY_PAIR == {
        "unrolled": (qps._penalty_factor_unrolled, qps._linv_pair_solve),
        "blocked": (qps._penalty_factor_blocked, qps._uinv_pair_solve)}


@pytest.mark.parametrize("n", [17, 40])
def test_a_matrix_that_is_not_positive_definite_gives_nan(n):
    """As the library does and as ``_polish_select`` relies on: a
    candidate whose penalty matrix is not SPD must come out NaN and
    lose, not come out wrong."""
    M = spd_stack(2, n, "well")
    M[1, n - 2, n - 2] = -1.0
    W = np.asarray(jax.jit(qps._blocked_factor_inverse)(jnp.asarray(M)))
    assert np.isfinite(W[0]).all() and np.isnan(W[1]).any()


def test_kkt_inverse_blocked_on_recorded_farmer_factors():
    """``_kkt_inverse_blocked`` on a wide farmer's own scaled factors
    (``crops_multiplier`` 2: n = 24) at three rho scales, against the
    numpy KKT ``_kkt_host`` builds: the cell's ``kkt_inverse_err``."""
    from mpisppy_tpu.core.ph import PHBase
    from mpisppy_tpu.ir.batch import build_batch
    from mpisppy_tpu.models import farmer
    batch = build_batch(farmer.scenario_creator, farmer.make_tree(6),
                        creator_kwargs={"crops_multiplier": 2})
    ph = PHBase(batch, {"subproblem_precision": "native"},
                dtype=jnp.float64)
    fac, _d = ph._get_factors(True)
    assert fac.A_s.shape == (6, 13, 24)
    for scale in (1e-3, 1.0, 1e3):
        rs = jnp.full((6,), scale)
        g = fac.Eb * fac.D
        Minv = np.asarray(jax.jit(qps._kkt_inverse_blocked)(
            fac.A_s, fac.rho_A * rs[:, None], fac.sigma,
            fac.P_s + g * g * fac.rho_b * rs[:, None]))
        M = qps._kkt_host(fac, np.asarray(rs))
        assert np.abs(M @ Minv - np.eye(24)).max() <= 1e-10


@pytest.mark.parametrize("n", [24, 112])
def test_a_rebuild_builds_the_rows_that_moved(monkeypatch, n):
    """``keep = (moved, old)``: the moved rows are gathered a chunk at
    a time and rebuilt into ``old`` (here marked, so that it shows); a
    chunk is filled up with rows that did not move, which come out what
    a full build gives them; every other row is ``old``'s."""
    rng = np.random.default_rng(3)
    S, m = 7, 9
    A = jnp.asarray(rng.standard_normal((S, m, n)))
    r = jnp.asarray(rng.random((S, m)) + 0.1)
    d = jnp.asarray(rng.random((S, n)) + 0.5)
    monkeypatch.setattr(qps, "_F64_BUILD_BYTES", 8 * n * n * 3)  # 3 rows
    full = np.asarray(jax.jit(
        lambda A, r, d: qps._kkt_inverse_blocked(A, r, 1e-6, d))(A, r, d))
    marked = jnp.full((S, n, n), 7.0)
    rebuild = jax.jit(lambda A, r, d, mv, old: qps._kkt_inverse_blocked(
        A, r, 1e-6, d, (mv, old)))

    def rows_built(*moved):
        mv = np.zeros(S, bool)
        mv[list(moved)] = True
        part = np.asarray(rebuild(A, r, d, jnp.asarray(mv), marked))
        built = [i for i in range(S) if not (part[i] == 7.0).all()]
        np.testing.assert_allclose(part[built], full[built], rtol=1e-12,
                                   atol=1e-14)
        return built

    assert rows_built() == []
    assert rows_built(4) == [0, 1, 4]              # one chunk of three
    assert rows_built(1, 4, 6) == [1, 4, 6]
    assert rows_built(0, 2, 3, 5) == [0, 1, 2, 3, 4, 5]    # two chunks
    assert rows_built(*range(S)) == list(range(S))  # the last one clamps
