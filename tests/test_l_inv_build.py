"""The explicit inverse of the shared f32 factor, built in column panels
(ISSUE 41; ``qp_solver._make_l_inv`` / ``_l_inv_by_panels``).

The reference is plain numpy (``numpy.linalg.inv`` of the float64 copy
of the SAME f32 factor): it imports nothing of ``ops/``. The panel
constant is patched small so that toy widths span several panels; a
jitted entry caches on shapes and never sees a patched module constant,
so the tests that patch clear jax's caches around themselves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpisppy_tpu.ops.qp_solver as qs
from mpisppy_tpu.core.ph import PHBase
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import uc

EPS32 = float(np.finfo(np.float32).eps)


@pytest.fixture
def small_panels(monkeypatch):
    """``set_(panel, block=None)``: patch the build's constants, with
    jax's caches cleared now and when the test ends."""
    def set_(panel, block=None):
        monkeypatch.setattr(qs, "_LINV_PANEL", panel)
        if block is not None:
            monkeypatch.setattr(qs, "_TRI_BLOCK", block)
        jax.clear_caches()
    yield set_
    jax.clear_caches()


def _factor(n, seed=0):
    rng = np.random.default_rng(seed)
    B = rng.normal(size=(n, n))
    L = jnp.linalg.cholesky(jnp.asarray(B @ B.T + n * np.eye(n),
                                        jnp.float32))
    return L, np.linalg.inv(np.asarray(L, np.float64))


# (n, panel): below one panel (today's single solve); an exact
# multiple; a ragged last panel that also ends in a short diagonal
# block; a ragged last panel of whole blocks (UC's case at a panel that
# does not divide n)
_SHAPES = [(100, 128), (256, 128), (300, 128), (640, 256)]


@pytest.mark.parametrize("where", ["eager", "in_cond"])
@pytest.mark.parametrize("container", ["bare", "prepared"])
@pytest.mark.parametrize("n,panel", _SHAPES)
def test_panel_build_equals_the_one_shot_inverse(small_panels, n, panel,
                                                 container, where):
    """Tolerance: forward substitution on the identity in f32 leaves
    each column of the inverse with a relative error of about
    cond(L) x eps32 x (a random walk over the n terms of its sums,
    sqrt(n)); both forms ARE that substitution (the panel build with
    its diagonal blocks inverted first, as the TPU's expander does), so
    each lies within that band of the float64 inverse, and they within
    twice it of each other. The upper triangle is never written: exact
    zeros, as the one-shot solve's."""
    small_panels(panel)
    L, ref = _factor(n, seed=n)
    arg = qs._prepare_factor(L) if container == "prepared" else L
    if where == "eager":
        got = jax.jit(qs._make_l_inv)(arg)
    else:
        # as the fused program's handoff calls it: the new inverse
        # against a carried one, under a traced predicate
        old = qs.LInv(jnp.zeros_like(L), L)
        got = jax.jit(lambda a, p: jax.lax.cond(
            p, lambda: old, lambda: qs._make_l_inv(a)))(arg, False)
    assert isinstance(got, qs.LInv)
    np.testing.assert_array_equal(np.asarray(got.tri), np.asarray(L))
    assert qs.l_inv_panels(n) == -(-n // panel)
    X = np.asarray(got.inv, np.float64)
    one = np.asarray(jax.lax.linalg.triangular_solve(
        L, jnp.eye(n, dtype=L.dtype), left_side=True, lower=True),
        np.float64)
    band = np.linalg.cond(ref) * EPS32 * np.sqrt(n) * np.abs(ref).max()
    assert np.abs(one - ref).max() <= band
    assert np.abs(X - ref).max() <= band
    assert np.abs(X - one).max() <= 2 * band
    assert (np.triu(X, 1) == 0).all()
    if n <= panel:
        np.testing.assert_array_equal(X, one)     # the same program


def _uc_toy(S=4):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs={"num_gens": 3, "num_hours": 6},
                       vector_patch=uc.scenario_vector_patch)


def _ph_pass(l_inv):
    ph = PHBase(_uc_toy(), {
        "defaultPHrho": 50.0, "subproblem_precision": "df32",
        "subproblem_max_iter": 450, "subproblem_eps": 1e-5,
        "subproblem_eps_hot": 1e-4, "subproblem_eps_dua_hot": 1e-2,
        "subproblem_stall_rel": 1.5e-3, "subproblem_tail_iter": 600,
        "subproblem_polish_hot": False, "subproblem_hospital": False,
        "subproblem_chunk": 2, "subproblem_kernel_l_inv": l_inv},
        dtype=jnp.float64)
    for it in range(2):             # iter-0 and one hot iteration
        ph.solve_loop(w_on=it > 0, prox_on=it > 0)
        ph.W = ph.W_new
    return ph


def test_uc_ph_pass_with_a_panelled_inverse_matches_l_inv_off(
        small_panels):
    """``tests/test_kernels.py``'s seeded UC toy and df32 recipe
    through ``PHBase`` (chunked, two chunks a pass: iter-0 and one hot
    iteration) with the panel below n, so that every inverse of the
    pass (the eager wrap of each mode's cold state, any rebuild after a
    rho refactorization) is built in several panels, against the run
    that substitutes (``l_inv`` off). What the parity test there states
    of two budget-capped df32 trajectories holds here: "tolerance-
    equivalent, not iterate-equal", conv within a quarter and every
    solve under the 1e-2 gate; x-bar within 1.2e-2 (read 5.5e-3: the
    solves stop at pri_rel 1.5e-3, and x-bar's binaries move by a few
    of those). Not compared later: from the third iteration on the
    degenerate LP relaxation lands the two runs on other vertices
    (0.25 apart, with the one-shot inverse as with the panelled
    one)."""
    small_panels(32, block=16)
    on, off = _ph_pass("on"), _ph_pass("off")
    n = on.batch.n
    assert qs.l_inv_panels(n) >= 3
    pt = on.phase_timing(True)
    assert pt["kernel"]["l_inv"] and not off.phase_timing(True)[
        "kernel"]["l_inv"]
    assert isinstance(on._qp_states[("chunks", True)][0].L, qs.LInv)
    # the build told what it built, and the solves what they applied
    assert pt["linv_build"]["n"] == n
    assert pt["linv_build"]["panels"] == qs.l_inv_panels(n)
    assert pt["linv_build"]["builds"] >= 1
    assert pt["linv_build"]["seconds"] > 0
    admm = pt["admm_iters_per_call"]
    assert admm["linv_applies"] == admm["tail"] * 2 * 2
    assert off.phase_timing(True)["admm_iters_per_call"][
        "linv_applies"] == 0
    assert off.phase_timing(True)["linv_build"] == {}
    np.testing.assert_allclose(np.asarray(on.xbar), np.asarray(off.xbar),
                               atol=1.2e-2)
    assert on.conv == pytest.approx(off.conv, rel=0.25)
    for ph in (on, off):
        assert float(np.asarray(ph._qp_states[True].pri_rel).max()) < 1e-2
