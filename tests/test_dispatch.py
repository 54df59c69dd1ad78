"""Device-paced APH φ-dispatch (ISSUE 16): ops/dispatch + the
dispatch-masked chunked loop + composition.

Covers the ISSUE's test satellite: device/host dispatch-selection
parity (bit-for-bit, including tie order and mesh-pad exclusion), the
frac=1.0 bit-equality guarantee, the dispatch-masked solve_loop's
equivalence to the plain chunked loop at full ids, the counter-
asserted solve savings at frac=0.2 (<= 0.25x full dispatch at the
same gap), the O(1) ``aph.gate_syncs`` contract on 1/2/4-device
meshes, compile-count == dispatch-bucket transitions, dispatch-driven
streaming staging (transfer-byte assertion), APH under active-set
compaction, checkpoint resume determinism, config/CLI plumbing, and
the analyze section + compare verdict.
"""

import json
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.aph import APH
from mpisppy_tpu.core.ph import PH
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer
from mpisppy_tpu.ops import dispatch as dispatch_ops
from mpisppy_tpu.ops.dispatch import (GATE_HEAD, dispatch_gate,
                                      dispatch_select, gather_chunks,
                                      gather_rows, place_chunks,
                                      scalar_gate, scatter_rows)
from mpisppy_tpu.parallel.mesh import make_mesh

EF3 = -108390.0


def farmer_batch(S=3):
    return build_batch(farmer.scenario_creator, farmer.make_tree(S))


def farmer_shared(S=6, seed=7):
    """Shared-structure (one A) farmer via the synth family — the
    representation the chunked loop (and hence chunked-skip dispatch)
    requires; plain build_batch farmer carries per-scenario A."""
    from mpisppy_tpu.stream import synth_batch
    b, _ = synth_batch(farmer.scenario_creator, farmer.make_tree(S),
                       farmer.scenario_synth_spec, seed=seed,
                       materialize_values=True)
    return b


def make_aph(num_scens=3, iters=5, mesh=None, shared=False, **opt):
    options = {"defaultPHrho": 1.0, "PHIterLimit": iters,
               "convthresh": -1.0, "subproblem_max_iter": 3000,
               "subproblem_eps": 1e-8}
    options.update(opt)
    b = farmer_shared(num_scens) if shared else farmer_batch(num_scens)
    return APH(b, options, mesh=mesh)


@pytest.fixture
def mem_obs():
    rec = obs.configure(out_dir=None)
    yield rec
    obs.shutdown()


# ---------------- device/host selection parity ----------------

def _host_mask(phis, last_dispatch, scnt, S_real, S):
    """The host reference (APH._dispatch_mask) on a bare namespace —
    the real reference code, not a test re-derivation."""
    ns = SimpleNamespace(batch=SimpleNamespace(S=S), _S_orig=S_real,
                         phis=phis, _last_dispatch=last_dispatch)
    # frac chosen so ceil(S_real * frac) == scnt exactly
    return APH._dispatch_mask(ns, 0, (scnt - 0.5) / S_real)


def test_dispatch_select_matches_host_reference_bitwise():
    """The jitted selection must equal the host reference bit-for-bit
    across random phis/recency draws WITH ties (quantized φ values,
    repeated last-dispatch iters) — the stable-sort tie-break contract,
    including mesh-pad exclusion (S_real < S)."""
    rng = np.random.default_rng(0)
    for S, S_real in [(8, 8), (8, 6), (12, 12), (12, 9)]:
        for scnt in sorted({1, 2, S_real // 2, S_real - 1}):
            if not 0 < scnt < S_real:
                continue
            for _ in range(8):
                phis = rng.integers(-3, 4, S).astype(np.float64) / 4.0
                phis[S_real:] = 0.0   # pad rows: prob 0 => phi 0
                last = rng.integers(0, 4, S).astype(np.int64)
                want = _host_mask(phis, last, scnt, S_real, S)
                got = np.asarray(dispatch_select(
                    jnp.asarray(phis), jnp.asarray(last),
                    scnt=scnt, S_real=S_real))
                assert got.tolist() == want.tolist(), \
                    (S, S_real, scnt, phis.tolist(), last.tolist())
                assert not got[S_real:].any()
                assert got.sum() == scnt


def test_gate_packing_layout():
    """dispatch_gate == [tau, phi, theta, conv, phi stats] ++ mask and
    scalar_gate is exactly its head — the ONE-row-per-iteration
    contract the host loop unpacks positionally."""
    phis = jnp.asarray([-2.0, 0.5, -1.0, 3.0, 0.0, 0.0])
    last = jnp.asarray([5, 1, 2, 3, 0, 0])
    g = np.asarray(dispatch_gate(1.5, -0.25, 0.75, 2.0, phis, last,
                                 scnt=2, S_real=4))
    assert g.shape == (GATE_HEAD + 6,)
    tau, phi, theta, conv, pmin, pmax, pneg = g[:GATE_HEAD].tolist()
    assert (tau, phi, theta, conv) == (1.5, -0.25, 0.75, 2.0)
    assert (pmin, pmax, int(pneg)) == (-2.0, 3.0, 2)
    want = np.asarray(dispatch_select(phis, last, scnt=2, S_real=4))
    assert ((g[GATE_HEAD:] != 0) == want).all()
    s = np.asarray(scalar_gate(1.5, -0.25, 0.75, 2.0, phis, S_real=4))
    assert s.tolist() == g[:GATE_HEAD].tolist()


# ---------------- one program each way (ISSUE 35) ----------------

@pytest.mark.parametrize("n_chunks", [1, 3])
def test_one_program_each_way_is_the_row_ops_bit_for_bit(n_chunks):
    """``gather_chunks`` is nine ``gather_rows`` a chunk and
    ``place_chunks`` fifteen ``scatter_rows`` (the inverse index built
    once instead of fifteen times), bit for bit, with a padded last
    chunk (its trailing id repeated, the repeats carrying identical
    rows); rows the ids do not name come through untouched."""
    rng = np.random.default_rng(35 + n_chunks)
    S, chunk, n, m = 13, 4, 7, 9
    real = n_chunks * chunk - 2
    ids_real = np.sort(rng.choice(S, real, replace=False))
    ids = np.concatenate([ids_real, np.full(2, ids_real[-1])])
    ids_stack = jnp.asarray(ids.reshape(n_chunks, chunk))
    widths = [(n,), (m,), (n,), (m,), (n,), (), (), (), ()]   # the store
    store = tuple(jnp.asarray(rng.standard_normal((S,) + w))
                  for w in widths)
    chunks = gather_chunks(store, ids_stack)
    assert len(chunks) == n_chunks and all(len(c) == 9 for c in chunks)
    for ci, got in enumerate(chunks):
        for f, g in zip(store, got):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(gather_rows(f, ids_stack[ci])))
    # the way back: the store's nine and the engine's six, (S, ·) each
    fulls = store + tuple(jnp.asarray(rng.standard_normal((S,) + w))
                          for w in [(n,), (m,), (n,), (), (), ()])
    rows = []
    for full in fulls:
        solved = rng.standard_normal((n_chunks * chunk,) + full.shape[1:])
        solved[real:] = solved[real - 1]        # pads repeat the last row
        rows.append(tuple(jnp.asarray(solved[c * chunk:(c + 1) * chunk])
                          for c in range(n_chunks)))
    placed = place_chunks(fulls, ids_stack, tuple(rows))
    assert len(placed) == 15
    untouched = np.setdiff1d(np.arange(S), ids_real)
    assert untouched.size
    for full, parts, got in zip(fulls, rows, placed):
        want = scatter_rows(full, jnp.asarray(ids), jnp.concatenate(parts))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(full)[untouched])
        np.testing.assert_array_equal(np.asarray(got)[ids_real],
                                      np.concatenate(parts)[:real])


# ---------------- the dispatch-masked chunked loop ----------------

def _settled_ph(S=6, chunk=2, iters=2):
    ph = PH(farmer_shared(S), {"defaultPHrho": 1.0, "PHIterLimit": iters,
                              "convthresh": -1.0, "subproblem_chunk": chunk,
                              "subproblem_max_iter": 3000,
                              "subproblem_eps": 1e-8})
    ph.ph_main(finalize=False)
    return ph


def test_solve_loop_dispatch_full_ids_equivalent():
    """solve_loop(dispatch=arange(S)) must reproduce the plain chunked
    pass to solver tolerance. Not bit-equal by design: the dispatch row
    store carries ONE (L, rho_scale) pair — the last chunk's — where the
    plain loop keeps per-chunk adaptive scalars, so early chunks iterate
    to the same fixed point under a different rho_scale."""
    ph_a, ph_b = _settled_ph(), _settled_ph()
    np.testing.assert_array_equal(np.asarray(ph_a.x), np.asarray(ph_b.x))
    ph_a.solve_loop(w_on=True, prox_on=True, update=False)
    ph_b.solve_loop(w_on=True, prox_on=True, update=False,
                    dispatch=np.arange(ph_b.batch.S))
    np.testing.assert_allclose(np.asarray(ph_a.x), np.asarray(ph_b.x),
                               rtol=1e-4, atol=1e-3)
    # duals are NOT compared elementwise: QP multipliers are non-unique
    # at degenerate vertices and the rho_scale path picks among them —
    # the objective is the dual-invariant check
    assert ph_b.Eobjective_value() == \
        pytest.approx(ph_a.Eobjective_value(), rel=1e-5)


def test_solve_loop_dispatch_partial_touches_only_dispatched():
    ph = _settled_ph()
    x0 = np.asarray(ph.x).copy()
    ph.solve_loop(w_on=True, prox_on=True, update=False,
                  dispatch=np.array([1, 4]))
    x1 = np.asarray(ph.x)
    for s in (0, 2, 3, 5):
        np.testing.assert_array_equal(x1[s], x0[s])


def test_solve_loop_dispatch_validation():
    ph = _settled_ph()
    with pytest.raises(ValueError):
        ph.solve_loop(w_on=True, prox_on=True, update=True,
                      dispatch=np.array([0]))
    with pytest.raises(ValueError):
        ph.solve_loop(w_on=True, prox_on=True, update=False,
                      dispatch=np.array([], dtype=np.int64))
    ph_nochunk = PH(farmer_batch(3), {"defaultPHrho": 1.0,
                                      "PHIterLimit": 1})
    ph_nochunk.ph_main(finalize=False)
    with pytest.raises(ValueError):
        ph_nochunk.solve_loop(w_on=True, prox_on=True, update=False,
                              dispatch=np.array([0]))


def test_dispatch_passes_share_the_staging_program_per_chunk_count():
    """The chunks' ids are an OPERAND of the one staging program
    (``core/ph._ph_stage_chunks``), so APH's dispatch passes, whose id
    set changes every iteration, compile it once per chunk COUNT
    exactly as their scatter-back does — not once per id set."""
    from mpisppy_tpu.core.ph import _ph_stage_chunks
    ph = _settled_ph(S=6, chunk=2)
    kw = dict(w_on=True, prox_on=True, update=False)
    # the jit's cache is the process's: count from empty (an earlier
    # test's engine may have compiled these very shapes)
    _ph_stage_chunks.clear_cache()
    ph.solve_loop(dispatch=np.array([0, 1, 2]), **kw)   # 2 chunks, padded
    assert _ph_stage_chunks._cache_size() == 1
    ph.reset_phase_timing()
    ph.solve_loop(dispatch=np.array([1, 3, 4, 5]), **kw)   # 2 chunks again
    assert _ph_stage_chunks._cache_size() == 1
    assert ph.phase_timing(True)["assemble_programs_per_call"] == 1
    ph.solve_loop(dispatch=np.array([0, 5]), **kw)      # 1 chunk: a new one
    assert _ph_stage_chunks._cache_size() == 2


# ---------------- frac=1.0 bit-equality + determinism ----------------

def test_full_dispatch_bit_equal_to_default():
    """frac=1.0 rides scalar_gate (no selection runs): the trajectory
    must be BIT-identical to an APH constructed without the option at
    all, and deterministic across runs."""
    runs = []
    for opt in ({}, {"dispatch_frac": 1.0}, {"dispatch_frac": 1.0}):
        aph = make_aph(iters=5, **opt)
        aph.APH_main(finalize=False)
        runs.append(aph)
    for aph in runs[1:]:
        np.testing.assert_array_equal(np.asarray(runs[0].x),
                                      np.asarray(aph.x))
        np.testing.assert_array_equal(np.asarray(runs[0].W),
                                      np.asarray(aph.W))
        np.testing.assert_array_equal(np.asarray(runs[0].z),
                                      np.asarray(aph.z))
        assert runs[0].tau == aph.tau and runs[0].phi == aph.phi
        assert runs[0].conv == aph.conv


# ---------------- the acceptance criterion: solve savings ----------------

def test_frac02_solve_count_quarter_of_full_at_same_gap(mem_obs):
    """ISSUE 16 acceptance: at dispatch_frac=0.2 the counter-asserted
    scenario-solve count is <= 0.25x full dispatch, while the wheel
    still lands at the same objective neighborhood (same gap)."""
    iters, S = 21, 10
    base = dict(num_scens=S, iters=iters, defaultPHrho=10.0,
                shared=True, subproblem_chunk=2)
    c0 = obs.counters_snapshot()
    full = make_aph(**base)
    full.APH_main(finalize=False)
    c1 = obs.counters_snapshot()
    part = make_aph(dispatch_frac=0.2, **base)
    part.APH_main(finalize=False)
    c2 = obs.counters_snapshot()

    def delta(a, b, k):
        return b.get(k, 0) - a.get(k, 0)

    solved_full = delta(c0, c1, "dispatch.solved_scenarios")
    solved_part = delta(c1, c2, "dispatch.solved_scenarios")
    # full: S per iteration; partial: S at iter 1 (forced), then
    # ceil(0.2*S)=2 — genuinely skipped solves, not masked launches
    assert solved_full == S * iters
    assert solved_part == S + 2 * (iters - 1)
    assert solved_part <= 0.25 * solved_full
    assert delta(c1, c2, "dispatch.skipped_scenarios") == \
        (S - 2) * (iters - 1)
    assert part._aph_status["solve_path"] == "chunked-skip"
    # same-gap check: both trajectories sit in the same objective
    # neighborhood of the EF optimum
    of, op = full.Eobjective_value(), part.Eobjective_value()
    assert abs(op - of) / abs(of) < 0.05


# ---------------- gate syncs: O(1) per iteration, on meshes ----------------

@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_gate_syncs_one_per_iteration_on_meshes(ndev, mem_obs):
    iters, S = 4, 6
    c0 = obs.counters_snapshot().get("aph.gate_syncs", 0)
    aph = make_aph(num_scens=S, iters=iters, dispatch_frac=0.5,
                   mesh=make_mesh(ndev))
    aph.APH_main(finalize=False)
    syncs = obs.counters_snapshot().get("aph.gate_syncs", 0) - c0
    assert syncs == iters, "the stacked gate contract: ONE D2H/iter"
    st = aph._aph_status
    assert st["scnt"] == 3 and st["dispatched"] == 3
    # mesh pad rows (S=6 on 4 devices pads to 8) never dispatch
    assert not np.asarray(aph._dispatched)[aph._S_orig:].any()
    if ndev > 1:
        assert st["solve_path"] == "masked-accept"


# ---------------- compiles == bucket transitions ----------------

def test_compile_count_tracks_dispatch_bucket_transitions(mem_obs):
    """Steady partial dispatch pays ONE bucket compile; every further
    iteration is a registry cache hit; a same-shape second wheel
    compiles nothing; a changed dispatch width is a new bucket."""
    dispatch_ops._BUCKET_REGISTRY.clear()
    iters = 5
    aph = make_aph(num_scens=8, iters=iters, dispatch_frac=0.5,
                   shared=True, subproblem_chunk=2)
    aph.APH_main(finalize=False)
    ctr = obs.counters_snapshot()
    # iter 1 forced full; iters 2..5 partial at constant scnt=4
    assert ctr.get("dispatch.bucket.compile", 0) == 1
    assert ctr.get("dispatch.bucket.cache_hit", 0) == iters - 2
    reg = dispatch_ops.bucket_registry()
    assert len(reg) == 1
    (fp, entry), = reg.items()
    assert entry["fields"]["n_chunks"] == 2   # ceil(4/2)
    assert entry["fields"]["chunk"] == 2
    # wheel B, same shapes: its transitions all hit the registry
    aph_b = make_aph(num_scens=8, iters=iters, dispatch_frac=0.5,
                     shared=True, subproblem_chunk=2)
    aph_b.APH_main(finalize=False)
    ctr2 = obs.counters_snapshot()
    assert ctr2.get("dispatch.bucket.compile", 0) == 1
    assert ctr2.get("dispatch.bucket.cache_hit", 0) == 2 * (iters - 1) - 1
    # a different dispatch width IS a transition: one more compile
    aph_b.solve_loop(w_on=True, prox_on=True, update=False,
                     dispatch=np.arange(6))   # 3 chunks, not 2
    assert obs.counters_snapshot().get("dispatch.bucket.compile", 0) == 2


# ---------------- dispatch-driven streaming staging ----------------

def test_streamed_dispatch_ships_fewer_bytes(mem_obs):
    """Composition with PR 14 streaming: a partial pass stages ONLY
    the dispatched chunks, so its device_put traffic is the chunk
    fraction, not the full pass (the transfer-byte assertion)."""
    aph = make_aph(num_scens=12, iters=2, dispatch_frac=0.25,
                   shared=True, subproblem_chunk=4,
                   scenario_source="streamed")
    aph.APH_main(finalize=False)
    try:
        kw = dict(w_on=True, prox_on=True, update=False)
        aph.solve_loop(**kw)                       # warm the full path
        b0 = obs.counter_value("xfer.device_put_bytes")
        aph.solve_loop(**kw)
        full_bytes = obs.counter_value("xfer.device_put_bytes") - b0
        ids = np.array([0, 1, 2])                  # 1 chunk of 3
        aph.solve_loop(dispatch=ids, **kw)         # warm the skip path
        b1 = obs.counter_value("xfer.device_put_bytes")
        aph.solve_loop(dispatch=ids, **kw)
        part_bytes = obs.counter_value("xfer.device_put_bytes") - b1
    finally:
        aph.close_stream()
    assert 0 < part_bytes < full_bytes
    # 1 of 3 chunks staged => ~1/3 of the bytes; allow 1/2 for slack
    assert part_bytes * 2 <= full_bytes


# ---------------- composition with active-set compaction ----------------

def test_aph_partial_dispatch_under_compaction(mem_obs):
    """The lifted PR 13 guard: compaction packs the variable axis
    while dispatch selects scenarios — a compacted APH wheel keeps
    skipping solves and stays in the full-dispatch trajectory's
    objective neighborhood."""
    from mpisppy_tpu.extensions.fixer import uniform_fix_list
    BIG = 2 ** 30

    def slot0_fix_list(b):
        spec = uniform_fix_list(b, tol=5e-1, nb=3, lb=3, ub=3,
                                integer_only=False)
        for k in ("nb", "lb", "ub"):
            a = np.minimum(spec[k], BIG).copy()
            a[1:] = BIG
            spec[k] = a
        return spec

    base = dict(num_scens=6, iters=25, defaultPHrho=5.0,
                shared=True, subproblem_chunk=2, shrink_fix=True,
                id_fix_list_fct=slot0_fix_list)
    ref = make_aph(**base)
    ref.APH_main(finalize=False)
    aph = make_aph(dispatch_frac=0.5, shrink_compact=True,
                   shrink_buckets="0.2", **base)
    aph.APH_main(finalize=False)
    st = aph._shrink_status
    assert st is not None and st["compactions"] >= 1
    assert aph._shrink is not None
    assert aph._aph_status["solve_path"] == "chunked-skip"
    # full-width state for every consumer despite the compacted solves
    assert np.asarray(aph.x).shape == (6, aph.batch.n)
    assert np.asarray(aph.z).shape[1] == aph.batch.K
    assert obs.counters_snapshot().get("dispatch.skipped_scenarios",
                                       0) > 0
    o_ref, o_c = ref.Eobjective_value(), aph.Eobjective_value()
    assert abs(o_c - o_ref) / abs(o_ref) < 0.05


# ---------------- checkpoint resume determinism ----------------

def test_ckpt_aph_state_roundtrip_and_resume_determinism(tmp_path,
                                                         mem_obs):
    from mpisppy_tpu.ckpt.manager import resume_hub
    from mpisppy_tpu.cylinders.hub import Hub
    d = str(tmp_path)
    opt = dict(num_scens=4, iters=4, dispatch_frac=0.5,
               shared=True, subproblem_chunk=2)
    src = make_aph(**opt)
    src.APH_main(finalize=False)
    hub = Hub(src, spokes=[], options={"checkpoint_dir": d,
                                       "checkpoint_fingerprint": "fp"})
    assert hub.ckpt.capture("test") is not None

    resumed = []
    for _ in range(2):
        aph = make_aph(**opt)
        assert resume_hub(Hub(aph, spokes=[]), d,
                          fingerprint="fp") is not None
        resumed.append(aph)
    for aph in resumed:
        # the full APH extra set round-trips bit-equal
        np.testing.assert_array_equal(np.asarray(aph.z),
                                      np.asarray(src.z))
        np.testing.assert_array_equal(np.asarray(aph.y_aph),
                                      np.asarray(src.y_aph))
        np.testing.assert_array_equal(np.asarray(aph.x),
                                      np.asarray(src.x))
        np.testing.assert_array_equal(np.asarray(aph.phis),
                                      np.asarray(src.phis))
        assert aph._last_dispatch.tolist() == \
            src._last_dispatch.tolist()
        assert aph._dispatched.tolist() == src._dispatched.tolist()
        assert aph._iter == src._iter
    # resume DETERMINISM: two engines resumed from one bundle and run
    # further must walk identical trajectories (same dispatch picks).
    # The transient resume Hubs above are gone — drop their dead
    # weakref spcomm so the engines run standalone.
    for aph in resumed:
        aph.spcomm = None
        aph.APH_main(finalize=False)
    a, b = resumed
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(np.asarray(a.W), np.asarray(b.W))
    np.testing.assert_array_equal(np.asarray(a.z), np.asarray(b.z))
    np.testing.assert_array_equal(np.asarray(a.phis),
                                  np.asarray(b.phis))
    assert a._dispatched.tolist() == b._dispatched.tolist()


def test_ckpt_pre_aph_bundle_cold_starts_projective_state(tmp_path,
                                                          mem_obs):
    """A PH-hub bundle resumed into an APH wheel: (W, xbar, rho)
    install warm, the APH extras are absent, and the projective state
    stays cold — no crash, no rejection."""
    from mpisppy_tpu.ckpt.manager import resume_hub
    from mpisppy_tpu.cylinders.hub import Hub
    d = str(tmp_path)
    ph = PH(farmer_batch(4), {"defaultPHrho": 1.0, "PHIterLimit": 3,
                              "convthresh": -1.0,
                              "subproblem_max_iter": 2000,
                              "subproblem_eps": 1e-7})
    ph.ph_main(finalize=False)
    hub = Hub(ph, spokes=[], options={"checkpoint_dir": d})
    assert hub.ckpt.capture("test") is not None
    aph = make_aph(num_scens=4)
    assert resume_hub(Hub(aph, spokes=[]), d) is not None
    np.testing.assert_allclose(np.asarray(aph.W), np.asarray(ph.W))
    assert float(np.abs(np.asarray(aph.z)).max()) == 0.0
    assert getattr(aph, "_warm_started", False)


# ---------------- config + CLI plumbing ----------------

def test_dispatch_config_validation_and_cli():
    from mpisppy_tpu.__main__ import config_from_args, make_parser
    from mpisppy_tpu.utils.config import AlgoConfig, RunConfig
    for bad in (dict(dispatch_frac=0.0), dict(dispatch_frac=1.5),
                dict(dispatch_frac=-0.2), dict(aph_nu=0.0),
                dict(aph_gamma=-1.0)):
        with pytest.raises(ValueError):
            AlgoConfig(**bad).validate()
    # partial dispatch is phi-based: APH hub only
    with pytest.raises(ValueError):
        RunConfig(hub="ph",
                  algo=AlgoConfig(dispatch_frac=0.5)).validate()
    RunConfig(hub="aph",
              algo=AlgoConfig(dispatch_frac=0.5)).validate()
    args = make_parser().parse_args(
        ["farmer", "--hub", "aph", "--dispatch-frac", "0.3",
         "--aph-nu", "2.0", "--aph-gamma", "0.5"])
    cfg = config_from_args(args)
    assert cfg.algo.dispatch_frac == 0.3
    assert cfg.algo.aph_nu == 2.0 and cfg.algo.aph_gamma == 0.5
    # to_options() is the ONE plumbing path: hub dicts AND the serve
    # bucket fingerprint read it, so the keys must be present
    o = cfg.algo.to_options()
    assert o["dispatch_frac"] == 0.3
    assert o["APHnu"] == 2.0 and o["APHgamma"] == 0.5


# ---------------- analyze: section, json, compare verdict ----------------

def _aph_run_dir(path, **opt):
    obs.configure(out_dir=str(path))
    try:
        aph = make_aph(**opt)
        aph.APH_main(finalize=False)
    finally:
        obs.shutdown()
    return str(path)


def test_analyze_aph_section_json_and_compare_verdict(tmp_path, capsys):
    from mpisppy_tpu.obs import analyze
    from mpisppy_tpu.obs.analyze import aph_summary, compare, load_run
    # the bucket registry is process-global: earlier tests may have
    # compiled this shape already, which would book pure cache hits
    dispatch_ops._BUCKET_REGISTRY.clear()
    opt = dict(num_scens=8, iters=5, dispatch_frac=0.5)
    a = _aph_run_dir(tmp_path / "a", shared=True,
                     subproblem_chunk=2, **opt)
    # same frac, NO chunking: masked acceptance launches S solves per
    # iteration — the exact silent degradation the verdict catches
    b = _aph_run_dir(tmp_path / "b", **opt)

    sa = aph_summary(load_run(a))
    assert sa is not None
    assert sa["gate_syncs_per_iteration"] == 1.0
    assert sa["solve_path"] == "chunked-skip"
    assert sa["dispatch_frac"] == 0.5
    assert 0 < sa["skipped_solve_savings"] < 1
    assert sa["bucket_compiles"] >= 1
    assert len(sa["trajectory"]) == sa["iterations"] == 5
    assert aph_summary(load_run(str(tmp_path / "a"))) is not None

    rc = analyze.main([a])
    assert rc == 0
    assert "== aph ==" in capsys.readouterr().out
    rc = analyze.main([a, "--json"])
    assert rc == 0
    js = json.loads(capsys.readouterr().out)
    assert js["aph"]["solve_path"] == "chunked-skip"

    ra, rb = load_run(a), load_run(b)
    text, passed = compare(ra, ra)
    assert "dispatch verdict [PASS]" in text
    text, passed = compare(ra, rb)
    assert "aph_dispatched_solves" in text or \
        "dispatch verdict [REGRESSION]" in text
    assert not passed
    # different fracs = config change, not a regression: abstain
    c = _aph_run_dir(tmp_path / "c", shared=True,
                     subproblem_chunk=2, num_scens=8, iters=5,
                     dispatch_frac=0.25)
    text, _ = compare(ra, load_run(c))
    assert "dispatch verdict [skipped]" in text
