"""Scenario streaming engine (ISSUE 15): double-buffered chunk
pipeline, int8 packed storage, device-side scenario synthesis.

Covers the ISSUE's test satellite: resident-vs-streamed-vs-synthesized
trajectory equivalence on farmer and chunked UC (bit-tight on a single
device — the exact setup surrogates make factors identical — and to
the sharded suite's tolerance on 2/4-device meshes), the flat
steady-state ``xfer.device_put_bytes`` assertion at growing S, int8
gate reject/accept cases, prefetch-thread shutdown on SIGTERM/preempt,
and the demonstration wheel (two chunks of 8,192 in the per-push tier;
the S = 100k of the acceptance criterion is its ``slow`` case).
Checkpoint resume, the hospital and the shrink x stream composition of
a streamed wheel: tests/test_stream_compacted.py.
"""

import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PH, PHBase
from mpisppy_tpu.cylinders.hub import Hub
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import farmer, uc
from mpisppy_tpu.parallel.mesh import make_mesh
from mpisppy_tpu.stream import (ChunkPipeline, SynthField, SynthSpec,
                                quantize_field, synth_batch,
                                synth_values)
from mpisppy_tpu.stream.quant import _reconstruct_f32

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FARMER_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 5, "convthresh": 0.0,
               "subproblem_chunk": 4, "subproblem_max_iter": 3000,
               "subproblem_eps": 1e-9}
UC_OPTS = {"defaultPHrho": 50.0, "PHIterLimit": 3, "convthresh": 0.0,
           "subproblem_chunk": 2, "subproblem_max_iter": 2000,
           "subproblem_eps": 1e-8}
UC_KW = {"num_gens": 3, "num_hours": 6}


@pytest.fixture
def mem_obs():
    rec = obs.configure(out_dir=None)
    yield rec
    obs.shutdown()


def farmer_pair(S=12, seed=7):
    """(materialized batch, broadcast-view batch, spec) of the farmer
    synth family — one data source, three representations."""
    tree = farmer.make_tree(S)
    b_res, spec = synth_batch(farmer.scenario_creator, tree,
                              farmer.scenario_synth_spec, seed=seed,
                              materialize_values=True)
    b_syn, spec2 = synth_batch(farmer.scenario_creator, tree,
                               farmer.scenario_synth_spec, seed=seed,
                               materialize_values=False)
    return b_res, b_syn, spec2


def uc_vp_batch(S=6):
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs=dict(UC_KW),
                       vector_patch=uc.scenario_vector_patch)


# ---------------- int8 quantization gate ----------------

def test_int8_gate_accepts_smooth_deltas_and_roundtrips():
    tmpl = np.array([1.0, 2.0, np.inf, 0.0])
    a = tmpl[None] + np.array([[0.0, 0.01, 0.0, 0.002],
                               [0.005, -0.01, 0.0, 0.0]])
    a[:, 2] = np.inf
    fld = quantize_field(a, tmpl, 1e-3)
    assert fld is not None
    rec = _reconstruct_f32(fld, slice(None))
    finite = np.isfinite(a)
    assert np.abs(rec[finite] - a[finite]).max() <= 1e-3 * (
        1 + np.abs(a[finite])).max()
    # the non-finite pattern survives packing verbatim
    assert np.isinf(rec[:, 2]).all()


def test_int8_gate_exact_for_unperturbed_rows():
    """A row identical to the template stores scale 0 — bit-exact."""
    tmpl = np.array([3.0, -5.0, 0.0])
    a = np.repeat(tmpl[None], 4, axis=0)
    fld = quantize_field(a, tmpl, 1e-12)
    assert fld is not None
    np.testing.assert_array_equal(_reconstruct_f32(fld, slice(None)), a)


def test_int8_gate_rejects_coarse_blocks():
    """A row mixing tiny and huge deltas cannot quantize within a tight
    tolerance (>= 3 distinct values so reconstruction can't land every
    entry on an int8 grid point)."""
    tmpl = np.zeros(3)
    a = np.array([[1.0, 3.0, 1e6]])
    assert quantize_field(a, tmpl, 1e-6) is None


def test_int8_gate_rejects_nonfinite_mismatch():
    assert quantize_field(np.array([[1.0, np.inf]]),
                          np.array([1.0, 2.0]), 1e-3) is None


def test_int8_engine_gate_reject_falls_back_to_exact_storage(mem_obs):
    """A tolerance the quantization cannot meet trips the gate: the
    perturbed field keeps f64 host storage, books the fallback
    counter + event, and the trajectory stays BIT-IDENTICAL to the
    resident wheel (exact storage is exact data)."""
    b_res, _, _ = farmer_pair()
    r0 = PH(b_res, options=dict(FARMER_OPTS)).ph_main()
    ph = PH(b_res, options=dict(FARMER_OPTS, scenario_source="streamed",
                                stream_int8=True,
                                stream_int8_tol=1e-12))
    r1 = ph.ph_main()
    kinds = {f: k for f, (k, _) in ph._stream_source._store.items()}
    assert kinds["l"] == "f64"          # gate fallback
    assert kinds["c"] == "const"        # template-shared, never shipped
    assert obs.counter_value("stream.int8_fallbacks") >= 1
    assert r1 == r0
    ph.close_stream()


def test_int8_engine_gate_accept_packs_and_tracks_exact(mem_obs):
    """At the default tolerance the farmer feed-rhs deltas pack int8
    (the varying-column mask keeps never-perturbed template columns
    exact): the host store shrinks, no fallback books, and the
    quantized wheel tracks the exact one within the gate's data
    perturbation (NOT bit-identical: int8 data is different data)."""
    b_res, _, _ = farmer_pair()
    ph0 = PH(b_res, options=dict(FARMER_OPTS))
    r0 = ph0.ph_main()
    ph1 = PH(b_res, options=dict(FARMER_OPTS,
                                 scenario_source="streamed",
                                 stream_int8=True,
                                 stream_int8_tol=1e-3))
    r1 = ph1.ph_main()
    src = ph1._stream_source
    kinds = {f: k for f, (k, _) in src._store.items()}
    assert kinds["l"] == "int8", kinds
    assert obs.counter_value("stream.int8_fallbacks") == 0
    full = sum(np.asarray(getattr(b_res, f)).nbytes
               for f in ("l", "u", "lb", "ub", "c"))
    assert src.host_nbytes() < full / 4
    assert r1[1] == pytest.approx(r0[1], rel=1e-3)
    np.testing.assert_allclose(np.asarray(ph1.xbar),
                               np.asarray(ph0.xbar), atol=1e-1)
    ph1.close_stream()


# ---------------- synthesis ----------------

def test_synth_values_deterministic_and_chunk_invariant():
    """fold_in(seed, scenario_id) makes a scenario's data independent
    of which chunk (or batch) requests it."""
    _, _, spec = farmer_pair()
    all_ids = synth_values(spec, np.arange(8))
    parts = [synth_values(spec, np.arange(lo, lo + 2))
             for lo in range(0, 8, 2)]
    for i, fld in enumerate(spec.fields):
        glued = np.concatenate([np.asarray(p[i]) for p in parts])
        np.testing.assert_array_equal(np.asarray(all_ids[i]), glued)


def test_synth_batch_materialized_matches_generator():
    b_res, b_syn, spec = farmer_pair(S=6)
    sl = spec.fields[0]
    vals = np.asarray(synth_values(spec, np.arange(6))[0])
    np.testing.assert_array_equal(b_res.l[:, sl.start:sl.stop], vals)
    # the broadcast-view twin carries template data only (zero-stride)
    assert b_syn.l.strides[0] == 0
    assert b_res.shared_A and b_syn.shared_A


def test_synth_spec_rejects_cost_fields_and_bad_widths():
    with pytest.raises(ValueError, match="may perturb"):
        SynthField("c", 0, 3)
    # a generator whose output width disagrees with the declared block
    # fails at BUILD time, not inside the chunk jit
    def bad_builder(f0, seed=0, **kw):
        return SynthSpec(seed=seed, fields=(SynthField("l", 0, 2),),
                         fn=lambda key: (jnp.zeros(3),))
    with pytest.raises(ValueError, match="per-scenario shape"):
        synth_batch(farmer.scenario_creator, farmer.make_tree(3),
                    bad_builder)


# ---------------- trajectory equivalence ----------------

def test_farmer_resident_streamed_synthesized_identical(mem_obs):
    """Single device: the exact setup surrogates make the factors
    bit-identical, the staged chunk data IS the resident data, so the
    three sources produce the SAME trajectory — equality, not
    tolerance."""
    b_res, b_syn, spec = farmer_pair()
    r0 = PH(b_res, options=dict(FARMER_OPTS)).ph_main()
    ph_s = PH(b_res, options=dict(FARMER_OPTS,
                                  scenario_source="streamed"))
    r1 = ph_s.ph_main()
    ph_y = PH(b_syn, options=dict(FARMER_OPTS,
                                  scenario_source="synthesized",
                                  synth_spec=spec))
    r2 = ph_y.ph_main()
    assert r1 == r0 and r2 == r0
    # streamed staged real transfers; synthesized staged none
    assert obs.counter_value("stream.chunks_shipped") > 0
    assert obs.counter_value("stream.synth_chunks") > 0
    ph_s.close_stream()
    ph_y.close_stream()


def test_uc_chunked_resident_vs_streamed_identical():
    """The standard (vector_patch) UC batch streams AS IS — streamed
    never changes the instance."""
    b = uc_vp_batch()
    r0 = PH(b, options=dict(UC_OPTS)).ph_main()
    ph = PH(b, options=dict(UC_OPTS, scenario_source="streamed"))
    r1 = ph.ph_main()
    assert r1 == r0
    ph.close_stream()


def test_uc_synth_family_resident_vs_synthesized_identical():
    tree = uc.make_tree(6)
    b_res, _ = synth_batch(uc.scenario_creator, tree,
                           uc.scenario_synth_spec,
                           creator_kwargs=dict(UC_KW), seed=3,
                           materialize_values=True)
    b_syn, spec = synth_batch(uc.scenario_creator, tree,
                              uc.scenario_synth_spec,
                              creator_kwargs=dict(UC_KW), seed=3,
                              materialize_values=False)
    # budget 8000: the synth family's wind walk is drawn by
    # jax.random, whose bits follow jax's threefry mode
    # (jax_threefry_partitionable, default on since jax 0.5) — under it
    # scen5 of seed 3 is a feasible LP (HiGHS) whose iter-0 ADMM needs
    # more than UC_OPTS' 2000 iterations to pass the 1e-3 feasibility
    # gate (pri_rel 7e-3 at 2000, 4e-16 at 8000)
    opts = dict(UC_OPTS, subproblem_max_iter=8000)
    r0 = PH(b_res, options=dict(opts)).ph_main()
    ph = PH(b_syn, options=dict(opts, scenario_source="synthesized",
                                synth_spec=spec))
    r1 = ph.ph_main()
    assert r1 == r0
    ph.close_stream()


@pytest.mark.parametrize(
    "ndev", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_streamed_and_synth_sharded_mesh(ndev):
    """2/4-device meshes: streamed == synthesized exactly (same chunk
    data, same SPMD programs), both within the sharded suite's usual
    tolerance of the single-device resident wheel (chunk-composition
    reordering — doc/sharding.md)."""
    opts = dict(FARMER_OPTS, PHIterLimit=4, subproblem_chunk=2)
    b_res, b_syn, spec = farmer_pair(S=16)
    r0 = PH(b_res, options=dict(opts)).ph_main()
    ph_s = PH(b_res, options=dict(opts, scenario_source="streamed"),
              mesh=make_mesh(ndev))
    r1 = ph_s.ph_main()
    ph_y = PH(b_syn, options=dict(opts, scenario_source="synthesized",
                                  synth_spec=spec), mesh=make_mesh(ndev))
    r2 = ph_y.ph_main()
    assert r2 == r1
    assert r1[0] == pytest.approx(r0[0], abs=1e-4)
    assert r1[1] == pytest.approx(r0[1], rel=1e-4)
    assert r1[2] == pytest.approx(r0[2], rel=1e-4)
    np.testing.assert_array_equal(np.asarray(ph_s.xbar),
                                  np.asarray(ph_y.xbar))
    ph_s.close_stream()
    ph_y.close_stream()


# ---------------- transfer accounting ----------------

@pytest.mark.parametrize("S", [32, 128])
def test_synthesized_steady_state_device_put_zero(mem_obs, S):
    """THE acceptance contract at growing S: once the warm states
    exist, a synthesized iteration books ZERO device_put bytes —
    nothing ships, at any S."""
    _, b_syn, spec = farmer_pair(S=S)
    ph = PH(b_syn, options=dict(FARMER_OPTS, PHIterLimit=2,
                                subproblem_chunk=8,
                                scenario_source="synthesized",
                                synth_spec=spec))
    ph.ph_main(finalize=False)
    for _ in range(2):
        before = obs.counter_value("xfer.device_put_bytes")
        ph.solve_loop(w_on=True, prox_on=True)
        assert obs.counter_value("xfer.device_put_bytes") == before, \
            f"S={S}: a synthesized steady-state iteration shipped bytes"
    ph.close_stream()


def test_streamed_per_iteration_bytes_flat(mem_obs):
    """Streamed steady-state iterations ship a CONSTANT number of
    bytes (two in-order passes of the chunk sequence) — flat across
    iterations, bounded staging residency."""
    b_res, _, _ = farmer_pair(S=16)
    ph = PH(b_res, options=dict(FARMER_OPTS, PHIterLimit=2,
                                subproblem_chunk=4,
                                scenario_source="streamed"))
    ph.ph_main(finalize=False)
    deltas = []
    for _ in range(3):
        before = obs.counter_value("xfer.device_put_bytes")
        ph.solve_loop(w_on=True, prox_on=True)
        deltas.append(obs.counter_value("xfer.device_put_bytes")
                      - before)
    assert len(set(deltas)) == 1, deltas
    assert deltas[0] > 0
    ph.close_stream()


@pytest.mark.parametrize("source", ["streamed", "synthesized"])
def test_streamed_telemetry_streaming_section(tmp_path, source):
    """End to end through the artifacts: a streamed or synthesized
    wheel's telemetry renders analyze's streaming section with the
    flatness verdict (a synthesized source stages on the device and
    ships nothing)."""
    from mpisppy_tpu.obs.analyze import load_run, streaming_summary
    obs.configure(out_dir=str(tmp_path))
    try:
        b_res, b_syn, spec = farmer_pair(S=8)
        ph = PH(b_res, options=dict(FARMER_OPTS, PHIterLimit=4,
                                    scenario_source="streamed")) \
            if source == "streamed" else \
            PH(b_syn, options=dict(FARMER_OPTS, PHIterLimit=4,
                                   subproblem_chunk=4,
                                   scenario_source="synthesized",
                                   synth_spec=spec))
        ph.ph_main()
        ph.close_stream()
    finally:
        obs.shutdown()
    sm = streaming_summary(load_run(str(tmp_path)))
    assert sm is not None and sm["source"] == source
    assert sm["device_put_flat_steady_state"] is True
    if source == "streamed":
        assert sm["chunks_shipped"] > 0 and sm["bytes_shipped"] > 0
        assert sm["prefetch_occupancy"] is not None
    else:
        assert sm["synth_chunks"] > 0


# ---------------- pipeline + shutdown ----------------

def test_chunk_pipeline_inorder_backpressure_and_stall_accounting(
        mem_obs):
    staged = []
    pipe = ChunkPipeline(lambda ci: staged.append(ci) or {"ci": ci},
                         n_chunks=6, depth=2)
    pipe.start_pass()
    got = [pipe.get(ci)["ci"] for ci in range(6)]
    assert got == list(range(6))
    # a second pass rewinds; a slow consumer never sees more than
    # depth chunks staged ahead
    pipe.start_pass()
    time.sleep(0.3)
    assert len(staged) <= 6 + 2 + 1   # pass 1 + <= depth(+in-flight)
    assert pipe.get(0)["ci"] == 0
    pipe.close()
    assert not pipe.alive
    pipe.close()                      # idempotent


def test_close_stream_stops_prefetch_thread_and_is_restartable():
    b_res, _, _ = farmer_pair(S=8)
    ph = PH(b_res, options=dict(FARMER_OPTS, PHIterLimit=2,
                                scenario_source="streamed"))
    ph.ph_main(finalize=False)
    src = ph._stream_source
    assert src.prefetch_alive
    ph.close_stream()
    assert not src.prefetch_alive
    # the next pass re-binds and keeps working (serve re-lease path)
    ph.solve_loop(w_on=True, prox_on=True)
    assert src.prefetch_alive
    ph.close_stream()
    assert not src.prefetch_alive


def test_hub_finalize_closes_stream_source(mem_obs):
    """The preemption sequence ends in hub_finalize (the preempted hub
    loop exits at its next termination check and finalizes) — THAT is
    where the prefetch thread stops: closing inside the signal frame
    would break the in-flight chunk pass it interrupts. The thread is
    a daemon besides, so a rough exit can never hang on it."""
    b_res, _, _ = farmer_pair(S=8)
    ph = PH(b_res, options=dict(FARMER_OPTS, PHIterLimit=2,
                                scenario_source="streamed"))
    ph.ph_main(finalize=False)
    assert ph._stream_source.prefetch_alive
    assert ph._stream_source._pipeline._thread.daemon
    hub = Hub(ph, spokes=[], options={})
    hub.handle_preemption(source="test")
    assert hub._preempted
    hub.hub_finalize()
    assert not ph._stream_source.prefetch_alive


@pytest.mark.slow
def test_sigterm_preempts_streamed_wheel_cleanly(tmp_path):
    """Process-level satellite: SIGTERM a live streamed CLI wheel with
    checkpointing armed — the preemption notice captures a bundle and
    the process EXITS (no hang on the prefetch thread)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MPISPPY_TPU_TELEMETRY_DIR", None)
    ck = str(tmp_path / "ckpt")
    proc = subprocess.Popen(
        [sys.executable, "-m", "mpisppy_tpu", "farmer",
         "--num-scens", "64", "--scenario-source", "synthesized",
         "--subproblem-chunk", "8", "--max-iterations", "500",
         "--convthresh", "-1", "--subproblem-max-iter", "2000",
         "--checkpoint-dir", ck, "--checkpoint-interval", "1"],
        cwd=REPO, env=env)
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            if os.path.isdir(ck) and os.listdir(ck):
                break
            if proc.poll() is not None:
                pytest.fail("wheel died before first checkpoint")
            time.sleep(0.5)
        else:
            pytest.fail("no checkpoint appeared")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=120)
        assert proc.returncode == 0
        from mpisppy_tpu.ckpt.bundle import load_bundle
        manifest, _, _ = load_bundle(ck)
        assert manifest.get("iter", 0) >= 0
    finally:
        if proc.poll() is None:
            proc.kill()


# ---------------- config / CLI / serve plumbing ----------------

def test_algo_config_stream_validation_and_options():
    from mpisppy_tpu.utils.config import AlgoConfig
    cfg = AlgoConfig(scenario_source="streamed", stream_int8=True)
    cfg.validate()
    opts = cfg.to_options()
    assert opts["scenario_source"] == "streamed"
    assert opts["stream_int8"] and opts["stream_depth"] == 2
    with pytest.raises(ValueError, match="scenario_source"):
        AlgoConfig(scenario_source="banana").validate()
    with pytest.raises(ValueError, match="stream_int8"):
        AlgoConfig(scenario_source="synthesized",
                   stream_int8=True).validate()
    # the shrink x stream composition: streamed sources COMPOSE with
    # compaction (the host store re-blocks at the compacted width);
    # only synthesized sources — full-width by construction — reject
    AlgoConfig(scenario_source="streamed", shrink_fix=True,
               shrink_compact=True).validate()
    with pytest.raises(ValueError, match="shrink_compact"):
        AlgoConfig(scenario_source="synthesized", shrink_fix=True,
                   shrink_compact=True).validate()


def test_cli_parses_stream_flags():
    from mpisppy_tpu.__main__ import config_from_args, make_parser
    args = make_parser().parse_args(
        ["farmer", "--scenario-source", "synthesized",
         "--subproblem-chunk", "16", "--stream-depth", "3"])
    cfg = config_from_args(args)
    assert cfg.algo.scenario_source == "synthesized"
    assert cfg.algo.stream_depth == 3
    assert cfg.hub_options["subproblem_chunk"] == 16


def test_engine_rejects_stream_without_chunk_or_shared_structure():
    b_res, _, _ = farmer_pair(S=4)
    with pytest.raises(ValueError, match="subproblem_chunk"):
        PHBase(b_res, {"scenario_source": "streamed"})
    # standard farmer carries per-scenario A — not streamable
    b_std = build_batch(farmer.scenario_creator, farmer.make_tree(3))
    with pytest.raises(ValueError, match="shared-structure"):
        PHBase(b_std, {"scenario_source": "streamed",
                       "subproblem_chunk": 2})


def test_vanilla_guards_spokes_and_missing_spec():
    from mpisppy_tpu.utils.config import (AlgoConfig, RunConfig,
                                          SpokeConfig)
    from mpisppy_tpu.utils.vanilla import build_batch_for, wheel_dicts
    cfg = RunConfig(model="farmer", num_scens=4,
                    algo=AlgoConfig(scenario_source="synthesized"),
                    hub_options={"subproblem_chunk": 2},
                    spokes=[SpokeConfig(kind="lagrangian")])
    with pytest.raises(ValueError, match="hub-only"):
        wheel_dicts(cfg)
    cfg2 = RunConfig(model="hydro", num_scens=4,
                     algo=AlgoConfig(scenario_source="synthesized"))
    with pytest.raises(ValueError, match="scenario_synth_spec"):
        build_batch_for(cfg2)


def test_serve_bucket_key_separates_stream_sources():
    """Streamed-on and streamed-off requests must never share a leased
    engine — the knobs ride AlgoConfig.to_options() into the bucket
    fingerprint."""
    from mpisppy_tpu.serve.batch import bucket_key
    base = {"model": "farmer", "num_scens": 3}
    k0 = bucket_key(dict(base))
    k1 = bucket_key(dict(base,
                         algo={"scenario_source": "streamed"}))
    k2 = bucket_key(dict(base, algo={"scenario_source": "streamed",
                                     "stream_int8": True}))
    assert len({k0, k1, k2}) == 3


def test_serve_install_batch_swaps_streamed_tenant(mem_obs):
    """install_batch on a streamed engine rebuilds the HOST store +
    surrogates instead of shipping device vectors: the re-leased
    engine solves tenant B's instance, not A's."""
    from mpisppy_tpu.serve.manager import install_batch
    tree = farmer.make_tree(12)
    b_a, _ = synth_batch(farmer.scenario_creator, tree,
                         farmer.scenario_synth_spec, seed=7,
                         materialize_values=True)
    b_b, _ = synth_batch(farmer.scenario_creator, tree,
                         farmer.scenario_synth_spec, seed=99,
                         materialize_values=True)
    opts = dict(FARMER_OPTS, scenario_source="streamed")
    ref_b = PH(b_b, options=dict(opts)).ph_main()
    ph = PH(b_a, options=dict(opts))
    ph.ph_main(finalize=False)
    install_batch(ph, b_b)
    got = ph.ph_main()
    assert got == ref_b
    ph.close_stream()


# ---------------- incumbent surface ----------------

def test_fixed_mode_consensus_eval_works_and_pools_guard(mem_obs):
    """fix_nonants + solve_loop(fixed=True) rides the same streamed
    chunk loop (the serve consensus path); the full-width incumbent
    pool entry points refuse loudly."""
    b_res, _, _ = farmer_pair()
    ph0 = PH(b_res, options=dict(FARMER_OPTS))
    ph0.ph_main()
    ph = PH(b_res, options=dict(FARMER_OPTS,
                                scenario_source="streamed"))
    ph.ph_main()
    xhat = np.asarray(ph.xbar)[0]
    got = ph.calculate_incumbent(xhat)
    assert got == pytest.approx(ph0.calculate_incumbent(xhat),
                                rel=1e-9)
    with pytest.raises(RuntimeError, match="full-width"):
        ph.evaluate_incumbent_pool(jnp.zeros((2, b_res.K)))
    with pytest.raises(RuntimeError, match="full-width"):
        ph.dive_nonant_candidates()
    ph.close_stream()


# ---------------- the scale demonstration ----------------

# S: two chunks of 8,192 in the per-push tier; the S = 100k of ISSUE 15's
# acceptance criterion (minutes of a CPU pass) in the slow one
@pytest.mark.parametrize(
    "S", [16_384, pytest.param(100_000, marks=pytest.mark.slow)])
def test_demo_wheel_100k_synthesized_flat_transfer(mem_obs, S):
    """THE ISSUE 15 acceptance demonstration: an S=100k farmer-family
    wheel (synthesized source) completes on the CPU tier with
    steady-state ``xfer.device_put_bytes`` flat (zero) across
    iterations — and engine construction never materializes an
    (S, m)-shaped host array (the batch vectors are zero-stride
    broadcast views). The same assertions hold at every S that is
    more than one chunk."""
    tree = farmer.make_tree(S)
    b, spec = synth_batch(farmer.scenario_creator, tree,
                          farmer.scenario_synth_spec, seed=11,
                          materialize_values=False)
    assert b.S == S > 8192 and b.l.strides[0] == 0
    ph = PH(b, options=dict(defaultPHrho=1.0, PHIterLimit=2,
                            convthresh=0.0, subproblem_chunk=8192,
                            subproblem_max_iter=150,
                            subproblem_eps=1e-6,
                            subproblem_hospital=False,
                            scenario_source="synthesized",
                            synth_spec=spec))
    ph.ph_main(finalize=False)
    before = obs.counter_value("xfer.device_put_bytes")
    ph.solve_loop(w_on=True, prox_on=True)
    assert obs.counter_value("xfer.device_put_bytes") == before
    assert obs.counter_value("stream.synth_chunks") > 0
    assert np.isfinite(ph.conv)
    ph.close_stream()
