"""A streamed wheel under the engine's other subsystems (ISSUEs 15 and
17): checkpoint resume, the hospital's rescue of a flagged row, and the
shrink x stream composition (compacted + streamed bit-equal to
compacted + resident; compile count == bucket transitions).

The streaming engine itself, and the batches and options these tests
share with it: tests/test_stream.py.
"""

import jax.numpy as jnp
import numpy as np

from mpisppy_tpu import obs
from mpisppy_tpu.core.ph import PH, PHBase
from mpisppy_tpu.cylinders.hub import Hub
from mpisppy_tpu.ir.batch import build_batch
from mpisppy_tpu.models import uc

from test_stream import FARMER_OPTS, UC_KW, farmer_pair, uc_vp_batch
from test_stream import mem_obs  # noqa: F401  (fixture by name)


# ---------------- checkpoint resume ----------------

def test_ckpt_resume_of_streamed_wheel(tmp_path, mem_obs):
    """A streamed wheel's bundle carries only the resident hub state —
    capture at iter k, resume a FRESH streamed engine, and the resumed
    trajectory matches the uninterrupted one exactly."""
    from mpisppy_tpu.ckpt.manager import resume_hub
    d = str(tmp_path)
    b_res, _, _ = farmer_pair()
    opts = dict(FARMER_OPTS, scenario_source="streamed")
    # uninterrupted reference: 5 + 3 iterations
    ph_ref = PH(b_res, options=dict(opts, PHIterLimit=8))
    ph_ref.ph_main()
    ph_ref.close_stream()
    # interrupted twin: 5 iterations, capture, resume, 3 more
    ph1 = PH(b_res, options=dict(opts, PHIterLimit=5))
    ph1.ph_main(finalize=False)
    hub1 = Hub(ph1, spokes=[], options={"checkpoint_dir": d,
                                        "checkpoint_fingerprint": "fp"})
    assert hub1.ckpt.capture("test")
    ph1.close_stream()
    ph2 = PH(b_res, options=dict(opts, PHIterLimit=3))
    hub2 = Hub(ph2, spokes=[])
    assert resume_hub(hub2, d, fingerprint="fp") is not None
    assert ph2._iter == ph1._iter
    # run the resumed engine standalone (the Hub above only hosted the
    # resume installation; its wheel loop is not under test)
    ph2.spcomm = None
    ph2.ph_main()
    # solver tolerance, not bit equality: the resumed engine rebuilds
    # COLD solver states (the bundle carries hub state only) — the
    # same band the ckpt suite's resume-determinism tests use
    np.testing.assert_allclose(np.asarray(ph2.xbar),
                               np.asarray(ph_ref.xbar), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ph2.W),
                               np.asarray(ph_ref.W), atol=1e-4)
    ph2.close_stream()


# ---------------- hospital under streaming ----------------

def test_hospital_rescues_flagged_row_under_streaming(mem_obs):
    """The hospital's per-scenario rescue stages exactly the flagged
    rows from the source (host gather / in-kernel synthesis) — the
    recovery surface survives streaming."""
    b = uc_vp_batch(S=8)
    opts = {"defaultPHrho": 50.0, "subproblem_max_iter": 1500,
            "subproblem_eps": 1e-6, "subproblem_chunk": 3,
            "subproblem_hospital_max": 4,
            "scenario_source": "streamed"}
    ph = PHBase(b, opts, dtype=jnp.float64)
    ph.solve_loop(w_on=False, prox_on=False)
    ph.W = ph.W_new
    ph.solve_loop(w_on=True, prox_on=True)
    factors, data = ph._get_factors(True)
    slices = ph._chunk_index(3)
    states = ph._qp_states[("chunks", True)]
    n, m = b.n, b.m
    recs = []
    for ci, (idx_c, real) in enumerate(slices):
        st = states[ci]
        if ci == 1:
            st = st._replace(pri_rel=st.pri_rel.at[0].set(1.0))
        recs.append([st, jnp.zeros((3, n)), jnp.zeros((3, m)),
                     jnp.zeros((3, n)), None, None])
    kw = dict(prox_on=True, precision=ph.sub_precision,
              sub_max_iter=ph.sub_max_iter, sub_eps=ph.sub_eps,
              sub_eps_hot=ph.sub_eps_hot,
              sub_eps_dua_hot=ph.sub_eps_dua_hot,
              tail_iter=ph.sub_tail_iter, stall_rel=ph.sub_stall_rel,
              segment=ph.sub_segment, polish_hot=ph.sub_polish_hot,
              polish_chunk=0, segment_lo=ph.sub_segment_lo)
    ph._hospitalize(True, slices, recs, data, thr=1e-2, w_on=True,
                    prox_on=True, kw=kw, stream=ph._stream_source)
    assert float(recs[1][0].pri_rel[0]) < 1e-2
    assert float(jnp.abs(recs[1][1][0]).max()) > 0.0
    assert obs.counter_value("stream.direct_fetches") > 0
    ph.close_stream()


# ---------------- shrink x stream composition (ISSUE 17) ----------------

def uc_int_batch(S=6):
    """Integer UC through the vector patch: shared-structure (so it
    streams) AND carries binaries (so the device fixer fixes and
    compaction engages) — the one family both subsystems accept."""
    return build_batch(uc.scenario_creator, uc.make_tree(S),
                       creator_kwargs=dict(UC_KW,
                                           relax_integrality=False),
                       vector_patch=uc.scenario_vector_patch)


SHRINK_STREAM_OPTS = {
    "defaultPHrho": 50.0, "PHIterLimit": 10, "convthresh": 0.0,
    "subproblem_chunk": 2, "subproblem_max_iter": 4000,
    "subproblem_eps": 1e-6, "iter0_infeasibility_abort": False,
    "shrink_fix": True, "shrink_compact": True, "shrink_buckets": "0.1",
    "id_fix_list_fct": lambda b: _uniform_fix_list(b, tol=1e-2, nb=3,
                                                   lb=3, ub=3)}


def _uniform_fix_list(b, **kw):
    from mpisppy_tpu.extensions.fixer import uniform_fix_list
    return uniform_fix_list(b, **kw)


def test_streamed_compacted_bit_equal_resident_compacted(tmp_path):
    """ISSUE 17 acceptance: a compacted+streamed wheel runs end to end
    bit-identical to compacted+resident on one device (the host store
    re-blocks at the compacted width; the transition pays ONE
    out-of-band full restage booked on its own counter), and the
    per-iteration ``stream.bytes_shipped`` is STRICTLY lower after the
    first compaction than before it — UC's varying ``ub`` block stages
    at the compacted column width."""
    import json

    ph0 = PH(uc_int_batch(), options=dict(SHRINK_STREAM_OPTS))
    r0 = ph0.ph_main()
    assert ph0._shrink_status["compactions"] == 1
    obs.configure(out_dir=str(tmp_path))
    try:
        ph1 = PH(uc_int_batch(), options=dict(SHRINK_STREAM_OPTS,
                                              scenario_source="streamed"))
        r1 = ph1.ph_main()
    finally:
        obs.shutdown()
    assert ph1._shrink_status["compactions"] == 1
    assert ph1._shrink_status["n_cols"] \
        == ph0._shrink_status["n_cols"] < ph1.batch.n
    assert r1 == r0
    np.testing.assert_array_equal(np.asarray(ph1.xbar),
                                  np.asarray(ph0.xbar))
    np.testing.assert_array_equal(np.asarray(ph1.W), np.asarray(ph0.W))
    ss = ph1._stream_source._status
    assert ss["compacted_transitions"] == 1
    assert ss["compacted_restage_bytes"] > 0
    # the per-iteration wire: strictly fewer bytes per pass once the
    # chunks stage compacted blocks. The transition iteration itself
    # mixes widths (last full pass + the out-of-band restage) —
    # compare the clean steady states on either side of it.
    events = [json.loads(ln) for ln in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    iters = [e for e in events if e.get("type") == "ph.iteration"]
    deltas = [e.get("counter_deltas", {}) for e in iters]
    tr = [i for i, d in enumerate(deltas)
          if d.get("stream.compacted_transitions", 0)]
    assert len(tr) == 1, f"expected one transition iteration: {tr}"
    shipped = [d.get("stream.bytes_shipped", 0) for d in deltas]
    before = [s for s in shipped[:tr[0]] if s > 0]
    after = [s for s in shipped[tr[0] + 1:] if s > 0]
    assert before and after
    assert max(after) < min(before), \
        f"compacted passes must ship fewer bytes: {before} -> {after}"
    # the one-off restage booked out of band, NOT on bytes_shipped
    assert sum(d.get("stream.compacted_restage_bytes", 0)
               for d in deltas) == ss["compacted_restage_bytes"]
    # analyze reads the same run the same way: one re-block, flat
    # transfers after it, the warm transplant landed
    from mpisppy_tpu.obs.analyze import (load_run, shrink_summary,
                                         streaming_summary)
    run = load_run(str(tmp_path))
    sm, sh = streaming_summary(run), shrink_summary(run)
    assert sh["compactions"] == 1 and sm["compacted_transitions"] == 1
    assert sm["device_put_flat_steady_state"] is not False
    assert sm["compacted_restage_bytes"] == ss["compacted_restage_bytes"]
    assert sh["transplant_cold_fallbacks"] == 0
    ph1.close_stream()
    ph0.close_stream()


def test_streamed_compacted_compile_count_tracks_transitions(tmp_path):
    """ISSUE 17 acceptance: compile count still == bucket transitions
    under streaming — a second same-shape streamed compacted wheel
    hits the shape registry and compiles NOTHING."""
    from mpisppy_tpu.ops import shrink as shrink_ops

    shrink_ops._BUCKET_REGISTRY.clear()
    obs.configure(out_dir=str(tmp_path))
    try:
        ph_a = PH(uc_int_batch(), options=dict(SHRINK_STREAM_OPTS,
                                               scenario_source="streamed"))
        ph_a.ph_main()
        assert ph_a._shrink_status["compactions"] == 1
        ctr = obs.counters_snapshot()
        assert ctr.get("shrink.bucket.compile", 0) == 1
        c0 = ctr.get("jax.compiles", 0)
        ph_a.close_stream()
        ph_b = PH(uc_int_batch(), options=dict(SHRINK_STREAM_OPTS,
                                               scenario_source="streamed"))
        ph_b.ph_main()
        assert ph_b._shrink_status["compactions"] == 1
        ctr2 = obs.counters_snapshot()
        assert ctr2.get("shrink.bucket.cache_hit", 0) >= 1
        assert ctr2.get("jax.compiles", 0) - c0 == 0, \
            "a same-shape streamed wheel's transition must compile " \
            "nothing"
        ph_b.close_stream()
    finally:
        obs.shutdown()
