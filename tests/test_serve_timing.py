"""The serving layer's own clock (mpisppy_tpu/serve/timing, ISSUE 50):
the wheel and request records, the stamp's ``steps``, the record's
``timeline``, ``GET /status`` -> ``timing`` and the spans around them,
over a toy farmer service with NO telemetry session.

One service (and its HTTP plane) per module: ``traffic`` sends it a cold
solo wheel, two warm data-only re-solves of the same shape and a stacked
pair, all over HTTP, and the tests read what that left behind.
"""

from __future__ import annotations

import json
import re
import sys
import threading
import time
import urllib.request

import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.serve import timing as stiming
from mpisppy_tpu.serve.timing import RING, STEPS, ServeTiming
from mpisppy_tpu.utils.config import ServeConfig

FARMER = {"model": "farmer", "num_scens": 3,
          "algo": {"max_iterations": 4}}
WHEEL_STEPS = STEPS[1:]          # ``serve.stack`` lies before the wheel
MARKS = ("t_pop0", "t_first", "t_group", "t_wheel0", "t_wheel1", "t_done")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _patch(k):
    return {"c": {"DevotedAcreage": [150.0 + k, 230.0, 260.0 - k]}}


def _http(url, obj=None):
    req = urllib.request.Request(
        url, data=None if obj is None else json.dumps(obj).encode())
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


def _solve(base, payload, timeout=180.0):
    """POST, poll, fetch: the terminal record as the client got it."""
    rid = _http(f"{base}/solve", payload)["request_id"]
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        rec = _http(f"{base}/result/{rid}")
        if rec["status"] in ("done", "failed"):
            return rec
        time.sleep(0.02)
    raise TimeoutError(rid)


def _solve_together(base, payloads):
    out = [None] * len(payloads)
    ths = [threading.Thread(target=lambda j=j: out.__setitem__(
        j, _solve(base, payloads[j]))) for j in range(len(payloads))]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=240)
    assert all(o is not None for o in out)
    return out


def _service(tmp, **over):
    from mpisppy_tpu.serve.manager import ServeService
    kw = dict(state_dir=str(tmp), batch_window=0.2, batch_max=4,
              checkpoint_interval=5.0)
    kw.update(over)
    return ServeService(ServeConfig(**kw).validate())


@pytest.fixture(scope="module")
def traffic(tmp_path_factory):
    """``{"svc", "base", "cold", "warm", "warm2", "pair",
    "warm_compiles", "status"}`` of one toy service, stopped."""
    from jax import monitoring

    from mpisppy_tpu.serve.http import ServeHTTPServer
    assert not obs.enabled()
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == _COMPILE_EVENT else None)
    svc = _service(tmp_path_factory.mktemp("serve_timing")).start()
    srv = ServeHTTPServer(svc, 0).start()
    base = f"http://127.0.0.1:{srv.port}"
    out = {"svc": svc, "base": base}
    try:
        solo = {**FARMER, "batchable": False}
        out["cold"] = _solve(base, solo)
        n0 = len(compiles)
        out["warm"] = _solve(base, {**solo, "patch": _patch(1)})
        out["warm2"] = _solve(base, {**solo, "patch": _patch(2)})
        out["warm_compiles"] = len(compiles) - n0
        (ent,) = svc.cache._entries.values()    # the solo engine, idle
        out["engine"] = ent.engine
        out["pair"] = _solve_together(
            base, [{**FARMER, "patch": _patch(3)},
                   {**FARMER, "patch": _patch(4)}])
        out["status"] = _http(f"{base}/status")
    finally:
        srv.stop()
        svc.stop()
    assert not obs.enabled()
    return out


def _wheel_of(traffic, rec):
    """The wheel record that answered ``rec`` (by the stamp's float)."""
    sec = rec["result"]["wheel"]["seconds"]
    found = [w for w in traffic["svc"].timing.snapshot()["wheels"]
             if w["seconds"] == sec]
    assert len(found) == 1, (sec, found)
    return found[0]


def test_wheel_marks_are_monotone(traffic):
    wheels = traffic["svc"].timing.snapshot()["wheels"]
    assert [w["seq"] for w in wheels] == [1, 2, 3, 4]
    assert [w["stack"] for w in wheels] == [1, 1, 1, 2]
    for w in wheels:
        marks = [w[k] for k in MARKS]
        assert all(m is not None for m in marks), w
        assert marks == sorted(marks), w
        assert w["worker"] == "serve-wheel0"


def _untimed(residuals, tight):
    """``residuals``: seconds between marks that only a few untimed
    lines separate. Those take microseconds (the smallest is under
    ``tight``), but on a test machine with every core taken a thread
    can lose the CPU for milliseconds between any two lines, so each
    one is only held to a tenth of a second; none is negative (a part
    is never longer than the whole it lies in)."""
    assert all(-1e-9 <= r < 0.1 for r in residuals), residuals
    assert min(residuals) < tight, residuals


def test_steps_sum_to_the_stamp_and_the_record_is_the_stamp(traffic):
    rest = []
    for rec in (traffic["cold"], traffic["warm"], *traffic["pair"]):
        stamp = rec["result"]["wheel"]
        assert set(stamp["steps"]) == set(WHEEL_STEPS)
        w = _wheel_of(traffic, rec)      # ``==`` after the JSON round trip
        assert w["seconds"] == w["t_wheel1"] - w["t_wheel0"]
        assert {k: w["steps"][k] for k in WHEEL_STEPS} == stamp["steps"]
        rest.append(stamp["seconds"] - sum(stamp["steps"].values()))
        # ``serve.stack`` lies before the wheel, inside the preparation
        assert 0 < w["steps"]["stack"] < w["t_wheel0"] - w["t_group"]
    _untimed(rest, 5e-3)
    assert len({r["result"]["wheel"]["seconds"]
                for r in traffic["pair"]}) == 1


def test_between_wheels_is_finish_idle_hold_prepare(traffic):
    wheels = traffic["svc"].timing.snapshot()["wheels"]
    parts = stiming.cycle_parts(wheels)
    assert parts[-1]["between_s"] is None       # the worker's last wheel
    rest = []
    for w, p, nxt, pn in zip(wheels, parts, wheels[1:], parts[1:]):
        assert p["between_s"] == nxt["t_wheel0"] - w["t_wheel1"]
        rest.append(p["between_s"] - (
            p["finish_s"] + pn["queue_idle_s"] + pn["batch_hold_s"]
            + pn["prepare_s"]))
    _untimed(rest, 5e-3)
    # the stacked pair's group was held open for stragglers (2 < 4):
    # the batch window ran out; a solo request's group closes at once
    assert 0.15 < parts[3]["batch_hold_s"] < 0.5
    assert parts[1]["batch_hold_s"] < 0.05


def test_ph_of_a_wheel_is_its_own_not_a_running_total(traffic):
    cold, warm, warm2 = (_wheel_of(traffic, traffic[k])
                         for k in ("cold", "warm", "warm2"))
    assert warm["cache_hit"] and warm2["cache_hit"]     # one leased engine
    # iter-0, four hot iterations, the results' evaluation
    assert cold["ph"]["calls"] == warm["ph"]["calls"] \
        == warm2["ph"]["calls"] == 6
    for w in (cold, warm, warm2):
        assert 0 < w["ph"]["admm_iters"] < 200_000
        # booked inside the wheel's main and results steps
        assert 0 < w["ph"]["solve"] < w["steps"]["main"] \
            + w["steps"]["results"]
    assert warm2["ph"]["admm_iters"] < 2 * warm["ph"]["admm_iters"]
    tot = traffic["svc"].timing.snapshot()["totals"]
    assert tot["ph"]["calls"] == 6 * 4 and tot["wheels"] == 4


def test_phase_booked_is_the_raw_totals_of_phase_timing(traffic):
    """The solo engine after its three wheels: ``phase_booked`` holds
    the exact accumulators that ``phase_timing`` turns into means, and
    zeros for a mode that never ran."""
    from mpisppy_tpu.serve.manager import ph_booked
    engine = traffic["engine"]
    booked = ph_booked(engine)
    assert booked["calls"] == 6 * 3
    solo = [_wheel_of(traffic, traffic[k])
            for k in ("cold", "warm", "warm2")]
    for k in ("calls", "admm_iters", "refactors", "capped"):
        assert booked[k] == sum(w["ph"][k] for w in solo), k
    assert booked["solve"] == pytest.approx(
        sum(w["ph"]["solve"] for w in solo), abs=1e-9)
    hot = engine.phase_booked(True)
    pt = engine.phase_timing(True)
    assert hot["calls"] == pt["calls"] == 4 * 3
    assert hot["solve"] / hot["calls"] == pt["seconds_per_call"]["solve"]
    admm = pt["admm_iters_per_call"]
    assert hot["admm_iters"] == pytest.approx(
        (admm["bulk"] + admm["tail"]) * pt["calls"])
    assert hot["capped"] == pt["exits"]["tail_capped"]
    assert engine.phase_timing("never") is None
    assert set(engine.phase_booked("never").values()) == {0}


def test_request_timeline_sums_to_its_life(traffic):
    reqs = {r["id"]: r for r in
            traffic["svc"].timing.snapshot()["requests"]}
    assert len(reqs) == 5
    rest = []
    for rec in (traffic["cold"], traffic["warm2"], *traffic["pair"]):
        tl = rec["timeline"]
        assert set(tl) == {"queue_s", "hold_s", "wheel_s", "finish_s"}
        assert all(v >= 0 for v in tl.values()), tl
        assert tl["wheel_s"] == rec["result"]["wheel"]["seconds"]
        life = rec["finished_unix"] - rec["submitted_unix"]
        # two clocks, each read a few lines from the other's
        rest.append(abs(sum(tl.values()) - life))
        m = reqs[rec["id"]]
        order = [m[k] for k in ("t_submit", "t_pop", "t_wheel0",
                                "t_wheel1", "t_finish")]
        assert order == sorted(order), m
        assert m["wheel_seq"] == _wheel_of(traffic, rec)["seq"]
    _untimed(rest, 10e-3)
    # a member of the pair waited for the batch window, in ``hold_s``
    assert max(r["timeline"]["hold_s"] for r in traffic["pair"]) > 0.15


def test_status_carries_timing_and_latest_outlives_stop(traffic):
    t = traffic["status"]["timing"]
    assert t["totals"]["wheels"] == 4 and t["totals"]["requests"] == 5
    assert t["last_wheels"] == 4
    med = t["median"]
    assert set(med) >= {f"{k}_s" for k in STEPS} | {
        "wheel_s", "between_s", "finish_s", "queue_idle_s",
        "batch_hold_s", "prepare_s", "req_queue_s", "ph_solve_s",
        "ph_admm_iters"}
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in med.values()), med
    assert traffic["svc"]._stop
    assert stiming.latest() is traffic["svc"].timing
    assert stiming.latest().snapshot()["totals"]["wheels"] == 4


def test_a_data_only_patch_compiles_nothing(traffic):
    for k in ("warm", "warm2"):
        stamp = traffic[k]["result"]["wheel"]
        assert stamp["cache_hit"] is True
        assert stamp["xla_compiles_delta"] == 0
    # no session counts compiles here: jax's own events say the same
    assert traffic["warm_compiles"] == 0


def test_rings_stay_at_their_bound_under_concurrent_writers():
    """5,000 synthetic wheels and requests from four threads while a
    fifth takes snapshots: the rings keep their bound, the totals count
    everything, and no snapshot holds a half-written record."""
    t = ServeTiming()
    n_threads, per = 4, 1250
    torn, stop = [], threading.Event()

    def write():
        for _ in range(per):
            rec = t.open_wheel({"t_pop0": 1.0, "t_first": 2.0,
                                "t_group": 3.0})
            rec["t_wheel1"] = time.perf_counter()
            rec.update(stack=1, cache_hit=True,
                       seconds=rec["t_wheel1"] - rec["t_wheel0"],
                       ph={"calls": 1, "admm_iters": 10})
            marks = stiming.new_request_marks(f"r{rec['seq']}")
            marks.update(t_submit=0.5, wheel_seq=rec["seq"])
            t.close_request(marks)
            t.close_wheel(rec)

    def read():
        while not stop.is_set():
            snap = t.snapshot()
            torn.extend(w for w in snap["wheels"]
                        if w["t_done"] is None or w["seconds"] is None)
            torn.extend(r for r in snap["requests"]
                        if r["t_finish"] is None)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ths = [threading.Thread(target=write) for _ in range(n_threads)]
        rd = threading.Thread(target=read)
        rd.start()
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=120)
        stop.set()
        rd.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in ths) and not rd.is_alive()
    assert not torn
    snap = t.snapshot()
    assert len(snap["wheels"]) == len(snap["requests"]) == RING
    assert snap["totals"]["wheels"] == snap["totals"]["requests"] \
        == n_threads * per
    assert snap["totals"]["ph"]["admm_iters"] == 10 * n_threads * per
    assert sorted(w["seq"] for w in snap["wheels"])[-1] == n_threads * per
    assert len({w["seq"] for w in snap["wheels"]}) == RING
    assert stiming.latest() is not t        # never started: not "latest"


def test_two_workers_interleave_whole_records(tmp_path):
    svc = _service(tmp_path, max_wheels=2, batch_max=1).start()
    try:
        reqs = [svc.submit({**FARMER, "patch": _patch(k)})
                for k in range(6)]
        t0 = time.monotonic()
        while time.monotonic() - t0 < 240:
            recs = [svc.result(r.id) for r in reqs]
            if all(r["status"] in ("done", "failed") for r in recs):
                break
            time.sleep(0.05)
        assert [r["status"] for r in recs] == ["done"] * 6
    finally:
        svc.stop()
    wheels = svc.timing.snapshot()["wheels"]
    assert sorted(w["seq"] for w in wheels) == [1, 2, 3, 4, 5, 6]
    assert {w["worker"] for w in wheels} == {"serve-wheel0",
                                             "serve-wheel1"}
    for w in wheels:                         # no torn record
        marks = [w[k] for k in MARKS]
        assert marks == sorted(marks), w
        assert w["seconds"] == w["t_wheel1"] - w["t_wheel0"]
        assert all(w["steps"][k] is not None for k in STEPS), w
        assert w["ph"]["calls"] == 6, w
    stamps = {r["result"]["wheel"]["seconds"] for r in recs}
    assert stamps == {w["seconds"] for w in wheels}
    # one worker's wheels follow one another; the two workers' overlap
    by_worker = {}
    for w in sorted(wheels, key=lambda w: w["seq"]):
        by_worker.setdefault(w["worker"], []).append(w)
    for mine in by_worker.values():
        for a, b in zip(mine, mine[1:]):
            assert a["t_done"] <= b["t_pop0"]
    a, b = by_worker.values()
    assert any(x["t_wheel0"] < y["t_wheel1"] and y["t_wheel0"]
               < x["t_wheel1"] for x in a for y in b)
    parts = stiming.cycle_parts(wheels)
    assert sum(p["between_s"] is None for p in parts) == 2


def test_a_capture_with_no_session_holds_the_dark_spans(tmp_path,
                                                        profiler_capture):
    """What the worker and the handlers do between two wheels is in a
    ``jax.profiler`` capture by name, with no telemetry session."""
    from mpisppy_tpu.serve.http import ServeHTTPServer
    assert not obs.enabled()
    svc = _service(tmp_path).start()
    srv = ServeHTTPServer(svc, 0).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        _solve(base, FARMER)         # warm: the capture holds no compile
        with profiler_capture as cap:
            # a wait that began before the capture is not in it: the
            # idle span seen is the one after this first wheel
            _solve(base, {**FARMER, "patch": _patch(7)})
            time.sleep(0.05)         # the worker waits on an empty queue
            recs = _solve_together(
                base, [{**FARMER, "patch": _patch(k)} for k in (5, 6)])
        assert [r["status"] for r in recs] == ["done"] * 2
    finally:
        srv.stop()
        svc.stop()
    assert not obs.enabled()
    names = {e[0] for e in cap.spans(("serve.",))}
    assert {"serve.queue.idle", "serve.batch.window",
            "serve.group.prepare", "serve.stack", "serve.wheel",
            "serve.finish", "serve.http.solve",
            "serve.http.result"} <= names, names
    assert all(re.match(r"^[a-z_]+\.[\w.\-]+$", n) for n in names)
    assert cap.inside("serve.stack", "serve.group.prepare")
    for name in ("serve.http.solve", "serve.http.result"):
        assert sum(e[0] == name for e in cap.events) >= 3, name
    # one span per wait on the condition: the batch window of 0.2 s
    # that the two groups (the lone request, the pair) were each held
    # for is a few of them (a push wakes the waiter), in sum the two
    # windows
    waits = [e for e in cap.events if e[0] == "serve.batch.window"]
    assert 2 <= len(waits) <= 6
    assert 0.3e9 < sum(e[3] - e[2] for e in waits) < 0.8e9
