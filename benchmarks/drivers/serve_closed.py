"""Driver ``serve_closed``: closed-loop clients against the serving
layer, started in-process the way ``serve_main`` (and
``chip_smoke.serve_leg``) start it: ``ServeService`` + ``ServeHTTPServer``
on an ephemeral loopback port.

Traffic parameters (``traffic/<mix>.json`` -> ``parameters``):
  clients           callers that each wait for their reply before the
                    next request (a rolling re-solve)
  patch_pool_seed, patch_pool_size
                    the instances: a fixed pool of patches, the same in
                    every run; ``--seed`` only shuffles who sends which
                    when (a wheel's seconds depend on its instances:
                    5.2-8.1 s by content, my chip run, PR 25, so seeds
                    that drew their own patches ran different work)
  poll_seconds      completion is looked for this often
  reference_sample  finished requests checked against the reference
  trace_seconds     seconds of the window the profiler records ...
  trace_offset_seconds
                    ... starting this long after the window opened
The request, the service settings and the patch come from the
configuration file (``request``, ``serve``, ``patch``).

Every request is the configuration's request plus a data-only patch
from the pool (the patched block scaled by U[1-r, 1+r] per entry),
``batchable`` true; client i's k-th request is entry
``order[(i + k * clients) % size]`` of the pool, ``order`` a
permutation drawn from ``--seed``. Set-up starts the service and warms every
engine the window can meet: the batcher stacks 1..batch_max same-bucket
requests into one wheel and every stack size is its own compiled shape,
so set-up sends k requests at once for k = 1..batch_max.

Latency is the client's: from just before the POST until the finished
record has been fetched with ``GET /result/<id>``. Completion is
noticed by asking the in-process service for the record's status every
``poll_seconds`` - a dictionary look-up, so that hundreds of polls a
second do not load the HTTP server they measure - and the record itself
then comes over HTTP.
"""

import json
import shutil
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

_TERMINAL = ("done", "failed")


def _http(url, obj=None, timeout=60):
    req = urllib.request.Request(
        url, data=None if obj is None else json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


class Client:
    """POST, wait, fetch: one request at a time."""

    def __init__(self, service, base, poll):
        self.service, self.base, self.poll = service, base, poll

    def solve(self, payload, timeout=900.0):
        """(record, latency seconds); record None if refused or lost."""
        t0 = time.perf_counter()
        try:
            rid = _http(f"{self.base}/solve", payload)["request_id"]
        except (urllib.error.URLError, KeyError, OSError):
            return None, time.perf_counter() - t0
        while time.perf_counter() - t0 < timeout:
            rec = self.service.result(rid)
            if rec is not None and rec["status"] in _TERMINAL:
                rec = _http(f"{self.base}/result/{rid}")
                return rec, time.perf_counter() - t0
            time.sleep(self.poll)
        return None, time.perf_counter() - t0


def patch_pool(cfg, params):
    """The run's instances, the same for every seed."""
    pt = cfg["patch"]
    r = float(pt["relative_range"])
    rng = np.random.default_rng(int(params["patch_pool_seed"]))
    return [{**cfg["request"], "batchable": True,
             "patch": {pt["field"]: {pt["block"]: [
                 float(b * rng.uniform(1.0 - r, 1.0 + r))
                 for b in pt["base"]]}}}
            for _ in range(int(params["patch_pool_size"]))]


def _ok(rec):
    res = (rec or {}).get("result") or {}
    return bool(rec) and rec["status"] == "done" \
        and res.get("feasible") is True and res.get("objective") is not None


def warm_up(client, pool, batch_max):
    """Send k requests at once for k = 1..batch_max until a wheel of
    every stack size has run (each is its own engine and compiled
    shape)."""
    seen, attempts = set(), 0
    while len(seen) < batch_max:
        k = min(set(range(1, batch_max + 1)) - seen)
        attempts += 1
        if attempts > 4 * batch_max:
            raise RuntimeError(f"warm-up never produced stacks "
                               f"{sorted(set(range(1, batch_max + 1)) - seen)}")
        recs = [None] * k
        ths = [threading.Thread(
            target=lambda j=j: recs.__setitem__(
                j, client.solve(pool[j % len(pool)])[0]))
            for j in range(k)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for rec in recs:
            if not _ok(rec):
                raise RuntimeError(f"warm-up request failed: {rec}")
            seen.add(int(rec["result"]["wheel"]["stack"]))
    return attempts


def run(run):
    import farmer_ef

    from mpisppy_tpu.serve.http import ServeHTTPServer
    from mpisppy_tpu.serve.manager import ServeService
    from mpisppy_tpu.utils.config import ServeConfig

    cfg, p, lim = run.config, run.params, run.limits
    n_clients = int(p["clients"])
    state = tempfile.mkdtemp(prefix="bench_serve_")
    service = ServeService(ServeConfig(state_dir=state,
                                       **cfg["serve"]).validate())
    service.start()
    server = ServeHTTPServer(service, 0).start()
    try:
        client = Client(service, f"http://127.0.0.1:{server.port}",
                        float(p["poll_seconds"]))
        t = time.perf_counter()
        pool = patch_pool(cfg, p)
        order = np.random.default_rng(run.seed).permutation(len(pool))
        attempts = warm_up(client, pool, int(cfg["serve"]["batch_max"]))
        run.span("warm_up", t)
        print(f"warm-up: stacks 1..{cfg['serve']['batch_max']} in "
              f"{attempts} rounds, {run.spans['warm_up']:.1f} s",
              flush=True)

        # ---- the window ----
        done, stop = [], threading.Event()

        def loop(i):
            k = 0
            while not stop.is_set():
                payload = pool[order[(i + k * n_clients) % len(pool)]]
                k += 1
                rec, lat = client.solve(payload)
                done.append({"payload": payload, "rec": rec,
                             "latency": lat})

        threads = [threading.Thread(target=loop, args=(i,),
                                    name=f"bench-client-{i}")
                   for i in range(n_clients)]
        t_open = run.open_window()
        for th in threads:
            th.start()
        if run.trace:
            # a slice from inside a wheel: the window's first instants
            # are request handling, with nothing on the device yet
            time.sleep(min(float(p["trace_offset_seconds"]),
                           run.seconds / 2))
            run.trace_start()
            with run.annotate("bench.traced"):
                time.sleep(min(float(p["trace_seconds"]), run.seconds))
            run.trace_stop()
        time.sleep(max(0.0, run.seconds - (time.perf_counter() - t_open)))
        stop.set()
        for th in threads:
            th.join()
        t_close = run.close_window()
        elapsed = t_close - t_open

        # ---- correct ----
        good = [d for d in done if _ok(d["rec"])]
        failed = len(done) - len(good)
        run.check("window_failed_requests", failed, 0, how="==")
        stamps = [d["rec"]["result"]["wheel"] for d in good]
        run.check("window_cache_misses",
                  sum(1 for s in stamps if s["cache_hit"] is not True), 0,
                  how="==")
        rng = np.random.default_rng([run.seed, 10 ** 6 + 1])
        k = min(int(p["reference_sample"]), len(good))
        sample = [good[i] for i in rng.choice(len(good), size=k,
                                              replace=False)]
        over, solo_stack, solo_diff, outer_over = [], [], [], []
        t = time.perf_counter()
        for d in sample:
            costs = next(iter(next(iter(
                d["payload"]["patch"].values())).values()))
            ef = farmer_ef.ef_optimum(np.asarray(costs))
            obj = float(d["rec"]["result"]["objective"])
            over.append((obj - ef) / abs(ef))
            solo, _lat = client.solve({**d["payload"], "batchable": False})
            if not _ok(solo):
                solo_diff.append(float("inf"))
                continue
            sres = solo["result"]
            solo_diff.append(abs(obj - float(sres["objective"]))
                             / abs(float(sres["objective"])))
            outer_over.append((float(sres["wheel"]["outer_bound"]) - ef)
                              / abs(ef))
            solo_stack.append(int(sres["wheel"]["stack"]))
        print(f"reference: {k} requests against the HiGHS extensive form "
              f"and their solo re-send in {time.perf_counter() - t:.1f} s",
              flush=True)
        # the incumbent is feasible: never better than the optimum ...
        run.check("objective_below_ef", -min(over, default=0.0),
                  lim["objective_below_ef"])
        # ... and ten PH iterations leave it this close above
        run.check("objective_above_ef", max(over, default=float("inf")),
                  lim["objective_above_ef"])
        run.check("outer_bound_above_ef",
                  max(outer_over, default=float("inf")),
                  lim["outer_bound_above_ef"])
        run.check("solo_vs_stacked_objective",
                  max(solo_diff, default=float("inf")),
                  lim["solo_vs_stacked_objective"])
        run.check("solo_resend_stack", max(solo_stack, default=0), 1,
                  how="==")
    finally:
        server.stop()
        service.stop(join_timeout=60.0)
        shutil.rmtree(state, ignore_errors=True)

    lats = sorted(d["latency"] for d in good)
    wheels = {}
    for d in good:
        w = d["rec"]["result"]["wheel"]
        wheels[d["rec"].get("group") or d["rec"]["id"]] = {
            "seconds": float(w["seconds"]), "stack": int(w["stack"])}
    p95 = float(np.quantile(lats, 0.95)) if lats else float("nan")
    print(f"window: {len(good)} of {len(done)} requests done in "
          f"{elapsed:.2f} s from {n_clients} clients; latency median "
          f"{float(np.median(lats)):.3f} s, p95 {p95:.3f} s over "
          f"{len(lats)} samples; {len(wheels)} wheels, stacks "
          f"{sorted({w['stack'] for w in wheels.values()})}", flush=True)
    return {"attempted": len(done), "failed": failed,
            "end_to_end": {"req_per_s": len(good) / elapsed},
            "observations": {
                "spans": dict(run.spans),
                "wheels": list(wheels.values()),
                "requests": [
                    {"latency": d["latency"],
                     "wheel_seconds":
                         float(d["rec"]["result"]["wheel"]["seconds"])}
                    for d in good]}}
