"""Multi-process cylinders over the native shared-memory windows.

The reference runs each cylinder as its own MPI process group and wires
the hub-spoke star with MPI RMA windows (ref. mpisppy/utils/sputils.py:
133-151 make_comms, cylinders/spcommunicator.py:97-124). Here each spoke
runs as its own OS process with its own engine (and its own Python/GIL,
solver state, and — on a multi-chip host — its own device), talking to
the hub through the native seqlock windows (ops/native/spwindow). The
write-id/kill protocol is byte-identical to the in-process backend, so
hub and spoke code runs unchanged.

Resource split: spoke processes default to the CPU backend
(JAX_PLATFORMS=cpu) so the accelerator stays exclusively the hub's —
bound evaluation rides host cores, the batched PH iteration rides the
chip. On a multi-chip host, per-spoke ``jax_platform`` /
``jax_visible_devices`` options pin each cylinder to its own chip (see
_spoke_worker) — the real deployment shape of the reference's
process grid (one cylinder per rank group, ref. sputils.py:133-151).

The full spoke taxonomy runs as processes, including the
cross-scenario cut spoke (its larger cut-window layout is sized by the
hub-side proxy).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import secrets
import time

from .. import global_toc, obs
from ..cylinders.spcommunicator import Window
from ..cylinders.spoke import ConvergerSpokeType
from .config import RunConfig, config_from_dict
from .runtime import child_jax_env, spawn_environment


def _telemetry_out_dir(cfg):
    """The run directory spoke children should capture into: the
    config's explicit ``telemetry_dir`` wins, then a programmatically
    configured parent session (``obs.configure(out_dir=...)`` with no
    config field — the path the env-var-only propagation silently
    dropped), then the env var the spawn children inherit anyway."""
    d = getattr(cfg, "telemetry_dir", None)
    if d:
        return d
    rec = obs.active()
    if rec is not None and rec.out_dir:
        return rec.out_dir
    return os.environ.get("MPISPPY_TPU_TELEMETRY_DIR") or None


class SpokeProxy:
    """Hub-side stand-in for a spoke living in another process: just the
    classification surface + the shared window pair."""

    def __init__(self, spoke_cls, S, K, hub_window, my_window):
        self._spoke_cls = spoke_cls
        self.converger_spoke_types = spoke_cls.converger_spoke_types
        self.converger_spoke_char = spoke_cls.converger_spoke_char
        self.is_cut_spoke = bool(getattr(spoke_cls, "is_cut_spoke", False))
        self._S, self._K = S, K
        self.hub_window = hub_window
        self.my_window = my_window

    def hub_read_layout(self):
        ts = self.converger_spoke_types
        return (ConvergerSpokeType.W_GETTER in ts,
                ConvergerSpokeType.NONANT_GETTER in ts)

    def remote_window_length(self) -> int:
        has_w, has_x = self.hub_read_layout()
        return self._S * self._K * (int(has_w) + int(has_x))

    def local_window_length(self) -> int:
        # the spoke class owns its payload layout (Spoke.payload_length:
        # 1 for bound spokes, 2 for the dual-typed EF-MIP spoke,
        # S*(1+K) for the cut spoke) — sizing it here too would let the
        # hub-side and child-side windows drift apart. Every spoke→hub
        # window carries the bound-flow lineage suffix
        # (spcommunicator.LINEAGE_SLOTS).
        from ..cylinders.spcommunicator import LINEAGE_SLOTS
        return self._spoke_cls.payload_length(self._S, self._K) \
            + LINEAGE_SLOTS


def _spoke_worker(cfg_dict, spoke_cfg_dict, hub_name, my_name, f32,
                  telemetry=None):
    """Runs in the child process: build the engine from the config, wire
    the shared windows, loop until the hub's kill signal.

    Per-process device assignment (the real multi-chip deployment shape:
    one cylinder per chip, ref. sputils.py:133-151 process-grid): the
    launcher starts this process with ``JAX_PLATFORMS`` (and, when the
    spoke's options pin a chip, the visible-devices variable) already
    in its environment — utils/runtime.child_jax_env /
    spawn_environment — so nothing here touches the platform."""
    opts = spoke_cfg_dict.get("options") or {}
    from .runtime import maybe_init_distributed, setup_jax_runtime

    setup_jax_runtime(f32)
    # a spoke pinned to its own accelerator slice on another host may
    # carry its own coordinator spec (options["coordinator"]) and join
    # a multi-process JAX cluster of its own; the HUB's coordinator
    # (cfg.coordinator) is deliberately NOT inherited here — spoke
    # processes default to isolated single-process runtimes
    maybe_init_distributed(opts.get("coordinator"))

    # telemetry capture for THIS cylinder process: role-suffixed
    # artifacts (events-<role>.jsonl / trace-<role>.json) in the run
    # directory the hub propagated through the bootstrap — spawned
    # children share no recorder with the parent, so without this the
    # spoke's bound events and spans silently vanish. The env-var path
    # still works when no explicit dir was propagated.
    from .. import obs as _obs
    if telemetry and telemetry.get("out_dir"):
        _obs.configure(out_dir=telemetry["out_dir"],
                       role=telemetry.get("role"), config=spoke_cfg_dict)
    elif telemetry:
        _obs.maybe_configure_from_env(role=telemetry.get("role"))

    from .config import SpokeConfig
    from .vanilla import spoke_dict

    cfg = config_from_dict(cfg_dict)
    sd = spoke_dict(cfg, SpokeConfig(**spoke_cfg_dict))
    opt = sd["opt_class"](**sd["opt_kwargs"])
    spoke = sd["spoke_class"](opt, **sd.get("spoke_kwargs", {}))
    spoke.hub_window = Window.shared(hub_name,
                                     spoke.remote_window_length(),
                                     create=False)
    spoke.my_window = Window.shared(my_name, spoke.local_window_length(),
                                    create=False)
    # fault injection (testing/faults.py) is gated on an EXPLICIT plan
    # (spoke option or env var): the import — and every wrapper it
    # installs — exists only in faulted test children, never on the
    # production path (tests/test_faults.py asserts the clean path
    # imports nothing from mpisppy_tpu.testing)
    fault_spec = opts.get("fault_plan") \
        or os.environ.get("MPISPPY_TPU_FAULT_PLAN")
    if fault_spec:
        # lint: ok[PURE001] env/option-gated: reached only in children given an explicit fault plan (clean-path probe backstops)
        from ..testing.faults import FaultInjector
        injector = FaultInjector.from_spec(
            fault_spec,
            index=(telemetry or {}).get("index", 0),
            gen=(telemetry or {}).get("gen", 0))
        injector.sleep_before_hello()
        injector.install(spoke)
    # startup handshake: a NaN hello tells the hub this spoke is wired and
    # looping (the reference's window-size Send/Recv handshake analog,
    # ref. hub.py:285-308). NaN never wins a bound comparison, so the
    # hub consumes it harmlessly.
    import numpy as np
    spoke.my_window.put(np.full(spoke.local_window_length(), np.nan))
    try:
        # warm resume (mpisppy_tpu.ckpt): a spoke handed a
        # ``resume_state`` option re-publishes its checkpointed best
        # bound as its FIRST publish — after the hello (the hub's
        # readiness gate) and before main() recomputes anything, so a
        # respawned incarnation's first bound is never worse than its
        # predecessor's best
        spoke.resume_publish()
        spoke.main()
        spoke.finalize()
    finally:
        # flush + close this process's telemetry BEFORE the windows
        # drop, so a hub-side merge running right after the join sees
        # complete role artifacts (atexit would also flush, but later
        # than the parent's join returns)
        _obs.shutdown()
        spoke.hub_window.close(unlink=False)
        spoke.my_window.close(unlink=False)


def _spoke_window_names(run_id, i, gen=0):
    """THE window naming scheme (creator and opener must agree).
    ``gen`` > 0 names a respawned incarnation's FRESH pair — a dead
    generation's windows are never reused (a crashed writer may have
    died mid-seqlock); they stay in the launcher's owned list and are
    unlinked at wheel teardown."""
    suffix = f"r{gen}" if gen else ""
    return f"{run_id}h{i}{suffix}", f"{run_id}s{i}{suffix}"


def _spoke_proxy(kind, run_id, i, S, K, create, gen=0):
    """One spoke's proxy with its window pair, on either side of the
    shm handshake (create=True: wheel launcher; False: a consumer in
    another process, e.g. the sharded-APH hub shard)."""
    from .vanilla import spoke_classes

    spoke_cls, _ = spoke_classes(kind)
    hub_name, my_name = _spoke_window_names(run_id, i, gen)
    proxy = SpokeProxy(spoke_cls, S, K, None, None)
    proxy.hub_window = Window.shared(
        hub_name, proxy.remote_window_length(), create=create)
    proxy.my_window = Window.shared(
        my_name, proxy.local_window_length(), create=create)
    return proxy


def open_spoke_proxies(spoke_kinds, run_id, S, K):
    """Open (create=False) the window pairs spawn_spoke_processes
    created — the consumer side of the ONE naming scheme."""
    return [_spoke_proxy(kind, run_id, i, S, K, create=False)
            for i, kind in enumerate(spoke_kinds)]


def _spawn_one_spoke(cfg: RunConfig, i, run_id, ctx, S, K, f32, tdir,
                     gen=0):
    """Window pair + worker process for ONE spoke (generation ``gen``).
    The single spawn body shared by the initial launch and the
    supervisor's respawn path — both incarnations are wired
    identically, only the window names and the telemetry role carry
    the generation."""
    from dataclasses import asdict

    sp = cfg.spokes[i]
    sp_dict = asdict(sp)
    if cfg.checkpoint_dir or cfg.resume_from:
        # checkpoint/resume wiring (mpisppy_tpu.ckpt): where this
        # incarnation WRITES its warm state, and — for respawns
        # (gen > 0, the supervisor path) or a --resume-from launch —
        # the state file it resumes FROM. This is what turns the
        # supervisor's respawn into "resume the spoke": generation N
        # starts from the freshest state generation N-1 persisted.
        from ..ckpt.spoke_state import spoke_resume_options
        for k, v in spoke_resume_options(
                cfg.checkpoint_dir, cfg.resume_from, i, sp.kind,
                gen=gen).items():
            sp_dict["options"].setdefault(k, v)
    proxy = _spoke_proxy(sp.kind, run_id, i, S, K, create=True, gen=gen)
    # explicit telemetry propagation (not only the inherited env var):
    # each child captures into the shared run dir under its own role
    # so artifacts never clobber; a respawned incarnation gets a
    # gen-suffixed role so the dead child's events survive beside it
    role = f"spoke{i}-{sp.kind}" + (f"-r{gen}" if gen else "")
    telemetry = {"out_dir": tdir, "role": role, "index": i, "gen": gen}
    p = ctx.Process(target=_spoke_worker,
                    args=(cfg.to_dict(), sp_dict,
                          *_spoke_window_names(run_id, i, gen), f32,
                          telemetry),
                    daemon=True)
    with spawn_environment(child_jax_env(sp_dict.get("options"))):
        p.start()
    return proxy, p


def spawn_spoke_processes(cfg: RunConfig, run_id, ctx, S, K, f32=False):
    """Create the window pair + worker process for every spoke in
    ``cfg`` (window names ``{run_id}h{i}`` / ``{run_id}s{i}`` — the ONE
    naming scheme; spin_the_wheel_processes and the sharded-APH wheel
    launcher both spawn through here). Returns (proxies, procs,
    owned_windows); the caller owns window unlink and process joins."""
    tdir = _telemetry_out_dir(cfg)
    proxies, procs, owned = [], [], []
    for i in range(len(cfg.spokes)):
        proxy, p = _spawn_one_spoke(cfg, i, run_id, ctx, S, K, f32, tdir)
        owned += [proxy.hub_window, proxy.my_window]
        proxies.append(proxy)
        procs.append(p)
    return proxies, procs, owned


def wait_spoke_hellos(cfg: RunConfig, proxies, procs, timeout, hub=None):
    """Block until every spoke's startup hello lands (so gap-based
    termination cannot fire before cold-starting spoke processes have
    joined the wheel). With ``hub`` given, a fired wheel watchdog
    aborts the wait — the deadline covers startup too."""
    deadline = time.monotonic() + timeout
    for i, proxy in enumerate(proxies):
        while proxy.my_window.read_id() == 0:
            if hub is not None and hub._watchdog_fired:
                raise TimeoutError(
                    "wheel deadline fired while waiting for spoke "
                    f"hellos (spoke {cfg.spokes[i].kind} still silent)")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"spoke {cfg.spokes[i].kind} (pid {procs[i].pid}) "
                    "never sent its startup hello")
            if not procs[i].is_alive():
                raise RuntimeError(
                    f"spoke {cfg.spokes[i].kind} died during startup")
            time.sleep(0.05)


def spin_the_wheel_processes(cfg: RunConfig, join_timeout=None, f32=False,
                             spoke_ready_timeout=None):
    """One hub (this process) + one OS process per spoke. Returns the hub
    after termination; ``hub._spoke_last_ids`` counts consumed updates
    (>= 1 is the startup hello; > 1 means real bound traffic).

    The hub waits up to ``spoke_ready_timeout`` for every spoke's startup
    hello before iterating, so a gap-based termination cannot fire before
    cold-starting spoke processes (JAX init + first compile) have joined
    the wheel. The spawn context is used so children re-initialize JAX
    cleanly (a forked JAX runtime is unsupported).

    The wheel is SUPERVISED (cylinders/supervisor.py, configured by
    ``cfg.supervisor``): dead spokes are detected from the hub's sync
    path and respawned on fresh window pairs with capped backoff,
    repeat offenders are quarantined while the wheel continues, and
    ``cfg.wheel_deadline`` arms a watchdog that terminates a hung
    wheel cleanly (telemetry flushed, partial bounds reported). Both
    timeouts default from the config (``cfg.join_timeout`` /
    ``cfg.spoke_ready_timeout``); explicit arguments win."""
    cfg.validate()
    # multi-host wheels: bring up multi-process JAX (DCN) before the
    # hub engine touches devices, so a ``mesh_devices`` hub shards over
    # the GLOBAL device set while spokes keep their per-process
    # runtimes (doc/sharding.md) — the PR 5 supervision layer
    # (heartbeats, respawn on fresh windows, quarantine) is exactly the
    # fault model a pod needs
    from .runtime import maybe_init_distributed

    maybe_init_distributed(cfg.coordinator)
    join_timeout = cfg.join_timeout if join_timeout is None \
        else join_timeout
    spoke_ready_timeout = cfg.spoke_ready_timeout \
        if spoke_ready_timeout is None else spoke_ready_timeout

    # a config-carried telemetry dir enables the parent's session too
    # (programmatic callers bypass __main__.run, which does this for
    # the CLI) — the hub's own events/trace must land beside the
    # spokes' role artifacts for the merge to mean anything
    if cfg.telemetry_dir and not obs.enabled():
        obs.configure(out_dir=cfg.telemetry_dir, config=cfg.to_dict())

    from .vanilla import hub_dict

    hub_d = hub_dict(cfg)
    hub_opt = hub_d["opt_class"](**hub_d["opt_kwargs"])
    # the cylinder wire format carries REAL scenarios only: a sharded
    # hub pads its batch to the mesh (doc/sharding.md) but spokes run
    # unpadded engines and the window lengths must agree on both sides
    S, K = getattr(hub_opt, "_S_orig", hub_opt.batch.S), hub_opt.batch.K
    run_id = f"/spw{os.getpid():x}{secrets.token_hex(4)}"

    ctx = mp.get_context("spawn")
    proxies, procs, owned = [], [], []
    supervisor = None
    hub = None
    prev_sigterm = None
    try:
        proxies, procs, owned = spawn_spoke_processes(cfg, run_id, ctx,
                                                      S, K, f32)
        hub = hub_d["hub_class"](hub_opt, spokes=proxies,
                                 **hub_d.get("hub_kwargs", {}))
        hub.classify_spokes()
        hub.windows_made = True
        hub.setup_hub()
        # supervision: liveness + respawn + quarantine polled from the
        # hub's sync path; the respawner re-enters _spawn_one_spoke on
        # a generation-suffixed fresh window pair
        from ..cylinders.supervisor import WheelSupervisor

        tdir = _telemetry_out_dir(cfg)

        def _respawner(i, gen):
            return _spawn_one_spoke(cfg, i, run_id, ctx, S, K, f32,
                                    tdir, gen=gen)

        supervisor = WheelSupervisor(
            proxies, procs, kinds=[sp.kind for sp in cfg.spokes],
            options=cfg.supervisor, respawner=_respawner, owned=owned)
        supervisor.attach(hub)
        if cfg.wheel_deadline:
            supervisor.start_watchdog(cfg.wheel_deadline)
        # deterministic hub-side faults (testing/faults.py): the
        # harness can preempt (SIGTERM) or crash the HUB process at a
        # named iteration, the way spoke plans crash spokes. Import
        # gated on the env var — the clean path imports nothing from
        # mpisppy_tpu.testing (tests/test_faults.py asserts it).
        hub_fault_spec = os.environ.get("MPISPPY_TPU_FAULT_PLAN")
        if hub_fault_spec:
            # lint: ok[PURE001] env-gated: MPISPPY_TPU_FAULT_PLAN only — the clean path never imports testing (probe backstops)
            from ..testing.faults import install_hub_faults
            install_hub_faults(hub, hub_fault_spec)
        # the preemption notice path (doc/fault_tolerance.md): with
        # checkpointing armed, SIGTERM forces one final bundle +
        # nonblocking telemetry flush + clean terminate instead of
        # losing the whole optimization state. Handler restored on
        # every exit path (outermost finally).
        if cfg.checkpoint_dir:
            import signal as _signal

            def _on_sigterm(signum, frame):
                hub.handle_preemption("sigterm")
            try:
                prev_sigterm = _signal.signal(_signal.SIGTERM,
                                              _on_sigterm)
            except ValueError:
                prev_sigterm = None     # not the main thread
        wait_spoke_hellos(cfg, proxies, procs, spoke_ready_timeout,
                          hub=hub)
        try:
            hub.main()
        finally:
            # no respawns once termination starts; then release the
            # spokes (the in-process wheel guards the same way,
            # utils/sputils.py) — otherwise the children poll forever
            # on windows the cleanup unlinks
            supervisor.shutdown()
            hub.send_terminate()
            for p in procs:
                p.join(timeout=join_timeout)
                if p.is_alive():
                    global_toc(f"multiproc: spoke pid {p.pid} missed the "
                               "join timeout; terminating")
                    p.terminate()
        hub.receive_bounds()
        hub.hub_finalize()
        tdir = _telemetry_out_dir(cfg)
        if tdir:
            # every child flushed its role artifacts before its join
            # returned; persist the hub's own trace, then merge all
            # processes onto one wall-clock-aligned Perfetto timeline
            obs.flush()
            from ..obs.merge import merge_traces
            try:
                merged = merge_traces(tdir)
                if merged:
                    global_toc(f"telemetry: merged multi-process trace "
                               f"-> {merged}")
            except Exception as e:   # diagnostics must not kill a run
                global_toc(f"telemetry: trace merge failed: {e!r}")
        return hub
    except BaseException:
        # startup-failure cleanup: a hello timeout (or any raise before
        # the normal terminate/join path) must not leak live children —
        # daemon processes would otherwise linger, polling windows the
        # finally below unlinks, until interpreter exit. The status
        # server's port is released the same way (the normal path stops
        # it in hub_finalize).
        if hub is not None:
            hub.shutdown_live()
        if supervisor is not None:
            supervisor.shutdown()
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.kill()
                p.join(timeout=10.0)
        raise
    finally:
        if prev_sigterm is not None:
            import signal as _signal
            _signal.signal(_signal.SIGTERM, prev_sigterm)
        for w in owned:
            w.close(unlink=True)
