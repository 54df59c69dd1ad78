"""Process-level JAX runtime setup shared by the CLI and process workers.

One place for the precision policy and the persistent compile cache so
hub and spoke processes can never silently diverge (the cache is only
shared when every process configures the same directory).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time

# the cache path is part of a cache entry's key, so the default must be
# a FIXED place every process of this checkout resolves identically:
# <checkout>/.jax_cache (git-ignored), never a temp name, pid or time
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where this process keeps its persistent XLA compile cache:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment places it (JAX
    reads that variable itself), else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def enable_honest_f32():
    """TPU f32 matmuls default to reduced (bf16-pass) precision —
    enough to stall the f32 ADMM phase near 1e-1 where true f32
    converges to ~1e-3 (measured: the f32 hub's iter-0 feasibility
    gate fails on TPU but passes on CPU with identical code). Solver
    math needs honest f32. ONE policy point: every entry path
    (setup_jax_runtime, __graft_entry__.py) calls this."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")


def setup_jax_runtime(f32: bool = False):
    """The ONE owner of the process-level JAX settings (precision
    policy + persistent compile cache): the CLI, ``serve``, the
    benchmark's harness, ``chip_smoke.py``, the test harness and every
    spawned spoke/shard call it. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set the cache directory is left to JAX (which reads the variable);
    no other directory is ever set in code."""
    import jax

    if not f32:
        jax.config.update("jax_enable_x64", True)
    enable_honest_f32()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


# One XLA compile of a UC-width program holds ~11 GiB of HOST memory
# for minutes (measured on the one-chip v5e host, CHANGES.md PR 24), and
# the threads of one wheel — hub, bound spokes — all reach their first
# solve at about the same time: three such compiles in flight ran a
# 40 GiB host out of memory. jax neither serializes concurrent compiles
# nor deduplicates two threads missing its cache on the SAME program.
_COMPILE_LOCK = threading.RLock()


class _Traced(Exception):
    """An operand is a tracer: the call is being inlined into an
    enclosing jit's trace."""


def compile_serialized(jitted, static_argnames=()):
    """Wrap a jitted entry point so that a call which has to COMPILE
    runs alone, process-wide. The first call per operand signature —
    pytree structure, shapes, dtypes, shardings, the named statics and
    positional ints/bools by value: what jit keys its own cache on
    (other python scalars are traced, their value is free) — takes one
    lock shared by every wrapped entry; a second thread after the same
    program waits, then hits jit's cache instead of compiling a twin.
    Calls whose signature has been seen never touch the lock, so a
    cylinder's warm iterations run straight through another cylinder's
    compile. Calls made while tracing an enclosing jit compile nothing
    here and pass through."""
    import jax

    statics = frozenset(static_argnames)
    seen = set()

    def leaf_key(x):
        if isinstance(x, jax.core.Tracer):
            raise _Traced
        shape = getattr(x, "shape", None)
        if shape is not None:
            return (tuple(shape), str(x.dtype),
                    getattr(x, "weak_type", False),
                    getattr(x, "sharding", None))
        return type(x) if isinstance(x, float) else x

    def call(*args, **kwargs):
        dyn = {k: v for k, v in kwargs.items() if k not in statics}
        leaves, treedef = jax.tree_util.tree_flatten((args, dyn))
        try:
            key = (treedef, tuple(leaf_key(x) for x in leaves),
                   tuple(sorted((k, v) for k, v in kwargs.items()
                                if k in statics)))
        except _Traced:
            return jitted(*args, **kwargs)
        if key in seen:
            return jitted(*args, **kwargs)
        with _COMPILE_LOCK:
            out = jitted(*args, **kwargs)
        seen.add(key)
        return out

    call.lower = jitted.lower
    call.__name__ = getattr(jitted, "__name__", "jitted")
    call.__wrapped__ = jitted
    return call


# ---- the wheel's ONE owner of the device queue (doc/cylinders.md) ----
# The cylinders of an in-process wheel are host threads that share one
# device and its one execution queue. Left to themselves they enqueue
# whole passes (a hub pass is 4 chunk solves at S = 256 / chunk 64, a
# Lagrangian pass 4, a pool round 20 plus a 4-chunk verification), and
# the hub's O(1) reads (the gate, ``float(conv)``, the exchange's
# read-backs) wait behind whatever another thread queued: hub
# iterations of 12.8 - 25 s with 1 - 4 s in the gate alone (my chip
# run, PR 39, S = 256). The policy, stated once: chunk solves of ALL
# cylinders are admitted at the grain of ONE chunk solve, ONE in flight
# at a time, in a FIXED cyclic order (hub, spoke 0, spoke 1, ...). A
# cylinder that is IN A PASS (the hub for as long as it iterates; a
# spoke from the hub payload it read to its next look for one) keeps
# its place in the cycle through the SHORT host work between its
# solves: the others wait for it, so which solve follows which does not
# depend on thread timing. A cylinder between passes, with nothing to
# solve, is passed over and loses nothing, and so is a spoke inside a
# host-only section of its pass (``wheel_host_section``: a host
# oracle's LPs and MILPs, the float64 certification): the wheel never
# waits for host work that may take minutes. So a read of the hub's
# waits for at most one foreign chunk solve, no cylinder starves
# (upstream gives each cylinder its own ranks: equal turns are the
# faithful mapping), and the same run interleaves the same way.


class WheelArbiter:
    """Admission of chunk solves for the cylinders of ONE in-process
    wheel (``utils/sputils.spin_the_wheel`` builds it when the wheel
    has spokes and hands every engine its ``port``). An engine outside
    such a wheel has no port and never comes here: the hub-only
    engine, a mesh, the serve path and APH's dispatch are untouched.

    A turn is held from admission until the caller leaves the ``with``
    block, which it does once its solve's outputs are READY (the
    engine blocks on them inside the turn): "in flight" means on the
    device's queue, not merely enqueued by the host."""

    LOG = 4096      # admitted turns kept for ``turn_log`` (tests, traces)

    def __init__(self, names):
        self.names = tuple(names)
        self._cv = threading.Condition()
        self._waiting = set()       # asked for a turn
        self._in_pass = set()       # keep their place between solves
        self._busy = False          # a turn is held: its solve is in flight
        self._last = len(self.names) - 1      # so cylinder 0 goes first
        self._log = collections.deque(maxlen=self.LOG)
        self.reset()

    def reset(self):
        """Zero the totals (the seconds reset with ``phase_timing``'s)."""
        with self._cv:
            self._tot = {n: {"turns": 0, "rows": 0, "queue_wait_s": 0.0,
                             "device_s": 0.0}
                         for n in self.names}

    def port(self, name):
        """One cylinder's way in: ``with port(rows): <enqueue one chunk
        solve and block on it>``, and ``port.begin_pass()`` /
        ``port.end_pass()`` around the work one hub payload (or, for
        the hub, one run) starts."""
        return _WheelPort(self, self.names.index(name))

    def _next(self):
        n = len(self.names)
        for k in range(1, n + 1):
            i = (self._last + k) % n
            if i in self._waiting or i in self._in_pass:
                return i
        return None

    def _set_in_pass(self, i, on):
        with self._cv:
            if on:
                self._in_pass.add(i)
            else:
                self._in_pass.discard(i)
                self._cv.notify_all()       # whoever waited for it

    @contextlib.contextmanager
    def _turn(self, i, rows):
        from .. import obs

        name = self.names[i]
        with obs.span("wheel.queue_wait", cat="wheel",
                      args={"cylinder": name}) as sp_w:
            with self._cv:
                self._waiting.add(i)
                self._cv.notify_all()       # it may be the awaited one
                while self._busy or self._next() != i:
                    self._cv.wait()
                self._waiting.discard(i)
                self._busy = True
                self._last = i
        t_adm = time.perf_counter()
        try:
            with obs.span("wheel.turn", cat="wheel",
                          args={"cylinder": name, "rows": rows}):
                yield
        finally:
            t_done = time.perf_counter()
            with self._cv:
                self._busy = False
                tot = self._tot[name]
                tot["turns"] += 1
                tot["rows"] += rows
                tot["queue_wait_s"] += sp_w.seconds
                tot["device_s"] += t_done - t_adm
                self._log.append((name, rows, t_adm, t_done))
                self._cv.notify_all()

    def totals(self):
        """{cylinder: {turns, rows, queue_wait_s, device_s}} since the
        last ``reset``."""
        with self._cv:
            return {n: dict(t) for n, t in self._tot.items()}

    def turn_log(self):
        """The last ``LOG`` admitted turns, oldest first:
        (cylinder, rows, admitted at, done at) on ``perf_counter``."""
        with self._cv:
            return list(self._log)


def wheel_pass(opt, begin):
    """Begin or end a pass of the cylinder whose engine ``opt`` is (a
    cylinder in a pass keeps its place in the arbiter's cycle); nothing
    for an engine outside an in-process wheel."""
    port = getattr(opt, "_wheel_port", None)
    if port is not None:
        port.begin_pass() if begin else port.end_pass()


@contextlib.contextmanager
def wheel_host_section(opt):
    """A host-only stretch inside a spoke's pass (a host oracle's LPs
    or MILPs, a subprocess, the float64 certification): the cylinder
    gives up its place in the arbiter's cycle for its length, so no
    other cylinder's turn waits for host work, and takes it back
    after. Nothing outside a pass or outside an in-process wheel."""
    port = getattr(opt, "_wheel_port", None)
    held = port is not None and port.in_pass()
    if held:
        port.end_pass()
    try:
        yield
    finally:
        if held:
            port.begin_pass()


class _WheelPort:
    """What one engine holds of its wheel's arbiter."""

    __slots__ = ("_arb", "_i")

    def __init__(self, arb, i):
        self._arb, self._i = arb, i

    def __call__(self, rows):
        return self._arb._turn(self._i, int(rows))

    def in_pass(self):
        return self._i in self._arb._in_pass

    def begin_pass(self):
        self._arb._set_in_pass(self._i, True)

    def end_pass(self):
        self._arb._set_in_pass(self._i, False)


_SPAWN_ENV_LOCK = threading.Lock()


def child_jax_env(options) -> dict:
    """The environment a spawned cylinder/shard process is born with,
    from its options: ``jax_platform`` ("cpu" default — one process per
    chip, and the chip is the hub's) as ``JAX_PLATFORMS``, and
    ``jax_visible_devices`` (pinning a cylinder to its own chip on a
    multi-chip host) as the platform's visible-devices variable."""
    opts = options or {}
    platform = str(opts.get("jax_platform", "cpu"))
    env = {"JAX_PLATFORMS": platform}
    vis = opts.get("jax_visible_devices")
    env_key = {"tpu": "TPU_VISIBLE_DEVICES", "gpu": "CUDA_VISIBLE_DEVICES",
               "cuda": "CUDA_VISIBLE_DEVICES"}.get(platform)
    if vis is not None and env_key:
        env[env_key] = str(vis)
    return env


@contextlib.contextmanager
def spawn_environment(env: dict):
    """Start a child process with ``env`` in its environment from its
    first instruction: a spawned interpreter copies the parent's
    environment when it is created, so the variables are set around
    ``Process.start()`` and restored after. That is what makes
    ``JAX_PLATFORMS`` reach the child BEFORE it imports jax (jax binds
    the variable when it is imported, and unpickling the worker
    function already imports its module) — a child told "cpu" this way
    never loads the accelerator library while the hub holds the chip.
    The parent's own jax read its environment long ago and is not
    affected."""
    with _SPAWN_ENV_LOCK:
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            yield
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


_DISTRIBUTED_UP = False


def maybe_init_distributed(coordinator) -> bool:
    """Multi-process JAX over DCN behind the ``RunConfig.coordinator``
    knob: when ``coordinator`` is set, call
    ``jax.distributed.initialize`` so every participating process sees
    the GLOBAL device set and ``parallel/mesh.make_mesh()`` builds a
    multi-host "scen" axis (the sharded PH step's psums then ride ICI
    within a host and DCN across hosts — doc/sharding.md). Idempotent;
    returns True when initialization ran (now or earlier).

    ``coordinator`` is a dict: ``address`` ("host:port", required),
    ``num_processes``, ``process_id``, optional ``local_device_ids``.
    Must run BEFORE the backend initializes — call it ahead of engine
    construction (the CLI and spin_the_wheel_processes both do)."""
    global _DISTRIBUTED_UP
    if not coordinator:
        return False
    if _DISTRIBUTED_UP:
        return True
    import jax

    kw = {"coordinator_address": coordinator["address"]}
    for src, dst in (("num_processes", "num_processes"),
                     ("process_id", "process_id"),
                     ("local_device_ids", "local_device_ids")):
        if coordinator.get(src) is not None:
            kw[dst] = coordinator[src]
    jax.distributed.initialize(**kw)
    _DISTRIBUTED_UP = True
    return True
