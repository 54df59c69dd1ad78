"""Process-level JAX runtime setup shared by the CLI and process workers.

One place for the precision policy and the persistent compile cache so
hub and spoke processes can never silently diverge (the cache is only
shared when every process configures the same directory).
"""

from __future__ import annotations

import contextlib
import os
import threading

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
    policy + persistent compile cache): the CLI, ``serve``, bench.py,
    the test harness and every spawned spoke/shard call it. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache directory is left
    to JAX (which reads the variable); no other directory is ever set
    in code."""
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
