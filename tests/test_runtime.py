"""Process start-up, device selection and the compile cache
(utils/runtime, obs/profile peaks, utils/vanilla mesh sizing) — pure-CPU unit tests of the places where a
fallback used to hide the device (ISSUE 24)."""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from mpisppy_tpu import obs
from mpisppy_tpu.obs import profile
from mpisppy_tpu.utils import runtime
from mpisppy_tpu.utils.config import RunConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT_CACHE_DIR = """
import jax
from mpisppy_tpu.utils.runtime import compile_cache_dir, setup_jax_runtime
before = jax.config.jax_compilation_cache_dir
setup_jax_runtime()
print("CACHE", before, jax.config.jax_compilation_cache_dir,
      compile_cache_dir())
"""


def _cache_dirs(env_dir):
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _REPORT_CACHE_DIR],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("CACHE")][0]
    return line.split()[1:]


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside_or_in_checkout(tmp_path,
                                                             placed):
    """With JAX_COMPILATION_CACHE_DIR set, setup_jax_runtime leaves the
    directory to jax (which read the variable itself); unset, the cache
    goes to the ONE fixed directory inside the checkout."""
    fixed = os.path.join(REPO, ".jax_cache")
    if placed:
        want = str(tmp_path / "cc")
        before, after, reported = _cache_dirs(want)
        assert before == after == reported == want
    else:
        before, after, reported = _cache_dirs(None)
        assert before == "None"
        assert after == reported == fixed


def test_one_setter_of_the_cache_directory():
    """The acceptance grep: utils/runtime.py is the only file that sets
    jax_compilation_cache_dir, and no /tmp cache path remains."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            text = open(path, encoding="utf-8").read()
            if '"jax_compilation_cache_dir"' in text \
                    or "/tmp/jax_cache" in text:
                hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("mpisppy_tpu", "utils", "runtime.py")]


def test_chip_smoke_reads_the_deployment_the_benchmark_states():
    """The bring-up check and the cells run ONE stated deployment:
    ``chip_smoke.deployment()`` is the configuration file's instance,
    recipe and shape, read without jax, and no second definition (a
    module ``bench``) can be imported from the checkout's root."""
    code = (
        "import sys, json, importlib.util\n"
        "sys.modules['jax'] = None   # import attempts now raise\n"
        "import chip_smoke\n"
        "cfg = json.load(open('benchmarks/configs/uc90x48_df32.json'))\n"
        "assert chip_smoke.deployment() == (cfg['instance'],\n"
        "    cfg['recipe'], cfg['shape']['n'], cfg['shape']['m'])\n"
        "assert importlib.util.find_spec('bench') is None\n"
        "print('ONE', cfg['shape']['n'], cfg['shape']['m'])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env={**os.environ, "PYTHONPATH": REPO},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["ONE", "13056", "26016"]


def test_spawn_environment_sets_and_restores(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    env = runtime.child_jax_env({"jax_platform": "tpu",
                                 "jax_visible_devices": "2"})
    assert env == {"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_DEVICES": "2"}
    assert runtime.child_jax_env(None) == {"JAX_PLATFORMS": "cpu"}
    with runtime.spawn_environment(env):
        assert os.environ["JAX_PLATFORMS"] == "tpu"
        assert os.environ["TPU_VISIBLE_DEVICES"] == "2"
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_DEVICES" not in os.environ


# ---------------- compiles run one at a time ----------------

def test_compile_serialized_gates_first_calls_only():
    """First calls (the ones that compile) of every wrapped entry run
    one at a time, process-wide, and two threads after the SAME program
    compile it once; a call whose signature was seen never waits for
    another thread's compile."""
    import threading
    import time

    state = {"in_flight": 0, "peak": 0, "runs": 0}
    lock = threading.Lock()
    release = threading.Event()

    def make(hold):
        def fake_jitted(x, *, k=0):
            with lock:
                state["in_flight"] += 1
                state["runs"] += 1
                state["peak"] = max(state["peak"], state["in_flight"])
            if hold:
                release.wait(timeout=30)
            else:
                time.sleep(0.05)
            with lock:
                state["in_flight"] -= 1
            return x
        fake_jitted.lower = None
        return runtime.compile_serialized(fake_jitted, ("k",))

    fast, slow = make(False), make(True)
    x4, x8 = jnp.zeros(4), jnp.zeros(8)
    # distinct shapes, a distinct static, and the same program twice:
    # every first call takes the one lock
    ts = [threading.Thread(target=fast, args=(a,), kwargs=kw)
          for a, kw in ((x4, {}), (x8, {}), (x4, {"k": 1}), (x4, {}))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert state["peak"] == 1 and state["runs"] == 4
    # a slow first call of ANOTHER entry holds the lock ...
    t_slow = threading.Thread(target=slow, args=(x4,))
    t_slow.start()
    while state["in_flight"] == 0:
        time.sleep(0.01)
    # ... a warm call of a seen signature runs straight through it,
    t_warm = threading.Thread(target=fast, args=(x8,))
    t_warm.start()
    t_warm.join(timeout=10)
    assert not t_warm.is_alive() and state["peak"] == 2
    # ... while an unseen one waits for it
    t_cold = threading.Thread(target=fast, args=(jnp.zeros(16),))
    t_cold.start()
    t_cold.join(timeout=0.3)
    assert t_cold.is_alive()
    release.set()
    for t in (t_slow, t_cold):
        t.join(timeout=30)
        assert not t.is_alive()


# ---------------- device peaks ----------------

def test_v5e_device_kind_resolves_to_the_v5e_row():
    """The attached chip reports ``TPU v5 lite``: that is the v5e row
    (197 TFLOP/s, 819 GB/s). The match is exact — ``TPU v5`` (a v5p's
    name) contains "v5" and must NOT be priced by the v5e row."""
    assert profile.peaks_for_kind("TPU v5 lite", "tpu") == (197e12, 819.0)
    assert profile.peaks_for_kind("tpu V5 LITE ", "tpu") == (197e12, 819.0)
    with pytest.raises(ValueError, match="no peak"):
        profile.peaks_for_kind("TPU v5", "tpu")


def test_unknown_accelerator_kind_is_an_error_not_cpu_nominal(monkeypatch):
    with pytest.raises(ValueError, match="no peak"):
        profile.peaks_for_kind("TPU v9 mega", "tpu")
    # the CPU tier keeps its documented nominal row whatever the host
    # CPU calls itself
    assert profile.peaks_for_kind("AMD EPYC 9B14", "cpu") == (1e11, 50.0)
    # ... and the session-level resolution raises too, instead of
    # pricing an unknown accelerator as a CPU
    fake = types.SimpleNamespace(device_kind="TPU v9 mega", platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    obs.configure(out_dir=None)
    try:
        with pytest.raises(ValueError, match="no peak"):
            profile.peaks()
        # the env pair is the escape — both, never one
        monkeypatch.setenv("MPISPPY_TPU_PEAK_FLOPS", "1e14")
        with pytest.raises(ValueError, match="TOGETHER"):
            profile.peaks()
        monkeypatch.setenv("MPISPPY_TPU_PEAK_HBM_GBPS", "900")
        assert profile.peaks() == (1e14, 900.0, "env", "TPU v9 mega")
    finally:
        obs.shutdown()


def test_cpu_session_resolves_table_row_cpu_tier():
    obs.configure(out_dir=None)
    try:
        flops, gbps, source, _kind = profile.peaks()
        assert (flops, gbps, source) == (1e11, 50.0, "table")
    finally:
        obs.shutdown()


# ---------------- no narrower mesh ----------------

def test_mesh_devices_beyond_visible_raises():
    from mpisppy_tpu.utils.vanilla import hub_dict
    n_vis = len(jax.devices())
    cfg = RunConfig(model="farmer", num_scens=3,
                    mesh_devices=n_vis + 1).validate()
    with pytest.raises(ValueError, match="exceeds the"):
        hub_dict(cfg)
    # the visible count itself is fine
    hd = hub_dict(RunConfig(model="farmer", num_scens=n_vis,
                            mesh_devices=n_vis).validate())
    assert hd["opt_kwargs"]["mesh"].devices.size == n_vis
