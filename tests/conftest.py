"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the stand-in for a TPU slice,
analogous to the reference testing multi-rank behavior by spawning MPI ranks
on one machine, ref. examples/afew.py:40-55) with f64 enabled so numerical
assertions can use tight tolerances. The platform is pinned to the CPU
here, before any computation runs, so the suite never reaches for an
accelerator even when one is attached (the chip is chip_smoke.py's).
"""

import jax

from mpisppy_tpu.utils.runtime import setup_jax_runtime

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# x64 + the precision policy + the persistent compile cache, from the
# same owner every production entry point uses (utils/runtime.py)
setup_jax_runtime()


def pytest_configure(config):
    # two tiers, mirroring the reference's per-push CI vs nightly sweep
    # (ref. .github/workflows/pull_push_regression.yml vs weekly.yml):
    # `pytest -m "not slow"` is the per-push tier (< 2 min), the full
    # suite the nightly one (< 10 min)
    config.addinivalue_line(
        "markers", "slow: long-running tier (full-suite runs only)")
