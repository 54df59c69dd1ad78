"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the stand-in for a TPU slice,
analogous to the reference testing multi-rank behavior by spawning MPI ranks
on one machine, ref. examples/afew.py:40-55) with f64 enabled so numerical
assertions can use tight tolerances. The platform is pinned to the CPU
here, before any computation runs, so the suite never reaches for an
accelerator even when one is attached (the chip is chip_smoke.py's).
"""

import jax
import pytest

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


class _ProfilerCapture:
    """``with cap: ...`` runs the block under ``jax.profiler`` (host
    ``TraceMe`` spans, no python tracer — what the benchmark's
    ``--trace 1`` records) and reads the capture back with
    ``ProfileData``: ``cap.events`` is every host event as
    ``(name, thread, start_ns, end_ns)``."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import glob

        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        path, = glob.glob(self.log_dir + "/**/*.xplane.pb", recursive=True)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.events += [
                        (e.name, line.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
        return False

    def spans(self, prefixes=("ph.", "qp.", "serve.")):
        """The program's own spans, in start order."""
        return sorted((e for e in self.events if e[0].startswith(prefixes)),
                      key=lambda e: (e[2], -e[3]))

    def inside(self, child, parent):
        """Every ``child`` span lies inside some ``parent`` span of the
        same thread (and there is at least one)."""
        kids = [e for e in self.events if e[0] == child]
        pars = [e for e in self.events if e[0] == parent]
        return bool(kids) and all(
            any(p[1] == k[1] and p[2] <= k[2] and k[3] <= p[3]
                for p in pars) for k in kids)


@pytest.fixture
def profiler_capture(tmp_path):
    return _ProfilerCapture(tmp_path / "profile")
