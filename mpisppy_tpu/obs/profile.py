"""The device peaks table: peak FLOP/s and HBM bandwidth by the exact
``device_kind`` jax reports, with its two-variable override.

What reads it: ``chip_smoke.py`` (the attached chip must resolve to the
v5e row), ``benchmarks/tests/test_yardstick.py`` (holds the benchmark's
``peaks.json`` equal to it) and ``tests/test_runtime.py``. The XLA
cost-model capture that used to live here is gone (PR 30): on the v5e
``Lowered.cost_analysis()`` returns ``None``, so it re-lowered every
program to learn nothing; the benchmark prices the chunk solve's bytes
from shapes (``benchmarks/bytes_model.py``), and compile attribution by
entry name is ``obs/resource.py``'s (``jax.compile.entry.<fn>``).

jax is imported lazily, inside :func:`peaks` only.
"""

from __future__ import annotations

import os

# Peak device throughput by the EXACT ``device_kind`` string jax
# reports (lower-cased): (peak FLOP/s, peak HBM GB/s). One row per kind
# a machine has actually reported: the attached v5e says ``TPU v5
# lite`` (published bf16 peak and HBM bandwidth, Google Cloud TPU v5e
# documentation; benchmarks/peaks.json carries the same row). Any
# other accelerator is an ERROR, never a default — add its row when a
# machine reports its name, or set BOTH MPISPPY_TPU_PEAK_FLOPS and
# MPISPPY_TPU_PEAK_HBM_GBPS. The CPU row is a NOMINAL placeholder that
# only tests/test_runtime.py still reads (ROADMAP C8).
_PEAKS_BY_KIND = {
    "tpu v5 lite": (197e12, 819.0),
    "cpu": (1e11, 50.0),
}


def peaks_for_kind(kind: str, platform: str = ""):
    """(peak_flops, peak_hbm_gbps) of one device, by its exact
    ``device_kind`` (case-insensitive). ``platform == "cpu"`` resolves
    to the CPU-tier nominal row whatever the host CPU calls itself;
    anything else outside the table raises."""
    if platform == "cpu":
        return _PEAKS_BY_KIND["cpu"]
    row = _PEAKS_BY_KIND.get(str(kind).strip().lower())
    if row is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth row for device_kind "
            f"{kind!r} (platform {platform!r}) in obs/profile."
            f"_PEAKS_BY_KIND; known: {sorted(_PEAKS_BY_KIND)}. Add the "
            "published peaks there, or set BOTH MPISPPY_TPU_PEAK_FLOPS "
            "and MPISPPY_TPU_PEAK_HBM_GBPS")
    return row


def peaks():
    """(peak_flops, peak_hbm_gbps, source, device_kind) of the first
    device jax reports — the env pair (both or neither) > the
    exact-``device_kind`` table; an accelerator outside the table with
    no override raises (peaks_for_kind)."""
    import jax

    dev = jax.devices()[0]
    kind, platform = str(dev.device_kind), str(dev.platform)
    env_f = os.environ.get("MPISPPY_TPU_PEAK_FLOPS")
    env_g = os.environ.get("MPISPPY_TPU_PEAK_HBM_GBPS")
    if bool(env_f) != bool(env_g):
        raise ValueError(
            "MPISPPY_TPU_PEAK_FLOPS and MPISPPY_TPU_PEAK_HBM_GBPS "
            "override the peaks table TOGETHER; only one of them is set")
    if env_f:
        return float(env_f), float(env_g), "env", kind
    return (*peaks_for_kind(kind, platform), "table", kind)
