"""Seconds of host batch build in set-up (the benchmark's own span
around ``ir/batch.build_batch``). Moves ``setup_s``."""


def read(obs):
    return (obs.get("spans") or {}).get("host_build")
