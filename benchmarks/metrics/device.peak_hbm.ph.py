"""Peak device memory in use on the fullest chip, in GB
(``device.memory_stats()["peak_bytes_in_use"]`` after the window)."""


def read(obs):
    b = obs.get("memory_peak_bytes")
    return b / 1e9 if b else None
