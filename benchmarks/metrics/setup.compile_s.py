"""Seconds jax spent making programs executable during set-up: XLA
compiles in a cold checkout, persistent-cache loads in a warm one (sum
of jax's backend-compile duration events before the window opened).
Moves ``setup_s``."""


def read(obs):
    return obs.get("setup_compile_s")
