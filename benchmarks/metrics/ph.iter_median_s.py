"""Median wall seconds of the window's hot PH iterations: the steadier
statistic beside ``ph_iter_s`` (a mean over a fixed range of
iterations), blind to a stall that the mean shows. Moves
``ph_iter_s``."""


def read(obs):
    return obs.get("iter_median_s")
