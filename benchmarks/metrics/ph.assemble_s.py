"""Mean host seconds per hot ``solve_loop`` call in the PH engine's
``assemble`` phase over the window (``PHBase.phase_timing(True)``).
Moves ``ph_iter_s``."""


def read(obs):
    ph = obs.get("phase")
    return ph["seconds_per_call"]["assemble"] if ph else None
