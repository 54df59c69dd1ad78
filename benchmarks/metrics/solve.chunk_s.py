"""Host seconds of the PH engine's solve phase per chunk solve, a mean
over ALL the window's iterations (``PHBase.phase_timing(True)`` solve
seconds per call / chunk solves per iteration). The pipelined chunk
loop blocks on the device in this phase only, so this is the fused
chunk-solve program's time as the host sees it. It is NOT device time
from the trace: the profiler can record one second of one iteration
(PERF.md section 3), less than one chunk solve. Moves ``ph_iter_s``."""


def read(obs):
    ph = obs.get("phase")
    if not ph or obs.get("platform") != "tpu":  # never from a rehearsal
        return None
    return ph["seconds_per_call"]["solve"] \
        / obs["chunk_solves_per_iteration"]
