"""Share of the scenarios that the window's dispatch-masked passes
actually SOLVED: 100 x solved / (solved + skipped), counted at the
launch sites (``PHBase.phase_timing()["dispatch"]``; chunk pad rows are
in neither count). The configuration's ``dispatch_frac`` says what it
must read (25 at 0.25); a pass that solved everyone and dropped the
unselected rows would read 100. A count, so a rehearsal reports it too.
``None`` where the program books none. Moves ``ph_iter_s``."""


def read(obs):
    d = (obs.get("phase") or {}).get("dispatch")
    if not d or not (d.get("solved", 0) + d.get("skipped", 0)):
        return None
    return 100.0 * d["solved"] / (d["solved"] + d["skipped"])
