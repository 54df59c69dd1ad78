"""Share of the traced slice's device-idle seconds that no span of the
program covers: the gaps of ``trace.idle_gaps`` whose label names no
program span / all gaps. A label is ``<innermost annotated span> /
<innermost runtime event>`` (``trace_reduce._label``); it names a
program span when its first part is a dotted lower-case name
(``trace_reduce``'s own pattern: ``ph.assemble``, ``qp.segment``) that
is not the benchmark's own (``bench.*``). What stays unattributed is
idle time the host spent where the program opens no span (PERF.md
section 7). ``None`` without a trace. Moves ``ph_iter_s``."""

import trace_reduce


def names_program_span(label):
    first = label.split(" / ", 1)[0]
    return bool(trace_reduce._ANNOTATED.match(first)) \
        and not first.startswith("bench.")


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    idle = sum(v for _label, v in tr["idle_gaps"])
    if not idle:
        return 0.0
    return 100.0 * sum(v for label, v in tr["idle_gaps"]
                       if not names_program_span(label)) / idle
