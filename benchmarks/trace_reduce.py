"""Reduction of a profiler trace (``*.xplane.pb``) to the benchmark's
device numbers. Kept with the benchmark so that every PR computes them
the same way; checked on the recorded trace under ``benchmarks/tests``.

What a v5e trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<k>``, with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<entry>(<fingerprint>)``), ``XLA Ops``
(one event per HLO op, named by its whole HLO text, ``%name = shape
op(...)``; a ``while`` spans the ops of its body, so events nest) and
``Async XLA Ops`` (copies in flight, not counted as busy); and one
``/host:CPU`` plane with a line per host thread: ``python`` holds the
``TraceAnnotation`` spans, the others the runtime's own events
(``tpu::System::Execute``, transfers, ...).

- busy   = union of the op intervals on a chip, averaged over chips;
- window = the span the driver annotated (``bench.traced``), else the
  extent of all device events;
- op time is SELF time (a ``while``'s own time excludes its body);
- collective time = union of the collective ops' intervals, and the
  part of it with no other op running beside it on that chip;
- idle gaps = the complement of busy inside the window on the first
  chip, each labelled by the innermost annotated span (dotted lower
  case: ``bench.ph_iter``) and the innermost runtime event that cover
  its middle, summed by label.
"""

from __future__ import annotations

import re

WINDOW_SPAN = "bench.traced"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|AllReduce|AllGather|ReduceScatter|CollectivePermute|AllToAll")
_NS = 1e-9


def load(path):
    """``{"device": {plane: {line: [(name, start_ns, end_ns)]}},
    "host": [(name, start_ns, end_ns)]}`` from an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            evs = [(_short(e.name) if is_dev else e.name, int(e.start_ns),
                    int(e.start_ns + e.duration_ns)) for e in line.events]
            if is_dev:
                lines[line.name] = evs
            else:
                out["host"].extend(evs)
        if is_dev:
            out["device"][plane.name] = lines
    return out


def _short(name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Merged, sorted list of (start, end) from overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events):
    """name -> self seconds: each event's duration minus the events
    nested directly inside it (one line of one chip)."""
    acc, stack = {}, []     # stack of [name, end, child_ns, dur]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            n, _e, child, dur = stack.pop()
            acc[n] = acc.get(n, 0) + dur - child
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0, e - s])
    while stack:
        n, _e, child, dur = stack.pop()
        acc[n] = acc.get(n, 0) + dur - child
    return {n: v * _NS for n, v in acc.items()}


def _ops_line(lines):
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    return [ev for name, evs in lines.items()
            if name not in ("Steps", "XLA Modules", "XLA TraceMe")
            for ev in evs]


_ANNOTATED = re.compile(r"^[a-z_]+\.[\w.\-]+$")


def _label(host, mid):
    span = event = None
    for name, s, e in host:
        if not s <= mid <= e:
            continue
        if _ANNOTATED.match(name):
            if span is None or e - s < span[1]:
                span = (name, e - s)
        elif event is None or e - s < event[1]:
            event = (name, e - s)
    parts = [x[0] for x in (span, event) if x]
    return " / ".join(parts) if parts else "no host span"


def reduce_events(tr):
    planes = sorted(tr["device"])
    if not planes:
        return None
    span = [(s, e) for name, s, e in tr["host"] if name == WINDOW_SPAN]
    all_ops = [ev for p in planes for ev in _ops_line(tr["device"][p])]
    if not all_ops:
        return None
    if span:
        w0, w1 = min(s for s, _ in span), max(e for _, e in span)
    else:
        w0 = min(s for _, s, _e in all_ops)
        w1 = max(e for _, _s, e in all_ops)
    clip = lambda evs: [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                        if e > w0 and s < w1]
    busy = coll = exposed = 0
    ops_acc, mods = {}, {}
    gaps = None
    for p in planes:
        ops = clip(_ops_line(tr["device"][p]))
        u = union((s, e) for _, s, e in ops)
        busy += total(u)
        for n, v in self_times(ops).items():
            ops_acc[n] = ops_acc.get(n, 0.0) + v
        c = union((s, e) for n, s, e in ops if _COLLECTIVE.search(n))
        # "compute beside it": leaf ops that are not collectives (a
        # while that spans the collective is not compute)
        other = union((s, e) for n, s, e in ops
                      if not _COLLECTIVE.search(n)
                      and not n.startswith("while"))
        coll += total(c)
        exposed += total(subtract(c, other))
        for n, s, e in clip(tr["device"][p].get("XLA Modules", [])):
            ent = mods.setdefault(n, [0.0, 0])
            ent[0] += (e - s) * _NS
            ent[1] += 1
        if gaps is None:
            gaps = subtract([[w0, w1]], u)
    by_label = {}
    for s, e in gaps:
        lab = _label(tr["host"], (s + e) // 2)
        by_label[lab] = by_label.get(lab, 0.0) + (e - s) * _NS
    nd = float(len(planes))
    top = sorted(ops_acc.items(), key=lambda kv: -kv[1])
    return {
        "n_device_planes": len(planes),
        "window_s": (w1 - w0) * _NS,
        "busy_s": busy * _NS / nd,
        "collective_s": coll * _NS / nd,
        "collective_exposed_s": exposed * _NS / nd,
        "top_ops": [[n, v / nd] for n, v in top],
        # name -> [seconds per chip, executions per chip]
        "modules": {n: [v[0] / nd, v[1] / nd] for n, v in mods.items()},
        "idle_gaps": [[n, v] for n, v in
                      sorted(by_label.items(), key=lambda kv: -kv[1])],
        "longest_gap_s": max((e - s for s, e in gaps), default=0) * _NS,
    }


def reduce_file(path):
    return reduce_events(load(path))
