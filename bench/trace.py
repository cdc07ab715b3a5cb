"""Reduce a profiler trace to the per-layer metrics' inputs.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
intervals: per device, the XLA programs (``modules``) and operations
(``ops``) that ran, and the host's named ranges (``spans``: the
program's ``TraceAnnotation`` spans and the benchmark's own).  Every
other function works on those lists of ``(name, start_ns, end_ns)``, so
it can be checked on a synthetic trace.

Busy time is the union of the device's operation intervals inside the
window; idle share is one minus busy over the window.  A program's device
time is the sum of its executions' durations.  Collective time that no
compute hides is the part of the union of collective operations that no
other operation on that device overlaps.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|psum|send|recv", re.I)


@dataclass
class Device:
    modules: list = field(default_factory=list)   # (name, start, end)
    ops: list = field(default_factory=list)


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # id -> Device
    spans: list = field(default_factory=list)     # (name, start, end)


def load(path: str) -> Trace:
    """Parse one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_ops: list = []
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if m:
            dev = tr.devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Modules":
                    dev.modules += evs
                elif line.name == "XLA Ops":
                    dev.ops += evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name.startswith("tf_XLA"):
                    # The CPU backend runs its operations on host threads
                    # (rehearsals only: a CPU time is never a device time).
                    cpu_ops += [ev for ev in evs if ev[2] > ev[1]
                                and not ev[0].startswith("Threadpool")]
                else:
                    tr.spans += evs
    if not tr.devices and cpu_ops:
        tr.devices[0] = Device(ops=cpu_ops)
    return tr


def program_name(module: str) -> str:
    """``jit_learn_slab(12)`` -> ``learn_slab``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return re.sub(r"^jit_", "", name)


def merge(intervals) -> list:
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def window_of(trace: Trace, name: str = "bench_window"):
    """(start, end) of the benchmark's window range on the host."""
    ws = [(s, e) for n, s, e in trace.spans if n == name]
    if not ws:
        raise ValueError(f"trace has no {name!r} range")
    return min(s for s, _ in ws), max(e for _, e in ws)


def busy(dev: Device, window) -> list:
    evs = dev.ops or dev.modules
    return merge(clip([(s, e) for _, s, e in evs], window))


def idle_share(dev: Device, window) -> float:
    return 1.0 - length(busy(dev, window)) / (window[1] - window[0])


def program_times(dev: Device, program: str, window) -> list:
    """Durations (ns) of ``program``'s executions that start in the
    window."""
    lo, hi = window
    return [e - s for n, s, e in dev.modules
            if program_name(n) == program and lo <= s < hi]


def exposed_collective(dev: Device, window) -> float:
    """ns of collective operations that no other operation overlaps."""
    coll = merge(clip([(s, e) for n, s, e in dev.ops if COLLECTIVE.search(n)],
                      window))
    other = merge(clip([(s, e) for n, s, e in dev.ops
                        if not COLLECTIVE.search(n)], window))
    hidden, j = 0.0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            hidden += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return length(coll) - hidden


def top_ops(dev: Device, window, n: int = 10) -> list:
    """[[op name, seconds]] of the operations that took most time."""
    tot: dict = defaultdict(float)
    for name, s, e in dev.ops or dev.modules:
        for a, b in clip([(s, e)], window):
            tot[name] += b - a
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: Device, spans, window, names, n: int = 10) -> list:
    """[[host span active in the gap, seconds]] of the longest idle gaps.
    A gap is named by the innermost of the ``names`` ranges (the
    program's spans and the loop's own) that covers its middle, or
    ``other`` when none does."""
    b = busy(dev, window)
    edges = [window[0]] + [x for iv in b for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [(ee - ss, nm) for nm, ss, ee in spans
                 if ss <= mid <= ee and nm in names]
        out.append([min(cover)[1] if cover else "other", (e - s) * 1e-9])
    return out
