"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, collective time and idle gaps named by host spans.

Device planes are ``/device:TPU:<n>``: their ``XLA Modules`` line holds one
event per program execution (named ``jit_<function>(<id>)``), their
``XLA Ops`` line one event per operation. The host thread that holds the
benchmark's ``bench.window`` span holds its other spans and JAX's own
dispatch spans. Every event is read on the trace's one clock, in
nanoseconds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.I)


@dataclass
class Trace:
    window: tuple                         # (start_ns, end_ns)
    modules: dict = field(default_factory=dict)   # device -> [(name, s, e)]
    ops: dict = field(default_factory=dict)       # device -> [(name, s, e)]
    host: list = field(default_factory=list)      # [(name, s, e, depth)]


def module_name(event_name: str) -> str:
    """``jit__batched_stage_impl(-12)`` -> ``_batched_stage_impl``."""
    name = re.sub(r"\(-?\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, host = {}, {}, []
    window = None
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Modules":
                    modules[plane.name] = evs
                elif line.name == "XLA Ops":
                    ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events),
                             key=lambda x: (x[1], -x[2]))
                spans = [x for x in evs if x[0] == WINDOW_SPAN]
                if not spans:
                    continue
                window = spans[0][1:]
                stack = []
                for name, s, t in evs:
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.append((name, s, t, len(stack)))
                    stack.append(t)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return Trace(window=window, modules=modules, ops=ops, host=host)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for _, s, e in intervals
            if e > lo and s < hi]


def union(spans) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(spans) -> float:
    return float(sum(e - s for s, e in spans))


def busy(tr: Trace, device: str) -> list:
    """Union of the intervals in which an operation ran on ``device``,
    inside the window (modules where a plane has no op line)."""
    evs = tr.ops.get(device) or tr.modules.get(device, [])
    return union(clip(evs, *tr.window))


def busy_s(tr: Trace) -> float:
    """Busy seconds averaged over the devices that ran anything."""
    devs = [d for d in set(tr.modules) | set(tr.ops) if busy(tr, d)]
    if not devs:
        return 0.0
    return sum(total(busy(tr, d)) for d in devs) / len(devs) / 1e9


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def module_seconds(tr: Trace) -> dict:
    """{program: device seconds in the window}, averaged over devices."""
    out: dict = {}
    devs = list(tr.modules)
    for d in devs:
        for name, s, e in tr.modules[d]:
            if e > tr.window[0] and s < tr.window[1]:
                n = module_name(name)
                out[n] = out.get(n, 0.0) + (min(e, tr.window[1])
                                            - max(s, tr.window[0])) / 1e9
    return {k: v / max(len(devs), 1) for k, v in out.items()}


def executions(tr: Trace) -> int:
    """Program executions in the window, on the busiest device."""
    return max((sum(1 for _, s, _ in evs if tr.window[0] <= s < tr.window[1])
                for evs in tr.modules.values()), default=0)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].lstrip("%")


def op_seconds(tr: Trace) -> dict:
    out: dict = {}
    for evs in tr.ops.values():
        for name, s, e in evs:
            if e > tr.window[0] and s < tr.window[1]:
                n = op_name(name)
                out[n] = out.get(n, 0.0) + (min(e, tr.window[1])
                                            - max(s, tr.window[0])) / 1e9
    n = max(len(tr.ops), 1)
    return {k: v / n for k, v in out.items()}


def exposed_collective_s(tr: Trace) -> float:
    """Per device, the time collective ops run with no other op running
    on that device; averaged over devices."""
    vals = []
    for evs in tr.ops.values():
        inside = [(n, s, e) for n, s, e in evs
                  if e > tr.window[0] and s < tr.window[1]]
        coll = union(clip([x for x in inside if COLLECTIVE.search(x[0])],
                          *tr.window))
        comp = union(clip([x for x in inside if not COLLECTIVE.search(x[0])],
                          *tr.window))
        vals.append(total(coll) - overlap(coll, comp))
    return sum(vals) / len(vals) / 1e9 if vals else 0.0


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(tr: Trace) -> list:
    """Idle (start, end) intervals of the busiest device in the window,
    longest first."""
    devs = sorted(set(tr.modules) | set(tr.ops),
                  key=lambda d: -total(busy(tr, d)))
    if not devs:
        return []
    edges = ([tr.window[0]] + [x for s in busy(tr, devs[0]) for x in s]
             + [tr.window[1]])
    out = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
           if edges[i + 1] > edges[i]]
    return sorted(out, key=lambda g: g[0] - g[1])


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """The ``top`` longest idle gaps, each named by the benchmark's span
    around it and the deepest host span at its middle."""
    out = []
    for s, e in gaps(tr)[:top]:
        mid = (s + e) / 2
        at = [h for h in tr.host if h[1] <= mid < h[2]]
        bench = [h for h in at if h[0].startswith("bench.")]
        outer = max(bench, key=lambda h: h[3])[0] if bench else "-"
        inner = max(at, key=lambda h: h[3])[0] if at else "-"
        out.append([f"{outer} > {inner}", (e - s) / 1e9])
    return out
