"""From a profiler trace (.xplane.pb) to busy and idle time, per-program
and per-operation time, collective time and labelled idle gaps.

All times are nanoseconds on the trace's own clock until the last step.
A ``Trace`` holds plain tuples, so tests build one from hand-made
intervals and run the same functions a chip trace goes through.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

OPS_LINES = ("XLA Ops",)            # per-operation line of a device plane
MODULE_LINES = ("XLA Modules",)     # one event per program execution
ANNOTATION_PREFIX = "bench."        # the benchmark's own host spans
WINDOW_BEGIN = "bench.window_begin"     # markers: the part of the trace
WINDOW_END = "bench.window_end"         # that is the measured window
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


@dataclasses.dataclass
class Trace:
    """ops / modules: {device plane: [(name, start_ns, dur_ns)]};
    host: [(name, start_ns, dur_ns)] of the benchmark's annotations."""
    ops: dict
    modules: dict
    host: list

    @property
    def device0(self):
        return sorted(self.ops)[0]

    def window(self):
        """The traced window: the span of the benchmark's annotations
        (the loop that was measured), or of the device events without
        them."""
        ev = self.host or [e for p in self.ops.values() for e in p]
        lo, hi = min(s for _, s, _ in ev), max(s + d for _, s, d in ev)
        for name, s, _ in self.host:
            if name == WINDOW_BEGIN:
                lo = max(lo, s)
            elif name == WINDOW_END:
                hi = min(hi, s)
        return lo, hi


def load(path) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                ev = [(short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)) for e in line.events]
                if line.name in OPS_LINES:
                    ops.setdefault(plane.name, []).extend(ev)
                elif line.name in MODULE_LINES:
                    modules.setdefault(plane.name, []).extend(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events
                         if e.name.startswith(ANNOTATION_PREFIX)]
    if not ops:
        raise RuntimeError(
            f"{path}: no device plane with a line named {OPS_LINES} "
            f"(planes: {[p.name for p in data.planes]})")
    return Trace(ops, modules, sorted(host, key=lambda e: e[1]))


def short_name(name):
    """An operation's event carries its whole HLO line
    (``%fusion.3 = f32[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ")[0].lstrip("%")


def clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events):
    """Merged [(start, end)] of the events' intervals."""
    merged = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(trace, plane) -> float:
    lo, hi = trace.window()
    return covered(union(clip(trace.ops[plane], lo, hi)))


def idle_share(trace, plane=None) -> float:
    lo, hi = trace.window()
    return 1.0 - busy_ns(trace, plane or trace.device0) / (hi - lo)


def program_durations(trace, plane=None):
    """{program: [dur_ns of each execution]}; a program's name is the
    module event's name without its run id."""
    out = defaultdict(list)
    lo, hi = trace.window()
    for name, s, d in trace.modules.get(plane or trace.device0, []):
        if lo <= s and s + d <= hi:
            out[re.sub(r"\(\d+\)$", "", name)].append(d)
    return dict(out)


def program_ns(trace, part, plane=None):
    """Durations of every execution of the programs whose name holds
    ``part`` (``decode`` finds ``jit_decode_paged``)."""
    return [d for name, ds in program_durations(trace, plane).items()
            if part in name for d in ds]


def op_totals(trace, plane=None):
    """{operation: ns of its own}: an operation that contains others (a
    while loop and its body) keeps only what its children leave."""
    out = defaultdict(float)
    lo, hi = trace.window()
    stack = []
    for name, s, d in sorted(clip(trace.ops[plane or trace.device0], lo, hi),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(d, stack[-1][1] - s)
        out[name] += d
        stack.append((name, s + d))
    return dict(out)


def collective_ns(trace, plane=None):
    """(total, exposed) time of collectives on one device.  Exposed: the
    collective's own events on the operation line, during which the core
    runs nothing else.  Total adds, for an asynchronous pair, the time
    from its ``-start`` to its ``-done`` (the part other operations
    hide)."""
    lo, hi = trace.window()
    ev = [e for e in clip(trace.ops[plane or trace.device0], lo, hi)
          if COLLECTIVE.match(e[0])]
    exposed = covered(union(ev))
    spans, open_ = [], defaultdict(list)
    for name, s, d in sorted(ev, key=lambda e: e[1]):
        kind = COLLECTIVE.match(name).group(1)
        if "-start" in name:
            open_[kind].append(s)
        elif "-done" in name and open_[kind]:
            s0 = open_[kind].pop(0)
            spans.append((kind, s0, s + d - s0))
        else:
            spans.append((kind, s, d))
    return covered(union(spans)), exposed


def idle_gaps(trace, plane=None):
    """{label: idle ns}: every gap of the device inside the window, given
    to the benchmark's annotation that covers most of it."""
    lo, hi = trace.window()
    busy = union(clip(trace.ops[plane or trace.device0], lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    out = defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, label = 0.0, "unannotated"
        for name, s, d in trace.host:
            ov = min(b, s + d) - max(a, s)
            if ov > best:
                best, label = ov, name
        out[label] += b - a
    return dict(out)


def host_spans(trace, name):
    return [(s, d) for n, s, d in trace.host if n == name]


def busy_inside(trace, spans, plane=None):
    """Device-busy ns inside each (start, dur) span."""
    busy = union(trace.ops[plane or trace.device0])
    out = []
    for s, d in spans:
        out.append(sum(max(0.0, min(e, s + d) - max(b, s))
                       for b, e in busy))
    return out


def device_summary(trace):
    """The result line's ``busy_s`` / ``window_s`` (busy averaged over the
    chips used) and its ``breakdown``."""
    lo, hi = trace.window()
    busy = sum(busy_ns(trace, p) for p in trace.ops) / len(trace.ops)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    families = defaultdict(float)   # fusion.12, fusion.13 -> fusion
    for name, ns in op_totals(trace).items():
        families[re.sub(r"[.\d]+$", "", name)] += ns
    return ({"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9},
            {"device_ops": top(families),
             "idle_gaps": top(idle_gaps(trace))})
