"""The program's own names in a profiler trace: its host spans (the
tracer's ``serving.*`` / ``train.*``, which sit on the host plane of the
.xplane.pb while a trace is captured), its ``jax.named_scope``s and its
kernels' names on the device operations.

Device-idle time goes to the engine-step phase the host was in, exactly
as ``trace_reduce.idle_gaps`` gives it to a ``bench.*`` span: the same
function, over a ``Trace`` whose ``host`` is the program's phase spans.
Device time goes to a scope through the compiled program's text: an
operation's event carries its HLO line without metadata, so the scope of
``fusion.12`` is looked up by that name among the ``op_name``s of the
instructions the fusion holds.

Pure functions over tuples like ``trace_reduce``'s; nanoseconds on the
trace's own clock.  A program without the names (the parent of the PR
that added them) reads as ``None`` everywhere.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict

from perf import common, trace_reduce

SPAN_PREFIXES = ("serving.", "train.")
# the six leaves of one engine step; serving.step and serving.prefill
# enclose them and take no gap of their own
PHASES = ("serving.schedule", "serving.admit", "serving.build",
          "serving.dispatch", "serving.sync", "serving.emit")
OUTSIDE = "unannotated"         # idle_gaps' label for "under no span"
# the program's scopes, for a reader that names no tuple of its own; an
# operation fused across two scopes goes to the first of the tuple
SCOPES = ("lm_head_ce", "attn", "mlp", "optimizer", "embed")
UNSCOPED = "unscoped"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")


# -- host spans ---------------------------------------------------------------

def find_xplane(trace_dir=None):
    """The run's .xplane.pb (the profiler window empties the directory
    before it starts, so there is one), or None."""
    found = glob.glob(os.path.join(trace_dir or common.TRACE_DIR, "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return found[0] if found else None


@functools.lru_cache(maxsize=2)
def _host_spans(path, _mtime):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIXES)]
    return tuple(sorted(out, key=lambda e: e[1]))


def spans(obs, trace_dir=None):
    """[(name, start_ns, dur_ns)] of the program's spans in the run's
    trace, by start; None without a trace or without any such span."""
    if not obs.get("trace"):
        return None
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return list(_host_spans(path, os.path.getmtime(path))) or None


# -- idle time by engine-step phase -------------------------------------------

def idle_by_phase(trace, host):
    """{phase or OUTSIDE: idle ns of device 0 inside the window}: every
    gap to the leaf span that covers most of it.  The window is cut at
    the middle of each program execution (the device is busy there, so
    no gap worth the name is split) and each piece goes through
    ``trace_reduce.idle_gaps`` with the spans that touch it: the same
    rule at a cost that does not grow with gaps x spans."""
    lo, hi = trace.window()
    plane = trace.device0
    busy = trace_reduce.union(trace_reduce.clip(trace.ops[plane], lo, hi))
    leaves = [e for e in trace_reduce.clip(host, lo, hi) if e[0] in PHASES]
    cuts = sorted({s + d / 2 for _, s, d in trace.modules.get(plane, [])
                   if lo < s + d / 2 < hi})
    edges = [lo, *cuts, hi]
    out = defaultdict(float)
    starts, ends = [b[0] for b in busy], [b[1] for b in busy]
    span_starts = [s for _, s, _ in leaves]
    first = 0       # leaves are sequential: one that ended stays ended
    for a, b in zip(edges, edges[1:]):
        ops = [("busy", s, e - s) for s, e in
               busy[bisect.bisect_right(ends, a):
                    bisect.bisect_left(starts, b)]]
        while first < len(leaves) and \
                leaves[first][1] + leaves[first][2] <= a:
            first += 1
        piece = leaves[first:bisect.bisect_left(span_starts, b)]
        part = trace_reduce.Trace(
            {plane: ops}, {}, piece + [(trace_reduce.WINDOW_BEGIN, a, 0.0),
                                       (trace_reduce.WINDOW_END, b, 0.0)])
        for label, ns in trace_reduce.idle_gaps(part).items():
            out[label] += ns
    return dict(out)


def idle_share_under(obs, phase):
    """% of the traced window in which device 0 was idle under ``phase``
    (OUTSIDE: under no phase of the program).  None where the trace
    holds no span of the program."""
    host = spans(obs)
    if not host or not any(n in PHASES for n, _, _ in host):
        return None
    trace = obs["trace"]
    lo, hi = trace.window()
    return 100.0 * idle_by_phase(trace, host).get(phase, 0.0) / (hi - lo)


# -- device time by scope and kernel ------------------------------------------

@functools.lru_cache(maxsize=None)
def _scope_pattern(scopes):
    """A scope is one component of an ``op_name`` path, bare or inside
    the transformations' wrappers (``transpose(jvp(attn))``); a function
    or a parameter that happens to hold the word (``jit(mlp)``,
    ``model.layers_0.mlp.up_proj.weight``) is not."""
    return re.compile(r"^(?:(?!p?jit\()[a-z_]+\()*(%s)\)*$"
                      % "|".join(map(re.escape, scopes)))


@functools.lru_cache(maxsize=8)
def scope_by_instruction(hlo_text, scopes=SCOPES):
    """{instruction: scope} from a compiled program's text: the first of
    ``scopes`` (a tuple; an architecture's reader passes its own) among
    the ``op_name``s of the instruction and, for a fusion, of every
    instruction it holds.  Instructions under no scope are left out; an
    empty result says the program carries no scope at all."""
    scope_of = _scope_pattern(scopes).match
    own, calls, held = {}, {}, defaultdict(set)
    comp = None
    for line in hlo_text.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        found = set()
        for op_name in _OP_NAME.findall(line):
            found.update(hit.group(1) for hit in map(
                scope_of, op_name.split("/")) if hit)
        own[m.group(1)] = found
        held[comp] |= found
        called = _CALLS.search(line)
        if called:
            calls[m.group(1)] = called.group(1)
    out = {}
    for name, found in own.items():
        found = found | held.get(calls.get(name), set())
        for scope in scopes:
            if scope in found:
                out[name] = scope
                break
    return out


def program_scopes(obs, program, scopes=SCOPES):
    """scope_by_instruction of ``obs["programs"][program]``; None where
    there is no such program or it carries none of ``scopes``."""
    prog = (obs.get("programs") or {}).get(program)
    if prog is None:
        return None
    return scope_by_instruction(prog.as_text(), scopes) or None


def kernel_of(name):
    """``paged_attention.3`` -> ``paged_attention``: a Pallas call's
    event is named for the kernel."""
    return re.sub(r"[.\d]+$", "", name)


def ns_by_label(totals, label_of):
    """{label: ns} from ``trace_reduce.op_totals``' {operation: ns}."""
    out = defaultdict(float)
    for name, ns in totals.items():
        out[label_of(name)] += ns
    return dict(out)


def per_execution(trace, part, label_of):
    """[{label: ns}] for every execution inside the window of the
    programs whose name holds ``part``: own time of the operations that
    run inside it (``trace_reduce.op_totals`` over that stretch)."""
    lo, hi = trace.window()
    plane = trace.device0
    ops = sorted(trace.ops[plane], key=lambda e: e[1])
    starts = [s for _, s, _ in ops]
    out = []
    for name, s, d in trace.modules.get(plane, []):
        if part not in name or s < lo or s + d > hi:
            continue
        inside = ops[bisect.bisect_left(starts, s):
                     bisect.bisect_left(starts, s + d)]
        stretch = trace_reduce.Trace(
            {plane: inside}, {}, [(trace_reduce.WINDOW_BEGIN, s, 0.0),
                                  (trace_reduce.WINDOW_END, s + d, 0.0)])
        out.append(ns_by_label(trace_reduce.op_totals(stretch), label_of))
    return out


def scope_ms_per_step(obs, scope):
    """Device ms a train step spends under ``scope``: own time of the
    window's operations under it over the steps the window holds (the
    benchmark's ``bench.train_step`` spans)."""
    trace = obs.get("trace")
    scopes = program_scopes(obs, "train") if trace else None
    if scopes is None:
        return None
    lo, hi = trace.window()
    steps = [s for s, _ in trace_reduce.host_spans(trace, "bench.train_step")
             if lo <= s < hi]
    if not steps:
        return None
    by = ns_by_label(trace_reduce.op_totals(trace),
                     lambda n: scopes.get(n, UNSCOPED))
    return by.get(scope, 0.0) / len(steps) / 1e6
