"""The engine's pipeline in a profiler trace: every program it hands the
device is a ``serving.dispatch`` span that carries ``seq`` and ``kind`` as
the event's stats, the ``serving.sync`` that waits for its output carries
the same ``seq``, and executions run in dispatch order, so the k-th
dispatch from the trace's end is the k-th execution of the engine's
programs from its end (the profiler stops after the drain).  ``seq`` makes
the pairing checkable: consecutive, the kinds agreeing, no hole.

One reduction a run, shared by the readers over it (kept on ``obs``): the
host plane is read once, and every step is a sort, a bisect over sorted
intervals or a prefix sum, so the cost grows with the trace and not with
gaps x spans.

Each idle gap of device 0 is split BY TIME between what the host was
doing in it, seen from the dispatch that enqueued the execution the gap
ends at: before that dispatch began the host was either inside a
``serving.sync`` (``host_blocked``: nothing to run, and the host waits for
a copy) or doing anything else (``host_working``: emit, the caller's loop,
schedule, admit, build); inside the dispatch span it was uploading and
calling (``upload``); after the span ended the rest is the runtime's and
the program's own (``launch``: the launch, and the bubbles between one
execution's operations).  A gap that ends at no execution of the engine's
is ``host_working`` whole.  The four sum to the window's idle time.

Pure functions over tuples and arrays like ``trace_reduce``'s; nanoseconds
on the trace's own clock.  A program that writes no ``seq`` (the parent of
the PR that added it) reads as ``None`` everywhere.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from perf import common, program_spans

STEP, DISPATCH, SYNC = "serving.step", "serving.dispatch", "serving.sync"
# a dispatch's ``kind`` is part of its program's name in the trace
KINDS = ("decode", "prefill_chunk", "spec_verify")
IDLE_PARTS = ("host_blocked", "host_working", "upload", "launch")
DISPATCHES = "paddle_tpu_serving_dispatches_total"


# -- the host plane, once -----------------------------------------------------

@functools.lru_cache(maxsize=2)
def _host_events(path, _mtime):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (STEP, DISPATCH, SYNC):
                    stats = dict(e.stats) if e.name != STEP else {}
                    out.append((e.name, float(e.start_ns),
                                float(e.duration_ns), stats.get("seq"),
                                stats.get("kind")))
    return tuple(sorted(out, key=lambda e: e[1]))


def events(obs, trace_dir=None):
    """((name, start_ns, dur_ns, seq, kind), ...) of the run's
    ``serving.step`` / ``.dispatch`` / ``.sync`` spans, by start; None
    without a trace or where no dispatch carries a ``seq``."""
    if not obs.get("trace"):
        return None
    path = program_spans.find_xplane(trace_dir)
    if path is None:
        return None
    found = _host_events(path, os.path.getmtime(path))
    numbered = any(e[0] == DISPATCH and e[3] is not None for e in found)
    return found if numbered else None


# -- the reduction ------------------------------------------------------------

def _merged(starts, ends):
    """Sorted disjoint (starts, ends) covering the same time."""
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    if not len(starts):
        return starts, ends
    reach = np.maximum.accumulate(ends)
    first = np.concatenate(([True], starts[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return starts[first], reach[last]


def _covered_before(starts, ends):
    """t -> the time sorted disjoint intervals cover before ``t``."""
    if not len(starts):
        return lambda t: np.zeros(np.shape(t))
    total = np.concatenate(([0.0], np.cumsum(ends - starts)))

    def at(t):
        began = np.searchsorted(starts, t, side="right")
        j = np.maximum(began - 1, 0)    # the last that began: up to t
        return np.where(began > 0, total[j] + np.clip(
            t - starts[j], 0.0, ends[j] - starts[j]), 0.0)
    return at


def pair(modules, host, lo, hi):
    """The engine's executions with the dispatch that enqueued each, as
    arrays by execution start: ``start`` / ``end`` of the execution,
    ``d_start`` / ``d_end`` of its dispatch span, ``seq``, ``kind`` (an
    index into KINDS), ``inside`` (the execution lies in the window).
    Kind by kind, the k-th dispatch from the trace's end with the k-th
    execution from its end; ``breaks`` counts the places inside the
    window where the numbers of two executions in a row are not
    consecutive — with none, host and device saw one order."""
    rows = []
    for k, kind in enumerate(KINDS):
        runs = sorted((s, s + d) for name, s, d in modules if kind in name)
        disp = [e for e in host if e[0] == DISPATCH and e[4] == kind
                and e[3] is not None]
        n = min(len(runs), len(disp))
        rows += [(s, e, d[1], d[1] + d[2], d[3], k) for (s, e), d in
                 zip(runs[len(runs) - n:], disp[len(disp) - n:])]
    cols = np.array(sorted(rows), float).reshape(-1, 6).T
    out = dict(zip(("start", "end", "d_start", "d_end"), cols[:4]))
    out["seq"], out["kind"] = cols[4].astype(np.int64), \
        cols[5].astype(np.int64)
    out["inside"] = (out["start"] >= lo) & (out["end"] <= hi)
    out["breaks"] = int((np.diff(out["seq"][out["inside"]]) != 1).sum())
    return out


def reduce(trace, host):
    """The window of ``trace`` against the engine's spans ``host``:
    ``window_ns``; ``idle_ns`` {part: ns} over IDLE_PARTS, summing to the
    idle time of device 0; ``bubbles_ns``, the part of ``launch`` that lies
    between one execution's own operations; ``no_step_ns``, the part of
    ``host_working`` outside every ``serving.step`` (no request pending,
    or the caller's loop); ``copyback_ns`` a decode
    dispatch of the window (its sync's end - the later of the sync's start
    and its execution's end); ``step_work_ns`` a ``serving.step`` of the
    window that holds a decode dispatch (the span - its syncs);
    ``executions``, how many of the engine's the window holds, and
    ``breaks``, how many times their numbers are not consecutive."""
    lo, hi = trace.window()
    plane = trace.device0
    ops = trace.ops[plane]
    t0 = np.fromiter((ev[1] for ev in ops), float, len(ops))
    t1 = t0 + np.fromiter((ev[2] for ev in ops), float, len(ops))
    t0, t1 = np.clip(t0, lo, hi), np.clip(t1, lo, hi)
    b0, b1 = _merged(t0[t1 > t0], t1[t1 > t0])
    ga, gb = np.concatenate(([lo], b1)), np.concatenate((b0, [hi]))
    ga, gb = ga[gb > ga], gb[gb > ga]

    p = pair(trace.modules.get(plane, []), host, lo, hi)
    syncs = [ev for ev in host if ev[0] == SYNC]
    steps = np.array([(ev[1], ev[1] + ev[2]) for ev in host
                      if ev[0] == STEP], float).reshape(-1, 2)
    sync_before = _covered_before(*_merged(
        np.array([ev[1] for ev in syncs], float),
        np.array([ev[1] + ev[2] for ev in syncs], float)))
    step_before = _covered_before(*_merged(steps[:, 0], steps[:, 1]))

    # the execution each gap ends at: the last one that began by then
    i = np.searchsorted(p["start"], gb, side="right") - 1
    ends_at = i >= 0
    ends_at[ends_at] = gb[ends_at] < p["end"][i[ends_at]]
    i = i[ends_at]
    a, b = ga[ends_at], gb[ends_at]
    ds, de = p["d_start"][i], p["d_end"][i]
    before = np.minimum(b, np.maximum(a, ds))       # the host's own part
    blocked = sync_before(before) - sync_before(a)
    upload = np.maximum(0.0, np.minimum(b, de) - np.maximum(a, ds))
    launch = np.maximum(0.0, b - np.maximum(a, de))
    idle = {"host_blocked": float(blocked.sum()),
            "host_working": float((before - a - blocked).sum()
                                  + (gb - ga)[~ends_at].sum()),
            "upload": float(upload.sum()), "launch": float(launch.sum())}
    # the host's own stretches of the gaps: [a, before], and a gap that
    # ends at no execution whole
    own0 = np.concatenate((a, ga[~ends_at]))
    own1 = np.concatenate((before, gb[~ends_at]))
    no_step = (own1 - own0 - (step_before(own1) - step_before(own0))).sum()

    inside = p["inside"]
    decode = inside & (p["kind"] == KINDS.index("decode"))
    read = {ev[3]: (ev[1], ev[1] + ev[2]) for ev in syncs
            if ev[3] is not None}
    copyback = [read[q][1] - max(read[q][0], end)
                for q, end in zip(p["seq"][decode].tolist(),
                                  p["end"][decode].tolist()) if q in read]

    # a step is the window's by the execution its decode dispatch caused
    issued = np.sort(p["d_start"][decode])
    holds = np.searchsorted(issued, steps[:, 1]) > \
        np.searchsorted(issued, steps[:, 0])
    steps = steps[holds]
    work = steps[:, 1] - steps[:, 0] - (sync_before(steps[:, 1])
                                        - sync_before(steps[:, 0]))
    return {"window_ns": hi - lo, "idle_ns": idle,
            "bubbles_ns": float((b - a)[a >= p["start"][i]].sum()),
            "no_step_ns": float(no_step),
            "copyback_ns": np.array(copyback, float),
            "step_work_ns": work, "executions": int(inside.sum()),
            "breaks": p["breaks"]}


def reduced(obs):
    """``reduce`` of the run's trace, once a run (kept on ``obs``); None
    without a trace or without a numbered dispatch."""
    if "_pipeline" not in obs:
        host = events(obs)
        got = obs["_pipeline"] = reduce(obs["trace"], host) if host \
            else None
        if got:
            common.say(f"pipeline: {got['executions']} executions of the "
                       f"engine's in the window, {got['breaks']} breaks "
                       f"in their dispatches' numbers; of the idle time "
                       f"{got['no_step_ns'] / 1e6:.1f} ms lay outside "
                       f"every serving.step and "
                       f"{got['bubbles_ns'] / 1e6:.1f} ms between one "
                       f"execution's own operations")
    return obs["_pipeline"]


# -- what the readers return --------------------------------------------------

def idle_share(obs, part):
    """% of the traced window in which device 0 was idle and the host was
    in ``part`` (one of IDLE_PARTS)."""
    got = reduced(obs)
    return None if got is None else \
        100.0 * got["idle_ns"][part] / got["window_ns"]


def median_ms(obs, key):
    got = reduced(obs)
    if got is None or not len(got[key]):
        return None
    return float(np.median(got[key]) / 1e6)


def fed_share():
    """% of the programs the engine handed the device, every kind, since
    the process began, that found it ``fed``; None where the program
    counts no such thing."""
    by = common.series(DISPATCHES)
    issued = sum(by.values())
    if not issued:
        return None
    return 100.0 * sum(v for k, v in by.items()
                       if k.split("/")[-1] == "fed") / issued
