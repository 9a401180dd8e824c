"""perf/pipeline_spans.py and the seven readers over it: on hand-made
tuples every idle gap is split by time into what the host was doing, the
four parts sum to the idle share, the copy-back and the step's own work
read the hand-made values; a trace without ``seq`` reads nothing; the
reduction's time grows with the trace and not with gaps x spans; and on
the CPU profiler's own host plane the engine's numbered spans come back
as written."""

import os
import time

import numpy as np
import pytest

from perf import common, pipeline_spans as pl, program_spans, \
    trace_reduce as tr

MS = 1e6    # ns
TPU = "/device:TPU:0"
SEVEN = ("device_fed_share.tpot", "step_host_work_ms.tpot",
         "copyback_ms.tpot", "idle_host_blocked_share.tpot",
         "idle_host_working_share.tpot", "idle_upload_share.tpot",
         "idle_launch_share.tpot")
FOUR = SEVEN[3:]


def _reader(name):
    return common.load_by_path(os.path.join(
        common.ROOT, "perf", "layer_metrics", name + ".py"),
        "perf_layer_metric")


def _ev(name, start, dur, seq=None, kind=None):
    return (name, start * MS, dur * MS, seq, kind)


@pytest.fixture()
def pipeline():
    """A 100 ms window.  The device runs a decode step 10-30 (with a 1 ms
    bubble between its two operations), a prefill chunk 44-70 and a decode
    step 80-95.  Four engine steps: each gap before an execution lies
    across a sync's tail, an emit, the next dispatch and its launch."""
    ops = [("fusion.1", 10 * MS, 10 * MS), ("fusion.2", 21 * MS, 9 * MS),
           ("fusion.3", 44 * MS, 26 * MS), ("fusion.4", 80 * MS, 15 * MS)]
    modules = [("jit_decode_paged(1)", 10 * MS, 20 * MS),
               ("jit_prefill_chunk(2)", 43.5 * MS, 26.5 * MS),
               ("jit_decode_paged(3)", 80 * MS, 15 * MS)]
    host = [
        _ev(pl.STEP, 0, 9),
        _ev(pl.DISPATCH, 5, 4, 1, "decode"),        # device starts at 10
        _ev(pl.STEP, 12, 31),
        _ev(pl.SYNC, 14, 19, 1),    # device done at 30, tokens at 33
        _ev(pl.DISPATCH, 38, 4, 2, "prefill_chunk"),    # starts at 44
        _ev(pl.STEP, 46, 28),
        _ev(pl.SYNC, 47, 25, 2),    # the last chunk's first token, at 72
        _ev(pl.STEP, 75, 24),
        _ev(pl.DISPATCH, 76, 3, 3, "decode"),       # starts at 80
        _ev(pl.SYNC, 81, 16, 3)]    # device done at 95, tokens at 97
    trace = tr.Trace({TPU: ops}, {TPU: modules},
                     [("bench.engine_step", 0, 100 * MS)])
    return trace, tuple(host)


def test_a_gap_is_split_by_time_into_what_the_host_was_doing(pipeline):
    trace, host = pipeline
    got = pl.reduce(trace, host)
    # 0-10: working 5, upload 4, launch 1.  20-21: the program's own
    # bubble.  30-44: the sync's tail 3, emit and build 5, upload 4,
    # launch 2.  70-80: sync 2, working 4, upload 3, launch 1.  95-100
    # ends at no execution: the host's, whole.
    assert got["idle_ns"] == {
        "host_blocked": pytest.approx(5 * MS),
        "host_working": pytest.approx(19 * MS),
        "upload": pytest.approx(11 * MS),
        "launch": pytest.approx(5 * MS)}
    assert got["bubbles_ns"] == pytest.approx(1 * MS)
    # of the host's 19, 74-75 and 99-100 lie between two steps
    assert got["no_step_ns"] == pytest.approx(2 * MS)
    assert sum(got["idle_ns"].values()) == pytest.approx(
        tr.idle_share(trace) * got["window_ns"])
    assert (got["executions"], got["breaks"]) == (3, 0)
    # seq 1: tokens at 33, device done at 30; seq 3: 97 against 95; the
    # chunk's read (seq 2) is no decode dispatch's
    assert sorted(got["copyback_ns"]) == pytest.approx([2 * MS, 3 * MS])
    # the steps that hold a decode dispatch: 9 ms with no sync, 24 - 16
    assert sorted(got["step_work_ns"]) == pytest.approx([8 * MS, 9 * MS])


def test_the_readers_read_the_hand_made_values(pipeline, monkeypatch):
    trace, host = pipeline
    monkeypatch.setattr(pl, "events", lambda obs, trace_dir=None: host)
    monkeypatch.setattr(common, "series", lambda name: {
        "decode/fed": 90.0, "decode/drained": 6.0,
        "prefill_chunk/fed": 3.0, "prefill_chunk/drained": 1.0}
        if name == pl.DISPATCHES else {})
    obs = {"trace": trace}
    got = {name: _reader(name).read(obs) for name in SEVEN}
    assert got == {"device_fed_share.tpot": pytest.approx(93.0),
                   "step_host_work_ms.tpot": pytest.approx(8.5),
                   "copyback_ms.tpot": pytest.approx(2.5),
                   "idle_host_blocked_share.tpot": pytest.approx(5.0),
                   "idle_host_working_share.tpot": pytest.approx(19.0),
                   "idle_upload_share.tpot": pytest.approx(11.0),
                   "idle_launch_share.tpot": pytest.approx(5.0)}
    # the window's own reader agrees: the same number, split four ways
    assert sum(got[name] for name in FOUR) == pytest.approx(
        _reader("device_idle_share.tpot").read(obs))
    # one reduction a run, kept on obs
    assert obs["_pipeline"] is pl.reduced(obs)


def test_a_window_cuts_gaps_and_keeps_only_its_own_pairs(pipeline):
    trace, host = pipeline
    trace.host = [(tr.WINDOW_BEGIN, 32 * MS, 0), (tr.WINDOW_END, 78 * MS, 0),
                  ("bench.engine_step", 0, 100 * MS)]
    got = pl.reduce(trace, host)
    # 32-44: sync 1, working 5, upload 4, launch 2; 70-78 is cut by the
    # window before the execution it ends at: the host's, whole
    assert got["idle_ns"] == {
        "host_blocked": pytest.approx(1 * MS),
        "host_working": pytest.approx(13 * MS),
        "upload": pytest.approx(4 * MS), "launch": pytest.approx(2 * MS)}
    assert sum(got["idle_ns"].values()) == pytest.approx(
        tr.idle_share(trace) * got["window_ns"])
    assert got["executions"] == 1       # the chunk alone lies inside
    assert not len(got["copyback_ns"]) and not len(got["step_work_ns"])
    monkey = {"trace": trace, "_pipeline": got}
    assert _reader("copyback_ms.tpot").read(monkey) is None
    assert _reader("step_host_work_ms.tpot").read(monkey) is None


def test_a_dispatch_out_of_order_is_counted(pipeline):
    trace, host = pipeline
    swapped = tuple(e if e[0] != pl.DISPATCH or e[3] != 3
                    else (*e[:3], 7, e[4]) for e in host)
    assert pl.reduce(trace, swapped)["breaks"] == 1
    # pairing is from the trace's end: a dispatch the trace holds no
    # execution of (issued before the profiler saw the device) is left out
    earlier = (_ev(pl.DISPATCH, -9, 1, 0, "decode"),) + host
    got = pl.reduce(trace, earlier)
    assert (got["executions"], got["breaks"]) == (3, 0)
    assert got["idle_ns"] == pl.reduce(trace, host)["idle_ns"]


def test_a_trace_without_seq_reads_nothing(pipeline, monkeypatch, tmp_path):
    trace, host = pipeline
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(b"")
    unnumbered = tuple((*e[:3], None, None) for e in host)
    monkeypatch.setattr(program_spans, "find_xplane",
                        lambda trace_dir=None: str(path))
    monkeypatch.setattr(pl, "_host_events", lambda path, mtime: unnumbered)
    monkeypatch.setattr(common, "series", lambda name: {})
    assert pl.events({"trace": trace}) is None
    assert {name: _reader(name).read({"trace": trace})
            for name in SEVEN} == dict.fromkeys(SEVEN)
    # and without a trace, or without a profile, nothing either
    assert pl.events({"trace": None}) is None
    monkeypatch.setattr(program_spans, "find_xplane",
                        lambda trace_dir=None: None)
    assert pl.events({"trace": trace}) is None
    # the numbered spans of the same run are found
    monkeypatch.setattr(program_spans, "find_xplane",
                        lambda trace_dir=None: str(path))
    monkeypatch.setattr(pl, "_host_events", lambda path, mtime: host)
    assert pl.events({"trace": trace}) == host


def _steps(n, ops_a_step):
    """``n`` decode steps of ``ops_a_step`` operations, 10 us apart with
    6 us busy, the host a step ahead as the pipeline keeps it."""
    per = ops_a_step * 10e3
    ops = [("fusion.1", i * 10e3, 6e3) for i in range(n * ops_a_step)]
    modules = [("jit_decode_paged(1)", k * per, per - 4e3)
               for k in range(n)]
    host = []
    for k in range(n):
        t = (k - 1) * per
        host += [(pl.STEP, t, per - 1e3, None, None),
                 (pl.DISPATCH, t + 1e3, 2e3, k + 1, "decode"),
                 (pl.SYNC, t + 4e3, per - 6e3, k, None)]
    return tr.Trace({TPU: ops}, {TPU: modules}, []), tuple(host)


def test_the_reduction_is_linear_in_the_trace():
    """A million busy intervals (a decode step is ~1000 operations) in
    seconds, and four times the trace in about four times the time: the
    accepted ``trace_reduce.busy_inside`` sets every span against every
    interval and takes minutes here."""
    took = {}
    for n in (250, 1000):
        trace, host = _steps(n, 1000)
        best = float("inf")
        for _ in range(2):
            t = time.perf_counter()
            got = pl.reduce(trace, host)
            best = min(best, time.perf_counter() - t)
        took[n] = best
        assert got["executions"] == n and got["breaks"] == 0
        # between two operations 4 us of nothing, the program's own
        assert sum(got["idle_ns"].values()) == pytest.approx(
            (n * 1000 - 1) * 4e3)
        assert got["bubbles_ns"] == pytest.approx(n * 999 * 4e3)
    assert took[1000] < 15.0, took
    assert took[1000] < 8 * took[250] + 0.5, took


# -- the engine's own spans, through the CPU profiler -------------------------

@pytest.fixture(scope="module")
def engine_profile(tmp_path_factory):
    """A tiny engine driven under the CPU profiler: the run's numbered
    spans as pipeline_spans reads them from the .xplane.pb."""
    import jax
    import paddle_tpu as pp
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128))
    eng = ContinuousBatchingEngine(model, slots=4, max_len=64,
                                   kv_block_size=8, prefill_chunk=16,
                                   prefill_buckets=(16,))
    rng = np.random.default_rng(3)
    logdir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(logdir)
    try:
        for n in (5, 21, 9):
            eng.add_request(rng.integers(0, 128, (n,)), max_new_tokens=6)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = program_spans.find_xplane(logdir)
    return pl._host_events(path, os.path.getmtime(path))


def test_the_engines_spans_keep_their_numbers_on_the_host_plane(
        engine_profile):
    host = engine_profile
    disp = [e for e in host if e[0] == pl.DISPATCH]
    assert [e[3] for e in disp] == list(range(disp[0][3],
                                              disp[0][3] + len(disp)))
    assert {e[4] for e in disp} == {"decode", "prefill_chunk"}
    kind = {e[3]: e[4] for e in disp}
    syncs = [e for e in host if e[0] == pl.SYNC]
    # every decode dispatch is read by a sync that names it, a chunk's
    # only when it was the prompt's last: three prompts, three reads
    read = [kind[e[3]] for e in syncs]
    assert read.count("decode") == list(kind.values()).count("decode")
    assert read.count("prefill_chunk") == 3
    # and a sync never starts before the dispatch it reads
    began = {e[3]: e[1] for e in disp}
    assert all(e[1] >= began[e[3]] for e in syncs)
    assert any(e[0] == pl.STEP for e in host)


def test_the_four_parts_sum_to_the_idle_share_on_a_profiled_run(
        engine_profile, monkeypatch):
    """The CPU profiler gives the host plane; executions stand in behind
    each dispatch (the CPU trace has no device plane), as long as the
    program they belong to would take."""
    host = engine_profile
    ops, modules, free = [], [], 0.0
    for e in host:
        if e[0] != pl.DISPATCH:
            continue
        start = max(free, e[1] + 0.7 * e[2])
        dur = 40e3 if e[4] == "decode" else 90e3
        name = "jit_decode_paged" if e[4] == "decode" else \
            "jit_prefill_chunk"
        modules.append((f"{name}({e[3]})", start, dur))
        ops += [("fusion.1", start + 1e3, dur / 2 - 2e3),
                ("fusion.2", start + dur / 2, dur / 2)]
        free = start + dur
    lo = min(e[1] for e in host)
    trace = tr.Trace({TPU: ops}, {TPU: modules},
                     [("bench.engine_step", lo, free + 1e5 - lo)])
    monkeypatch.setattr(pl, "events", lambda obs, trace_dir=None: host)
    obs = {"trace": trace}
    parts = {name: _reader(name).read(obs) for name in FOUR}
    assert all(v is not None and 0.0 <= v <= 100.0 for v in parts.values())
    assert sum(parts.values()) == pytest.approx(
        _reader("device_idle_share.tpot").read(obs), abs=0.1)
    got = pl.reduced(obs)
    assert got["executions"] == len(modules) and got["breaks"] == 0
    assert parts["idle_upload_share.tpot"] > 0
    assert _reader("copyback_ms.tpot").read(obs) > 0
    assert _reader("step_host_work_ms.tpot").read(obs) > 0
