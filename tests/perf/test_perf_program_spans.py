"""The readers of the program's own names (perf/program_spans.py and the
fifteen layer metrics over it) on hand-made tuples: idle time by
engine-step phase against ``trace_reduce.idle_share``, device time by
scope against a hand-written program text, the per-token gap tail from
hand-made stamps — and nothing at all from a program without the names."""

import os
import subprocess
import sys

import pytest

from perf import common, program_spans as ps, trace_reduce as tr

ROOT = common.ROOT
MS = 1e6    # ns
TPU = "/device:TPU:0"
IDLE = ("schedule", "admit", "build", "dispatch", "sync", "emit",
        "outside_step")
NEW = ([f"idle_{p}_share.tpot" for p in IDLE]
       + ["itl_p99_ms.tpot", "paged_attention_device_ms.tpot",
          "prefill_attn_device_ms.ttft", "attn_device_ms.train",
          "mlp_device_ms.train", "head_ce_device_ms.train",
          "optimizer_device_ms.train", "unscoped_device_share.train"])


def _reader(name):
    return common.load_by_path(os.path.join(
        ROOT, "perf", "layer_metrics", name + ".py"), "perf_layer_metric")


# -- idle time by engine-step phase -------------------------------------------

@pytest.fixture()
def serve_trace():
    """Two engine steps over a 100 ms window.  The device runs a decode
    program 10-40 and a prefill chunk 60-90; the host's phases tile each
    step, and 2 ms between the steps belong to the caller's loop."""
    ops = [("fusion.1", 10 * MS, 12 * MS), ("paged_attention.3", 22 * MS,
                                            6 * MS),
           ("fusion.2", 28 * MS, 12 * MS), ("fusion.7", 60 * MS, 30 * MS)]
    modules = [("jit_decode_paged(5)", 10 * MS, 30 * MS),
               ("jit_prefill_chunk(6)", 60 * MS, 30 * MS)]
    bench = [("bench.engine_step", 0, 48 * MS),
             ("bench.collect", 48 * MS, 2 * MS),
             ("bench.engine_step", 50 * MS, 50 * MS)]
    spans = [("serving.step", 0, 48 * MS),
             ("serving.schedule", 0, 2 * MS),
             ("serving.build", 2 * MS, 3 * MS),
             ("serving.dispatch", 5 * MS, 6 * MS),      # device starts at 10
             ("serving.sync", 11 * MS, 30 * MS),        # device done at 40
             ("serving.emit", 41 * MS, 7 * MS),
             ("serving.step", 50 * MS, 50 * MS),
             ("serving.schedule", 50 * MS, 1 * MS),
             ("serving.admit", 51 * MS, 4 * MS),
             ("serving.build", 55 * MS, 1 * MS),
             ("serving.prefill", 56 * MS, 43 * MS),
             ("serving.dispatch", 56 * MS, 5 * MS),     # device starts at 60
             ("serving.sync", 61 * MS, 38 * MS),        # device done at 90
             ("serving.emit", 99 * MS, 1 * MS)]
    return tr.Trace({TPU: ops}, {TPU: modules}, bench), spans


def test_idle_goes_to_the_leaf_phase_and_sums_to_the_idle_share(
        serve_trace):
    trace, spans = serve_trace
    got = ps.idle_by_phase(trace, spans)
    # 0-10 idle: schedule 2, build 3, dispatch 5.  40-60 idle: sync's tail
    # 1, emit 7, nobody 2 (48-50), schedule 1, admit 4, build 1, dispatch
    # 4.  90-100 idle: sync's tail 9, emit 1.  The window is cut at the
    # executions' middles (25, 75) only, so each gap stays whole and
    # goes to the leaf that covers most of it.
    lo, hi = trace.window()
    assert sum(got.values()) == pytest.approx(
        tr.idle_share(trace) * (hi - lo))
    assert sum(got.values()) == pytest.approx(40 * MS)
    # one gap, one label: 0-10 -> dispatch (5 of 10), 40-60 -> emit (7 of
    # 20), 90-100 -> sync (9 of 10)
    assert got == {"serving.dispatch": pytest.approx(10 * MS),
                   "serving.emit": pytest.approx(20 * MS),
                   "serving.sync": pytest.approx(10 * MS)}
    assert "serving.step" not in got and "serving.prefill" not in got


def test_a_gap_straddling_two_spans_goes_to_the_one_covering_most():
    trace = tr.Trace({TPU: [("fusion.1", 0, 10 * MS),
                            ("fusion.2", 20 * MS, 10 * MS)]}, {},
                     [("bench.engine_step", 0, 30 * MS)])
    spans = [("serving.emit", 9 * MS, 4 * MS),         # 3 of the gap
             ("serving.schedule", 13 * MS, 6 * MS),    # 6 of the gap
             ("serving.build", 19 * MS, 5 * MS)]       # 1 of the gap
    assert ps.idle_by_phase(trace, spans) == {
        "serving.schedule": pytest.approx(10 * MS)}
    # and a gap that no phase touches belongs to the caller's loop
    assert ps.idle_by_phase(trace, [("serving.emit", 0, 5 * MS)]) == {
        ps.OUTSIDE: pytest.approx(10 * MS)}


def test_the_seven_idle_readers_sum_to_the_idle_share(serve_trace,
                                                      monkeypatch):
    trace, spans = serve_trace
    monkeypatch.setattr(ps, "spans", lambda obs, trace_dir=None: spans)
    obs = {"trace": trace}
    shares = {p: _reader(f"idle_{p}_share.tpot").read(obs) for p in IDLE}
    assert all(v is not None for v in shares.values())
    assert sum(shares.values()) == pytest.approx(
        100.0 * tr.idle_share(trace))
    assert shares["dispatch"] == pytest.approx(10.0)
    assert shares["outside_step"] == 0.0
    # the window's own reader agrees: the same number, split seven ways
    assert sum(shares.values()) == pytest.approx(
        _reader("device_idle_share.tpot").read(obs))


def test_cutting_at_executions_costs_nothing_but_time():
    """Many executions, many spans: the pieces give what one pass of
    ``trace_reduce.idle_gaps`` over everything gives."""
    ops, modules, spans = [], [], []
    for i in range(40):
        t = i * 10 * MS
        ops += [(f"fusion.{i}", t + 2 * MS, 3 * MS),
                (f"fusion.{i}b", t + 5.5 * MS, 3.5 * MS)]
        modules.append(("jit_decode_paged(1)", t + 2 * MS, 7 * MS))
        spans += [("serving.schedule", t, 0.4 * MS),
                  ("serving.build", t + 0.4 * MS, 0.6 * MS),
                  ("serving.dispatch", t + MS, 1.5 * MS),
                  ("serving.sync", t + 2.5 * MS, 6.7 * MS),
                  ("serving.emit", t + 9.2 * MS, 0.7 * MS)]
    trace = tr.Trace({TPU: ops}, {TPU: modules},
                     [("bench.engine_step", 0, 400 * MS)])
    whole = tr.idle_gaps(tr.Trace({TPU: ops}, {}, spans + [
        (tr.WINDOW_BEGIN, 0, 0), (tr.WINDOW_END, 400 * MS, 0)]))
    got = ps.idle_by_phase(trace, spans)
    assert got == {k: pytest.approx(v) for k, v in whole.items()}


# -- device time by scope and kernel ------------------------------------------

HLO = '''HloModule jit__step_impl

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %t = f32[8]{0} tanh(%p), metadata={op_name="jit(_step_impl)/jvp(attn)/tanh"}
  ROOT %a = f32[8]{0} add(%t, %t), metadata={op_name="jit(_step_impl)/jvp(mlp)/add"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(_step_impl)/transpose(jvp(lm_head_ce))/mul"}
}

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%p.2), metadata={op_name="jit(_step_impl)/jit(mlp)/jit(fused_mlp)/neg"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="step_args[0][\'model.layers_0.mlp.up_proj.weight\']"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step_impl)/jvp(mlp)/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step_impl)/transpose(jvp(lm_head_ce))/mul"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %flash_fwd.4 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_impl)/jvp(attn)/flash_fwd/pallas_call"}
  %copy.9 = f32[8]{0} copy(%flash_fwd.4)
  ROOT %sub.5 = f32[8]{0} subtract(%copy.9, %x), metadata={op_name="jit(_step_impl)/optimizer/sub"}
}
'''


class _Program:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_scope_of_an_instruction_and_of_a_fusion_across_scopes():
    scopes = ps.scope_by_instruction(HLO)
    # fusion.1 holds attn and mlp: attn comes first in the stated order
    assert ps.SCOPES == ("lm_head_ce", "attn", "mlp", "optimizer", "embed")
    assert scopes["fusion.1"] == "attn"
    assert scopes["fusion.2"] == "lm_head_ce"
    assert scopes["flash_fwd.4"] == "attn"
    assert scopes["sub.5"] == "optimizer"
    # jit(mlp) is a function's name and .mlp. a parameter's, not the
    # scope; no metadata at all is no scope either
    assert not {"fusion.3", "copy.9", "x"} & set(scopes)
    assert ps.kernel_of("paged_attention.12") == "paged_attention"
    # the whole of it, as it read before the scopes were an argument;
    # naming the default tuple changes nothing
    assert scopes == ps.scope_by_instruction(HLO, ps.SCOPES) == {
        "t": "attn", "a": "mlp", "m": "lm_head_ce", "fusion.1": "attn",
        "fusion.2": "lm_head_ce", "flash_fwd.4": "attn", "sub.5": "optimizer"}


# an architecture's own scope: the add inside fusion.1 is the toy's shift
SHIFTED = HLO.replace("jvp(mlp)/add", "shift/add")


@pytest.mark.parametrize("scopes,fused", [
    (("shift", "attn", "mlp"), "shift"), (("attn", "shift", "mlp"), "attn"),
    (("shift", "mlp"), "shift")])
def test_scopes_are_the_readers_to_name(scopes, fused):
    """A reader of another architecture passes its own tuple: its scope is
    found, a fusion across two of them goes to the first of *that* tuple,
    and what the tuple leaves out is no scope."""
    got = ps.scope_by_instruction(SHIFTED, scopes)
    assert got["a"] == "shift" and got["fusion.1"] == fused
    assert ("flash_fwd.4" in got) == ("attn" in scopes)
    assert not {"fusion.2", "sub.5", "fusion.3", "x"} & set(got)
    # the default tuple knows no such scope and reads the rest as before
    assert "a" not in ps.scope_by_instruction(SHIFTED)
    obs = {"programs": {"decode": _Program(SHIFTED)}}
    assert ps.program_scopes(obs, "decode", scopes) == got
    assert ps.program_scopes(obs, "decode", ("router",)) is None


@pytest.fixture()
def train_obs():
    """Two steps in a 100 ms window, each: fusion.1 10, flash_fwd.4 6,
    fusion.2 12, copy.9 2 (unnamed), a while loop of 10 that holds
    sub.5 for 8 of them."""
    ops, host = [], []
    for t in (0, 50 * MS):
        ops += [("fusion.1", t, 10 * MS), ("flash_fwd.4", t + 10 * MS,
                                           6 * MS),
                ("fusion.2", t + 16 * MS, 12 * MS),
                ("copy.9", t + 28 * MS, 2 * MS),
                ("while.1", t + 30 * MS, 10 * MS),
                ("sub.5", t + 31 * MS, 8 * MS)]
        host.append(("bench.train_step", t, 45 * MS))
    return {"trace": tr.Trace({TPU: ops}, {TPU: []}, host),
            "programs": {"train": _Program(HLO)}}


def test_train_scope_readers_and_the_unscoped_share(train_obs):
    read = lambda name: _reader(name).read(train_obs)
    assert read("attn_device_ms.train") == pytest.approx(16.0)
    assert read("head_ce_device_ms.train") == pytest.approx(12.0)
    assert read("optimizer_device_ms.train") == pytest.approx(8.0)
    assert read("mlp_device_ms.train") == 0.0
    # copy.9 (2) and the while's own time (10 - 8) of 40 busy a step
    assert read("unscoped_device_share.train") == pytest.approx(10.0)


def test_serve_kernel_and_scope_inside_one_execution(serve_trace):
    trace, _ = serve_trace
    named = _Program(HLO.replace("%fusion.2 =", "%fusion.7 ="))
    obs = {"trace": trace, "programs": {"decode": named,
                                        "prefill_chunk": named}}
    assert _reader("paged_attention_device_ms.tpot").read(obs) == \
        pytest.approx(6.0)
    # fusion.7 (lm_head_ce) is all the chunk runs: nothing under attn
    assert _reader("prefill_attn_device_ms.ttft").read(obs) == 0.0
    attn = _Program(HLO.replace("%fusion.1 =", "%fusion.7 ="))
    obs["programs"]["prefill_chunk"] = attn
    assert _reader("prefill_attn_device_ms.ttft").read(obs) == \
        pytest.approx(30.0)


# -- the per-token gap tail ---------------------------------------------------

def test_itl_p99_from_hand_made_stamps(monkeypatch):
    from paddle_tpu.observability import tracing
    t = tracing.Tracer(sample=1.0)
    monkeypatch.setattr(tracing, "_TRACER", t)

    def request(rid, stamps):
        root = t.start_span("serving.request", rid=rid)
        root.set_attribute("token_stamps", stamps)
        root.end()

    request(0, [(9.0, 1), (9.5, 1)])            # an older engine's rid 0
    request(0, [(1.0, 1), (1.010, 1), (1.020, 1), (1.100, 2)])
    request(1, [(2.0, 1), (2.010, 1)])
    request(2, [(3.0, 1), (5.0, 1)])            # due after the profiler
    request(3, [(4.0, 1)])                      # one token: no gap
    obs = {"untraced_until": 30.0, "requests": [
        {"rid": 0, "ok": True, "due_s": 1.0},
        {"rid": 1, "ok": True, "due_s": 2.0},
        {"rid": 2, "ok": True, "due_s": 31.0},
        {"rid": 3, "ok": True, "due_s": 3.0},
        {"rid": 4, "ok": False, "due_s": 4.0}]}
    # gaps: 10, 10, 40, 40 (80 ms over 2 tokens), 10
    import numpy as np
    assert _reader("itl_p99_ms.tpot").read(obs) == pytest.approx(
        float(np.percentile([10, 10, 40, 40, 10], 99)))


# -- a program without the names, and the harness -----------------------------

@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name, serve_trace, tmp_path, monkeypatch):
    from paddle_tpu.observability import tracing
    monkeypatch.setattr(tracing, "_TRACER", tracing.Tracer(sample=1.0))
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path))
    read = _reader(name).read
    # an untraced run; then a traced run of a program that has neither
    # spans nor scopes nor stamps (the parent of the PR that added them)
    bare = {"trace": None, "requests": [], "programs": {}}
    assert read(bare) is None
    plain = _Program(HLO.replace("attn", "a").replace("mlp", "m")
                     .replace("lm_head_ce", "h").replace("optimizer", "o"))
    parent = {"trace": serve_trace[0],
              "requests": [{"rid": 0, "ok": True, "due_s": 0.0}],
              "programs": {"train": plain, "decode": plain,
                           "prefill_chunk": plain}}
    assert read(parent) is None


def test_run_list_resolves_the_new_entries(tree, bench):
    """On both benchmarks (conftest.py): a metric may have more cells than
    the one it was written for, and every cell on its list resolves it."""
    out = subprocess.run([sys.executable, "perf/run.py", "--list"],
                         cwd=tree, capture_output=True, text=True,
                         check=True).stdout
    listed = {line.split(":")[0]:
              line.rsplit("layer metrics ", 1)[1].split(",")
              for line in out.splitlines()}
    assert set(listed) == {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        cell = "train-1chip" if name.endswith(".train") else "serve-chat"
        assert cell in entries[name]["workloads"]
        assert all(name in listed[c] for c in entries[name]["workloads"])
        assert "roofline" not in name and "mfu" not in name
