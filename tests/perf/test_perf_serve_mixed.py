"""The cell ``serve-mixed`` (architecture ``afmoe``) end to end on the CPU
at a test's size, through ``kinds/serve.py``'s real control flow: gated
grouped-query attention, three sliding-window layers (rotary, a ring of
blocks a request in the window group) to one full layer (no rotary, the
full group) under ``ContinuousBatchingEngine``, the plain reference of
perf/archs/, every metric the cell lists — the int8 control of the same
reference, a window the program gets wrong that must read not correct,
the configuration's file against the contract and the arch file's counts,
and the five new readers on a hand-made trace and the engine's gauges."""

import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from perf import common, flops

CELL = "serve-mixed"
# every width a test's size; the kinds (S S S F S, a dense layer first),
# the router's width (published) over the experts held, a window the
# prompts pass several times and the untied head stay
TINY = dict(hidden_size=64, num_hidden_layers=5, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            num_experts=4, num_experts_per_tok=4,
            published={"num_experts": 16}, vocab_size=256,
            max_position_embeddings=128, torch_dtype="float32")
MIX = {"params": {"rate_per_s": 20.0, "schedule_seed": 1,
                  "prompt": {"median": 20, "sigma": 0.8, "min": 8,
                             "max": 60},
                  "output": {"median": 8, "sigma": 0.7, "min": 2,
                             "max": 16}},
       "system": {"engine": {"slots": 4, "max_len": 96, "kv_block_size": 4,
                             "num_kv_blocks": 97, "num_window_blocks": 25,
                             "prefill_chunk": 8, "steps_per_sync": 2}}}


def _bench():
    return common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))


def _cell():
    bench = _bench()
    cell = common.resolve_cell(bench, CELL)
    cell["config"].update(TINY)
    cell["traffic"]["params"] = MIX["params"]
    cell["traffic"]["system"] = MIX["system"]
    return bench, cell


def _args(trace):
    return argparse.Namespace(seed=2 ** 31 + 46, seconds=2.0, trace=trace)


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- the files against the contract -------------------------------------------

def test_the_configuration_is_the_catalogs_with_its_cuts_listed():
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}[
        "trinity-large-preview.L5"]
    cfg = common.load_json(os.path.join(common.ROOT, entry["file"]))
    assert cfg["source"] == entry["source"]
    cut = {"num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"}
    assert set(cfg["published"]) == set(entry["reduced"]) == cut
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (60, 6, 256, 200192)
    assert len(pub["layer_types"]) == 60 and \
        pub["layer_types"].count("sliding_attention") == 45
    assert all(cfg[k] != pub[k] for k in cut)
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 16, 25024)
    assert cfg["layer_types"] == pub["layer_types"][:5]
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["num_experts_per_tok"],
            cfg["route_scale"]) == (3072, 12288, 3072, 48, 8, 128, 4096,
                                    4, 2.448)
    assert cfg["assumed"] and "16 v5e" in cfg["deployment"]
    arch = common.arch_of(cfg)
    # one whole period at the published 3 : 1 behind the dense layer
    assert [arch.layer_kind(cfg, i) for i in range(5)] == [
        "window+dense", "window+experts", "window+experts",
        "full+experts", "window+experts"]


def test_the_arch_files_counts_are_the_leaves():
    """``flops.total_params`` is the sum of the leaves at the cut (2.51 B)
    and for the whole model (the published '400B'); a token meets 4 x 16
    / 256 of a layer's held experts; the cache counts are a key and a
    value a layer, cut at the window where a layer keeps one."""
    cfg = common.load_json(os.path.join(
        common.ROOT, "perf/configs/trinity-large-preview.L5.json"))
    arch = common.arch_of(cfg)
    held = sum(int(np.prod(s)) for _, s, _ in arch.leaves(cfg))
    assert flops.total_params(cfg) == held == 2509964544
    whole = dict(cfg, **cfg["published"], published={})
    assert flops.total_params(whole) == sum(
        int(np.prod(s)) for _, s, _ in arch.leaves(whole))
    assert 395e9 < flops.total_params(whole) < 405e9
    expert = 3 * 3072 * 3072
    assert flops.layer_matmul_params(cfg, 1) - flops.layer_matmul_params(
        dict(cfg, num_experts_per_tok=0), 1) == 0.25 * expert
    assert flops.layer_matmul_params(cfg, 0) == arch._dense_params(cfg, 0)
    assert arch._attn_params(cfg) == 62914560 + 2 * 128 + 4 * 3072
    assert arch.kv_layer_bytes(cfg) == 4096
    assert flops.kv_bytes_per_token(cfg) == 5 * 4096
    assert arch.window_decode_bytes(cfg, 1000.0) == 4 * 1000 * 4096
    # a chunk of 512 from position 8192: every pair in the full layer,
    # 4096 a query in the window layers
    full = 512 * 8192 + 512 * 513 // 2
    assert arch.walk_cost(cfg, 8192, 512) == \
        (full + 4 * 512 * 4096) * 4 * 48 * 128
    assert arch.walk_cost(cfg, 0, 512) == 5 * (512 * 513 // 2) * 4 * 48 * 128
    # a decode step without the program's sums leaves the window layers
    # out; what it counts for the full layer is the harness's tokens
    step = arch.decode_step_bytes(cfg, 0.0)
    assert arch.decode_step_bytes(cfg, 1000.0) - step == 1000 * 4096


def test_run_list_resolves_the_cell():
    out = subprocess.run([sys.executable, "perf/run.py", "--list"],
                         cwd=common.ROOT, capture_output=True, text=True,
                         check=True).stdout
    line = [ln for ln in out.splitlines() if ln.startswith(CELL + ":")]
    assert len(line) == 1
    assert "arch perf/archs/afmoe.py" in line[0]
    assert "traffic perf/traffic/mixed-open-0.8.json" in line[0]
    listed = line[0].rsplit("layer metrics ", 1)[1].split(",")
    bench = _bench()
    assert listed == [m["name"] for m in common.metrics_of(
        bench, "per_layer", CELL)]
    for name in ("window_attn_device_ms.tpot", "window_decode_roofline.tpot",
                 "attn_walk_roofline.ttft", "kv_window_held_share.ttft",
                 "kv_group_peak_share.ttft"):
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert entry["workloads"] == [CELL] and name in listed
    # it divides by one pool: not this cell's
    assert "kv_blocks_peak_share.ttft" not in listed
    assert "paged_attention_device_ms.tpot" in listed
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1
    mix = common.load_json(os.path.join(
        common.ROOT, "perf/traffic/mixed-open-0.8.json"))
    eng, p = mix["system"]["engine"], mix["params"]
    # the longest prompt with the longest output fits a slot's table
    assert eng["max_len"] >= p["prompt"]["max"] + p["output"]["max"] + 1
    assert eng["max_len"] % eng["kv_block_size"] == 0
    # whole rings: 21 of 289 blocks and the scratch block fit the group
    ring = -(-(4096 + eng["prefill_chunk"]) // eng["kv_block_size"]) + 1
    assert ring == 289 and (eng["num_window_blocks"] - 1) // ring == 21
    # the rate is 0.8 x the highest rate the sweep's own flag calls
    # sustained with every lower one, and nothing else
    sweep = mix["knee_sweep"]
    rate, flag = (sweep["columns"].index(c)
                  for c in ("rate_per_s", "sustained"))
    held = [row[rate] for i, row in enumerate(sweep["table_40s"])
            if all(r[flag] for r in sweep["table_40s"][:i + 1])]
    assert sweep["knee_per_s"] == max(held)
    assert p["rate_per_s"] == pytest.approx(0.8 * max(held))


# -- the cell on the CPU ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_serve_mixed_cell(on_cpu, capsys, monkeypatch, tmp_path, trace):
    from perf.kinds import serve
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    monkeypatch.setattr(serve, "TRACE_SECONDS", 0.8)
    monkeypatch.setattr(serve, "TRACE_SETTLE_S", 0.2)
    bench, cell = _cell()
    assert serve.run(bench, cell, _args(trace), time.perf_counter()) == 0
    out = _result(capsys)
    assert out["correct"] is True
    assert out["attempted"] == 40 and out["failed"] == 0
    want = {m["name"] for m in common.metrics_of(
        bench, "per_layer" if trace else "end_to_end", CELL)}
    # the CPU stand-in trace has no operation of the program's: what
    # reads a scope's or a kernel's time finds nothing here
    from_the_trace = {n for n in want if re.search(
        r"^(moe|attn|window_attn|paged_attention)_(device_ms|roofline)|"
        r"^(window_decode|attn_walk)_roofline|^hbm_peak", n)}
    assert want - set(out["metrics"]) <= from_the_trace
    if trace:
        share = out["metrics"]["moe_local_pick_share.tpot"]["value"]
        assert 10.0 < share < 50.0      # a quarter of the experts are held
        # rings of 5 blocks where one table gives up to 19 a request
        held = out["metrics"]["kv_window_held_share.ttft"]["value"]
        assert 0 < held < 70.0
        assert 0 < out["metrics"]["kv_group_peak_share.ttft"]["value"] <= 100
        # the program's annotations are in the run's own trace: a count a
        # decode dispatch over the four expert layers of its two fused
        # steps, the live keys, a context a chunk
        arch = common.arch_of(cell["config"])
        (lo, hi), counts = arch.dispatch_counts()
        assert lo < hi and {n for _, _, n in counts} == {8}
        assert arch.dispatch_steps(cell["config"]) == 2
        (lo, hi), live = arch.kv_live()
        assert lo < hi and live
        assert all(0 < w <= t and w <= 8 * rows for _, rows, t, w in live)
        assert any(w < t for _, _, t, w in live)    # rows past the window
        full, windowed = arch.window_live()
        assert 0 < windowed < full
        (lo, hi), chunks = arch.chunk_contexts()
        assert lo < hi and chunks
        assert common.series(
            "paddle_tpu_paged_attention_path_total")["walk"] > 0
        assert common.total(
            "paddle_tpu_serving_kv_window_blocks_used_peak") > 0


def test_serve_mixed_int8_control_runs_and_moves_the_logits(
        on_cpu, capsys, monkeypatch):
    """perf/control.py's path runs on this cell, and the arch file's
    reference honours ``precision="int8"``: its logits move by a
    thirtieth of their spread, where the program's lie within 1e-5 of
    the float32 reference."""
    import jax
    import jax.numpy as jnp
    from perf import weights
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    bench, cell = _cell()
    serve.run(bench, cell, _args(0), time.perf_counter(), control="int8")
    text = capsys.readouterr().out
    assert re.search(r"control\[int8\] served_gap_max: \S+ \(limit", text)
    assert re.search(r"control\[int8\] served_gap_mean: \S+ \(limit", text)
    cfg = cell["config"]
    arch = common.arch_of(cfg)
    w = weights.make_all(cfg, 5, jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 48)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(arch.logits(w, cfg, ids))
        low = np.asarray(arch.logits(w, cfg, ids, "int8"))
        got = np.asarray(arch.build(cfg, 5, jax.devices()[0])(ids))
    assert np.abs(got - sound).max() < 1e-5 * np.abs(sound).max()
    assert np.abs(low - sound).max() > 0.03 * sound.std()


def test_a_window_one_position_short_is_not_correct(on_cpu, capsys,
                                                    monkeypatch):
    """The program's window layers see 7 positions where the
    configuration says 8 (every reader masks one key too many): the
    served tokens are not the reference's, though no request fails."""
    from paddle_tpu.inference import kv_cache
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    real = kv_cache.paged_cache_attention

    def short(*args, window=None, **kw):
        return real(*args, window=window and window - 1, **kw)

    monkeypatch.setattr(kv_cache, "paged_cache_attention", short)
    bench, cell = _cell()
    serve.run(bench, cell, _args(0), time.perf_counter())
    out = _result(capsys)
    assert out["correct"] is False and out["failed"] == 0


# -- the new readers on a hand-made trace -------------------------------------

MS = 1e6    # ns
DECODE = '''
ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %fusion.1 = f32[8] fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(decode_paged)/while/body/closed_call/attn/attn_window/dot_general"}
  %paged_attention.2 = f32[8] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_paged)/while/body/closed_call/attn/attn_window/jit(_paged_decode)/pallas_call"}
  %paged_attention.3 = f32[8] custom-call(%paged_attention.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_paged)/while/body/closed_call/attn/attn_full/jit(_paged_decode)/pallas_call"}
  %fusion.4 = f32[8] fusion(%paged_attention.3), kind=kLoop, calls=%f4, metadata={op_name="jit(decode_paged)/while/body/closed_call/moe/dot_general"}
  ROOT %fusion.5 = f32[8] fusion(%fusion.4), kind=kLoop, calls=%f5, metadata={op_name="jit(decode_paged)/lm_head_ce/dot_general"}
}
'''
CHUNK = '''
ENTRY %main.2 (p0: f32[8]) -> f32[8] {
  %fusion.6 = f32[8] fusion(%p0), kind=kLoop, calls=%f6, metadata={op_name="jit(prefill_chunk)/attn/attn_window/dot_general"}
  %fusion.7 = f32[8] fusion(%fusion.6), kind=kLoop, calls=%f7, metadata={op_name="jit(prefill_chunk)/attn/attn_window/paged_chunk_attention/while/body/dot_general"}
  ROOT %fusion.8 = f32[8] fusion(%fusion.7), kind=kLoop, calls=%f8, metadata={op_name="jit(prefill_chunk)/moe/dot_general"}
}
'''


class _Program:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def _reader(name):
    return common.load_by_path(os.path.join(
        common.ROOT, "perf", "layer_metrics", name + ".py"),
        "perf_layer_metric")


def test_the_window_readers_on_a_hand_made_trace(monkeypatch):
    """Two decode executions (under ``attn_window`` 2 + 4 and 2 + 6 ms, of
    which the kernel 4 and 6; the full layer's kernel 3 beside them) and
    two prefill chunks (the walk 10 and 14 ms): the window readers read
    the nested scope and the kernel under it, the accepted readers the
    whole ``attn`` and every ``paged_attention`` call, each roofline is
    the arch file's count over its time, and a program without the scopes
    reads nothing."""
    from perf import trace_reduce as tr
    plane = "/device:TPU:0"
    ops, modules = [], []
    for start, ker in ((10, 4), (50, 6)):
        t = start * MS
        modules.append(("jit_decode_paged(5)", t, (2 + ker + 3 + 3 + 1) * MS))
        for name, d in (("fusion.1", 2), ("paged_attention.2", ker),
                        ("paged_attention.3", 3), ("fusion.4", 3),
                        ("fusion.5", 1)):
            ops.append((name, t, d * MS))
            t += d * MS
    for start, walk in ((25, 10), (80, 14)):
        t = start * MS
        modules.append(("jit_prefill_chunk(7)", t, (3 + walk + 2) * MS))
        for name, d in (("fusion.6", 3), ("fusion.7", walk),
                        ("fusion.8", 2)):
            ops.append((name, t, d * MS))
            t += d * MS
    trace = tr.Trace({plane: ops}, {plane: modules},
                     [("bench.engine_step", 0, 100 * MS)])
    bench = _bench()
    cell = common.resolve_cell(bench, CELL)
    cfg = cell["config"]
    obs = {"trace": trace, "cell": cell, "live_rows": 20.0,
           "live_kv_tokens": 150000.0,
           "programs": {"decode": _Program(DECODE),
                        "prefill_chunk": _Program(CHUNK)},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    arch = common.arch_of(cfg)
    monkeypatch.setattr(arch, "window_live", lambda: (150000.0, 60000.0))
    monkeypatch.setattr(arch, "chunk_contexts", lambda: (
        (0.0, 100 * MS), ((26 * MS, 8192, 512), (81 * MS, 0, 300))))
    assert _reader("window_attn_device_ms.tpot").read(obs) == \
        pytest.approx(7.0)
    assert _reader("attn_device_ms.tpot").read(obs) == pytest.approx(10.0)
    assert _reader("paged_attention_device_ms.tpot").read(obs) == \
        pytest.approx(8.0)
    # an execution is a dispatch: the cell's engine fuses four steps
    steps = cell["traffic"]["system"]["engine"]["steps_per_sync"]
    assert steps == 4
    assert _reader("window_decode_roofline.tpot").read(obs) == pytest.approx(
        100 * steps * arch.window_decode_bytes(cfg, 60000.0) / 819e9 / 5e-3)
    need = arch.walk_cost(cfg, 8192, 512) + arch.walk_cost(cfg, 0, 300)
    assert _reader("attn_walk_roofline.ttft").read(obs) == pytest.approx(
        100 * need / 197e12 / 24e-3)
    # the decode step's bytes take the program's sums, not the harness's
    monkeypatch.setattr(arch, "window_touched", lambda: 2.0)
    monkeypatch.setattr(arch, "dispatch_counts", lambda: None)
    got = arch.decode_step_bytes(cfg, 999.0)
    assert got == arch.decode_step_bytes(cfg, 150000.0)
    monkeypatch.setattr(arch, "window_live", lambda: None)
    assert got - arch.decode_step_bytes(cfg, 0.0) == \
        4096 * (150000 + 4 * 60000)
    monkeypatch.setattr(arch, "window_live", lambda: (150000.0, 60000.0))
    # ... and are a dispatch's: the steps it fused, by the program's count
    # of expert layer-steps (four expert layers), times a step's
    monkeypatch.setattr(arch, "dispatch_counts", lambda: (
        (0.0, 100 * MS), ((20 * MS, 30, 16), (60 * MS, 34, 16),
                          (200 * MS, 9, 4))))
    assert arch.dispatch_steps(cfg) == 4
    assert arch.decode_step_bytes(cfg, 999.0) == 4 * got
    monkeypatch.setattr(arch, "dispatch_counts", lambda: None)
    # a program without the scopes (the parent's): nothing, and no raise
    bare = dict(obs, programs={
        "decode": _Program(DECODE.replace("attn_window/", "").replace(
            "attn_full/", "")),
        "prefill_chunk": _Program(CHUNK.replace(
            "attn_window/paged_chunk_attention/", ""))})
    bare.pop("_window_runs", None)
    assert _reader("window_attn_device_ms.tpot").read(bare) is None
    assert _reader("window_decode_roofline.tpot").read(bare) is None
    assert _reader("attn_walk_roofline.ttft").read(bare) is None


def test_the_group_readers_read_the_engines_gauges(monkeypatch):
    """The two KV-cache readers over a registry's values: the window
    group's peak over the full group's, and the fuller group's peak share
    of its ids; without a window group's gauges, nothing."""
    values = {"paddle_tpu_serving_kv_blocks_used": 100.0,
              "paddle_tpu_serving_kv_blocks_free": 900.0,
              "paddle_tpu_serving_kv_blocks_used_peak": 400.0,
              "paddle_tpu_serving_kv_window_blocks_used": 50.0,
              "paddle_tpu_serving_kv_window_blocks_free": 150.0,
              "paddle_tpu_serving_kv_window_blocks_used_peak": 120.0}
    monkeypatch.setattr(common, "total", lambda name: values.get(name, 0))
    assert _reader("kv_window_held_share.ttft").read({}) == \
        pytest.approx(30.0)
    assert _reader("kv_group_peak_share.ttft").read({}) == \
        pytest.approx(60.0)
    for name in list(values):
        if "window" in name:
            values.pop(name)
    assert _reader("kv_window_held_share.ttft").read({}) is None
    assert _reader("kv_group_peak_share.ttft").read({}) is None
