"""The cell ``serve-reason`` (architecture ``kimi_linear``) end to end on
the CPU at a test's size, through ``kinds/serve.py``'s real control flow:
KDA layers with slot state beside latent attention over a latent block
pool under ``ContinuousBatchingEngine``, the plain reference of
perf/archs/, every metric the cell lists — the int8 control of the same
reference, a broken path that must read not correct, the configuration's
file against the contract and the arch file's counts, and the four
``kda_*`` readers on a hand-made trace."""

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

CELL = "serve-reason"
# every width a test's size; the kinds (K K K M K, a dense layer first),
# the router's width (published) over the experts held and the untied
# head stay
TINY = dict(hidden_size=64, num_hidden_layers=5, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=4, num_experts_per_token=4,
            published={"num_experts": 16}, vocab_size=256,
            model_max_length=128, torch_dtype="float32")
LINEAR = {"head_dim": 16, "num_heads": 4}
MIX = {"params": {"rate_per_s": 20.0, "schedule_seed": 1,
                  "prompt": {"median": 20, "sigma": 0.8, "min": 8,
                             "max": 60},
                  "output": {"median": 8, "sigma": 0.7, "min": 2,
                             "max": 16}},
       "system": {"engine": {"slots": 4, "max_len": 96, "kv_block_size": 8,
                             "num_kv_blocks": 49, "prefill_chunk": 16}}}


def _bench():
    return common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))


def _cell():
    bench = _bench()
    cell = common.resolve_cell(bench, CELL)
    cell["config"].update(TINY)
    cell["config"]["linear_attn_config"] = dict(
        cell["config"]["linear_attn_config"], **LINEAR)
    cell["traffic"]["params"] = MIX["params"]
    cell["traffic"]["system"] = MIX["system"]
    return bench, cell


def _args(trace):
    return argparse.Namespace(seed=2 ** 31 + 43, seconds=2.0, trace=trace)


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- the files against the contract -------------------------------------------

def test_the_configuration_is_the_catalogs_with_its_cuts_listed():
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}[
        "kimi-linear-48b-a3b.L8"]
    cfg = common.load_json(os.path.join(common.ROOT, entry["file"]))
    assert cfg["source"] == entry["source"]
    assert set(cfg["published"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 64, 40960)
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_token"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8)
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(lin["kda_layers"]) == 20 and len(lin["full_attn_layers"]) == 7
    assert cfg["assumed"] and "16 v5e" in cfg["deployment"]
    arch = common.arch_of(cfg)
    # two whole periods at the published 3 : 1, the dense layer first
    assert [arch.layer_kind(cfg, i) for i in range(8)] == [
        "kda+dense", "kda+experts", "kda+experts", "mla+experts",
        "kda+experts", "kda+experts", "kda+experts", "mla+experts"]


def test_the_arch_files_counts_are_the_leaves():
    """``flops.total_params`` is the sum of the leaves at the cut and for
    the whole model (49.1 B, the published '48B'); a token meets 8 x 64 /
    256 of a layer's held experts; the cache and the state are what the
    engine's pools hold a token and a slot."""
    cfg = common.load_json(os.path.join(
        common.ROOT, "perf/configs/kimi-linear-48b-a3b.L8.json"))
    arch = common.arch_of(cfg)
    held = sum(int(np.prod(s)) for _, s, _ in arch.leaves(cfg))
    assert flops.total_params(cfg) == held == 3772368832
    whole = dict(cfg, **cfg["published"], published={})
    assert flops.total_params(whole) == sum(
        int(np.prod(s)) for _, s, _ in arch.leaves(whole))
    assert 48e9 < flops.total_params(whole) < 50e9
    expert = 3 * 2304 * 1024
    assert flops.layer_matmul_params(cfg, 1) - flops.layer_matmul_params(
        dict(cfg, num_experts_per_token=0), 1) == 2 * expert
    assert flops.layer_matmul_params(cfg, 0) == arch._dense_params(cfg, 0)
    assert flops.kv_bytes_per_token(cfg) == 2 * 640 * 2
    assert arch.state_bytes_per_slot(cfg) == 6 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    # the latent counts are sarvam_mla's over the two latent layers
    ops, moved = arch.latent_decode_cost(cfg, 1000.0)
    assert moved == 1000 * 2560 and ops == 2 * 1000 * 2 * 32 * (1024 + 64)
    assert arch.latent_prefill_cost(cfg, 512, 512) == \
        2 * (512 * 512 + 512 * 513 / 2) * 2 * 32 * 320
    # a decode step: the state of the live rows twice, the latent rows
    step = arch.decode_step_bytes(cfg, 0.0, live_rows=0.0)
    assert arch.decode_step_bytes(cfg, 1000.0, live_rows=10.0) - step == \
        20 * arch.state_bytes_per_slot(cfg) + 1000 * 2560
    assert arch.kda_step_bytes(cfg, 10.0) == 10 * 6 * 4 * (
        2 * 32 * 128 * 128 + 5 * 4096 + 32)
    ops, moved = arch.kda_scan_cost(cfg, 512)
    assert ops == 6 * 512 * 7 * 32 * 128 * 128
    assert moved == 6 * 4 * (2 * 32 * 128 * 128 + 512 * (5 * 4096 + 32))
    assert moved / 819e9 > ops / 197e12         # the bytes bound it
    assert arch.ssm_step_bytes(cfg, 10.0) > arch.kda_step_bytes(cfg, 10.0)
    assert arch.ssm_scan_cost(cfg, 512)[0] > ops


def test_run_list_resolves_the_cell():
    out = subprocess.run([sys.executable, "perf/run.py", "--list"],
                         cwd=common.ROOT, capture_output=True, text=True,
                         check=True).stdout
    line = [ln for ln in out.splitlines() if ln.startswith(CELL + ":")]
    assert len(line) == 1
    assert "arch perf/archs/kimi_linear.py" in line[0]
    assert "traffic perf/traffic/reason-open-0.8.json" in line[0]
    listed = line[0].rsplit("layer metrics ", 1)[1].split(",")
    bench = _bench()
    assert listed == [m["name"] for m in common.metrics_of(
        bench, "per_layer", CELL)]
    for name in ("kda_device_ms.tpot", "kda_device_ms.ttft",
                 "kda_step_roofline.tpot", "kda_scan_roofline.ttft"):
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert entry["workloads"] == [CELL] and name in listed
    assert "paged_attention_device_ms.tpot" not in listed
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1
    mix = common.load_json(os.path.join(
        common.ROOT, "perf/traffic/reason-open-0.8.json"))
    eng, p = mix["system"]["engine"], mix["params"]
    # the longest prompt with the longest output fits a slot's table
    assert eng["max_len"] >= p["prompt"]["max"] + p["output"]["max"] + 1
    assert eng["max_len"] % eng["kv_block_size"] == 0


# -- the cell on the CPU ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_serve_reason_cell(on_cpu, capsys, monkeypatch, tmp_path, trace):
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
        r"^(ssm|kda|moe|attn|latent_attention)_(device_ms|roofline|step_"
        r"roofline|scan_roofline)|^latent_(decode|prefill)_roofline|"
        r"^hbm_peak", n)}
    assert want - set(out["metrics"]) <= from_the_trace
    if trace:
        touched = out["metrics"]["moe_experts_touched.tpot"]["value"]
        share = out["metrics"]["moe_local_pick_share.tpot"]["value"]
        assert 0 < touched <= TINY["num_experts"]
        assert 10.0 < share < 50.0      # a quarter of the experts are held
        # the program's annotations are in the run's own trace: a count a
        # decode dispatch over the four expert layers, a context a chunk
        arch = common.arch_of(cell["config"])
        (lo, hi), counts = arch.dispatch_counts()
        assert lo < hi and {n for _, _, n in counts} == {4}
        (lo, hi), chunks = arch.chunk_contexts()
        assert lo < hi and chunks
        assert common.total("paddle_tpu_serving_state_bytes") > 0
        assert common.series(
            "paddle_tpu_latent_attention_path_total")["chunk_expanded"] > 0


def test_serve_reason_int8_control_runs_and_moves_the_logits(
        on_cpu, capsys, monkeypatch):
    """perf/control.py's path runs on this cell, and the arch file's
    reference honours ``precision="int8"``: its logits move by a
    thirtieth of their spread, where the program's lie within 5e-6 of
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
    assert np.abs(got - sound).max() < 5e-6 * np.abs(sound).max()
    assert np.abs(low - sound).max() > 0.03 * sound.std()


def test_a_state_that_never_leaves_zero_is_not_correct(on_cpu, capsys,
                                                       monkeypatch):
    """Every write of a slot's state stores zeros (the recurrence starts
    from nothing at each dispatch): the served tokens are not the
    reference's, though no request fails."""
    from paddle_tpu.models import hybrid
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    real = hybrid._state_out

    def frozen(state, info, tail, h):
        return real(state, info, tail, h * 0)

    monkeypatch.setattr(hybrid, "_state_out", frozen)
    bench, cell = _cell()
    serve.run(bench, cell, _args(0), time.perf_counter())
    out = _result(capsys)
    assert out["correct"] is False and out["failed"] == 0


# -- the new readers on a hand-made trace -------------------------------------

MS = 1e6    # ns
DECODE = '''
ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %fusion.1 = f32[8] fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(decode_paged)/while/body/closed_call/ssm/dot_general"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(decode_paged)/while/body/closed_call/ssm/kda/mul"}
  %fusion.3 = f32[8] fusion(%fusion.2), kind=kLoop, calls=%f3, metadata={op_name="jit(decode_paged)/while/body/closed_call/moe/dot_general"}
  ROOT %fusion.4 = f32[8] fusion(%fusion.3), kind=kLoop, calls=%f4, metadata={op_name="jit(decode_paged)/lm_head_ce/dot_general"}
}
'''
CHUNK = '''
ENTRY %main.2 (p0: f32[8]) -> f32[8] {
  %fusion.5 = f32[8] fusion(%p0), kind=kLoop, calls=%f5, metadata={op_name="jit(prefill_chunk)/ssm/dot_general"}
  %fusion.6 = f32[8] fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(prefill_chunk)/ssm/kda/while/body/dot_general"}
  ROOT %fusion.7 = f32[8] fusion(%fusion.6), kind=kLoop, calls=%f7, metadata={op_name="jit(prefill_chunk)/moe/dot_general"}
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


def test_the_kda_readers_on_a_hand_made_trace():
    """Two decode executions (``ssm`` 2 + 4 and 2 + 6 ms, of which the
    recurrence 4 and 6) and two prefill chunks (recurrence 10 and 14 ms):
    the ``kda`` readers read the nested scope alone, the accepted ``ssm``
    readers the whole, each roofline is the arch file's count over its
    time, and a program without the scope reads nothing."""
    from perf import trace_reduce as tr
    plane = "/device:TPU:0"
    ops, modules = [], []
    for start, rec in ((10, 4), (50, 6)):
        t = start * MS
        modules.append(("jit_decode_paged(5)", t, (2 + rec + 3 + 1) * MS))
        for name, d in (("fusion.1", 2), ("fusion.2", rec), ("fusion.3", 3),
                        ("fusion.4", 1)):
            ops.append((name, t, d * MS))
            t += d * MS
    for start, rec in ((25, 10), (70, 14)):
        t = start * MS
        modules.append(("jit_prefill_chunk(7)", t, (3 + rec + 2) * MS))
        for name, d in (("fusion.5", 3), ("fusion.6", rec),
                        ("fusion.7", 2)):
            ops.append((name, t, d * MS))
            t += d * MS
    trace = tr.Trace({plane: ops}, {plane: modules},
                     [("bench.engine_step", 0, 100 * MS)])
    bench = _bench()
    cell = common.resolve_cell(bench, CELL)
    cfg = cell["config"]
    obs = {"trace": trace, "cell": cell, "live_rows": 40.0,
           "programs": {"decode": _Program(DECODE),
                        "prefill_chunk": _Program(CHUNK)},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    arch = common.arch_of(cfg)
    assert _reader("kda_device_ms.tpot").read(obs) == pytest.approx(5.0)
    assert _reader("kda_device_ms.ttft").read(obs) == pytest.approx(12.0)
    assert _reader("ssm_device_ms.tpot").read(obs) == pytest.approx(7.0)
    assert _reader("ssm_device_ms.ttft").read(obs) == pytest.approx(15.0)
    assert _reader("kda_step_roofline.tpot").read(obs) == pytest.approx(
        100 * arch.kda_step_bytes(cfg, 40.0) / 819e9 / 5e-3)
    ops_, moved = arch.kda_scan_cost(cfg, 512)
    assert _reader("kda_scan_roofline.ttft").read(obs) == pytest.approx(
        100 * max(ops_ / 197e12, moved / 819e9) / 12e-3)
    assert _reader("ssm_step_roofline.tpot").read(obs) == pytest.approx(
        100 * arch.ssm_step_bytes(cfg, 40.0) / 819e9 / 7e-3)
    # the parent's program: ``ssm`` and no ``kda`` inside it
    bare = dict(obs, _scope_runs={}, _recurrence_runs={}, programs={
        "decode": _Program(DECODE.replace("/kda", "")),
        "prefill_chunk": _Program(CHUNK.replace("/kda", ""))})
    for name in ("kda_device_ms.tpot", "kda_device_ms.ttft",
                 "kda_step_roofline.tpot", "kda_scan_roofline.ttft"):
        assert _reader(name).read(bare) is None
        assert _reader(name).read(dict(obs, trace=None,
                                       _recurrence_runs={})) is None
    assert _reader("ssm_device_ms.tpot").read(bare) == pytest.approx(7.0)
    # a cell of another architecture (its file names no such scope)
    other = dict(obs, cell=common.resolve_cell(bench, "serve-rag"),
                 _scope_runs={}, _recurrence_runs={})
    assert _reader("kda_device_ms.tpot").read(other) is None
    assert _reader("kda_scan_roofline.ttft").read(other) is None
