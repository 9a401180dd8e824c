"""The cell ``serve-longctx`` (architecture ``sarvam_mla``) end to end on
the CPU at a test's size, through ``kinds/serve.py``'s real control flow:
latent attention under ``ContinuousBatchingEngine`` with a latent block
pool, the plain reference of perf/archs/, every metric the cell lists —
the int8 control of the same reference, two broken paths that must read
not correct, and the four new readers on a hand-made trace."""

import argparse
import json
import os
import re
import time

import numpy as np
import pytest

from perf import common

# every width a test's size; the kinds (a dense layer, then expert
# layers), the router's width (published) over the experts held, the
# untied head and the YaRN scaling stay
TINY = dict(hidden_size=64, num_hidden_layers=3, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4, head_dim=48,
            q_head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
            num_experts_per_tok=4, published={"num_experts": 16},
            vocab_size=256, max_position_embeddings=128,
            torch_dtype="float32")
MIX = {"params": {"rate_per_s": 20.0, "schedule_seed": 1,
                  "prompt": {"median": 20, "sigma": 0.8, "min": 8,
                             "max": 60},
                  "output": {"median": 8, "sigma": 0.7, "min": 2,
                             "max": 16}},
       "system": {"engine": {"slots": 4, "max_len": 96, "kv_block_size": 8,
                             "num_kv_blocks": 49, "prefill_chunk": 16}}}


def _cell():
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.resolve_cell(bench, "serve-longctx")
    cell["config"].update(TINY)
    cell["config"]["rope_scaling"] = dict(
        cell["config"]["rope_scaling"], original_max_position_embeddings=32)
    cell["traffic"]["params"] = MIX["params"]
    cell["traffic"]["system"] = MIX["system"]
    return bench, cell


def _args(trace):
    return argparse.Namespace(seed=2 ** 31 + 39, seconds=2.0, trace=trace)


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_longctx_cell(on_cpu, capsys, monkeypatch, tmp_path, trace):
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
        bench, "per_layer" if trace else "end_to_end", "serve-longctx")}
    assert "paged_attention_device_ms.tpot" not in want
    # the CPU stand-in trace has no operation of the program's: what
    # reads a scope's or a kernel's time finds nothing here
    from_the_trace = {n for n in want if re.search(
        r"^(moe|attn|latent_attention)_(device_ms|roofline)|"
        r"^latent_(decode|prefill)_roofline|^hbm_peak", n)}
    assert want - set(out["metrics"]) <= from_the_trace
    if trace:
        touched = out["metrics"]["moe_experts_touched.tpot"]["value"]
        share = out["metrics"]["moe_local_pick_share.tpot"]["value"]
        assert 0 < touched <= TINY["num_experts"]
        assert 10.0 < share < 50.0      # a quarter of the experts are held
        # the program's annotations are in the run's own trace: a count a
        # decode dispatch over the two expert layers, a context a chunk
        arch = common.arch_of(cell["config"])
        (lo, hi), counts = arch.dispatch_counts()
        assert lo < hi and {n for _, _, n in counts} == {2}
        (lo, hi), chunks = arch.chunk_contexts()
        assert lo < hi and chunks
        assert all(0 <= start < 60 and 0 < tokens <= 16
                   and start % 16 == 0 for _, start, tokens in chunks)
        assert common.series(
            "paddle_tpu_latent_attention_path_total")["chunk_expanded"] > 0


def test_serve_longctx_int8_control_runs_and_moves_the_logits(
        on_cpu, capsys, monkeypatch):
    """perf/control.py's path runs on this cell, and the arch file's
    reference honours ``precision="int8"``: its logits move by a
    thirtieth of their spread (three layers at this size), where the program's lie within 5e-6 of the float32
    reference."""
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


def test_a_token_altered_where_it_is_produced_is_not_correct(
        on_cpu, capsys, monkeypatch):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    real = ContinuousBatchingEngine.finished

    def altered(self):
        for rid, prompt, out in real(self):
            yield rid, prompt, [(t + 1) % TINY["vocab_size"] if i == 1
                                else t for i, t in enumerate(out)]

    monkeypatch.setattr(ContinuousBatchingEngine, "finished", altered)
    bench, cell = _cell()
    serve.run(bench, cell, _args(0), time.perf_counter())
    assert _result(capsys)["correct"] is False


def test_a_cache_frozen_at_zero_is_not_correct(on_cpu, capsys, monkeypatch):
    """The latent rows never reach the pool (every write stores zeros):
    each token attends an empty context, and the served tokens are not
    the reference's."""
    from paddle_tpu.models import latent_attention
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    real = latent_attention.latent_cache_attention

    def frozen(q, row, *args, **kw):
        return real(q, row * 0, *args, **kw)

    monkeypatch.setattr(latent_attention, "latent_cache_attention", frozen)
    bench, cell = _cell()
    serve.run(bench, cell, _args(0), time.perf_counter())
    out = _result(capsys)
    assert out["correct"] is False and out["failed"] == 0


# -- the new readers on a hand-made trace -------------------------------------

MS = 1e6    # ns
DECODE = '''
ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %fusion.1 = f32[8] fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(decode_paged)/while/body/closed_call/attn/mul"}
  %latent_attention.3 = f32[8] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_paged)/while/body/closed_call/attn/latent_attention"}
  %fusion.2 = f32[8] fusion(%latent_attention.3), kind=kLoop, calls=%f2, metadata={op_name="jit(decode_paged)/while/body/closed_call/moe/dot_general"}
  ROOT %fusion.3 = f32[8] fusion(%fusion.2), kind=kLoop, calls=%f3, metadata={op_name="jit(decode_paged)/lm_head_ce/dot_general"}
}
'''
CHUNK = '''
ENTRY %main.2 (p0: f32[8]) -> f32[8] {
  %fusion.4 = f32[8] fusion(%p0), kind=kLoop, calls=%f4, metadata={op_name="jit(prefill_chunk)/attn/dot_general"}
  %fusion.5 = f32[8] fusion(%fusion.4), kind=kLoop, calls=%f5, metadata={op_name="jit(prefill_chunk)/attn/latent_chunk_attention/while/body/dot_general"}
  ROOT %fusion.6 = f32[8] fusion(%fusion.5), kind=kLoop, calls=%f6, metadata={op_name="jit(prefill_chunk)/moe/dot_general"}
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


def test_the_latent_readers_on_a_hand_made_trace(monkeypatch):
    """Two decode executions (``attn`` = its fusion 1 + the kernel 3 and
    5 ms; the kernel by its name) and three prefill chunks, of which two
    lie inside the window (attention proper 4 and 12 ms, at starts 0 and
    512): each roofline is the arch file's count over those times, and a
    program without the names reads nothing."""
    from perf import trace_reduce as tr
    plane = "/device:TPU:0"
    ops, modules = [], []
    for start, kernel in ((20, 3), (60, 5)):
        t = start * MS
        modules.append(("jit_decode_paged(5)", t, (1 + kernel + 2 + 1) * MS))
        for name, d in (("fusion.1", 1), ("latent_attention.3", kernel),
                        ("fusion.2", 2), ("fusion.3", 1)):
            ops.append((name, t, d * MS))
            t += d * MS
    for start, core in ((2, 9), (30, 4), (40, 12)):
        t = start * MS
        modules.append(("jit_prefill_chunk(7)", t, (2 + core + 3) * MS))
        for name, d in (("fusion.4", 2), ("fusion.5", core),
                        ("fusion.6", 3)):
            ops.append((name, t, d * MS))
            t += d * MS
    trace = tr.Trace({plane: ops}, {plane: modules},
                     [(tr.WINDOW_BEGIN, 18 * MS, 0.0),
                      ("bench.engine_step", 18 * MS, 70 * MS),
                      (tr.WINDOW_END, 95 * MS, 0.0)])
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.resolve_cell(bench, "serve-longctx")
    cfg = cell["config"]
    obs = {"trace": trace, "cell": cell, "live_kv_tokens": 100000.0,
           "programs": {"decode": _Program(DECODE),
                        "prefill_chunk": _Program(CHUNK)},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    arch = common.arch_of(cfg)
    assert _reader("attn_device_ms.tpot").read(obs) == pytest.approx(5.0)
    assert _reader("latent_attention_device_ms.tpot").read(obs) == \
        pytest.approx(4.0)
    assert _reader("prefill_attn_device_ms.ttft").read(obs) == \
        pytest.approx(10.0)                 # the accepted reader: 6, 14
    ops_, moved = arch.latent_decode_cost(cfg, 100000.0)
    assert moved / 819e9 > ops_ / 197e12    # the bytes bound the kernel
    assert _reader("latent_decode_roofline.tpot").read(obs) == \
        pytest.approx(100 * 2 * (moved / 819e9) / 8e-3)
    # the chunks' contexts: the last three annotations are the three
    # executions', whatever their own times (an earlier one is a chunk
    # from before the profiler started)
    found = ((float("-inf"), float("inf")),
             ((0.5 * MS, 7168, 512), (1 * MS, 1024, 512), (3 * MS, 0, 512),
              (4 * MS, 512, 512)))
    monkeypatch.setattr(arch, "chunk_contexts", lambda: found)
    need = arch.latent_prefill_cost(cfg, 0, 512) + \
        arch.latent_prefill_cost(cfg, 512, 512)
    assert _reader("latent_prefill_roofline.ttft").read(obs) == \
        pytest.approx(100 * need / 197e12 / 16e-3)
    assert arch.latent_prefill_cost(cfg, 512, 512) == \
        5 * (512 * 512 + 512 * 513 / 2) * 2 * 64 * 320
    monkeypatch.setattr(arch, "chunk_contexts", lambda: None)
    assert _reader("latent_prefill_roofline.ttft").read(obs) is None
    monkeypatch.setattr(arch, "chunk_contexts", lambda: found)
    unnamed = tr.Trace({plane: [(n.replace("latent_attention",
                                           "closed_call"), t, d)
                                for n, t, d in ops]}, trace.modules,
                       trace.host)
    bare = dict(obs, _scope_runs={}, trace=unnamed, programs={
        "decode": _Program(DECODE.replace("attn", "a").replace(
            "latent_attention", "k")),
        "prefill_chunk": _Program(CHUNK.replace(
            "latent_chunk_attention", "walk"))})
    assert _reader("attn_device_ms.tpot").read(bare) is None
    assert _reader("latent_attention_device_ms.tpot").read(bare) is None
    assert _reader("latent_decode_roofline.tpot").read(bare) is None
    assert _reader("latent_prefill_roofline.ttft").read(bare) is None
    # a program of another architecture (no such names in its file)
    other = dict(obs, cell=common.resolve_cell(bench, "serve-chat"),
                 _scope_runs={})
    assert _reader("latent_attention_device_ms.tpot").read(other) is None
    assert _reader("latent_prefill_roofline.ttft").read(other) is None
