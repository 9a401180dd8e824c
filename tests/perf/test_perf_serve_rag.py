"""The cell ``serve-rag`` (architecture ``granite_moe_hybrid``) end to end
on the CPU at a test's size, through ``kinds/serve.py``'s real control
flow: the hybrid model under ``ContinuousBatchingEngine`` with slot state
beside the block pool, the plain reference of perf/archs/, every metric
the cell lists — and the int8 control of the same reference, which must
read above the sound run."""

import argparse
import json
import os
import re
import time

import numpy as np
import pytest

from perf import common

# every width a test's size; the kinds, the router's width (published)
# over the experts held and the tied head stay.  embedding_multiplier 1:
# at 64 wide the layers add little to the stream, and with the published
# 12 the tied head returns the input token whatever the layers do, which
# no control can be told from
TINY = dict(hidden_size=64, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=32, shared_intermediate_size=48,
            num_local_experts=4, num_experts_per_tok=2,
            published={"num_local_experts": 8},
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8, vocab_size=256,
            attention_multiplier=1 / 16, embedding_multiplier=1.0,
            max_position_embeddings=128,
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
    cell = common.resolve_cell(bench, "serve-rag")
    cell["config"].update(TINY)
    cell["traffic"]["params"] = MIX["params"]
    cell["traffic"]["system"] = MIX["system"]
    return bench, cell


def _args(trace):
    return argparse.Namespace(seed=2 ** 31 + 37, seconds=2.0, trace=trace)


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_rag_cell(on_cpu, capsys, monkeypatch, tmp_path, trace):
    from perf.kinds import serve
    # a trace directory of this test's own: test_perf_rehearsal.py's
    # traced cells empty perf/.trace from another worker
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    monkeypatch.setattr(serve, "TRACE_SECONDS", 0.8)
    monkeypatch.setattr(serve, "TRACE_SETTLE_S", 0.2)
    bench, cell = _cell()
    assert serve.run(bench, cell, _args(trace), time.perf_counter()) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["attempted"] == 40 and out["failed"] == 0
    want = {m["name"] for m in common.metrics_of(
        bench, "per_layer" if trace else "end_to_end", "serve-rag")}
    # the CPU stand-in trace has no operation of the program's: what
    # reads a scope's or a kernel's time finds nothing here
    from_the_trace = {n for n in want if re.search(
        r"^(ssm|moe)_(device_ms|roofline|step_roofline|scan_roofline)|"
        r"^paged_attention_device_ms|^hbm_peak", n)}
    assert want - set(out["metrics"]) <= from_the_trace
    if trace:
        touched = out["metrics"]["moe_experts_touched.tpot"]["value"]
        share = out["metrics"]["moe_local_pick_share.tpot"]["value"]
        assert 0 < touched <= TINY["num_local_experts"]
        assert 20.0 < share < 80.0      # half of the experts are held
        # the program's per-dispatch counts are in the run's own trace,
        # a dispatch of one step over the three layers each, and some
        # lie between the benchmark's window markers
        arch = common.arch_of(cell["config"])
        (lo, hi), counts = arch.dispatch_counts()
        assert lo < hi and {n for _, _, n in counts} == {3}
        assert all(0 <= t <= 3 * TINY["num_local_experts"]
                   for _, t, _ in counts)       # one row may pick none
        assert 0 < arch.window_touched() <= TINY["num_local_experts"]


def test_serve_rag_int8_control_runs_and_moves_the_logits(on_cpu, capsys,
                                                          monkeypatch):
    """perf/control.py's path runs on this cell, and the arch file's
    reference honours ``precision="int8"``: its logits move by a tenth
    of their spread, where the program's lie within 1e-6 of the float32
    reference (at this size and vocabulary the few served tokens'
    argmax survives int8, so the served gaps alone cannot show it)."""
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
    assert np.abs(got - sound).max() < 1e-6
    assert np.abs(low - sound).max() > 0.1 * sound.std()


# -- the scope readers on a hand-made trace -----------------------------------

MS = 1e6    # ns
HLO = '''
ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %fusion.1 = f32[8] fusion(%p0), kind=kLoop, calls=%f1, metadata={op_name="jit(decode_paged)/while/body/closed_call/ssm/mul"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(decode_paged)/while/body/closed_call/moe/dot_general"}
  %ragged-dot-none.7 = f32[8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.3 = f32[8] fusion(%ragged-dot-none.7), kind=kLoop, calls=%f3, metadata={op_name="jit(decode_paged)/lm_head_ce/dot_general"}
}
'''


class _Program:
    def as_text(self):
        return HLO


def _reader(name):
    return common.load_by_path(os.path.join(
        common.ROOT, "perf", "layer_metrics", name + ".py"),
        "perf_layer_metric")


def test_scope_readers_count_the_compilers_kernel_under_moe(monkeypatch):
    """Two decode executions: ``ssm`` 4 + 6 ms, ``moe`` = its fusion and
    the grouped-matmul kernel the compiler wrote (named, unscoped): 2 + 8
    and 2 + 10 ms; the rooflines divide the arch file's bytes by those
    times, ``moe_roofline.tpot`` the bytes of what each execution's own
    dispatch counted over both executions' time; a program with none of
    the scopes reads nothing."""
    from perf import trace_reduce as tr
    plane = "/device:TPU:0"
    ops, modules = [], []
    for start, ssm, kernel in ((10, 4, 8), (50, 6, 10)):
        t = start * MS
        modules.append(("jit_decode_paged(5)", t, (ssm + 2 + kernel + 1)
                        * MS))
        for name, d in (("fusion.1", ssm), ("fusion.2", 2),
                        ("ragged-dot-none.7", kernel), ("fusion.3", 1)):
            ops.append((name, t, d * MS))
            t += d * MS
    trace = tr.Trace({plane: ops}, {plane: modules},
                     [("bench.engine_step", 0, 100 * MS)])
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = common.resolve_cell(bench, "serve-rag")
    obs = {"trace": trace, "cell": cell, "live_rows": 10.0,
           "programs": {"decode": _Program()},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert _reader("ssm_device_ms.tpot").read(obs) == pytest.approx(5.0)
    assert _reader("moe_device_ms.tpot").read(obs) == pytest.approx(11.0)
    assert _reader("ssm_device_ms.ttft").read(obs) is None   # no
    assert _reader("moe_device_ms.ttft").read(obs) is None   # such
    #                                                                program
    arch = common.arch_of(cell["config"])
    want = arch.ssm_step_bytes(cell["config"], 10.0) / 819e9 / 5e-3 * 100
    assert _reader("ssm_step_roofline.tpot").read(obs) == \
        pytest.approx(want)
    # the program's counts: a dispatch's reaches the host just after its
    # execution ends (25 and 69 ms); one from before the window (5 ms)
    # and a stray one after the second's (90 ms) belong to no execution
    # here.  10 layers: 120 and 300 experts in sum
    counts = ((float("-inf"), float("inf")),
              ((5 * MS, 360, 10), (25.5 * MS, 120, 10),
               (69.5 * MS, 300, 10), (90 * MS, 360, 10)))
    monkeypatch.setattr(arch, "dispatch_counts", lambda: counts)
    want = (arch.moe_step_bytes(cell["config"], 120, 10)
            + arch.moe_step_bytes(cell["config"], 300, 10)) \
        / 819e9 / 22e-3 * 100
    assert _reader("moe_roofline.tpot").read(obs) == pytest.approx(want)
    assert arch.moe_step_bytes(cell["config"], 120, 10) > \
        120 * 3 * 4096 * 768 * 2                    # the touched experts
    # the whole step's count: the window's median dispatch (its four
    # read 36, 12, 30, 36 a layer: 33), every layer; none without a count
    step = arch.decode_step_bytes(cell["config"], 0.0, live_rows=0.0)
    assert step == arch.decode_step_bytes(cell["config"], 0.0)
    monkeypatch.setattr(arch, "dispatch_counts", lambda: None)
    assert step - arch.decode_step_bytes(cell["config"], 0.0) == \
        33 * 10 * 3 * 4096 * 768 * 2
    assert _reader("moe_roofline.tpot").read(obs) is None
    monkeypatch.setattr(arch, "dispatch_counts", lambda: counts)
    bare = dict(obs, programs={"decode": type("P", (), {
        "as_text": lambda self: HLO.replace("ssm", "s").replace(
            "moe", "m").replace("lm_head_ce", "h")})()}, _scope_runs={})
    assert _reader("ssm_device_ms.tpot").read(bare) is None
    assert _reader("moe_roofline.tpot").read(bare) is None
