"""Each kind of cell end to end on the CPU at a tiny size, through the
kinds' real control flow (the look for a chip is skipped here, in the
tests' ``on_cpu`` fixture in conftest.py; run.py has no option for it) —
and with the timed path broken underneath, where ``correct`` must come out
false."""

import argparse
import json
import os
import time

import pytest

from perf import common

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            max_position_embeddings=128,
            # float32 here: the cells' limits are read at the cells' own
            # sizes, where a norm averages over millions of elements; a
            # 64-wide bfloat16 model is further from float32 than they allow
            torch_dtype="float32")


def _cell(name, **traffic):
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    if name == "train-4chip":
        # not a cell of BENCHMARK.json yet (PERF.md §7): the one-chip
        # cell's files under the sharded trainer's mesh, as PR 21 ran it
        cell = dict(common.resolve_cell(bench, "train-1chip"), chips=4)
        cell["traffic"]["system"].update(mesh={"fsdp": 2, "tp": 2},
                                         batch_axis="fsdp")
    else:
        cell = common.resolve_cell(bench, name)
    cell["config"].update(TINY)
    for k, v in traffic.items():
        cell["traffic"][k].update(v)
    return bench, cell


def _result(capsys):
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


def _args(trace=0, seconds=0.5):
    return argparse.Namespace(seed=2 ** 31 + 77, seconds=seconds,
                              trace=trace)


@pytest.mark.parametrize("name,trace", [("train-1chip", 0),
                                        ("train-1chip", 1),
                                        ("train-4chip", 1)])
def test_train_cell(on_cpu, capsys, name, trace):
    from perf.kinds import train
    bench, cell = _cell(name, params={"batch": 4, "seq": 32})
    assert train.run(bench, cell, _args(trace), time.perf_counter()) == 0
    out = _result(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == cell["chips"]
    # each number compared, beside its limit, last in the line; the
    # second step's loss is printed and not compared (PERF.md section 2)
    assert list(out)[-1] == "checks" and list(out["checks"]) == [
        "loss_step1_rel", "grad_norm_worst_leaf", "grad_norm_mean_leaf",
        "delta_norm_worst_leaf"]
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    want = [m["name"] for m in common.metrics_of(
        bench, "per_layer" if trace else "end_to_end", cell["name"])]
    missing = set(want) - set(out["metrics"])
    # the CPU reports no memory statistics; nothing else may be missing
    assert missing <= {"hbm_peak_gb.train"}, missing
    if trace:
        assert out["device"]["busy_s"] > 0 and out["breakdown"]["idle_gaps"]
        assert out["metrics"]["recompiles.train"]["value"] == 0


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(
        on_cpu, capsys, monkeypatch):
    import jax
    from perf.kinds import train
    real = train.build

    class Frozen:
        def __init__(self, step):
            self.step = step

        def __getattr__(self, k):
            return getattr(self.step, k)

        def __call__(self, batch):
            s = self.step
            keep = jax.tree.map(lambda a: a.copy(), (s.params, s.opt_state))
            loss = s(batch)
            s.params, s.opt_state = keep
            return loss

    monkeypatch.setattr(train, "build", lambda *a: Frozen(real(*a)))
    bench, cell = _cell("train-1chip", params={"batch": 4, "seq": 32})
    train.run(bench, cell, _args(), time.perf_counter())
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    assert out["correct"] is False
    # the parameters' change reads 1: said in the line and on stderr
    delta = out["checks"]["delta_norm_worst_leaf"]
    assert delta["value"] == pytest.approx(1.0, abs=1e-3)
    assert delta["value"] > delta["limit"]
    assert captured.err.strip().splitlines()[-1].startswith(
        "check delta_norm_worst_leaf: ") and "FAILED" in captured.err


def test_train_part_of_the_batch_left_out_is_not_correct(
        on_cpu, capsys, monkeypatch):
    from perf.kinds import train
    real = train.build

    class HalfBatch:
        def __init__(self, step):
            self.step = step

        def __getattr__(self, k):
            return getattr(self.step, k)

        def compile(self, batch):
            return self.step.compile({k: v[:2] for k, v in batch.items()})

        def __call__(self, batch):
            return self.step({k: v[:2] for k, v in batch.items()})

    monkeypatch.setattr(train, "build", lambda *a: HalfBatch(real(*a)))
    bench, cell = _cell("train-1chip", params={"batch": 4, "seq": 32})
    train.run(bench, cell, _args(), time.perf_counter())
    assert _result(capsys)["correct"] is False


SERVE = dict(
    params={"rate_per_s": 20.0, "schedule_seed": 1,
            "prompt": {"median": 20, "sigma": 0.8, "min": 8, "max": 60},
            "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 16}},
    system={"engine": {"slots": 4, "max_len": 96, "kv_block_size": 8,
                       "prefill_chunk": 16}})


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell(on_cpu, capsys, monkeypatch, trace):
    from perf.kinds import serve
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    monkeypatch.setattr(serve, "TRACE_SECONDS", 0.8)
    monkeypatch.setattr(serve, "TRACE_SETTLE_S", 0.2)
    bench, cell = _cell("serve-chat", **SERVE)
    assert serve.run(bench, cell, _args(trace, 2.0),
                     time.perf_counter()) == 0
    out = _result(capsys)
    assert out["correct"] is True
    assert out["attempted"] == 40 and out["failed"] == 0
    assert list(out)[-1] == "checks" and list(out["checks"]) == [
        "served_gap_max", "served_gap_mean", "failed_share"]
    want = [m["name"] for m in common.metrics_of(
        bench, "per_layer" if trace else "end_to_end", "serve-chat")]
    assert set(want) - set(out["metrics"]) <= {"hbm_peak_gb.tput"}


def test_serve_token_altered_where_it_is_produced_is_not_correct(
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
    bench, cell = _cell("serve-chat", **SERVE)
    serve.run(bench, cell, _args(0, 2.0), time.perf_counter())
    assert _result(capsys)["correct"] is False
