"""What the tests of the benchmark share: the CPU stand-ins of a chip
run, and the two benchmarks the contract is tested on — the accepted one
as it stands, and a copy that already holds an accepted cell of another
architecture."""

import json
import os
import shutil
import types

import pytest

from perf import common, flops, trace_reduce

MS = 1e6
DATA = os.path.join(os.path.dirname(__file__), "data")

# a serving cell at a test's size of the toy architecture (data/
# two_kinds_arch.py): sizes, mix and engine
TOY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=4,
           num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
           max_position_embeddings=128, torch_dtype="float32")
TOY_PARAMS = {"rate_per_s": 20.0, "schedule_seed": 1,
              "prompt": {"median": 20, "sigma": 0.8, "min": 8, "max": 60},
              "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 16}}
TOY_ENGINE = {"slots": 4, "max_len": 96, "kv_block_size": 8,
              "prefill_chunk": 16}


def add_cell(root, bench, name, conf, traffic, metrics):
    """A configuration, a traffic mix and a cell of them, as new files
    and entries of the checkout at ``root``; the cell's name goes on the
    lists of ``metrics``.  Returns the benchmark as written."""
    for kind, data in (("configs", conf), ("traffic", traffic)):
        with open(os.path.join(root, "perf", kind, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": name, "source": conf["source"],
                         "file": f"perf/configs/{name}.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    b["workloads"].append({"name": name, "config": name, "traffic": name,
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return b


def add_toy_cell(root, bench, name, arch, metrics):
    """``add_cell`` of the toy architecture, its file copied to
    perf/archs/<arch>.py: the serving configuration and mix at TOY's
    sizes."""
    shutil.copy(os.path.join(DATA, "two_kinds_arch.py"),
                os.path.join(root, "perf", "archs", arch + ".py"))
    conf = common.load_json(os.path.join(
        root, "perf/configs/mistral-7b-v0.3.L12.json"))
    mix = common.load_json(os.path.join(
        root, "perf/traffic/chat-open-0.8.json"))
    mix["params"], mix["system"] = TOY_PARAMS, {"engine": TOY_ENGINE}
    return add_cell(root, bench, name, dict(conf, arch=arch, **TOY), mix,
                    metrics)


@pytest.fixture(scope="session")
def cells():
    return types.SimpleNamespace(add=add_cell, add_toy=add_toy_cell)


def copy_benchmark(src, dst):
    """BENCHMARK.json and the files under its ``paths``, nothing else."""
    os.makedirs(dst)
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    for p in common.load_json(os.path.join(src, "BENCHMARK.json"))["paths"]:
        shutil.copytree(os.path.join(src, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns(
                            ".cache", ".trace", "__pycache__"))
    return dst


@pytest.fixture(scope="session", params=["accepted", "accepted-other"])
def tree(request, tmp_path_factory):
    """The root of a benchmark the contract must hold on: the repo's own,
    and a copy in which a cell ``accepted-other`` of architecture
    ``two_kinds`` sits on every list ``serve-chat`` sits on."""
    if request.param == "accepted":
        return common.ROOT
    root = str(copy_benchmark(
        common.ROOT, tmp_path_factory.mktemp("other") / "checkout"))
    bench = common.load_json(os.path.join(root, "BENCHMARK.json"))
    add_toy_cell(root, bench, "accepted-other", "two_kinds", {
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
        if "serve-chat" in m.get("workloads", ())})
    return root


@pytest.fixture(scope="session")
def bench(tree):
    return common.load_json(os.path.join(tree, "BENCHMARK.json"))


@pytest.fixture()
def copy(tmp_path, tree):
    """A scratch checkout of ``tree``'s benchmark, for a test to add to."""
    return copy_benchmark(tree, tmp_path / "checkout")


@pytest.fixture()
def on_cpu(monkeypatch):
    import jax
    from paddle_tpu import compile_cache
    monkeypatch.setattr(common, "require_device",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(compile_cache, "enable_persistent_cache",
                        lambda: None)
    monkeypatch.setattr(flops, "peaks", lambda kind: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    # the profiler runs, but a CPU trace has no device plane: hand-made
    # intervals stand in, through the same reduction
    monkeypatch.setattr(trace_reduce, "load", lambda path: trace_reduce.Trace(
        {"/device:TPU:0": [("fusion.1", 0, 5 * MS), ("fusion.2", 7 * MS,
                                                     MS)]},
        {"/device:TPU:0": [("jit_decode_paged(1)", 0, 5 * MS),
                           ("jit_prefill_chunk(2)", 7 * MS, MS)]},
        [("bench.engine_step", 0, 6 * MS), ("bench.train_step", 6 * MS,
                                            3 * MS)]))
