"""BENCHMARK.json against the benchmark's contract, and the harness's
promise that a new cell, of an architecture it has or of a new one, is new
files and new entries only — on the accepted benchmark and on one that
already holds an accepted cell of another architecture (conftest.py's
``tree``, ``bench`` and ``copy``)."""

import argparse
import functools
import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expand|experts_per_tok")


def _cells_of(metric, bench):
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def test_keys_names_and_units(bench, tree):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(tree, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    assert all(line(w) for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in bench[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in bench[g]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"])


def test_files_exist_and_metrics_connect(bench, tree):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(tree, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        # every cut is listed, and is a cut of the published value
        assert set(conf["published"]) == set(c["reduced"])
        assert all(conf[k] != v for k, v in conf["published"].items())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for name, w in cells.items():
        assert os.path.isfile(os.path.join(
            tree, "perf", "traffic", w["traffic"] + ".json"))
        mine = [m["name"] for m in e2e.values()
                if name in _cells_of(m, bench)]
        assert len(mine) >= 2 and "setup_s" in mine
        assert any(name in _cells_of(m, bench) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            tree, "perf", "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        for cell in _cells_of(m, bench):
            assert cell in _cells_of(e2e[m["moves"]], bench), (m, cell)


def test_four_chip_share(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, math.floor(0.25 * len(bench["workloads"])))


def _run(root, *args, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "perf/run.py", *args], cwd=root,
                          env=e, capture_output=True, text=True, timeout=120)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_entries_only(copy, bench):
    before = _digest(copy)
    conf = json.load(open(copy / "perf/configs/internlm2-1.8b.L4.json"))
    conf["num_hidden_layers"] = 2
    (copy / "perf/configs/new-model.L2.json").write_text(json.dumps(conf))
    (copy / "perf/traffic/new-mix.json").write_text(json.dumps({
        "kind": "train", "generator": "new_batches",
        "params": {"batch": 2, "seq": 128},
        "system": {"adamw": {}}, "limits": {}}))
    (copy / "perf/generators/new_batches.py").write_text(
        "def batch(params, cfg, seed, step):\n    return {}\n")
    (copy / "perf/layer_metrics/new_metric.train.py").write_text(
        "def read(obs):\n    return 1.0\n")
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "new-model.L2", "source": conf["source"],
                         "file": "perf/configs/new-model.L2.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "new-model.L2",
                           "traffic": "new-mix", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("new-cell")
    b["per_layer"].append({
        "name": "new_metric.train", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["new-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(b))
    r = _run(copy, "--list")
    assert r.returncode == 0, r.stderr
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("new-cell:")]
    assert line and "perf/configs/new-model.L2.json" in line[0]
    assert "generator new_batches" in line[0]
    assert "new_metric.train" in line[0]
    after = _digest(copy)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}, changed


def _arch_files_named(root, listing):
    """{cell: (the arch file its ``--list`` line names, the one its
    configuration names: the ``arch`` key, ``gqa_decoder`` without
    one)}, read from the files of the checkout at ``root``."""
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    files = {c["name"]: c["file"] for c in b["configs"]}
    out = {}
    for w in b["workloads"]:
        conf = json.load(open(os.path.join(root, files[w["config"]])))
        line, = [ln for ln in listing.splitlines()
                 if ln.startswith(w["name"] + ":")]
        out[w["name"]] = (re.search(r" arch (\S+),", line).group(1),
                          f"perf/archs/{conf.get('arch', 'gqa_decoder')}.py")
    return out


@pytest.mark.parametrize("reference,trace", [
    ("its own", 0), ("its own", 1), ("without the odd layers", 0)],
    ids=["its own", "its own, traced", "without the odd layers"])
def test_a_new_architecture_is_new_files_and_entries_only(
        copy, bench, cells, on_cpu, capsys, monkeypatch, tmp_path,
        reference, trace):
    """An architecture whose layers are of two kinds, the odd ones with a
    1-D leaf that is no gain, drawn by an initialiser of the arch file's
    own and added under a scope of its own: an arch file, a configuration
    that names it, a mix, a reader that passes its own scopes, and
    entries.  ``--list`` names, for every cell, the arch file its
    configuration names, and the serve rehearsal runs the cell on the CPU
    to ``correct: true`` against its own reference — and to false against
    a reference that knows one kind of layer.  Traced, the reader finds
    the scope in the compiled decode program and the decode roofline's
    count is handed the live rows it turns on."""
    from perf import common
    from perf.kinds import serve
    before = _digest(copy)
    shutil.copy(os.path.join(ROOT, "tests/perf/data/shift_ops.tpot.py"),
                copy / "perf/layer_metrics/shift_ops.tpot.py")
    b = cells.add_toy(copy, bench, "toy", "toy_kinds", (
        "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s",
        "cache_misses.setup", "decode_roofline"))
    b["per_layer"].append({
        "name": "shift_ops.tpot", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p95_ms", "workloads": ["toy"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(b))
    r = _run(copy, "--list")
    assert r.returncode == 0, r.stderr
    named = _arch_files_named(copy, r.stdout)
    assert set(named) == {w["name"] for w in b["workloads"]}
    assert all(listed == own for listed, own in named.values()), named
    assert named["toy"][0] == "perf/archs/toy_kinds.py"
    assert named["serve-chat"][0] == "perf/archs/gqa_decoder.py"

    # the rehearsal, in this process, finding the copy's files by name
    monkeypatch.setattr(common, "ROOT", str(copy))
    monkeypatch.setattr(common, "read_layer_metrics", functools.partial(
        common.read_layer_metrics, root=str(copy)))
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(serve, "WARM_PROMPTS", (20, 9))
    monkeypatch.setattr(serve, "TRACE_SECONDS", 0.8)
    monkeypatch.setattr(serve, "TRACE_SETTLE_S", 0.2)
    cell = common.resolve_cell(b, "toy", str(copy))
    arch = common.arch_of(cell["config"])
    assert arch.__file__ == str(copy / "perf/archs/toy_kinds.py")
    kinds = [k for _, _, k in arch.layer_leaves(cell["config"], 1)]
    assert kinds.count("log_uniform") == 1 and "log_uniform" not in [
        k for _, _, k in arch.layer_leaves(cell["config"], 0)]
    if reference != "its own":
        monkeypatch.setattr(arch, "layer", common.arch_of({}).layer)
    args = argparse.Namespace(seed=2 ** 31 + 78, seconds=2.0, trace=trace)
    assert serve.run(b, cell, args, time.perf_counter()) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["attempted"] == 40 and out["failed"] == 0
    assert out["correct"] is (reference == "its own")
    if trace:
        assert set(out["metrics"]) == {"cache_misses.setup",
                                       "decode_roofline", "shift_ops.tpot"}
        assert out["metrics"]["shift_ops.tpot"]["value"] >= 2   # two layers
    after = _digest(copy)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}, changed


def test_an_architecture_with_no_file_is_no_run(copy, bench, cells,
                                                tmp_path):
    conf = json.load(open(copy / "perf/configs/internlm2-1.8b.L4.json"))
    mix = json.load(open(copy / "perf/traffic/lm-16k.json"))
    cells.add(copy, bench, "lost", dict(conf, arch="nowhere"), mix,
              ("train_tokens_per_s_per_chip", "cache_misses.setup"))
    path = str(copy / "perf/archs/nowhere.py")
    for args in (("--list",), ("--workload", "lost", "--seed", "1",
                               "--seconds", "1", "--trace", "0")):
        r = _run(copy, *args,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        assert r.returncode not in (0, 2) and path in r.stderr
        assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def _perf_sources(root):
    for d, _, files in os.walk(os.path.join(root, "perf")):
        for f in files:
            if f.endswith(".py"):
                p = os.path.join(d, f)
                yield os.path.relpath(p, root), open(p).read()


MODEL = re.compile(r"paddle_tpu\.models|LlamaForCausalLM|LlamaConfig")
WIDTHS = re.compile(r"intermediate_size|num_attention_heads|"
                    r"num_key_value_heads|head_dim")
DECODER = re.compile(r"^.*reference(?:\.| import )decoder.*$", re.M)
SHARED = re.compile(r"from perf\.reference\.decoder import "
                    r"(adamw|matmul|_q8)(, (adamw|matmul|_q8))*$")


def test_only_an_arch_file_knows_a_model(tree):
    """Nothing under perf/ outside perf/archs/ imports the program's
    models, names a model class or reads a width that only some
    architectures have; perf/reference/decoder.py (gqa_decoder's
    reference) is reached through perf/archs/gqa_decoder.py alone, its
    shared ``adamw`` / ``matmul`` controls excepted."""
    seen = 0
    for path, text in _perf_sources(tree):
        if path.startswith("perf/archs/"):
            continue
        seen += 1
        assert not MODEL.search(text), path
        if path != "perf/reference/decoder.py":
            assert not WIDTHS.search(text), path
        for line in DECODER.findall(text):
            assert SHARED.search(line.strip()), (path, line)
    assert seen > 40
    gqa = open(os.path.join(tree, "perf/archs/gqa_decoder.py")).read()
    assert MODEL.search(gqa) and DECODER.search(gqa)


def test_no_tpu_no_result(tmp_path):
    r = _run(ROOT, "--workload", "train-1chip", "--seed", "1", "--seconds",
             "1", "--trace", "0",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert r.returncode == 2
    assert "refusing to run" in r.stderr and "'tpu'" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def test_no_program_no_result(copy, tmp_path):
    r = _run(copy, "--workload", "train-1chip", "--seed", "1", "--seconds",
             "1", "--trace", "0",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert r.returncode != 0
    assert "not in this checkout" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
