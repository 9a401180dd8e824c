"""What every kind of cell shares: the device gate, the compile cache,
counters, the profiler window, the check's verdict and the result line."""

from __future__ import annotations

import contextlib
import functools
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, "perf", ".cache")      # git-ignored, fixed
TRACE_DIR = os.path.join(ROOT, "perf", ".trace")      # git-ignored


class NoChip(SystemExit):
    """The run cannot be a benchmark run: no result line, exit 2."""


def say(msg):
    print(f"[perf] {msg}", flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_by_path(path, name):
    """Import one file of a by-name directory (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(bench, name, root=ROOT):
    """The cell's entry with its configuration and traffic files read."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perf: no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_entry"] = conf
    cell["config_file"] = conf["file"]
    cell["config"] = load_json(os.path.join(root, conf["file"]))
    cell["traffic_file"] = f"perf/traffic/{cell['traffic']}.json"
    cell["traffic"] = load_json(os.path.join(root, cell["traffic_file"]))
    cell["kind"] = cell["traffic"]["kind"]
    return cell


def load_generator(traffic, root=ROOT):
    """The traffic mix's generator, by the name its file gives."""
    return load_by_path(os.path.join(
        root, "perf", "generators", traffic["generator"] + ".py"),
        "perf_generator")


@functools.lru_cache(maxsize=None)
def _arch_at(path):
    return load_by_path(path, "perf_arch")


def arch_path(cfg):
    """perf/archs/<name>.py for the configuration's ``arch`` key
    (``gqa_decoder`` without the key); no such file, no run."""
    name = cfg.get("arch", "gqa_decoder")
    path = os.path.join(ROOT, "perf", "archs", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"perf: {path} is missing (the configuration "
                         f"names arch {name!r})")
    return path


def arch_of(cfg):
    """The configuration's architecture file: the program's model, the
    leaves, the plain reference and the counts."""
    return _arch_at(arch_path(cfg))


def metrics_of(bench, group, cell_name):
    """Metric entries of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def use_cache_dir():
    """Before jax is imported: the compile cache (and, under it, the
    program's executables and autotune winners, which follow the same
    variable) at JAX_COMPILATION_CACHE_DIR where set, else at a fixed
    path inside the checkout."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_device(chips):
    """A TPU with at least ``chips`` devices, or no run at all."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"perf: refusing to run: jax's first device is on platform "
              f"{devs[0].platform!r}, not 'tpu' (the benchmark has no CPU "
              f"fallback)", file=sys.stderr)
        raise NoChip(2)
    if len(devs) < chips:
        print(f"perf: refusing to run: the cell needs {chips} chip(s), "
              f"jax reports {len(devs)}", file=sys.stderr)
        raise NoChip(2)
    return devs[:chips]


def device_info(devices, extra=None):
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    info.update(extra or {})
    return info


def series(name):
    """{labels: value} of one registry metric of the program."""
    from paddle_tpu.observability import default_registry
    m = default_registry().get(name)
    return {"/".join(k) or "all": c.value() for k, c in m.series()} \
        if m is not None else {}


def total(name):
    return sum(series(name).values())


def watch_cache_misses():
    """Names of the programs that jax compiles because its persistent
    cache did not hold them, collected from jax's own log."""
    import logging
    names = []

    class Handler(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "CACHE MISS" in msg:
                names.append(msg.split("'")[1] if "'" in msg else msg[:80])

    log = logging.getLogger("jax._src.compiler")
    log.addHandler(Handler(level=logging.DEBUG))
    log.setLevel(logging.DEBUG)
    log.propagate = False
    return names


def pallas_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def seed_key(seed):
    """A 31-bit number for APIs that take an int32 seed."""
    return int(seed) % (2 ** 31 - 1)


@contextlib.contextmanager
def profiler_window(on):
    """jax's profiler around a block; yields a dict that gets ``path``
    (the .xplane.pb) and the host-clock bounds when the block ends."""
    out = {}
    if not on:
        yield out
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    out["t0"] = time.perf_counter()
    try:
        yield out
    finally:
        out["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                               f"{TRACE_DIR}")
        out["path"] = found[0]


class Check:
    """The numbers compared, each beside its limit; ``ok`` is their and."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, what):
        value = float(value)
        ok = value <= limit and value == value      # NaN fails
        self.rows.append((name, value, limit, ok))
        say(f"check {name}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if ok else 'FAILED'} — {what}")
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r[3] for r in self.rows)


def read_layer_metrics(bench, cell, obs, root=ROOT):
    """Each per-layer metric of this cell through its own reader file; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics_of(bench, "per_layer", cell["name"]):
        path = os.path.join(root, "perf", "layer_metrics", m["name"] + ".py")
        value = load_by_path(path, "perf_layer_metric").read(obs)
        if value is None:
            say(f"layer metric {m['name']}: nothing to read")
            continue
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]) and value > 100.0:
            raise AssertionError(
                f"{m['name']} reads {value} % of a peak: the operations or "
                f"bytes are counted too high, or the time leaves out work")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(bench, cell, *, trace, correct, attempted, failed, end_to_end,
         layer, device, breakdown=None, check=None):
    """The result line: the contract's one JSON object, last on stdout,
    the numbers ``check`` compared last in it (``checks``: each with its
    limit) and, a line each, last on stderr."""
    dev = f"{device['kind']} x{device['count']}"
    if trace:
        metrics = layer
    else:
        metrics = {}
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in end_to_end:
                raise KeyError(f"cell {cell['name']} did not measure "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}
    for k, v in {**{k: {"value": v} for k, v in end_to_end.items()},
                 **layer}.items():
        say(f"metric {k} = {v['value']!r} on {dev}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    rows = check.rows if check is not None else []
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit, _ in rows}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    for name, value, limit, ok in rows:
        print(f"check {name}: {value:.6g} (limit {limit:g}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
