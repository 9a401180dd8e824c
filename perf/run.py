"""The benchmark's one command.

  python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's ``workloads``.  Everything that
belongs to one configuration, one architecture, one traffic mix or one
per-layer metric is a file found by its name: perf/configs/ (which names
the architecture; ``gqa_decoder`` where it names none), perf/archs/ (the
program's model, the seeded leaves, the plain reference and the counts),
perf/traffic/ (which names the kind of cell and its generator),
perf/generators/, perf/layer_metrics/.  A new cell, of an architecture the
benchmark has or of a new one, is new files and new entries; no file here
is edited for it.

Runs on a TPU only.  Without one, or with fewer chips than the cell asks,
it prints no result line and exits 2.  ``--list`` prints the cells and the
files each resolves to (no jax).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse    # noqa: E402
import importlib   # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from perf import common
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if args.list:
        for w in bench["workloads"]:
            cell = common.resolve_cell(bench, w["name"], ROOT)
            layer = [m["name"] for m in
                     common.metrics_of(bench, "per_layer", w["name"])]
            for m in layer:
                path = os.path.join(ROOT, "perf", "layer_metrics", m + ".py")
                if not os.path.isfile(path):
                    raise SystemExit(f"perf: {path} is missing")
            gen = os.path.join(ROOT, "perf", "generators",
                               cell["traffic"]["generator"] + ".py")
            if not os.path.isfile(gen):
                raise SystemExit(f"perf: {gen} is missing")
            arch = os.path.relpath(common.arch_path(cell["config"]), ROOT)
            print(f"{w['name']}: kind {cell['kind']}, chips {w['chips']}, "
                  f"config {cell['config_file']}, arch {arch}, traffic "
                  f"{cell['traffic_file']}, generator "
                  f"{cell['traffic']['generator']}, layer metrics "
                  f"{','.join(layer)}")
        return 0
    if args.workload is None:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = common.resolve_cell(bench, args.workload, ROOT)
    common.arch_path(cell["config"])     # no architecture file, no run
    common.use_cache_dir()
    try:
        import paddle_tpu  # noqa: F401
    except ImportError as e:
        print(f"perf: the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    kind = importlib.import_module(f"perf.kinds.{cell['kind']}")
    return kind.run(bench, cell, args, T_START)


if __name__ == "__main__":
    sys.exit(main())
