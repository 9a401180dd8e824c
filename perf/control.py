"""A cell's lower-precision control, on the chip at the cell's own size:

  python3 perf/control.py --workload <cell> --seed <n> --seconds <s>

runs the cell as run.py does and, beside each number the check compares,
prints the number the plain reference gives when it is computed in the
precision below the configuration's (``control[int8] ...  FAILS``).  The
benchmark's own runs never run it; limits are set between the sound runs'
largest readings and these (PERF.md, section 2)."""

import sys
import time

T_START = time.perf_counter()

import run as _run   # noqa: E402  (perf/run.py: puts the root on sys.path)


def main(argv=None) -> int:
    import argparse
    import importlib
    import os
    from perf import common
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", default="int8")
    args = ap.parse_args(argv)
    args.trace = 0
    bench = common.load_json(os.path.join(_run.ROOT, "BENCHMARK.json"))
    cell = common.resolve_cell(bench, args.workload, _run.ROOT)
    common.use_cache_dir()
    kind = importlib.import_module(f"perf.kinds.{cell['kind']}")
    return kind.run(bench, cell, args, T_START, control=args.control)


if __name__ == "__main__":
    sys.exit(main())
