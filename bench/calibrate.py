#!/usr/bin/env python3
"""Calibration runs of one cell on the chip, many in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 --seconds 10
        [--control w8a8] [--engine KEY=JSON ...] [--rates 2 3 4]
        [--seed-stride N] [--trace]
        [--save-trace PATH] [--spec-root DIR] [--out FILE]

Each run is ``bench.harness.run_cell`` as ``bench/run.py`` makes it, and
prints one JSON line with the seed, the rate, ``correct``, the numbers
compared, the metrics and the diagnostics.  ``--control`` judges the
check's lower-precision control (``w8`` or ``w8a8``, the architecture's
reference in ``bench/arch``) in the program's place; ``--engine`` overrides ServeConfig fields of the
cell's configuration; ``--rates`` sweeps an open-loop mix's arrival rate to find the
knee, each rate on its own seeds with ``--seed-stride`` (rate i adds i
times the stride to each seed); ``--spec-root`` reads BENCHMARK.json and its data files from
another directory (the tiny cells in ``bench/testdata``); ``--out``
appends the lines to a file as well.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", default=None, choices=("w8", "w8a8"))
    ap.add_argument("--engine", nargs="*", default=[])
    ap.add_argument("--rates", type=float, nargs="*", default=[None])
    ap.add_argument("--seed-stride", type=int, default=0)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--save-trace", default=None)
    ap.add_argument("--spec-root", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench.harness import Spec, run_cell

    spec = Spec(Path(args.spec_root), Path(args.spec_root)) if args.spec_root else Spec()
    t_start = T_START
    engine = {k: json.loads(v) for k, v in (e.split("=", 1) for e in args.engine)}
    for i, rate in enumerate(args.rates):
        for seed in (s + i * args.seed_stride for s in args.seeds):
            r = run_cell(
                args.workload, seed, args.seconds, args.trace, t_start, spec=spec,
                engine_overrides=engine, control=args.control,
                traffic_overrides={"rate_rps": rate} if rate else None,
                keep_trace=args.save_trace,
            )
            line = {"workload": args.workload, "seed": seed, "rate": rate,
                    "engine": engine,
                    "control": args.control, "trace": args.trace, **r}
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
            t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
