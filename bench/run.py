#!/usr/bin/env python3
"""The chip benchmark of the int8 CNN2Gate executor: one run of one cell.

    python3 bench/run.py --workload vgg16.b1 --seed 7 --seconds 30 --trace 0

Needs a TPU whose kind is in ``bench/peaks.json`` and as many chips as
the cell asks for; otherwise it exits 2 and prints no result.  Earlier
lines say what the run did; the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``checks``, the
numbers compared with their limits, comes last and is repeated as the
last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", help="keep the profiler trace here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number")
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT))
    from bench import harness

    cell = harness.load_cell(args.workload)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    try:
        devices = harness.check_device(cell.chips, peaks)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    cache_dir = harness.use_cache()
    print(f"{args.workload}: seed {args.seed}, {len(devices)} x {devices[0].device_kind}, "
          f"cache {cache_dir}", flush=True)

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, trace_dir=args.trace_dir, peaks=peaks)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
