#!/usr/bin/env python3
"""Readings that set the limit of a cell's output check, in one process
on the chip.  The benchmark's own runs never run this.

    python3 bench/control.py --workload vgg16.b1 --seconds 3 \
        --program-seeds 11,12,13 --control-seeds 21,22,23

For each program seed it makes a short run of the cell as ``run.py``
does (the lower readings); for each control seed the same run with the
reference computed one precision below the configuration (int4 for
int8) in the program's place (the upper readings).  With
``--vary-weights`` each seed also makes the weights and the calibration
image, in place of the configuration's ``weights_seed``, so a program
run compiles its own executor.  Prints one JSON line per run with the
numbers compared, then a summary line.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--vary-weights", action="store_true")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    try:
        harness.check_device(cell.chips, peaks)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.use_cache()

    runs = [("program", s, harness.program_executor) for s in args.program_seeds]
    runs += [("control_int4", s, harness.control_executor(4)) for s in args.control_seeds]
    summary = {}
    for kind, seed, make in runs:
        r = harness.run_cell(cell, seed, args.seconds, False, t_start=time.perf_counter(),
                             make_executor=make, peaks=peaks,
                             weights_seed=seed if args.vary_weights else None)
        line = {"kind": kind, "seed": seed, "vary_weights": args.vary_weights,
                "correct": r["correct"], "checks": r["checks"]}
        print(json.dumps(line), flush=True)
        summary.setdefault(kind, []).append(r["checks"]["logprob_gap"]["value"])
    print(json.dumps({"workload": args.workload, "logprob_gap": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
