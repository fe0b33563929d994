#!/usr/bin/env python3
"""The slowest requests of one cell's window beside the Python
collector's pauses in it, each with its time.

    python3 bench/stalls.py --workload vgg16.b1 --seed 7 --seconds 30

Sets the cell up with the harness's own steps, as ``bench/run.py`` does
(executor, warm-up, the set-up's objects frozen out of the collector's
passes), then drives one untraced window with the collector watched
(``repro.core.telemetry.watch_gc``).  Prints the slowest requests and
the longest pauses, each with its start in seconds from the window's
start, and, as the last line, a JSON object with the same and the
pause histogram.  Like ``bench/run.py`` it needs the cell's chips, and
exits 2 without them.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: entries of each list printed
TOP = 8


def overlaps(t0: float, t1: float, pauses) -> float:
    """Seconds of ``pauses`` (``(start, seconds, generation)``) inside
    ``[t0, t1)``."""
    return sum(max(0.0, min(t1, s + d) - max(t0, s)) for s, d, _ in pauses)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.core import telemetry

    cell = harness.load_cell(args.workload)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    try:
        harness.check_device(cell.chips, peaks)
    except harness.NoChip as e:
        print(f"stalls: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.use_cache()
    cfg, mix = cell.config, cell.traffic
    x_cal = harness.calibration_image(cfg, cfg["weights_seed"])
    pool = harness.request_pool(cfg, mix, args.seed)
    fn = harness.program_executor(cell, cfg["weights_seed"], x_cal, harness.Spans())
    harness.drive(fn, pool, mix["in_flight"], requests=harness.WARMUP_REQUESTS)

    registry = telemetry.MetricsRegistry()
    gc.collect()
    gc.freeze()
    watch = telemetry.watch_gc(registry)
    try:
        win = harness.drive(fn, pool, mix["in_flight"], seconds=args.seconds)
    finally:
        watch.stop()
        gc.unfreeze()
    w0 = float(win.t0[0])
    lat = win.t1 - win.t0
    slow = np.argsort(lat)[::-1][:TOP]
    pauses = list(watch.pauses)
    slowest = [{"ms": 1e3 * float(lat[i]), "at_s": float(win.t0[i]) - w0,
                "gc_ms": 1e3 * overlaps(win.t0[i], win.t1[i], pauses)} for i in slow]
    longest = [{"ms": 1e3 * d, "at_s": s - w0, "generation": g}
               for s, d, g in watch.longest(TOP)]
    hist = registry.snapshot()["histograms"].get(telemetry.GC_PAUSE, {"count": 0, "sum": 0.0})
    print(f"window: {len(lat)} requests in {win.seconds:.3f} s, median "
          f"{1e3 * float(np.median(lat)):.3f} ms; collector: {hist['count']} passes, "
          f"{hist['sum']:.4f} s", flush=True)
    print("slowest requests (ms @ s, ms of collector inside): "
          + ", ".join(f"{r['ms']:.3f} @ {r['at_s']:.3f} ({r['gc_ms']:.3f})" for r in slowest))
    print("longest collector pauses (ms @ s, generation): "
          + ", ".join(f"{p['ms']:.3f} @ {p['at_s']:.3f} (gen {p['generation']})"
                      for p in longest), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "requests": len(lat), "window_s": win.seconds,
                      "latency_p50_ms": 1e3 * float(np.median(lat)),
                      "slowest": slowest, "gc_longest": longest, "gc_pause_s": hist}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
