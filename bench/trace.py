"""The profiler trace of a window, reduced to what the metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: per device plane the events of its op line, and the
harness's own host spans (``window``, ``put``, ``call``,
``fetch``).  :func:`reduce` works on those lists alone, so it can be
checked on a small recorded trace without a chip.

    python3 bench/trace.py <trace dir> [--requests 3 --out trimmed.json]

prints the planes and lines of a recorded trace with their most frequent
event names, and writes its first requests, trimmed, for the tests.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: the harness's host spans (harness.drive, harness.run_cell)
HOST_SPANS = ("window", "put", "call", "fetch")
#: device planes, and the line of one that holds its ops
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: the line of a device plane that holds one event per program run
RUNS_LINE = "XLA Modules"
#: entries of the breakdown lists
TOP = 10


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python tracing on the host
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _profile(log_dir: str):
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))


def load(log_dir: str) -> Dict:
    """``{"devices": {index: {"ops": [[name, start_ns, dur_ns], ...],
    "runs": [...]}}, "host": [[span, start_ns, dur_ns], ...]}``: per
    device its ops and its program runs, and the harness's host spans."""
    data = _profile(log_dir)
    devices: Dict[int, Dict[str, List]] = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, RUNS_LINE):
                dev = devices.setdefault(int(m.group(1)), {"ops": [], "runs": []})
                dev["ops" if line.name == OPS_LINE else "runs"] += [
                    [e.name, e.start_ns, e.duration_ns] for e in line.events]
            elif plane.name.startswith("/host:"):
                host += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                         if e.name in HOST_SPANS]
    return {"devices": {str(k): v for k, v in sorted(devices.items())}, "host": host}


def op_name(event: str) -> str:
    """The op's own name.  On a TPU's op line an event is named by its
    whole HLO instruction (``%qconv2d.16 = s8[...] custom-call(...
    %qconv2d.15 ...)``), where the operands name other ops."""
    return event.split(" = ", 1)[0].lstrip("%")


def short(event: str) -> str:
    """An op's name and result type, without layouts: ``qconv2d.16 =
    s8[1,60,56,128]``."""
    name, _, rest = event.partition(" = ")
    kind = re.sub(r"\{[^{}]*\}", "", rest).split(" ", 1)[0]
    return f"{name.lstrip('%')} = {kind}" if kind else name.lstrip("%")


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _attribute(a: float, b: float, spans: List[Tuple[float, float, str]],
               ends: List[float], into: collections.Counter) -> None:
    """Split the idle interval [a, b) over the host spans it overlaps
    (``spans`` sorted and disjoint, as one thread's are, ``ends`` their
    ends); what no span covers is "between spans"."""
    covered = 0.0
    i = bisect.bisect_right(ends, a)
    while i < len(spans) and spans[i][0] < b:
        s0, s1, name = spans[i]
        o = min(b, s1) - max(a, s0)
        if o > 0:
            into[name] += o
            covered += o
        i += 1
    if b - a - covered > 0:
        into["between spans"] += b - a - covered


def reduce(tr: Dict, n_devices: int = 1) -> Optional[Dict]:
    """Busy and idle time of the devices used over the traced window,
    the ops of the window's requests, the ops that took most time, and
    idle time by what the host was doing.  None where the trace holds
    no device op.

    The ops of the window's requests are those that start inside a
    program run that overlaps the window: the host's spans and the
    device's events lie on clocks that agree to some microseconds, so a
    run at the window's edge may stick out of it.  A trace without runs
    keeps the ops that lie wholly inside the window."""
    windows = [(s, s + d) for n, s, d in tr["host"] if n == "window"]
    spans = sorted((s, s + d, n) for n, s, d in tr["host"] if n != "window")
    ends = [e for _, e, _ in spans]
    devs = [tr["devices"][k] for k in sorted(tr["devices"], key=int)][:n_devices]
    if not devs or not any(d["ops"] for d in devs):
        return None
    if windows:
        w0, w1 = windows[0]
    else:
        w0 = min(s for d in devs for _, s, _ in d["ops"])
        w1 = max(s + du for d in devs for _, s, du in d["ops"])
    window_ns = w1 - w0
    busy_ns, by_op, gaps = 0.0, collections.Counter(), collections.Counter()
    inside = []
    for d in devs:
        ops = [(n, max(s, w0), min(s + du, w1)) for n, s, du in d["ops"]
               if s + du > w0 and s < w1]
        for n, a, b in ops:
            by_op[short(n)] += b - a
        busy = _union([(a, b) for _, a, b in ops])
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _attribute(a, b, spans, ends, gaps)
        runs = sorted((s, s + du) for _, s, du in d.get("runs", []) if s + du > w0 and s < w1)
        if runs:
            starts = [a for a, _ in runs]
            inside += [[n, du] for n, s, du in d["ops"]
                       if (i := bisect.bisect_right(starts, s) - 1) >= 0 and s <= runs[i][1]]
        else:
            inside += [[n, du] for n, s, du in d["ops"] if s >= w0 and s + du <= w1]
    k = len(devs)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / k / 1e9,
        "ops": inside,
        "devices": k,
        "top_ops": [[n, v / k / 1e9] for n, v in by_op.most_common(TOP)],
        "idle_gaps": [[f"idle during {n}", v / k / 1e9] for n, v in gaps.most_common(TOP)],
    }


def kernel_share(rec: Dict, pattern: str, kind: str) -> Optional[float]:
    """Roofline share, in %, of the kernel whose op names match
    ``pattern``: the least time its calls in the window could take
    (bench/counts.py, the configuration's ``kind`` layers, once per
    request of the traced window) over the device time they took.  None, with a line saying
    why, unless the window holds exactly one such call per layer and
    request."""
    t = rec["trace"]
    if not t:
        return None
    rows = [r for r in rec["counts"] if r["kind"] == kind]
    requests = rec["trace_requests"]
    rx = re.compile(pattern)
    calls = [du for n, du in t["ops"] if rx.search(op_name(n))]
    if len(calls) != len(rows) * requests * t["devices"]:
        print(f"{kind} kernels ({pattern}): {len(calls)} calls in the window, expected "
              f"{len(rows)} per request x {requests} requests; metric left out", flush=True)
        return None
    from bench.counts import roofline_s
    return 100.0 * requests * roofline_s(rows, rec["peak"]) / (sum(calls) / t["devices"] / 1e9)


def trim(tr: Dict, requests: int) -> Dict:
    """The traced window cut after its first ``requests`` requests: the
    window span ends where the next request's put starts, and only the
    host spans that start before then are kept, with each device's first
    ``requests`` program runs that overlap the window and their ops."""
    w0, _ = next((s, d) for n, s, d in tr["host"] if n == "window")
    puts = sorted(s for n, s, _ in tr["host"] if n == "put" and s >= w0)
    cut = puts[requests]
    host = [[n, s, d] for n, s, d in tr["host"] if n != "window" and w0 <= s < cut]
    devices = {}
    for k, v in tr["devices"].items():
        runs = sorted(r for r in v.get("runs", []) if r[1] + r[2] > w0)[:requests]
        ops = [o for o in v["ops"] if any(s <= o[1] <= s + d for _, s, d in runs)]
        devices[k] = {"ops": ops, "runs": runs}
    return {"devices": devices, "host": [["window", w0, cut - w0]] + sorted(host, key=lambda e: e[1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Look at a recorded trace, or trim it for the tests.")
    ap.add_argument("log_dir")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for plane in _profile(args.log_dir).planes:
        print(plane.name)
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print(f"  {line.name!r}: {sum(names.values())} events, {names.most_common(12)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(trim(load(args.log_dir), args.requests), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
