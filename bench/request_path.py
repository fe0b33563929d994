"""The request path of a traced window, from the runtime's own events.

:mod:`bench.trace` reduces a window to what the accepted metrics read,
and keeps only the harness's four host spans.  This module reads the
same ``.xplane.pb`` once more and adds, under new keys and without
changing anything :mod:`bench.trace` returns:

* :func:`load`: ``trace.load``'s dict plus ``"runtime"``, the runtime's
  host events on the request path (:data:`RUNTIME`: the execute
  enqueue, the copies to and from the device, the host's read of the
  completion flag) and the program's own ``cnn2gate.*`` spans, and
  ``"stages"``, per device the stage of each op (its
  ``jax.named_scope`` in the executor, from the op's event metadata);
* :func:`reduce`: ``trace.reduce``'s dict plus ``"requests"``, per
  request its put span, its enqueue, its device run and the end of its
  fetch, ``"clock"``, two bounds on the offset between the host's and
  the device's clocks, ``"split"``, the medians of the request's parts,
  and ``"stage_ms"``, device time per stage.

    python3 bench/request_path.py <trace dir> [--requests 3 --out trimmed.json]

prints the per-request split, the clock check and the stages with the
most device time of a trace kept by ``bench/run.py --trace-dir``, and
writes its first requests, trimmed, for the tests.

A request is paired with its device run by order: the i-th request the
window sends (in the order of its put) with the i-th program run on
"XLA Modules" that overlaps the window, as the device runs one
program at a time in dispatch order.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

#: the runtime's host events on the request path, by what they are (the
#: names libtpu records on a TPU v5 lite under jax 0.9): the execute
#: call that launches the program, the host-to-device copy of the input,
#: the host's first read of the device's completion flag, and the
#: device-to-host copy of the answer, issued and done
RUNTIME = {
    "enqueue": re.compile(r"^tpu::System::Execute$"),
    "h2d": re.compile(r"^tpu::System::TransferToDevice$"),
    "sync": re.compile(r"^ReadSyncFlag$"),
    "d2h": re.compile(r"^tpu::System::TransferFromDevice$"),
    "d2h_done": re.compile(r"^tpu::System::TransferFromDevice=>IssueEvent=>Done$"),
}
#: the program's own spans (repro.core.telemetry.Tracer)
PROGRAM_SPAN = "cnn2gate."
#: the executor's own scope: an op's stage is the path's next part
EXECUTOR_SCOPE = re.compile(r"jit\(forward\)/([^/]+)")
#: entries of the printed stage table
TOP = 10


def kind(name: str) -> Optional[str]:
    """What the host event ``name`` is on the request path, or None."""
    if name.startswith(PROGRAM_SPAN):
        return "program"
    return next((k for k, rx in RUNTIME.items() if rx.search(name)), None)


def stage(texts: List[str]) -> Optional[str]:
    """The executor stage (``conv_1``, ``gemm_33``, ``ingress``, ...) of
    the first of ``texts`` that holds the op's scope path
    (``jit(forward)/<stage>/...``), or None."""
    return next((m.group(1) for v in texts if (m := EXECUTOR_SCOPE.search(v))), None)


def _varint(b, i: int):
    r = shift = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << shift
        if x < 0x80:
            return r, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes of a length-delimited field, raw bytes else."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_texts(path: str) -> Dict[str, Dict[str, List[str]]]:
    """Per device plane, each op's name with the text values of the
    stats its event metadata holds (where the profiler puts an op's
    ``jax.named_scope`` path), read from the ``.xplane.pb`` itself:
    ``jax.profiler.ProfileData`` gives an event's own stats only.  The
    walk follows xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, str_value = 5, ref_value = 7."""
    out = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k in (4, 5):
                entry = dict(_fields(v))  # a map entry: key = 1, value = 2
                if k == 4:
                    events.append(entry.get(2, b""))
                else:
                    stat_names[entry.get(1)] = next(
                        (bytes(x).decode() for j, x in _fields(entry.get(2, b"")) if j == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        ops = out[name] = {}
        for md in events:
            op, texts = "", []
            for k, v in _fields(md):
                if k == 2:
                    op = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if 5 in stat:
                        texts.append(bytes(stat[5]).decode(errors="replace"))
                    elif 7 in stat:
                        texts.append(stat_names.get(stat[7], ""))
            ops[op] = texts
    return out


def load(log_dir: str) -> Dict:
    """:func:`bench.trace.load`'s dict, plus ``"runtime"``: ``[[kind,
    name, start_ns, dur_ns], ...]``, sorted by start, and ``"stages"``:
    ``{device: [[stage, start_ns, dur_ns], ...]}`` of the ops on the op
    line whose event metadata names a stage."""
    import jax
    tr = trace.load(log_dir)
    runtime, stages = [], {}
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    texts = op_texts(path)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace.OPS_LINE:
                md = texts.get(plane.name, {})
                stages[m.group(1)] = [[s, e.start_ns, e.duration_ns] for e in line.events
                                      if (s := stage(md.get(e.name, [])))]
            elif plane.name.startswith("/host:"):
                runtime += [[k, e.name, e.start_ns, e.duration_ns] for e in line.events
                            if (k := kind(e.name))]
    tr["runtime"] = sorted(runtime, key=lambda e: e[2])
    tr["stages"] = stages
    return tr


def _median(xs: List[float]) -> Optional[float]:
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return None
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2


def requests(tr: Dict, device: str = "0") -> List[Dict]:
    """Per request of the window, in ns on the trace's clock, each as
    ``[start, end]``: ``put`` and ``call`` (the harness's spans), ``run``
    (its device run), ``fetch_end`` (a number), and the runtime's
    events of :data:`RUNTIME` (``enqueue``, ``h2d``, ``sync``, ``d2h``,
    ``d2h_done``).  Each kind is paired with the requests by order, as
    the runtime serves them in dispatch order; a kind the window does
    not hold once per request is None throughout."""
    w0, w1 = next((s, s + d) for n, s, d in tr["host"] if n == "window")
    spans = {n: sorted([s, s + d] for m, s, d in tr["host"] if m == n and w0 <= s < w1)
             for n in ("put", "call", "fetch")}
    dev = tr["devices"].get(device, {})
    runs = sorted([s, s + d] for _, s, d in dev.get("runs", []) if s + d > w0 and s < w1)
    n = min(len(runs), *(len(v) for v in spans.values()))
    rt = [e for e in tr.get("runtime", []) if w0 <= e[2] < w1]
    events = {}
    for k in RUNTIME:
        es = [[s, s + d] for kk, _, s, d in rt if kk == k]
        events[k] = es if len(es) == n else [None] * n
    return [{"put": spans["put"][i], "call": spans["call"][i], "run": runs[i],
             "fetch_end": spans["fetch"][i][1], **{k: v[i] for k, v in events.items()}}
            for i in range(n)]


def launch_ref(reqs: List[Dict]) -> str:
    """Where a launch is timed from: the runtime's ``enqueue`` where it
    recorded one for every request, else the harness's ``call`` span."""
    return "enqueue" if reqs and all(r["enqueue"] for r in reqs) else "call"


def clock_check(reqs: List[Dict]) -> Dict:
    """Two bounds on the offset between the host's and the device's
    clocks: the least (run start - enqueue start; the call's start
    where :func:`launch_ref` says so), which a device clock behind the
    host's makes negative, and the least (fetch end - run
    end), which a device clock ahead of it makes negative; each in µs
    with the number of requests it is taken over.  ``offset_us`` is the
    least shift of the device's events that makes both bounds hold, 0
    where both are already >= 0: it is added to ``launch`` and taken
    from ``return`` in :func:`split`."""
    ref = launch_ref(reqs)
    launch = [r["run"][0] - r[ref][0] for r in reqs]
    ret = [r["fetch_end"] - r["run"][1] for r in reqs]
    a = min(launch) / 1e3 if launch else None
    b = min(ret) / 1e3 if ret else None
    offset = 0.0
    if a is not None and a < 0:
        offset = -a
    elif b is not None and b < 0:
        offset = b
    return {"launch_min_us": a, "launch_n": len(launch), "launch_from": ref,
            "return_min_us": b, "return_n": len(ret), "offset_us": offset}


def split(reqs: List[Dict], offset_us: float = 0.0) -> Dict:
    """Medians over the requests, in ms: ``latency`` (put start to fetch
    end), ``put``, ``dispatch`` (put end to enqueue start), ``launch``
    (run start - enqueue start, or the call's start: ``launch_from``
    says which, :func:`launch_ref`), ``run`` (the
    device run, on the device's clock alone) and ``return`` (fetch end -
    run end); launch and return corrected by ``offset_us``.  The rest
    is ``latency`` less the five parts that tile it.  Where the runtime
    records them, the return's parts too: ``return_notice`` (run end to
    the host's first read of the completion flag, corrected),
    ``return_issue`` (to the device-to-host copy's issue),
    ``return_copy`` (the copy, to its completion) and ``return_wake``
    (to the fetch's end)."""
    if not reqs:
        return {}
    ref = launch_ref(reqs)
    o = offset_us * 1e3

    def start(r):
        return r[ref][0]

    parts = {
        "latency": [r["fetch_end"] - r["put"][0] for r in reqs],
        "put": [r["put"][1] - r["put"][0] for r in reqs],
        "dispatch": [start(r) - r["put"][1] for r in reqs],
        "launch": [r["run"][0] + o - start(r) for r in reqs],
        "run": [r["run"][1] - r["run"][0] for r in reqs],
        "return": [r["fetch_end"] - r["run"][1] - o for r in reqs],
    }
    if all(r["sync"] and r["d2h"] and r["d2h_done"] for r in reqs):
        parts.update({
            "return_notice": [r["sync"][0] - r["run"][1] - o for r in reqs],
            "return_issue": [r["d2h"][0] - r["sync"][0] for r in reqs],
            "return_copy": [r["d2h_done"][1] - r["d2h"][0] for r in reqs],
            "return_wake": [r["fetch_end"] - r["d2h_done"][1] for r in reqs],
        })
    out = {k: _median(v) / 1e6 for k, v in parts.items()}
    out["launch_from"] = ref
    out["requests"] = len(reqs)
    return out


def stage_ms(tr: Dict, n_requests: int, device: str = "0") -> List[List]:
    """Device time per executor stage over the window's program runs,
    in ms per request, most first: ``[[stage, ms], ...]``."""
    w0, w1 = next((s, s + d) for n, s, d in tr["host"] if n == "window")
    runs = sorted((s, s + d) for _, s, d in tr["devices"].get(device, {}).get("runs", [])
                  if s + d > w0 and s < w1)
    starts = [a for a, _ in runs]
    by = collections.Counter()
    for name, s, d in tr.get("stages", {}).get(device, []):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s <= runs[i][1]:
            by[name] += d
    return [[k, v / 1e6 / max(n_requests, 1)] for k, v in by.most_common()]


def reduce(tr: Dict, n_devices: int = 1) -> Optional[Dict]:
    """:func:`bench.trace.reduce`'s dict, unchanged, plus
    ``"requests"`` (:func:`requests`), ``"clock"`` (:func:`clock_check`),
    ``"split"`` (:func:`split`, corrected by the clock check's offset)
    and ``"stage_ms"`` (:func:`stage_ms`)."""
    r = trace.reduce(tr, n_devices=n_devices)
    if r is None:
        return None
    reqs = requests(tr)
    clock = clock_check(reqs)
    r.update(requests=reqs, clock=clock, split=split(reqs, clock["offset_us"]),
             stage_ms=stage_ms(tr, len(reqs)))
    return r


def clock_line(clock: Dict) -> str:
    def us(v):
        return "none" if v is None else f"{v:.1f} us"
    return (f"clock check: least run start - {clock['launch_from']} start "
            f"{us(clock['launch_min_us'])} "
            f"over {clock['launch_n']} requests; least fetch end - run end "
            f"{us(clock['return_min_us'])} over {clock['return_n']} requests; "
            f"device events shifted by {clock['offset_us']:.1f} us")


def trim(tr: Dict, requests: int) -> Dict:
    """:func:`bench.trace.trim` of the window, with the runtime's events
    and the ops' stages that lie before its new end."""
    out = trace.trim(tr, requests)
    w0, d = next((s, d) for n, s, d in out["host"] if n == "window")
    out["runtime"] = [e for e in tr.get("runtime", []) if w0 <= e[2] < w0 + d]
    keep = {k: {(o[1], o[2]) for o in v["ops"]} for k, v in out["devices"].items()}
    out["stages"] = {k: [s for s in v if (s[1], s[2]) in keep.get(k, ())]
                     for k, v in tr.get("stages", {}).items()}
    return out


def report(r: Dict) -> List[str]:
    """The lines printed for a reduced window."""
    s = r["split"]
    lines = [clock_line(r["clock"])]
    if s:
        lines.append(
            f"request path over {s['requests']} requests, medians (ms): latency "
            f"{s['latency']:.4f} = put {s['put']:.4f} + dispatch {s['dispatch']:.4f} + launch "
            f"{s['launch']:.4f} (from {s['launch_from']}) + run {s['run']:.4f} + return "
            f"{s['return']:.4f}")
    if "return_notice" in s:
        lines.append(
            f"return, medians (ms): notice {s['return_notice']:.4f}, issue "
            f"{s['return_issue']:.4f}, copy {s['return_copy']:.4f}, wake {s['return_wake']:.4f}")
    lines.append("stages by device time (ms per request): " + ", ".join(
        f"{k} {v:.4f}" for k, v in r["stage_ms"][:TOP]))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="The request path of a kept trace.")
    ap.add_argument("log_dir")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    tr = load(args.log_dir)
    r = reduce(tr)
    if r is None:
        print("no device op in the trace")
        return 1
    names = collections.Counter((k, n) for k, n, _, _ in tr["runtime"])
    print("runtime events kept: " + ", ".join(f"{k}:{n} x{c}" for (k, n), c in names.items()))
    for line in report(r):
        print(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(trim(tr, args.requests), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
