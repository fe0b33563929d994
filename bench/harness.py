"""One run of one benchmark cell: set-up, a measured window of closed-loop
requests, and the check of every answer against the plain reference.

Everything that belongs to one configuration, traffic mix or metric is
data found by its name in ``BENCHMARK.json``:

  * ``bench/configs/<config>.json``: the model's layers and how the
    program builds it; its ``reference`` names a module of
    ``bench/reference/``, ``weights_seed`` makes its weights and its
    calibration image, and ``logprob_gap_limit`` is the limit of the
    output check;
  * ``bench/traffic/<mix>.json``: batch, requests in flight and the size
    of the seeded image pool, read by :func:`drive`;
  * ``bench/metrics/<metric>.py``: a ``read(rec)`` that takes one metric
    from the run record (see :func:`run_cell`), or returns None where it
    finds nothing to read.

A request is what a client feels: ``device_put`` of the image batch, the
call, and fetching the probabilities back into host memory.

The executor holds its weights and scales as constants, so the weights
come from the configuration's fixed ``weights_seed`` and a cell compiles
one program: ``--seed`` draws the request pool alone, and every run after
a cell's first loads its executor from the persistent cache.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import counts, trace  # noqa: E402

#: requests sent before the window, after the executor is built
WARMUP_REQUESTS = 16
#: longest traced window of a ``--trace 1`` run, after its untraced one
TRACE_SECONDS = 2.0
#: least probability the output check reads: float32 flushes values
#: under 1.2e-38 to zero, so below this the log of a tail probability
#: says how a softmax rounds, not what the network computed
P_FLOOR = 1e-30
#: the backend compile (or persistent-cache load) event JAX records
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: size limit of the persistent compilation cache for this process: a
#: VGG-16 executor with its weights as constants serializes to about
#: 332 MB, over the 192 MiB some machines set
CACHE_MAX_BYTES = 4 * 1024 ** 3


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its
    configuration, traffic mix and metrics, each found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    # a metric with a "workloads" list is these cells'; a per-layer one
    # without it belongs to every cell that reports the metric it moves
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if (name in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per, root)


def use_cache() -> str:
    """Turn on JAX's persistent compilation cache through the program's
    own helper (the directory ``JAX_COMPILATION_CACHE_DIR`` names, else
    the checkout's ``.jax_compile_cache/``) and return its directory.
    For this process the size limit is raised so that a VGG-16 executor
    fits, and every compile, however short, is cached."""
    import jax
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def check_device(chips: int, peaks: Dict):
    """The devices to run on, or :class:`NoChip`: a TPU whose kind is in
    the table of peaks, with at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX's default device is {devices[0].platform!r}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def calibration_image(cfg: Dict, weights_seed: int) -> np.ndarray:
    """The one standard-normal NCHW float32 image that calibrates the
    scales, drawn with the weights from ``weights_seed``."""
    rng = np.random.default_rng([weights_seed, 0])
    return rng.standard_normal((1,) + tuple(cfg["input_chw"]), dtype=np.float32)


def request_pool(cfg: Dict, mix: Dict, seed: int) -> np.ndarray:
    """The seeded request pool: (pool_images / batch) batches of
    standard-normal NCHW float32 images; every seed sends the same
    sizes."""
    rng = np.random.default_rng([seed, 1])
    b = mix["batch"]
    n = mix["pool_images"]
    if n % b:
        raise ValueError("pool_images must be a multiple of batch")
    return rng.standard_normal((n // b, b) + tuple(cfg["input_chw"]), dtype=np.float32)


class Spans:
    """Host-clock spans of the harness's own steps, in seconds."""

    def __init__(self):
        self.s: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t0


def program_executor(cell: Cell, weights_seed: int, x_cal: np.ndarray,
                     spans: Spans) -> Callable:
    """The entry the window drives: ``CNN2Gate.build("fullflow")`` of the
    configuration's graph at the cell's batch with the weights of
    ``weights_seed``, calibrated on ``x_cal`` with the float pass on the
    host's CPU device."""
    import jax
    from repro.core.synthesis import CNN2Gate
    from repro.models import cnn

    cfg = cell.config
    with spans("graph"):
        graph = getattr(cnn, cfg["builder"])(batch=cell.traffic["batch"], seed=weights_seed,
                                             **cfg["builder_kwargs"])
        gate = CNN2Gate.from_graph(graph)
    with spans("calibrate"):
        with jax.default_device(jax.devices("cpu")[0]), \
                jax.default_matmul_precision("highest"):
            gate.calibrate_quantization(x_cal)
    with spans("compile"):
        return gate.build("fullflow")


def reference(cell: Cell, weights_seed: int, x_cal: np.ndarray, bits: int = 8):
    mod = load_module(cell.root / "bench" / "reference" / f"{cell.config['reference']}.py")
    return mod.build(cell.config, weights_seed, x_cal, bits)


def control_executor(bits: int = 4):
    """The control: the reference computed in ``bits``, in the program's
    place."""
    def make(cell: Cell, weights_seed: int, x_cal: np.ndarray, spans: Spans) -> Callable:
        return reference(cell, weights_seed, x_cal, bits)
    return make


@dataclasses.dataclass
class Window:
    """What one closed-loop window did: per request its pool batch,
    start and end on the host clock, and the answer."""
    batch_idx: List[int]
    t0: np.ndarray
    t1: np.ndarray
    outputs: List[np.ndarray]

    @property
    def seconds(self) -> float:
        return float(self.t1[-1] - self.t0[0])


def drive(fn: Callable, pool: np.ndarray, in_flight: int, *,
          seconds: Optional[float] = None, requests: Optional[int] = None) -> Window:
    """Closed loop over the pool's batches in order, ``in_flight``
    requests outstanding: a request is put and dispatched while older
    ones run, and each is timed from its put to its fetch.  Sends until
    ``seconds`` have passed (or ``requests`` were sent), then drains."""
    import jax
    from jax.profiler import TraceAnnotation

    clock = time.perf_counter
    idx, t0s, t1s, outs = [], [], [], []
    queue = collections.deque()
    sent = 0
    stop = clock() + seconds if seconds is not None else math.inf

    def more() -> bool:
        return sent < requests if requests is not None else clock() < stop

    while True:
        while len(queue) < in_flight and more():
            j = sent % len(pool)
            t0 = clock()
            with TraceAnnotation("put"):
                x = jax.device_put(pool[j])
            with TraceAnnotation("call"):
                y = fn(x)
            queue.append((j, t0, y))
            sent += 1
        if not queue:
            break
        j, t0, y = queue.popleft()
        with TraceAnnotation("fetch"):
            out = np.asarray(y)
        t1s.append(clock())
        idx.append(j)
        t0s.append(t0)
        outs.append(out)
    return Window(idx, np.asarray(t0s), np.asarray(t1s), outs)


def logprob_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap between two probability arrays in log space (natural
    units, the gap of the logits), each probability taken as at least
    :data:`P_FLOOR`; infinite where a value is not finite or the shapes
    differ."""
    if got.shape != want.shape:
        return math.inf
    a = np.maximum(np.asarray(got, np.float64), P_FLOOR)
    b = np.maximum(np.asarray(want, np.float64), P_FLOOR)
    d = np.abs(np.log(a) - np.log(b))
    d = np.where(np.isfinite(d), d, math.inf)
    return float(d.max()) if d.size else 0.0


def judge(wins: List[Window], want: np.ndarray, limit: float) -> Dict:
    """Every answer of the windows against the reference's answer for
    its pool batch; the numbers compared, each with its limit."""
    gap = max(logprob_gap(out, want[j]) for w in wins
              for j, out in zip(w.batch_idx, w.outputs))
    return {"logprob_gap": {"value": gap, "limit": limit}}


def read_metrics(cell: Cell, entries: List[Dict], rec: Dict) -> Dict:
    out = {}
    for m in entries:
        v = load_module(cell.root / "bench" / "metrics" / f"{m['name']}.py").read(rec)
        if v is None:
            print(f"metric {m['name']}: nothing to read, left out", flush=True)
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, make_executor: Callable = program_executor,
             weights_seed: Optional[int] = None, trace_dir: Optional[str] = None,
             peaks: Optional[Dict] = None) -> Dict:
    """One run: set-up from ``t_start`` (the process's start on the host
    clock) to the window's first request, the window, then the check.
    ``seed`` draws the request pool; the weights and the calibration
    image come from ``weights_seed``, by default the configuration's.
    Traced, a second window of at most :data:`TRACE_SECONDS` runs under
    the profiler after the untraced one.  Returns the result line's
    object."""
    import jax
    from jax import monitoring

    cfg, mix = cell.config, cell.traffic
    peaks = peaks if peaks is not None else json.loads(
        (cell.root / "bench" / "peaks.json").read_text())
    devices = jax.devices()[:max(cell.chips, 1)]
    peak = peaks.get(devices[0].device_kind)
    compiles = [0]

    def on_event(event: str, *_a, **_k):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    spans = Spans()
    wseed = cfg["weights_seed"] if weights_seed is None else weights_seed
    x_cal = calibration_image(cfg, wseed)
    pool = request_pool(cfg, mix, seed)
    fn = make_executor(cell, wseed, x_cal, spans)
    with spans("warmup"):
        drive(fn, pool, mix["in_flight"], requests=WARMUP_REQUESTS)
    monitoring.register_event_duration_secs_listener(on_event)
    setup_s = time.perf_counter() - t_start
    tdir, wins = None, []
    # the set-up's objects are left out of the collector's passes in the
    # windows, so that a full pass there walks only the window's own
    gc.collect()
    gc.freeze()
    try:
        wins.append(drive(fn, pool, mix["in_flight"], seconds=seconds))
        if traced:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            trace.start(tdir)
            try:
                with jax.profiler.TraceAnnotation("window"):
                    wins.append(drive(fn, pool, mix["in_flight"],
                                      seconds=min(seconds, TRACE_SECONDS)))
            finally:
                trace.stop()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
        gc.unfreeze()
    win = wins[0]
    lat = np.sort(win.t1 - win.t0)[::-1]
    print(f"slowest requests (ms): {np.round(1e3 * lat[:5], 3).tolist()}; time above the "
          f"median: {float(np.sum(np.maximum(lat - np.median(lat), 0))):.3f} s", flush=True)
    print(f"window: {len(win.outputs)} requests in {win.seconds:.3f} s"
          + (f"; traced window: {len(wins[1].outputs)} requests in {wins[1].seconds:.3f} s"
             if traced else "")
          + f"; compilations inside the windows: {compiles[0]}", flush=True)
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    reduced = None
    if traced:
        reduced = trace.reduce(trace.load(tdir), n_devices=len(devices))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    # free the program before the reference runs on the same chip
    del fn
    gc.collect()
    jax.clear_caches()
    with spans("reference"):
        ref = reference(cell, wseed, x_cal)
        want = ref.probabilities(pool.reshape((-1,) + pool.shape[2:]))
        want = want.reshape(pool.shape[:2] + want.shape[1:])
    checks = judge(wins, want, cfg["logprob_gap_limit"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"spans (s): {json.dumps(spans.s)}", flush=True)

    rec = {
        "cell": cell, "config": cfg, "traffic": mix, "peak": peak,
        "spans": spans.s, "setup_s": setup_s,
        "latencies_s": win.t1 - win.t0, "images": len(win.outputs) * mix["batch"],
        "window_s": win.seconds, "counts": counts.layer_counts(cfg, mix["batch"]),
        "trace": reduced, "trace_requests": len(wins[1].outputs) if traced else 0,
    }
    metrics = read_metrics(cell, cell.per_layer if traced else cell.end_to_end, rec)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": sum(len(w.outputs) for w in wins),
              "failed": 0, "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result
