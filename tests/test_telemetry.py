"""Telemetry layer: registry semantics under threads, histogram edge
conventions, Chrome-trace schema validity, span nesting, the stage-timed
executor's parity/coverage, and the jaxpr-identity guarantee that
telemetry never perturbs the default executor."""
import gc
import glob
import json
import threading

import jax
import numpy as np
import pytest

from repro.core import pipeline as pipe
from repro.core import telemetry as tele
from repro.core.synthesis import CNN2Gate
from repro.models import cnn

RNG = np.random.default_rng(23)


# ------------------------------------------------------------ registry

def test_counter_thread_safety_smoke():
    reg = tele.MetricsRegistry()
    c = reg.counter("hits")
    n_threads, n_incs = 8, 2000

    def worker():
        for _ in range(n_incs):
            reg.counter("hits").inc()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_incs


def test_counter_monotonic_and_kind_mismatch():
    reg = tele.MetricsRegistry()
    reg.counter("a").inc(2.5)
    with pytest.raises(ValueError):
        reg.counter("a").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("a")           # already a counter
    with pytest.raises(TypeError):
        reg.histogram("a")


def test_gauge_set_add():
    g = tele.MetricsRegistry().gauge("depth")
    g.set(3)
    g.add(-1)
    assert g.value == 2.0


def test_histogram_bucket_edges_inclusive():
    # Prometheus `le` convention: a value on the edge lands IN that
    # bucket, the first value past it in the next.
    reg = tele.MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 1.0000001, 2.0, 4.0, 4.0000001, 100.0):
        h.record(v)
    assert h.counts == [1, 2, 1, 2]    # last is the +Inf overflow
    assert h.count == 6
    assert h.min == 1.0 and h.max == 100.0


def test_histogram_percentiles():
    h = tele.MetricsRegistry().histogram("lat", buckets=(1.0, 2.0, 4.0))
    assert h.percentile(50) is None    # empty
    for v in (0.5, 1.5, 1.6, 3.0):
        h.record(v)
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 2.0           # falls in the (1, 2] bucket
    # percentiles are clamped to the observed range, never a raw edge
    assert h.percentile(0) >= 0.5
    assert h.percentile(100) <= 3.0
    h.record(50.0)                     # overflow bucket
    assert h.percentile(99) == 50.0    # +Inf bucket reports observed max
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_rejects_bad_buckets():
    reg = tele.MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=())
    with pytest.raises(ValueError):
        reg.histogram("bad2", buckets=(1.0, 1.0))


def test_snapshot_shape_and_json_round_trip():
    reg = tele.MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(7)
    reg.histogram("h", buckets=(1.0, 2.0)).record(1.5)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    hist = snap["histograms"]["h"]
    assert hist["count"] == 1 and hist["bucket_counts"] == [0, 1, 0]
    for k in ("sum", "min", "max", "mean", "p50", "p95", "p99",
              "buckets"):
        assert k in hist
    json.dumps(snap)                   # must be JSON-serializable
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


# -------------------------------------------------------------- tracer

def test_chrome_trace_schema():
    tr = tele.Tracer()
    with tr.span("outer", cat="test", args={"k": 1}):
        pass
    tr.add_span("injected", ts_us=1.0, dur_us=2.0, cat="stage")
    doc = tr.to_chrome_trace()
    blob = json.dumps(doc)             # Perfetto needs valid JSON
    doc = json.loads(blob)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:                     # complete-event required keys
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "cat"):
            assert key in ev, f"missing {key!r} in {ev}"
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float))
        assert ev["dur"] >= 0


def test_span_nesting_containment():
    # Perfetto infers nesting from containment per tid: the child span
    # interval must lie inside the parent's.
    tr = tele.Tracer()
    with tr.span("parent"):
        with tr.span("child"):
            pass
    by_name = {e["name"]: e for e in tr.events()}
    p, c = by_name["parent"], by_name["child"]
    assert p["tid"] == c["tid"]
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]


def test_span_records_error():
    tr = tele.Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("kaput")
    (ev,) = tr.events()
    assert "RuntimeError" in ev["args"]["error"]


def test_tracer_drops_past_max_events():
    tr = tele.Tracer(max_events=2)
    for i in range(5):
        tr.add_span(f"s{i}", 0.0, 1.0)
    assert len(tr.events()) == 2
    assert tr.dropped == 3


def test_tracer_export(tmp_path):
    tr = tele.Tracer()
    with tr.span("s"):
        pass
    path = tr.export(str(tmp_path / "sub" / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "s"


def test_span_yields_its_timing():
    tr = tele.Tracer()
    with tr.span("s") as sp:
        assert sp.name == "s" and sp.dur_us is None
    (ev,) = tr.events()
    assert sp.ts_us == ev["ts"] and sp.dur_us == ev["dur"] >= 0


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    """Under jax.profiler a span is also an annotation of the same name
    on the profile's host plane, on the device events' clock."""
    tr = tele.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("cnn2gate.test.outer"):
            with tr.span("cnn2gate.test.inner"):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {e.name: e for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events}
    outer, inner = host["cnn2gate.test.outer"], host["cnn2gate.test.inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    assert [e["name"] for e in tr.events()] == ["cnn2gate.test.inner",
                                                "cnn2gate.test.outer"]


def test_watch_gc_records_each_pass_until_stopped():
    reg = tele.MetricsRegistry()
    w = tele.watch_gc(reg, keep=4)
    try:
        for _ in range(6):
            gc.collect()
    finally:
        w.stop()
    h = reg.histogram(tele.GC_PAUSE, tele.GC_PAUSE_BUCKETS_S)
    assert h.count >= 6 and len(w.pauses) == 4
    assert all(d >= 0 and g in (0, 1, 2) for _, d, g in w.pauses)
    assert [p[1] for p in w.longest(2)] == sorted((p[1] for p in w.pauses),
                                                  reverse=True)[:2]
    n = h.count
    gc.collect()
    assert h.count == n and w not in gc.callbacks


# ------------------------------------------------- set-up spans and scopes

def test_calibrate_and_build_record_their_parts():
    tr = tele.get_tracer()
    tr.reset()
    try:
        g = CNN2Gate.from_graph(cnn.tiny_cnn(batch=1))
        x = RNG.standard_normal((1, 3, 32, 32)).astype(np.float32)
        g.calibrate_quantization(x)
        g.build("fullflow")
        ev = {e["name"]: e for e in tr.events()}
    finally:
        tele.reset()
    for parent, children in (("cnn2gate.calibrate", ("float_pass", "quantize")),
                             ("cnn2gate.build", ("lower", "compile"))):
        p = ev[parent]
        for c in children:
            e = ev[f"{parent}.{c}"]
            assert p["ts"] <= e["ts"] and e["ts"] + e["dur"] <= p["ts"] + p["dur"]
    assert g.synthesis_time_s == pytest.approx(
        (ev["cnn2gate.build.lower"]["dur"] + ev["cnn2gate.build.compile"]["dur"]) / 1e6)


def test_build_records_each_fc_stages_tiles():
    """Each FC stage's blocks and grid steps, as ``fc_tiles`` chose them
    for the traced batch, are gauges on the default registry."""
    from repro.kernels.qgemm import fc_tiles
    tele.reset()
    try:
        g = CNN2Gate.from_graph(cnn.tiny_cnn(batch=2))
        x = RNG.standard_normal((2, 3, 32, 32)).astype(np.float32)
        g.calibrate_quantization(x)
        g.build("fullflow")
        gauges = tele.get_registry().snapshot()["gauges"]
    finally:
        tele.reset()
    fcs = [ql for ql in g.quantized.layers if ql.info.kind == pipe.P.FC]
    assert fcs
    for ql in fcs:
        t = fc_tiles(2, *ql.w_q.shape)
        pre = f"cnn2gate.fc.{ql.info.name}."
        assert gauges[pre + "grid_steps"] == t.grid_steps
        assert gauges[pre + "weight_block_bytes"] == t.weight_block_bytes
        assert (gauges[pre + "block_m"], gauges[pre + "block_k"],
                gauges[pre + "block_n"]) == (t.bm, t.bk, t.bn)


TINY_STAGES = [li.name for li in CNN2Gate.from_graph(cnn.tiny_cnn(batch=1)).parsed.layers]


@pytest.fixture(scope="module")
def tiny_lowered_text():
    g = CNN2Gate.from_graph(cnn.tiny_cnn(batch=1))
    x = RNG.standard_normal((1, 3, 32, 32)).astype(np.float32)
    g.calibrate_quantization(x)
    ex = pipe.make_executor(g.quantized, 16, 32, interpret=True)
    return ex.lower(jax.ShapeDtypeStruct(x.shape, np.float32)).as_text(debug_info=True)


@pytest.mark.parametrize("stage", TINY_STAGES + ["ingress", "egress"])
def test_every_stage_names_its_ops(tiny_lowered_text, stage):
    """Each stage's ops carry its name in their location (the op_name a
    device trace shows), under the executor's own scope."""
    assert f"jit(forward)/{stage}/" in tiny_lowered_text


# ----------------------------------------------- stage-timed executor

@pytest.fixture(scope="module")
def gate():
    g = CNN2Gate.from_graph(cnn.resnet_tiny(batch=1))
    x = (RNG.standard_normal((1, 3, 32, 32)) * 0.5).astype(np.float32)
    g.calibrate_quantization(x)
    return g, x


def test_telemetry_off_keeps_jaxpr_identical(gate):
    """Default executor jaxpr must be byte-identical whether or not
    telemetry has been exercised in the process — the observability
    layer must never perturb the compiled program."""
    g, x = gate
    base = str(jax.make_jaxpr(
        pipe.make_executor(g.quantized, 16, 32, interpret=True))(x))
    tele.get_tracer().add_span("noise", 0.0, 1.0)
    tele.get_registry().counter("noise").inc()
    try:
        probe = str(jax.make_jaxpr(
            pipe.make_executor(g.quantized, 16, 32, interpret=True,
                               stage_timed=False, tracer=None))(x))
    finally:
        tele.reset()
    assert probe == base


def test_stage_timed_parity_and_coverage(gate):
    g, x = gate
    plain = pipe.make_executor(g.quantized, 16, 32, interpret=True)
    tr = tele.Tracer()
    timed = pipe.make_executor(g.quantized, 16, 32, interpret=True,
                               stage_timed=True, tracer=tr)
    y0 = np.array(plain(x))
    y1, timings = timed(x)
    np.testing.assert_array_equal(y0, np.array(y1))   # bit-exact

    names = [t["stage"] for t in timings]
    assert names[0] == "ingress" and names[-1] == "egress"
    scheduled = [ql.info.name for ql in g.quantized.layers]
    assert names[1:-1] == scheduled                   # full coverage
    assert all(t["wall_us"] >= 0 for t in timings)
    # every stage produced a span on the tracer
    span_names = {e["name"] for e in tr.events()
                  if e.get("cat") == "stage"}
    assert set(scheduled) <= span_names


def test_stage_timed_exclusive_with_hooks(gate):
    g, _ = gate
    with pytest.raises(ValueError, match="stage_timed"):
        pipe.make_executor(g.quantized, 16, 32, interpret=True,
                           stage_timed=True, audit=True)
    with pytest.raises(ValueError, match="stage_timed"):
        pipe.make_executor(g.quantized, 16, 32, interpret=True,
                           stage_timed=True,
                           checkpoints=[g.quantized.layers[0].info.name])


# ------------------------------------------------ attribution profile

def test_spearman_rank_correlation():
    from repro.launch.profile import spearman
    assert spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1.0, 1.0, 1.0], [1, 2, 3]) is None  # constant side
    assert spearman([1], [2]) is None                    # too few
    # monotone nonlinear map preserves ranks exactly
    a = [1.0, 4.0, 2.0, 8.0, 5.0]
    assert spearman(a, [v ** 3 for v in a]) == pytest.approx(1.0)


def test_profile_model_report_shape():
    from repro.launch import profile as prof
    tr = tele.Tracer()
    doc = prof.profile_model("tiny_cnn", iters=1, warmup=1, tracer=tr)
    s = doc["summary"]
    assert s["n_stages"] == len(doc["stages"]) > 0
    for row in doc["stages"]:
        for key in ("stage", "kind", "wall_us", "model_us", "ddr_bytes",
                    "vmem_bytes", "macs", "model_wall_ratio"):
            assert key in row
        assert row["wall_us"] >= 0 and row["model_us"] > 0
    assert "ingress" in doc["overhead_us"]
    assert "egress" in doc["overhead_us"]
    json.dumps(doc)                    # BENCH-ready


# ----------------------------------------------- instrumented consumers

def test_robust_evaluator_mirrors_stats_to_registry():
    from repro.core import dse
    from repro.core.resources import ResourceReport

    class TinySpace(dse.DesignSpace):
        def options(self):
            return [(0,), (1,)]

        def axes(self):
            return [[0, 1]]

        def evaluate(self, option):
            pct = 40.0 + 10.0 * option[0]
            return ResourceReport(
                percents={k: pct for k in ("lut", "dsp", "mem", "reg")},
                raw={"pct": pct}, fits=True)

    reg, tr = tele.MetricsRegistry(), tele.Tracer()
    ev = dse.RobustEvaluator(TinySpace(), registry=reg, tracer=tr)
    for opt in ev.options():
        ev.evaluate(opt)
    snap = reg.snapshot()["counters"]
    assert snap.get("dse.evaluated") == ev.stats["evaluated"] == 2
    assert any(e["name"] == "dse.evaluate" for e in tr.events())


def test_bench_json_schema(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "REPO_ROOT", str(tmp_path))
    with pytest.raises(TypeError):
        common.write_bench_json("x", [1, 2, 3])
    path = common.write_bench_json("x", {"ok": 1})
    with open(path) as f:
        doc = json.load(f)
    assert doc["bench"] == "x" and doc["results"] == {"ok": 1}
    for key in common.ENV_REQUIRED_KEYS:
        assert key in doc["env"]
