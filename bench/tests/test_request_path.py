"""The request path of a traced window (bench/request_path.py): on a
hand-made trace whose answers are known, and on the recorded vgg16.b1
trace, whose reduction by bench/trace.py it must leave as it is."""
import json

import pytest

from bench import request_path as rp
from bench import trace
from conftest import ROOT


def _trace(device_shift=0):
    # window 0..100 us; request 1: put 0-10, call 10-20 (enqueue 12-18),
    # run 15-40, fetch 20-50 (completion read at 42, copy 44-47 done at
    # 48); request 2: put 50-60, call 60-70 (enqueue 61-66), run 70-80,
    # fetch 70-95 (read 83, copy 85-88 done at 90).  ``device_shift``
    # moves the device's clock against the host's.
    host = [["window", 0, 100_000],
            ["put", 0, 10_000], ["call", 10_000, 10_000], ["fetch", 20_000, 30_000],
            ["put", 50_000, 10_000], ["call", 60_000, 10_000], ["fetch", 70_000, 25_000]]
    runtime = [["program", "cnn2gate.build", 1_000, 500],
               ["h2d", "tpu::System::TransferToDevice", 2_000, 5_000],
               ["enqueue", "tpu::System::Execute", 12_000, 6_000],
               ["sync", "ReadSyncFlag", 42_000, 1_000],
               ["d2h", "tpu::System::TransferFromDevice", 44_000, 3_000],
               ["d2h_done", "tpu::System::TransferFromDevice=>IssueEvent=>Done", 47_000, 1_000],
               ["h2d", "tpu::System::TransferToDevice", 52_000, 5_000],
               ["enqueue", "tpu::System::Execute", 61_000, 5_000],
               ["sync", "ReadSyncFlag", 83_000, 1_000],
               ["d2h", "tpu::System::TransferFromDevice", 85_000, 3_000],
               ["d2h_done", "tpu::System::TransferFromDevice=>IssueEvent=>Done", 88_000, 2_000]]
    d = device_shift
    runs = [["forward", 15_000 + d, 25_000], ["forward", 70_000 + d, 10_000]]
    ops = [["%qconv2d.1 = s8[1]", 16_000 + d, 8_000], ["%qgemm.1 = s8[1]", 25_000 + d, 14_000],
           ["%qconv2d.1 = s8[1]", 71_000 + d, 4_000], ["%qgemm.1 = s8[1]", 76_000 + d, 3_000]]
    stages = {"0": [["conv1", 16_000 + d, 8_000], ["fc6", 25_000 + d, 14_000],
                    ["conv1", 71_000 + d, 4_000], ["fc6", 76_000 + d, 3_000]]}
    return {"devices": {"0": {"ops": ops, "runs": runs}}, "host": host,
            "runtime": runtime, "stages": stages}


def test_each_request_is_paired_with_its_run_and_runtime_events():
    reqs = rp.requests(_trace())
    assert [r["run"] for r in reqs] == [[15_000, 40_000], [70_000, 80_000]]
    assert [r["enqueue"] for r in reqs] == [[12_000, 18_000], [61_000, 66_000]]
    assert [r["h2d"] for r in reqs] == [[2_000, 7_000], [52_000, 57_000]]
    assert [r["sync"][0] for r in reqs] == [42_000, 83_000]
    assert [r["d2h_done"][1] for r in reqs] == [48_000, 90_000]
    assert [r["fetch_end"] for r in reqs] == [50_000, 95_000]
    assert [r["put"] for r in reqs] == [[0, 10_000], [50_000, 60_000]]
    # a kind the window does not hold once per request is left out
    tr = _trace()
    tr["runtime"] = [e for e in tr["runtime"] if e[2] != 83_000]
    assert [r["sync"] for r in rp.requests(tr)] == [None, None]
    assert "return_notice" not in rp.split(rp.requests(tr))


def test_the_split_of_a_request_adds_up_to_its_latency():
    r = rp.reduce(_trace())
    s = r["split"]
    assert s["launch_from"] == "enqueue" and s["requests"] == 2
    # medians of two: launch 3 and 9 us, run 25 and 10, return 10 and 15
    assert s["launch"] == pytest.approx(6e-3)
    assert s["run"] == pytest.approx(17.5e-3)
    assert s["return"] == pytest.approx(12.5e-3)
    assert s["put"] == pytest.approx(10e-3)
    assert s["dispatch"] == pytest.approx(1.5e-3)
    assert s["latency"] == pytest.approx(47.5e-3)
    # return 10 and 15 us: read 2 and 3 after the run, issued 2 later,
    # copied in 4 and 5, and the fetch ends 2 and 5 after
    assert [s[f"return_{k}"] for k in ("notice", "issue", "copy", "wake")] == [
        pytest.approx(2.5e-3), pytest.approx(2e-3), pytest.approx(4.5e-3), pytest.approx(3.5e-3)]
    assert r["stage_ms"] == [["fc6", pytest.approx(8.5e-3)], ["conv1", pytest.approx(6e-3)]]


def test_the_clock_check_bounds_the_offset_and_corrects_for_it():
    c = rp.clock_check(rp.requests(_trace()))
    assert c == {"launch_min_us": 3.0, "launch_n": 2, "launch_from": "enqueue",
                 "return_min_us": 10.0, "return_n": 2, "offset_us": 0.0}
    # a device clock 5 us behind the host's starts the first run before
    # its enqueue: the bound is negative, and the split is corrected
    shifted = rp.reduce(_trace(device_shift=-5_000))
    assert shifted["clock"]["launch_min_us"] == pytest.approx(-2.0)
    assert shifted["clock"]["offset_us"] == pytest.approx(2.0)
    s = shifted["split"]
    assert s["launch"] == pytest.approx((0 + 6) / 2 * 1e-3)
    assert s["launch"] + s["return"] == pytest.approx(rp.reduce(_trace())["split"]["launch"]
                                                      + rp.reduce(_trace())["split"]["return"])
    # ahead of it, the runs end after the host has their answers
    ahead = rp.clock_check(rp.requests(_trace(device_shift=12_000)))
    assert ahead["return_min_us"] == pytest.approx(-2.0)
    assert ahead["offset_us"] == pytest.approx(-2.0)
    line = rp.clock_line(shifted["clock"])
    assert line.startswith("clock check: least run start - enqueue start -2.0 us over 2 requests")
    assert "least fetch end - run end" in line


def test_without_runtime_events_a_launch_is_timed_from_the_call():
    tr = _trace()
    tr["runtime"] = []
    r = rp.reduce(tr)
    assert r["split"]["launch_from"] == r["clock"]["launch_from"] == "call"
    assert r["clock"]["launch_min_us"] == pytest.approx(5.0)  # run 15 - call 10


def test_host_events_are_sorted_by_kind():
    assert rp.kind("tpu::System::Execute") == "enqueue"
    assert rp.kind("tpu::System::TransferFromDevice") == "d2h"
    assert rp.kind("tpu::System::TransferFromDevice=>IssueEvent") is None
    assert rp.kind("cnn2gate.calibrate.float_pass") == "program"
    assert rp.kind("put") is None and rp.kind("CommonPjRtLoadedExecutable::Execute") is None
    assert rp.stage(["xla", "jit(forward)/conv_1/jit(qconv2d)/pallas_call"]) == "conv_1"
    assert rp.stage(["%fusion = s8[1] fusion(...)"]) is None


def _recorded():
    return json.loads((ROOT / "bench" / "tests" / "vgg16_b1_trace.json").read_text())


def test_the_recorded_trace_keeps_what_bench_trace_reduces():
    """Every key bench/trace.py returns on the recorded trace is there,
    with the same value; idle_gaps keeps its names."""
    tr = _recorded()
    before = trace.reduce(tr)
    r = rp.reduce(_recorded())
    assert {k: r[k] for k in before} == before
    assert set(r) - set(before) == {"requests", "clock", "split", "stage_ms"}
    # the values bench/trace.py gave when the fixture was recorded
    assert r["window_s"] == pytest.approx(0.008060059)
    assert r["busy_s"] == pytest.approx(0.004215584)
    assert len(r["ops"]) == 308 and r["devices"] == 1
    assert r["idle_gaps"] == [["idle during fetch", pytest.approx(0.002785758)],
                              ["idle during put", pytest.approx(0.00070763)],
                              ["idle during call", pytest.approx(0.000312677)],
                              ["idle during between spans", pytest.approx(3.841e-05)]]
    assert r["top_ops"][0] == ["qgemm.3 = s8[8,4096]", pytest.approx(0.003058178)]


def test_the_recorded_trace_without_runtime_events():
    # two requests: call starts 388.6 us into the window, the run covers
    # 682.2-2790.4 and the fetch ends at 4206.1; then 4554.5, 4573.5-
    # 6682.0 and 8051.1
    r = rp.reduce(_recorded())
    assert r["clock"]["launch_from"] == "call"
    assert r["clock"]["launch_min_us"] == pytest.approx(19.0, abs=0.1)
    assert r["clock"]["return_min_us"] == pytest.approx(1369.1, abs=0.1)
    assert r["split"]["run"] == pytest.approx(2.1083, abs=1e-4)
    assert r["split"]["return"] == pytest.approx(1.3924, abs=1e-4)


@pytest.mark.parametrize("metric,span", [("float_pass_s", "cnn2gate.calibrate.float_pass"),
                                         ("lower_s", "cnn2gate.build.lower")])
def test_set_up_readers_take_the_programs_newest_span(metric, span):
    from bench import harness
    from repro.core import telemetry
    read = harness.load_module(ROOT / "bench" / "metrics" / f"{metric}.py").read
    tracer = telemetry.get_tracer()
    tracer.reset()
    try:
        assert read({}) is None  # a program that records no such span
        tracer.add_span(span, 0.0, 2.5e6)
        tracer.add_span("cnn2gate.build.compile", 0.0, 9e6)
        tracer.add_span(span, 3e6, 1.25e6)
        assert read({}) == pytest.approx(1.25)
    finally:
        telemetry.reset()


def _recorded_with_runtime():
    """Three requests of a vgg16.b1 window traced on a TPU v5 lite with
    the runtime's events and the ops' stages, trimmed by
    ``bench/request_path.py --requests 3``."""
    return json.loads((ROOT / "bench" / "tests" / "vgg16_b1_request_path.json").read_text())


def test_recorded_request_path_and_clock_check():
    r = rp.reduce(_recorded_with_runtime())
    c = r["clock"]
    # the device's events lie ~1.1 ms early on the host's line: every
    # run "starts" before the runtime launched it
    assert c["launch_from"] == "enqueue" and c["launch_n"] == c["return_n"] == 3
    assert c["launch_min_us"] == pytest.approx(-1146.31, abs=0.01)
    assert c["return_min_us"] == pytest.approx(2257.42, abs=0.01)
    assert c["offset_us"] == pytest.approx(1146.31, abs=0.01)
    assert rp.clock_line(c) == (
        "clock check: least run start - enqueue start -1146.3 us over 3 requests; least "
        "fetch end - run end 2257.4 us over 3 requests; device events shifted by 1146.3 us")
    s = r["split"]
    assert s["run"] == pytest.approx(2.1083, abs=1e-4)  # device_run_ms
    assert s["launch"] == pytest.approx(0.0620, abs=1e-4)  # launch_ms
    assert s["return"] == pytest.approx(1.1482, abs=1e-4)  # return_ms
    assert s["put"] == pytest.approx(0.2455, abs=1e-4)
    # medians of the parts of three requests: they add up to within 2%
    parts = s["put"] + s["dispatch"] + s["launch"] + s["run"] + s["return"]
    assert parts == pytest.approx(s["latency"], rel=0.02)
    assert s["return_copy"] == pytest.approx(0.1961, abs=1e-4)


def test_recorded_stages_and_kernels():
    tr = _recorded_with_runtime()
    r = rp.reduce(tr)
    names = [trace.op_name(n) for n, _ in r["ops"]]
    assert sum(n.startswith("qconv") for n in names) == 13 * 3
    assert sum(n.startswith("qgemm") for n in names) == 3 * 3
    stages = dict(r["stage_ms"])
    assert r["stage_ms"][0][0] == "gemm_33"  # FC1
    assert {"ingress", "egress", "conv_1", "conv_29", "gemm_37"} <= set(stages)
    # the stages cover the device's run, less the ops no scope names
    assert 0.97 * r["split"]["run"] < sum(stages.values()) <= r["split"]["run"]


def test_trim_keeps_the_runtime_and_stages_of_the_requests_it_keeps():
    tr = _recorded_with_runtime()
    cut = rp.trim(tr, 2)
    r = rp.reduce(cut)
    assert len(r["requests"]) == 2 and r["split"]["launch_from"] == "enqueue"
    assert len(cut["runtime"]) == 2 * len(rp.RUNTIME)
    assert cut["stages"]["0"] and len(cut["stages"]["0"]) < len(tr["stages"]["0"])


def _pb(*fields):
    """Protobuf wire bytes of ``(number, value)`` fields: an int is a
    varint, bytes or str length-delimited."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_texts_reads_the_scope_from_event_metadata(tmp_path):
    # XSpace.planes = 1; XPlane.name = 2, event_metadata = 4 and
    # stat_metadata = 5 (map entries: key = 1, value = 2);
    # XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    # str_value = 5, ref_value = 7
    scope = "jit(forward)/gemm_33/jit(qgemm)/pallas_call"
    device = _pb((2, "/device:TPU:0"),
                 (5, _pb((1, 7), (2, _pb((1, 7), (2, "tf_op"))))),
                 (5, _pb((1, 9), (2, _pb((1, 9), (2, "jit(forward)/ingress/mul"))))),
                 (4, _pb((1, 1), (2, _pb((1, 1), (2, "%qgemm.3 = s8[8,4096]"),
                                         (5, _pb((1, 7), (5, scope))))))),
                 (4, _pb((1, 2), (2, _pb((1, 2), (2, "%fusion.1 = f32[1]"),
                                         (5, _pb((1, 7), (7, 9))))))),
                 (4, _pb((1, 3), (2, _pb((1, 3), (2, "%copy.4 = s8[1]"))))))
    host = _pb((2, "/host:CPU"), (4, _pb((1, 1), (2, _pb((1, 1), (2, "put"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device)))
    texts = rp.op_texts(str(path))
    assert list(texts) == ["/device:TPU:0"]
    ops = texts["/device:TPU:0"]
    assert ops["%qgemm.3 = s8[8,4096]"] == [scope]
    assert {k: rp.stage(v) for k, v in ops.items()} == {
        "%qgemm.3 = s8[8,4096]": "gemm_33", "%fusion.1 = f32[1]": "ingress",
        "%copy.4 = s8[1]": None}
