"""The reduction from a device trace to the metrics, on a hand-made
trace whose answers are known."""
import pytest

from bench import trace


def _trace():
    # host spans: window 0..100 us; a request of put 0-10, call 10-20,
    # fetch 20-60, then another of put 60-70, call 70-75, fetch 75-100
    host = [["window", 0, 100_000],
            ["put", 0, 10_000], ["call", 10_000, 10_000], ["fetch", 20_000, 40_000],
            ["put", 60_000, 10_000], ["call", 70_000, 5_000], ["fetch", 75_000, 25_000]]
    ops = [["_qconv_band_kernel", 22_000, 8_000], ["fusion.1", 29_000, 3_000],
           ["_qgemm_kernel", 32_000, 8_000],
           ["_qconv_band_kernel", 77_000, 8_000], ["_qgemm_kernel", 85_000, 5_000],
           ["_qconv_band_kernel", 99_000, 4_000]]  # cut by the window's end
    return {"devices": {"0": {"ops": ops}}, "host": host}


def test_busy_idle_and_ops_inside_the_window():
    r = trace.reduce(_trace())
    assert r["window_s"] == pytest.approx(100e-6)
    # union: 22-40, 77-90, 99-100 (clipped)
    assert r["busy_s"] == pytest.approx((18 + 13 + 1) * 1e-6)
    assert [n for n, _ in r["ops"]] == ["_qconv_band_kernel", "fusion.1", "_qgemm_kernel",
                                        "_qconv_band_kernel", "_qgemm_kernel"]
    assert r["top_ops"][0] == ["_qconv_band_kernel", pytest.approx(17e-6)]
    gaps = dict(r["idle_gaps"])
    assert gaps["idle during put"] == pytest.approx(20e-6)
    assert gaps["idle during call"] == pytest.approx(15e-6)
    assert gaps["idle during fetch"] == pytest.approx((2 + 20 + 2 + 9) * 1e-6)
    assert sum(gaps.values()) == pytest.approx(100e-6 - r["busy_s"])


def test_no_device_op_means_nothing_to_read():
    t = _trace()
    t["devices"]["0"]["ops"] = []
    assert trace.reduce(t) is None


def _rec(tr, n_fc=1, requests=2):
    rows = [{"kind": "conv", "ops": 4e6, "bytes": 1e3}] + [{"kind": "fc", "ops": 0, "bytes": 82e3}] * n_fc
    return {"trace": trace.reduce(tr), "counts": rows, "trace_requests": requests,
            "traffic": {"batch": 4}, "peak": {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e10}}


def test_kernel_share_over_every_request_of_the_window():
    rec = _rec(_trace())
    # conv: 2 runs x 4 us least time over 16 us of kernel time
    assert trace.kernel_share(rec, "qconv", "conv") == pytest.approx(100 * 8 / 16)
    # fc: 2 runs x 8.2 us over 13 us
    assert trace.kernel_share(rec, "qgemm", "fc") == pytest.approx(100 * 16.4 / 13)


def test_kernel_share_left_out_unless_each_request_has_each_layer(capsys):
    assert trace.kernel_share(_rec(_trace(), n_fc=2), "qgemm", "fc") is None
    assert "expected 2 per request x 2 requests" in capsys.readouterr().out
    assert trace.kernel_share(_rec(_trace(), requests=3), "qconv", "conv") is None


def _recorded():
    """Two requests of a vgg16.b1 window traced on a TPU v5 lite and
    trimmed by ``bench/trace.py --requests 2``."""
    import json
    from conftest import ROOT
    return json.loads((ROOT / "bench" / "tests" / "vgg16_b1_trace.json").read_text())


def test_recorded_trace_holds_each_layer_once_per_request():
    import json
    from bench import counts
    from conftest import ROOT
    tr = _recorded()
    r = trace.reduce(tr)
    names = [trace.op_name(n) for n, _ in r["ops"]]
    assert sum(n.startswith("qconv") for n in names) == 13 * 2
    assert sum(n.startswith("qgemm") for n in names) == 3 * 2
    # the ops that merely take a kernel's output name it as an operand
    assert sum("%qconv" in n for n, _ in r["ops"]) > 13 * 2
    assert 0 < r["busy_s"] < r["window_s"]
    cfg = json.loads((ROOT / "bench" / "configs" / "vgg16.json").read_text())
    rec = {"trace": r, "counts": counts.layer_counts(cfg, 1), "trace_requests": 2,
           "peak": json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]}
    for kind, pattern in (("conv", "qconv"), ("fc", "qgemm")):
        assert 0 < trace.kernel_share(rec, pattern, kind) < 100


def test_a_run_that_sticks_out_of_the_window_keeps_its_ops():
    # host spans and device events lie on clocks that can disagree by
    # some hundreds of microseconds (one chip trace read a request's first
    # conv before the window opened): move the device 100 us ahead of it
    tr = _recorded()
    dev = tr["devices"]["0"]
    w0 = next(s for n, s, _ in tr["host"] if n == "window")
    shift = min(s for _, s, _ in dev["runs"]) - w0 + 100_000
    for line in ("ops", "runs"):
        dev[line] = [[n, s - shift, d] for n, s, d in dev[line]]
    names = [trace.op_name(n) for n, _ in trace.reduce(tr)["ops"]]
    assert sum(n.startswith("qconv") for n in names) == 13 * 2
    del dev["runs"]  # without the runs only the ops wholly inside the window count
    names = [trace.op_name(n) for n, _ in trace.reduce(tr)["ops"]]
    assert sum(n.startswith("qconv") for n in names) < 13 * 2
