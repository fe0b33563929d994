"""A CPU rehearsal of a run: the request loop, the percentile and rate
arithmetic and the result line, on tiny_cnn through the harness's own
functions (the look for a chip is skipped)."""
import json
import time

import numpy as np
import pytest

from bench import harness
from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_cell(batch=2, in_flight=2, pool_images=4):
    cfg = json.loads((ROOT / "bench" / "tests" / "tiny_cnn.json").read_text())
    mix = {"loop": "closed", "batch": batch, "in_flight": in_flight, "pool_images": pool_images}
    return harness.Cell("tiny_cnn.test", cfg, mix, 1, SPEC["end_to_end"],
                        [m for m in SPEC["per_layer"] if "workloads" not in m], ROOT)


def cpu_peaks():
    import jax
    return {jax.devices()[0].device_kind: {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


@pytest.mark.parametrize("in_flight", [1, 2, 3])
def test_drive_keeps_requests_in_flight_and_cycles_the_pool(in_flight):
    import jax
    fn = jax.jit(lambda x: x.sum(axis=(1, 2, 3)))
    pool = np.arange(3 * 2 * 4, dtype=np.float32).reshape(3, 2, 1, 2, 2)
    win = harness.drive(fn, pool, in_flight, requests=7)
    assert win.batch_idx == [0, 1, 2, 0, 1, 2, 0]
    assert np.all(win.t1 >= win.t0) and np.all(np.diff(win.t1) >= 0)
    for j, out in zip(win.batch_idx, win.outputs):
        np.testing.assert_array_equal(out, pool[j].sum(axis=(1, 2, 3)))
    assert win.seconds == pytest.approx(win.t1[-1] - win.t0[0])


def test_drive_stops_sending_when_the_window_closes():
    fn = lambda x: x  # noqa: E731
    pool = np.zeros((2, 1, 1), np.float32)
    t = time.perf_counter()
    win = harness.drive(fn, pool, 2, seconds=0.2)
    assert 0.2 <= time.perf_counter() - t < 1.0
    assert len(win.outputs) > 10


def test_end_to_end_arithmetic():
    cell = harness.load_cell("alexnet.b1")
    lat = np.arange(1, 101) / 1e3  # 1..100 ms
    rec = {"latencies_s": lat, "images": 400, "window_s": 2.0, "setup_s": 12.5,
           "config": cell.config, "peak": {"int8_ops_per_s": 393e12}}
    got = harness.read_metrics(cell, cell.end_to_end, rec)
    assert got["latency_p50_ms"]["value"] == pytest.approx(50.5)
    assert got["latency_p95_ms"]["value"] == pytest.approx(95.05)
    assert got["images_per_s"] == {"value": 200.0, "unit": "images/s"}
    assert got["setup_s"]["value"] == 12.5
    mfu = harness.load_module(ROOT / "bench" / "metrics" / "mfu_pct.py").read(rec)
    assert mfu == pytest.approx(100 * 1.42837696e9 * 200 / 393e12)


@pytest.mark.parametrize("traced", [False, True])
def test_a_whole_run_on_tiny_cnn(traced):
    cell = tiny_cell()
    r = harness.run_cell(cell, 2 ** 31 + 11, 0.5, traced, t_start=time.perf_counter(),
                         peaks=cpu_peaks())
    assert list(r)[-1] == "checks"
    assert r["correct"] is True
    assert r["checks"]["logprob_gap"]["value"] <= r["checks"]["logprob_gap"]["limit"]
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in (cell.per_layer if traced else cell.end_to_end)}
    # the device trace of a CPU run holds no TPU plane: those metrics stay out
    have = set(r["metrics"])
    if traced:
        assert have == {"calibrate_s", "compile_s", "mfu_pct"}
    else:
        assert have == names
        m = r["metrics"]
        assert m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
        assert m["setup_s"]["value"] > 0 and m["images_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"


def test_the_seed_draws_the_requests_and_not_the_weights():
    cell = tiny_cell()
    seen = []

    def make(c, weights_seed, x_cal, spans):
        seen.append((weights_seed, x_cal.copy()))
        return harness.program_executor(c, weights_seed, x_cal, spans)

    for seed in (2 ** 31 + 1, 4_000_000_003):
        harness.run_cell(cell, seed, 0.1, False, t_start=time.perf_counter(),
                         make_executor=make, peaks=cpu_peaks())
    assert [w for w, _ in seen] == [cell.config["weights_seed"]] * 2
    np.testing.assert_array_equal(seen[0][1], seen[1][1])
    a = harness.request_pool(cell.config, cell.traffic, 2 ** 31 + 1)
    b = harness.request_pool(cell.config, cell.traffic, 4_000_000_003)
    assert a.shape == b.shape == (2, 2, 3, 32, 32) and not np.array_equal(a, b)
    np.testing.assert_array_equal(a, harness.request_pool(cell.config, cell.traffic, 2 ** 31 + 1))
