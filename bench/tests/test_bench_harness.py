"""The harness finds everything by name, and refuses to run without the
chip and the program."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import harness
from conftest import ROOT


def _copy_bench(dst):
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    _copy_bench(tmp_path)
    bench = tmp_path / "bench"
    shutil.copy(ROOT / "bench" / "tests" / "tiny_cnn.json", bench / "configs" / "tiny_cnn.json")
    (bench / "traffic" / "b4.json").write_text(json.dumps(
        {"loop": "closed", "batch": 4, "in_flight": 3, "pool_images": 8}))
    (bench / "metrics" / "worst_latency_ms.py").write_text(
        "def read(rec):\n    return 1e3 * max(rec['latencies_s'])\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_cnn", "source": "tests", "reduced": [],
                            "file": "bench/configs/tiny_cnn.json", "why": "test"})
    spec["workloads"].append({"name": "tiny_cnn.b4", "config": "tiny_cnn", "traffic": "b4",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "worst_latency_ms", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "whole step",
                              "moves": "latency_p95_ms", "workloads": ["tiny_cnn.b4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny_cnn.b4", root=tmp_path)
    assert cell.config["builder"] == "tiny_cnn"
    assert cell.traffic["in_flight"] == 3
    assert "worst_latency_ms" in [m["name"] for m in cell.per_layer]
    # a metric listed for other cells only is not this cell's
    assert "conv_roofline_pct" not in [m["name"] for m in cell.per_layer]
    rec = {"latencies_s": [0.001, 0.004, 0.002]}
    got = harness.read_metrics(cell, [m for m in cell.per_layer
                                      if m["name"] == "worst_latency_ms"], rec)
    assert got == {"worst_latency_ms": {"value": pytest.approx(4.0), "unit": "ms"}}
    x = np.ones((1,) + tuple(cell.config["input_chw"]), np.float32)
    assert harness.reference(cell, 3, x).probabilities(np.concatenate([x, -x])).shape == (2, 10)


def test_every_cell_of_the_benchmark_loads():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py").read)


def _run(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "alexnet.b1", "--seed", "3000000001",
         "--seconds", "1", *extra], cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_command_refuses_a_machine_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_an_unknown_device_kind(monkeypatch):
    import jax
    fake = [types.SimpleNamespace(platform="tpu", device_kind="TPU v99")]
    monkeypatch.setattr(jax, "devices", lambda *a: fake)
    with pytest.raises(harness.NoChip, match="not in bench/peaks.json"):
        harness.check_device(1, json.loads((ROOT / "bench" / "peaks.json").read_text()))
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.check_device(4, {"TPU v99": {}})


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    _copy_bench(tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_the_cache_directory_of_the_environment_is_kept(tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir", "jax_compilation_cache_max_size",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert harness.use_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_compilation_cache_max_size == harness.CACHE_MAX_BYTES
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
