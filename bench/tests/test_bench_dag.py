"""Configurations whose layers form a graph: the plain reference over
the graph (bench/reference/dag_cnn.py) against the chain reference and
the program's calibration, and a residual network brought into a copy
of the benchmark as new files and entries only, run on the CPU."""
import json
import time

import numpy as np
import pytest

from bench import harness
from bench.reference import dag_cnn, sequential_cnn
from conftest import ROOT
from test_bench_control import _broken, _half_the_batch_left_out, _one_answer_altered
from test_bench_harness import _copy_bench
from test_bench_rehearsal import cpu_peaks


def fixture(name):
    return json.loads((ROOT / "bench" / "tests" / f"{name}.json").read_text())


@pytest.mark.parametrize("bits", [8, 4])
def test_on_a_chain_the_graph_reference_is_the_sequential_one(bits):
    cfg = fixture("tiny_cnn")
    x_cal = harness.calibration_image(cfg, 2 ** 31 + 5)
    images = np.random.default_rng(3).standard_normal((20, 3, 32, 32), dtype=np.float32)
    want = sequential_cnn.build(cfg, 2 ** 31 + 5, x_cal, bits).probabilities(images)
    got = dag_cnn.build(cfg, 2 ** 31 + 5, x_cal, bits).probabilities(images)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name,hw", [("resnet_tiny", 32), ("resnet18", 64)])
def test_the_exponents_are_the_programs(name, hw):
    """Every conv's, FC's and add's (m_w, m_x, m_y) as the program's
    calibration sets them, in the program's stage order (a folded add
    right after its conv); resnet18 at 64x64 brings in the stem's padded
    max-pool, identity and projection merges."""
    import jax
    from repro.core import parser as P
    from repro.core.synthesis import CNN2Gate
    from repro.models import cnn

    cfg = dict(fixture(name), input_chw=[3, hw, hw])
    seed = 4_000_000_011
    x_cal = harness.calibration_image(cfg, seed)
    gate = CNN2Gate.from_graph(getattr(cnn, cfg["builder"])(batch=1, seed=seed, in_hw=hw))
    with jax.default_device(jax.devices("cpu")[0]), jax.default_matmul_precision("highest"):
        specs = gate.calibrate_quantization(x_cal)
    program = []
    for li in gate.parsed.layers:
        if li.kind in (P.CONV, P.FC, P.ADD):
            program.append(specs[li.name])
        if li.merge is not None:
            program.append(specs[li.merge.name])
    ref = dag_cnn.build(cfg, seed, x_cal)
    assert [(s.m_w, s.m_x, s.m_y) for s in program] == \
        [(e["m_w"], e["m_x"], e["m_y"]) for e in ref.exponents.values()]


def _graph_cell(root):
    """A copy of the benchmark under ``root`` with resnet_tiny and a cell
    of it added as new files and entries only."""
    _copy_bench(root)
    bench = root / "bench"
    (bench / "configs" / "resnet_tiny.json").write_text(
        (ROOT / "bench" / "tests" / "resnet_tiny.json").read_text())
    (bench / "traffic" / "b4.json").write_text(json.dumps(
        {"loop": "closed", "batch": 4, "in_flight": 2, "pool_images": 8}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "resnet_tiny", "source": "tests", "reduced": [],
                            "file": "bench/configs/resnet_tiny.json", "why": "test"})
    spec["workloads"].append({"name": "resnet_tiny.b4", "config": "resnet_tiny",
                              "traffic": "b4", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_cell("resnet_tiny.b4", root=root)


def _run(cell, make=harness.program_executor, seed=2 ** 31 + 29, traced=False):
    return harness.run_cell(cell, seed, 0.3, traced, t_start=time.perf_counter(),
                            make_executor=make, peaks=cpu_peaks(), weights_seed=seed)


@pytest.mark.parametrize("traced", [False, True])
def test_a_graph_cell_comes_in_as_new_files_only(tmp_path, traced):
    cell = _graph_cell(tmp_path)
    assert cell.config["reference"] == "dag_cnn"
    r = harness.run_cell(cell, 3_000_000_019, 0.5, traced, t_start=time.perf_counter(),
                         peaks=cpu_peaks())
    assert r["correct"] is True
    assert r["checks"]["logprob_gap"]["value"] <= r["checks"]["logprob_gap"]["limit"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the device trace of a CPU run holds no TPU plane: those metrics stay out
    want = {"calibrate_s", "compile_s", "mfu_pct"} if traced else \
        {m["name"] for m in cell.end_to_end}
    assert set(r["metrics"]) == want


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 23, 4_000_000_007])
def test_the_int4_control_of_a_graph_cell_is_not_correct(tmp_path, seed):
    r = _run(_graph_cell(tmp_path), harness.control_executor(4), seed)
    assert r["correct"] is False
    gap = r["checks"]["logprob_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("fault", [_one_answer_altered, _half_the_batch_left_out])
def test_a_broken_timed_path_of_a_graph_cell_is_not_correct(tmp_path, fault):
    r = _run(_graph_cell(tmp_path), _broken(fault))
    assert r["correct"] is False
    assert r["checks"]["logprob_gap"]["value"] > r["checks"]["logprob_gap"]["limit"]
