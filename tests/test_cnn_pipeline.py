"""End-to-end CNN2Gate pipeline: parse -> quantize -> build -> run.

Validates the paper's emulation-mode loop: the int8 pipelined executor
must agree with the float oracle (top-1) and the fullflow AOT build must
be bit-identical to emulation.
"""
import contextlib
import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.synthesis import CNN2Gate
from repro.core import parser
from repro.core import pipeline as pipe
from repro.models import cnn

RNG = np.random.default_rng(42)


@pytest.fixture(scope="module")
def tiny_gate():
    g = cnn.tiny_cnn(batch=4)
    gate = CNN2Gate.from_graph(g)
    x = RNG.standard_normal((4, 3, 32, 32)).astype(np.float32) * 0.5
    gate.calibrate_quantization(x)
    return gate, g, x


def test_int8_emulation_top1_matches_float(tiny_gate):
    gate, g, x = tiny_gate
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    assert y_q.shape == y_f.shape == (4, 10)
    assert np.all(y_q.argmax(-1) == y_f.argmax(-1))
    assert not np.any(np.isnan(y_q))


def test_softmax_head_calibrated_from_its_logits():
    """A stage with a fused softmax holds int8 *logits*: calibration
    scales them from the logits' range, not from the probabilities' —
    which would clip every logit past +-2 and flatten the output."""
    g = cnn.tiny_cnn(batch=4)
    head = [n for n in g.nodes if n.op_type == "Gemm"][-1]
    g.initializers[head.inputs[1]] = g.initializers[head.inputs[1]] * 64
    x = RNG.standard_normal((4, 3, 32, 32)).astype(np.float32) * 0.5
    gate = CNN2Gate.from_graph(g)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    assert np.all(y_q.argmax(-1) == y_f.argmax(-1))
    assert np.abs(y_q - y_f).max() < 0.1


def test_int8_output_invariant_to_hardware_options(tiny_gate):
    """(N_i, N_l) trade resources for speed — results must be identical
    (the paper's options only change kernel blocking)."""
    gate, _g, x = tiny_gate
    y1 = np.asarray(gate.build("emulation", n_i=4, n_l=4)(jnp.asarray(x)))
    y2 = np.asarray(gate.build("emulation", n_i=16, n_l=32)(jnp.asarray(x)))
    np.testing.assert_array_equal(y1, y2)


def test_fullflow_bit_identical_to_emulation(tiny_gate):
    gate, _g, x = tiny_gate
    y_e = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(gate.build("fullflow")(jnp.asarray(x)))
    np.testing.assert_array_equal(y_e, y_f)
    assert gate.synthesis_time_s > 0
    assert gate.compiled.memory_analysis() is not None


def test_fullflow_call_runs_the_synthesized_executable(tiny_gate):
    """``build("fullflow")`` compiles once (``synthesis_time_s``): a
    call at the graph's shape reuses that executable and compiles
    nothing more."""
    gate, _g, x = tiny_gate
    run = gate.build("fullflow")
    xj = jnp.asarray(x)
    with jax.log_compiles(True), _captured_compile_logs() as logs:
        run(xj).block_until_ready()
    assert not [r for r in logs if "Compiling" in r], logs


@contextlib.contextmanager
def _captured_compile_logs():
    records: list = []

    class _Keep(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = _Keep(level=logging.DEBUG)
    logger = logging.getLogger("jax")
    old = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        yield records
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old)


@pytest.mark.parametrize("build", [cnn.tiny_cnn, cnn.resnet_tiny,
                                   cnn.googlenet_tiny])
def test_executor_bit_exact_vs_stagewise_oracle(build):
    """The fused executor (kernels, fused merges and pools) equals the
    plain-XLA stagewise oracle replay of the same quantized program."""
    x = RNG.standard_normal((2, 3, 24, 24)).astype(np.float32) * 0.5
    gate = CNN2Gate.from_graph(build(batch=2, in_hw=24))
    gate.calibrate_quantization(x)
    xj = jnp.asarray(x)
    got = np.asarray(gate.build("emulation")(xj))
    np.testing.assert_array_equal(
        got, np.asarray(pipe.oracle_replay(gate.quantized, xj)))
    q = np.asarray(pipe.oracle_replay(gate.quantized, xj, dequantize=False))
    assert q.dtype == np.int8 and q.shape == got.shape


def test_latency_model_reproduces_table1():
    gate_a = CNN2Gate.from_graph(cnn.alexnet())
    gate_v = CNN2Gate.from_graph(cnn.vgg16())
    # Arria 10 @ (16,32): paper 18.24 ms / 205 ms
    a = gate_a.latency_report("ARRIA10", 16, 32).total_s * 1e3
    v = gate_v.latency_report("ARRIA10", 16, 32).total_s * 1e3
    assert abs(a - 18.24) / 18.24 < 0.05
    assert abs(v - 205.0) / 205.0 < 0.20
    # Cyclone V @ (8,8): paper 153 ms AlexNet
    c = gate_a.latency_report("5CSEMA5", 8, 8).total_s * 1e3
    assert abs(c - 153.0) / 153.0 < 0.05


def test_fig6_breakdown_structure():
    """Fig. 6: per-stage times; later conv stages cheaper than conv2."""
    gate = CNN2Gate.from_graph(cnn.alexnet())
    rep = gate.latency_report("ARRIA10", 16, 32)
    convs = [l for l in rep.layers if l.kind == "conv"]
    fcs = [l for l in rep.layers if l.kind == "fc"]
    assert len(convs) == 5 and len(fcs) == 3
    assert max(c.time_s for c in convs[2:]) < convs[1].time_s * 2
    # FC stages are memory-bound (weights dominate)
    assert all(f.t_memory > f.t_compute for f in fcs)


def test_gops_performance_density():
    """Table 3: performance density GOp/s/DSP = 0.266 for this work."""
    gate = CNN2Gate.from_graph(cnn.alexnet())
    rep = gate.latency_report("ARRIA10", 16, 32)
    dse_res = gate.explore("ARRIA10", algo="bf")
    dsp = dse_res.best_report.raw["dsp"]
    density = rep.gops / dsp
    assert abs(density - 0.266) / 0.266 < 0.10


def test_memory_schedule_covers_all_layers():
    pm = parser.parse(cnn.alexnet())
    sched = parser.memory_schedule(pm, 16, 32)
    assert len(sched) == len(pm.layers)
    assert all(s["read_vectors"] > 0 and s["lanes"] > 0 for s in sched)
