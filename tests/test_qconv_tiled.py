"""Row-tiled qconv kernel + whole-network fused NHWC executor.

Parity matrix (bit-exact vs kernels/ref.py oracles): stride-2 convs,
pool windows straddling row-band boundaries (AlexNet's overlapping
3x3/2 pool), Cout not a multiple of 128, block_h not dividing H.  Plus
the executor's no-transpose invariant, the row-band VMEM working-set
drop, and the block_h DSE axis.
"""
import numpy as np
import jax
from jax.extend.core import ClosedJaxpr, Jaxpr
import jax.numpy as jnp
import pytest

from repro.core import dse
from repro.core import pipeline as pipe
from repro.core.parser import parse
from repro.core.resources import (FPGA_BOARDS, VMEM_BUDGET_BYTES,
                                  conv_band_working_set)
from repro.core.spaces import CNNDesignSpace
from repro.core.synthesis import CNN2Gate
from repro.kernels import ops, ref
from repro.kernels.qconv import band_geometry, qconv2d, vmem_bytes
from repro.models import cnn

RNG = np.random.default_rng(7)


def i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, shape, np.int8))


# ------------------------------------------------------ kernel parity
@pytest.mark.parametrize("cfg", [
    # (h, w, cin, cout, k, stride, pool, block_h)
    (16, 16, 4, 8, 3, 1, None, 4),        # plain banding
    (23, 23, 8, 32, 5, 2, None, 3),       # stride-2, block_h !| oh
    (27, 27, 16, 64, 3, 1, (3, 2), 2),    # AlexNet 3x3/2 pool straddles bands
    (27, 27, 16, 64, 3, 1, (3, 2), 5),    # same, ragged band count
    (14, 14, 32, 130, 3, 1, (2, 2), 3),   # cout not a multiple of 128
    (11, 11, 8, 16, 3, 2, (2, 2), 1),     # stride-2 conv + pool, 1-row bands
    (18, 18, 4, 24, 3, 1, (2, 2), 100),   # block_h > oh clamps to one band
    # sh*sw*cin > 128: phase-split band (narrower strided convs fold
    # to depth)
    (17, 17, 40, 32, 3, 2, None, 3),      # stride-2, block_h !| oh
    (20, 20, 36, 16, 3, 2, (2, 2), 2),    # stride-2 conv + pool
])
@pytest.mark.parametrize("shift,relu", [(7, True), (4, False)])
def test_tiled_qconv_matches_ref(cfg, shift, relu):
    h, w, cin, cout, k, stride, pool, bh = cfg
    x = i8(2, h, w, cin)
    wt = i8(k, k, cin, cout)
    b = jnp.asarray(RNG.integers(-1000, 1000, (cout,), np.int32))
    got = qconv2d(x, wt, b, strides=(stride, stride), shift=shift, relu=relu,
                  pool=pool, block_cout=64, block_h=bh, interpret=True)
    want = ref.qconv2d_ref(x, wt, b, (stride, stride), shift, relu, pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_h_invariance():
    """Every band height must give the identical bit pattern."""
    x, wt = i8(1, 21, 21, 8), i8(3, 3, 8, 16)
    outs = [np.asarray(qconv2d(x, wt, None, strides=(1, 1), shift=6,
                               relu=True, pool=(3, 2), block_h=bh,
                               interpret=True))
            for bh in (1, 2, 4, 7, None)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_band_geometry_halo():
    # no pool: halo is the kh-1 conv overlap
    conv_rows, in_rows, in_step = band_geometry(4, 3, 1, None)
    assert (conv_rows, in_rows, in_step) == (4, 6, 4)
    # AlexNet 3x3/2 pool: last window carries pw-ps=1 row past the stride
    conv_rows, in_rows, in_step = band_geometry(4, 3, 1, (3, 2))
    assert conv_rows == 9 and in_rows == 11 and in_step == 8
    # stride-2 conv scales the input step
    _cr, in_rows2, in_step2 = band_geometry(4, 3, 2, None)
    assert in_step2 == 8 and in_rows2 == 9


# ------------------------------------------- NHWC pool paths (int8-native)
@pytest.mark.parametrize("window,stride,pads", [
    (2, 2, (0, 0, 0, 0)), (3, 2, (0, 0, 0, 0)), (2, 2, (1, 0, 1, 0))])
def test_nhwc_pools_match_ref(window, stride, pads):
    x = i8(2, 12, 12, 5)
    got_max = ops.maxpool2d_nhwc(x, window, stride, pads)
    got_avg = ops.avgpool2d_nhwc(x, window, stride, pads)
    xp_max = jnp.pad(x, ((0, 0), (pads[0], pads[2]), (pads[1], pads[3]),
                         (0, 0)), constant_values=ref.INT8_MIN)
    np.testing.assert_array_equal(
        np.asarray(got_max), np.asarray(ref.maxpool2d_ref(xp_max, window, stride)))
    # independent numpy window-loop oracle for the avg pool (exclude-pad
    # divide): ops.avgpool2d_nhwc shares code with ref.avgpool2d_ref, so
    # comparing those two against each other would prove nothing
    xn = np.asarray(x, np.int64)
    oh = (12 + pads[0] + pads[2] - window) // stride + 1
    ow = (12 + pads[1] + pads[3] - window) // stride + 1
    want = np.zeros((2, oh, ow, 5), np.int64)
    for i in range(oh):
        for j in range(ow):
            h0, h1 = max(0, i * stride - pads[0]), \
                min(12, i * stride - pads[0] + window)
            w0, w1 = max(0, j * stride - pads[1]), \
                min(12, j * stride - pads[1] + window)
            count = (h1 - h0) * (w1 - w0)
            want[:, i, j, :] = np.floor(
                (xn[:, h0:h1, w0:w1, :].sum((1, 2)) + count // 2) / count)
    np.testing.assert_array_equal(np.asarray(got_avg),
                                  np.clip(want, -128, 127))
    assert got_max.dtype == jnp.int8 and got_avg.dtype == jnp.int8


# --------------------------------------------------- fused executor
def _count_transposes(jaxpr) -> int:
    """Transpose eqns reaching XLA, recursing through pjit/closed calls
    but NOT into pallas_call (its internal emulation is opaque)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose":
            n += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                n += _count_transposes(v.jaxpr)
            elif isinstance(v, Jaxpr):
                n += _count_transposes(v)
    return n


@pytest.fixture(scope="module")
def tiny_gate():
    gate = CNN2Gate.from_graph(cnn.tiny_cnn(batch=2))
    x = (RNG.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    return gate, x


def test_executor_single_ingress_conversion(tiny_gate):
    """Whole-network fused dataflow: exactly ONE layout transpose (the
    NCHW->NHWC ingress; tiny_cnn ends in FC so there is no egress one).
    The seed executor emitted two per conv/pool stage."""
    gate, x = tiny_gate
    ex = pipe.make_executor(gate.quantized, interpret=True)
    jaxpr = jax.make_jaxpr(lambda v: ex(v))(jnp.asarray(x))
    assert _count_transposes(jaxpr.jaxpr) == 1


def test_executor_matches_oracle_chain(tiny_gate):
    """Fused NHWC executor == float oracle top-1 and invariant to
    block_h (pure blocking knob)."""
    gate, x = tiny_gate
    g = cnn.tiny_cnn(batch=2)
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    outs = [np.asarray(pipe.run_int8(gate.quantized, jnp.asarray(x),
                                     interpret=True, block_h=bh))
            for bh in (None, 2, 3, 8)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    assert np.all(outs[0].argmax(-1) == y_f.argmax(-1))


def test_executor_caches_per_config(tiny_gate):
    gate, x = tiny_gate
    qm = gate.quantized
    qm._executors.clear()
    pipe.run_int8(qm, jnp.asarray(x), interpret=True)
    pipe.run_int8(qm, jnp.asarray(x), interpret=True)
    pipe.run_int8(qm, jnp.asarray(x), interpret=True, block_h=4)
    assert len(qm._executors) == 2


def test_fc_weight_staging_nhwc_flatten_order():
    """The conv->FC boundary needs no runtime transpose: FC rows are
    permuted at build time to NHWC-flatten order."""
    gate = CNN2Gate.from_graph(cnn.tiny_cnn(batch=1))
    x = (RNG.standard_normal((1, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    fc = next(ql for ql in gate.quantized.layers if ql.info.kind == "fc")
    w_raw = gate.parsed.graph.initializers[fc.info.weight]
    from repro.core.quantize import quantize_weights
    w_q, _ = quantize_weights(w_raw, None, fc.spec)
    prev4d = next(li for li in reversed(gate.parsed.layers[
        :gate.parsed.layers.index(fc.info)]) if len(li.out_shape) == 4)
    _n, c, h, w = prev4d.out_shape
    want = (w_q.reshape(c, h, w, -1).transpose(1, 2, 0, 3)
            .reshape(w_q.shape[0], -1))
    np.testing.assert_array_equal(np.asarray(fc.w_q), want)


# ------------------------------------------------ VMEM working-set model
def test_vgg_layer_working_set_drops_4x():
    """Acceptance: VGG-16 224x224x64 layer (3x3/1, pad 1) per-step VMEM
    drops >= 4x with row-band tiling."""
    whole = vmem_bytes(226, 226, 64, 3, 3, 128, 224, 224)
    band = vmem_bytes(226, 226, 64, 3, 3, 128, 224, 224, block_h=8)
    assert whole / band >= 4.0
    assert band <= VMEM_BUDGET_BYTES  # the tiled band actually fits VMEM
    assert whole > VMEM_BUDGET_BYTES  # ...which the whole plane did not


def test_band_working_set_monotone_in_block_h():
    pm = parse(cnn.alexnet())
    ws = [conv_band_working_set(pm.layers, 32, bh) for bh in (1, 4, 16, 64)]
    assert ws == sorted(ws)
    assert conv_band_working_set(pm.layers, 32, None) >= ws[-1]


# ----------------------------------------------------- block_h in the DSE
def test_dse_explores_block_h_axis():
    pm = parse(cnn.alexnet())
    space = CNNDesignSpace(pm, FPGA_BOARDS["ARRIA10"],
                           block_h_options=[4, 8, 16])
    assert len(space.axes()) == 3
    assert all(len(o) == 3 for o in space.options())
    res = dse.rl_dse(space, seed=0)
    assert res.found and len(res.best) == 3
    assert res.best[2] in (4, 8, 16)


def test_dse_rejects_oversized_row_band():
    """A band whose working set exceeds the board's on-chip memory must
    be infeasible (mem quota > 100), and the fitter must avoid it."""
    pm = parse(cnn.alexnet())
    board = FPGA_BOARDS["5CSEMA5"]  # 4 Mbit on-chip
    space = CNNDesignSpace(pm, board, block_h_options=[1, 55])
    rep_big = space.evaluate((8, 8, 55))   # whole-plane-scale band
    assert rep_big.percents["mem"] > 100.0 and not rep_big.fits
    rep_small = space.evaluate((8, 8, 1))  # line-buffer-scale band
    assert rep_small.fits
    res = dse.brute_force(space)
    assert res.found and res.best[2] == 1


def test_explore_with_block_h_through_synthesis():
    gate = CNN2Gate.from_graph(cnn.alexnet())
    res = gate.explore("ARRIA10", algo="bf", block_h_options=[4, 8])
    assert res.found and len(res.best) == 3
    assert res.best[:2] == (16, 32)  # paper's decision is preserved
