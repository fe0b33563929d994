"""Per-output-channel power-of-two quantization (DESIGN.md §8).

Three layers of guarantees:

  * **kernel parity** — the per-lane shift-vector epilogues of the
    dense band kernel, the depthwise band kernel and the FC kernel are
    bit-exact against the per-channel ``ref.py`` oracles across ragged
    Cout, block_cin sweeps, strides, fused pools and the fused-skip
    epilogue on a per-channel host conv;
  * **per-tensor invariance** — with scalar specs nothing changes:
    outputs are byte-identical, and a jaxpr probe shows no shift-vector
    operand is staged on any kernel call;
  * **accuracy** — per-channel calibration is never worse than
    per-tensor on a fixed-seed mobilenet_tiny batch (depthwise layers
    are the motivating case), and is strictly better when channel
    magnitudes are skewed.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pipeline as pipe
from repro.core import quantize as Q
from repro.core import verify as V
from repro.core.synthesis import CNN2Gate
from repro.kernels import ops, ref
from repro.models import cnn

RNG = np.random.default_rng(7)


def _rand_shifts(n, lo=0, hi=14):
    return tuple(int(s) for s in RNG.integers(lo, hi, n))


# ------------------------------------------------------ kernel parity

@pytest.mark.parametrize("cout", [16, 32, 130])
@pytest.mark.parametrize("block_cin", [None, 8, 16])
def test_dense_per_channel_parity(cout, block_cin):
    """Dense band kernel == per-channel oracle (incl. ragged Cout=130
    across Cout tiles and the Cin contraction sweep)."""
    x = jnp.asarray(RNG.integers(-128, 128, (2, 12, 12, 24)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, 24, cout)), jnp.int8)
    b = jnp.asarray(RNG.integers(-1000, 1000, (cout,)), jnp.int32)
    shifts = _rand_shifts(cout)
    got = ops.qconv2d_nhwc(x, w, b, shift=shifts, relu=True,
                           block_cout=64, block_h=4, block_cin=block_cin,
                           interpret=True)
    want = ref.qconv2d_ref(x, w, b, (1, 1), shifts, True, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("strides,pool", [((1, 1), (2, 2)), ((2, 2), None),
                                          ((1, 1), (3, 2))])
def test_dense_per_channel_pool_stride_parity(strides, pool):
    """Per-lane requant composes with fused max-pool and strides
    exactly as the scalar epilogue does (pool runs on requantized
    int8, so the vector shift must land before the window max)."""
    cout = 40
    x = jnp.asarray(RNG.integers(-128, 128, (2, 13, 13, 16)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, 16, cout)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (cout,)), jnp.int32)
    shifts = _rand_shifts(cout)
    got = ops.qconv2d_nhwc(x, w, b, strides=strides, shift=shifts,
                           relu=True, pool=pool, block_cout=32, block_h=2,
                           interpret=True)
    want = ref.qconv2d_ref(x, w, b, strides, shifts, True, pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("c", [32, 48, 130])
def test_depthwise_per_channel_parity(c):
    """Depthwise band kernel: the channel tile IS the lane dim, so the
    shift row tiles with it (ragged C=130 exercises the padded tile)."""
    x = jnp.asarray(RNG.integers(-128, 128, (2, 10, 10, c)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, 1, c)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (c,)), jnp.int32)
    shifts = _rand_shifts(c)
    got = ops.qconv2d_nhwc(x, w, b, shift=shifts, relu=True, groups=c,
                           block_cout=32, block_h=3, interpret=True)
    want = ref.qconv2d_ref(x, w, b, (1, 1), shifts, True, None, groups=c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_depthwise_per_channel_pool_parity():
    c = 24
    x = jnp.asarray(RNG.integers(-128, 128, (1, 12, 12, c)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, 1, c)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (c,)), jnp.int32)
    shifts = _rand_shifts(c)
    got = ops.qconv2d_nhwc(x, w, b, shift=shifts, relu=True, pool=(2, 2),
                           groups=c, block_cout=16, block_h=2,
                           interpret=True)
    want = ref.qconv2d_ref(x, w, b, (1, 1), shifts, True, (2, 2), groups=c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [64, 130])
@pytest.mark.parametrize("block_k", [32, 128])
def test_fc_per_channel_parity(n, block_k):
    x = jnp.asarray(RNG.integers(-128, 128, (5, 96)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (96, n)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (n,)), jnp.int32)
    shifts = _rand_shifts(n)
    got = ops.qgemm(x, w, b, shift=shifts, relu=True, block_n=64,
                    block_k=block_k, interpret=True)
    want = ref.qgemm_ref(x, w, b, shifts, True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_grouped_fallback_per_channel():
    """Ragged grouped convs run on the reference path — the vector
    shift must flow through the dispatch unchanged."""
    g, cin, cout = 3, 12, 18
    x = jnp.asarray(RNG.integers(-128, 128, (1, 8, 8, cin)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, cin // g, cout)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (cout,)), jnp.int32)
    shifts = _rand_shifts(cout)
    got = ops.qconv2d_nhwc(x, w, b, shift=shifts, relu=True, groups=g,
                           interpret=True)
    want = ref.qconv2d_ref(x, w, b, (1, 1), shifts, True, None, groups=g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pool", [None, (2, 2)])
@pytest.mark.parametrize("block_cin", [None, 8])
def test_fused_skip_with_per_channel_host(pool, block_cin):
    """Residual-add epilogue fusion on a per-channel host conv: the
    per-lane conv requant runs first (producing exactly the int8
    tensor the standalone conv would have written), then the scalar
    merge alignment/requant — bit-exact vs the two-stage oracle."""
    cout = 24
    x = jnp.asarray(RNG.integers(-128, 128, (2, 9, 9, 16)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (3, 3, 16, cout)), jnp.int8)
    b = jnp.asarray(RNG.integers(-500, 500, (cout,)), jnp.int32)
    shifts = _rand_shifts(cout)
    skip = jnp.asarray(RNG.integers(-128, 128, (2, 7, 7, cout)), jnp.int8)
    got = ops.qconv2d_nhwc(x, w, b, shift=shifts, relu=True, skip=skip,
                           skip_shifts=(1, 0), merge_shift=1,
                           merge_relu=True, pool=pool, block_cout=16,
                           block_h=2, block_cin=block_cin, interpret=True)
    conv8 = ref.qconv2d_ref(x, w, b, (1, 1), shifts, True, None)
    want = ref.qadd_ref([conv8, skip], (1, 0), 1, True)
    if pool is not None:
        want = ref.maxpool2d_ref(want, pool[0], pool[1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------- quantize.py unit rules

def test_per_channel_spec_shift_vector():
    spec = Q.QuantSpec(m_w=(7, 5, 9), m_x=4, m_y=3)
    assert spec.per_channel and spec.m_w_min == 5
    assert spec.requant_shift == (8, 6, 10)
    with pytest.raises(ValueError):
        _ = Q.QuantSpec(m_w=(7, 1), m_x=1, m_y=5).requant_shift


def test_per_channel_weight_quantization_oihw_and_fc():
    """Each Cout lane quantizes at its own exponent; biases land on
    their lane's accumulator scale."""
    w = np.asarray([[[[0.5]]], [[[0.0625]]]], np.float32)  # OIHW (2,1,1,1)
    b = np.asarray([0.25, 0.25], np.float32)
    spec = Q.QuantSpec(m_w=(6, 9), m_x=4, m_y=4)
    wq, bq = Q.quantize_weights(w, b, spec)
    assert wq[0, 0, 0, 0] == round(0.5 * 2 ** 6)
    assert wq[1, 0, 0, 0] == round(0.0625 * 2 ** 9)
    assert bq[0] == round(0.25 * 2 ** 10) and bq[1] == round(0.25 * 2 ** 13)
    # FC: output features on the last axis
    wfc = np.asarray([[0.5, 0.0625]], np.float32)
    wq2, _ = Q.quantize_weights(wfc, None, spec)
    assert wq2[0, 0] == round(0.5 * 2 ** 6)
    assert wq2[0, 1] == round(0.0625 * 2 ** 9)


def test_per_channel_exponents_reduce_roundtrip_error():
    """Skewed channel magnitudes: per-channel max-abs exponents beat
    the single per-tensor exponent at round-trip."""
    cout = 8
    w = np.stack([RNG.standard_normal((4, 3, 3)).astype(np.float32)
                  * (2.0 ** -c) for c in range(cout)])
    m_pt = Q.best_pow2_exponent(w)
    m_pc = Q.best_pow2_exponents_per_channel(w)
    assert len(m_pc) == cout and min(m_pc) >= m_pt

    def rt_err(wq_m):
        err = 0.0
        for c in range(cout):
            m = wq_m[c] if isinstance(wq_m, tuple) else wq_m
            q = Q.quantize_array(w[c], m)
            err += float(np.mean((Q.dequantize_array(q, m) - w[c]) ** 2))
        return err

    assert rt_err(m_pc) < rt_err(m_pt)


def test_requantize_per_channel_matches_per_lane_scalar():
    acc = RNG.integers(-(2 ** 20), 2 ** 20, (6, 4))
    shifts = (0, 3, 7, 12)
    spec = Q.QuantSpec(m_w=tuple(s for s in shifts), m_x=0, m_y=0)
    got = Q.requantize(acc, spec)
    for c, s in enumerate(shifts):
        want = Q.requantize(acc[:, c], Q.QuantSpec(m_w=s, m_x=0, m_y=0))
        np.testing.assert_array_equal(got[:, c], want)


# ------------------------------------------- end-to-end + invariance

def _calibrated(build, x, per_channel, **kw):
    gate = CNN2Gate.from_graph(build(batch=x.shape[0], in_hw=x.shape[-1]),
                               **kw)
    gate.calibrate_quantization(x, per_channel=per_channel)
    return gate


@pytest.mark.parametrize("build", [cnn.resnet_tiny, cnn.mobilenet_tiny])
def test_per_channel_end_to_end_bit_exact_vs_stagewise_oracle(build):
    """Whole-network per-channel executor == stage-by-stage per-channel
    oracle replay (conv/dwconv/FC/merge all covered; resnet_tiny also
    exercises the fused-skip epilogue under a per-channel host)."""
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    gate = _calibrated(build, x, per_channel=True)
    xj = jnp.asarray(x)
    got = np.asarray(gate.build("emulation")(xj))

    # oracle replay over the *unfused* program with the same specs
    gate_u = CNN2Gate.from_graph(build(batch=2, in_hw=32), fuse_skip=False)
    gate_u.apply_quantization(gate.specs)
    want = pipe.oracle_replay(gate_u.quantized, xj)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_per_tensor_outputs_byte_identical_and_no_shift_operand():
    """per_channel=False must be a no-op: byte-identical logits whether
    the flag is threaded or not, and the jaxpr stages no shift-vector
    operand on any kernel call (the pallas_call arity probe — the
    per-channel program stages exactly one extra (1, Cout) operand)."""
    rng = np.random.default_rng(5)
    x = (rng.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    xj = jnp.asarray(x)

    gate = _calibrated(cnn.resnet_tiny, x, per_channel=False)
    y_default = np.asarray(gate.build("emulation")(xj))
    gate2 = CNN2Gate.from_graph(cnn.resnet_tiny(batch=2, in_hw=32))
    gate2.apply_quantization(gate.specs, per_channel=False)
    y_strict = np.asarray(gate2.build("emulation")(xj))
    np.testing.assert_array_equal(y_default, y_strict)

    def pallas_arities(qm):
        # the verifier's reusable probe (one walker shared with the
        # fusion tests' eqn counts and the QV5xx CLI probes)
        return V.pallas_call_arities(
            V.executor_jaxpr(qm, batch=xj.shape[0]))

    scalar_arities = pallas_arities(gate.quantized)
    gate_pc = _calibrated(cnn.resnet_tiny, x, per_channel=True)
    vector_arities = pallas_arities(gate_pc.quantized)
    assert len(scalar_arities) == len(vector_arities) > 0
    # every weighted kernel call stages exactly one extra operand (the
    # per-lane shift row); the per-tensor program stages none
    assert all(v == s + 1 for s, v in zip(scalar_arities, vector_arities)), \
        (scalar_arities, vector_arities)


def test_per_channel_strict_flag_rejects_vector_specs():
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((1, 3, 32, 32)) * 0.5).astype(np.float32)
    gate = _calibrated(cnn.mobilenet_tiny, x, per_channel=True)
    gate2 = CNN2Gate.from_graph(cnn.mobilenet_tiny(batch=1, in_hw=32))
    with pytest.raises(ValueError):
        gate2.apply_quantization(gate.specs, per_channel=False)


def test_per_channel_true_upgrades_scalar_specs_bit_identically():
    """build_quantized(per_channel=True) on scalar specs runs the
    shift-vector datapath with uniform counts — numerics unchanged."""
    rng = np.random.default_rng(4)
    x = (rng.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    xj = jnp.asarray(x)
    gate = _calibrated(cnn.resnet_tiny, x, per_channel=False)
    y_scalar = np.asarray(gate.build("emulation")(xj))
    gate_up = CNN2Gate.from_graph(cnn.resnet_tiny(batch=2, in_hw=32))
    gate_up.apply_quantization(gate.specs, per_channel=True)
    assert all(ql.spec.per_channel for ql in gate_up.quantized.layers
               if ql.info.kind in (pipe.P.CONV, pipe.P.FC))
    # the DSE must see the widened program (it reads the quantized
    # layers, not the raw scalar specs) and charge shift-vector bytes
    assert gate_up.per_channel and not gate.per_channel
    assert gate_up.design_space("ARRIA10").weight_bytes > \
        gate.design_space("ARRIA10").weight_bytes
    y_vec = np.asarray(gate_up.build("emulation")(xj))
    np.testing.assert_array_equal(y_scalar, y_vec)


# ------------------------------------------------ accuracy regression

def _stagewise_dequant_error(gate, x):
    """Calibration-accuracy metric: run the int8 program stage by
    stage and sum, over every weighted stage, the mean |dequantized
    stage output - float oracle activation|.  This is the quantity a
    calibration actually controls (the final logits also fold in the
    shared per-tensor activation grids, which per-channel weight
    scales cannot move)."""
    qm = gate.quantized
    acts = cnn.collect_activations(gate.parsed.graph, x)
    tensor_m = pipe.thread_scales(gate.parsed, gate.specs)
    xj = jnp.asarray(x)
    h = jnp.clip(jnp.round(xj * 2.0 ** qm.input_m), -128, 127
                 ).astype(jnp.int8)
    h = jnp.transpose(h, (0, 2, 3, 1))
    env = {gate.parsed.input_name: h}
    total = 0.0
    for ql in qm.layers:
        li = ql.info
        if li.kind == pipe.P.CONV:
            pool = ((li.pool.kernel_shape[0], li.pool.strides[0])
                    if li.pool is not None else None)
            h = ops.qconv2d_nhwc(env[li.inputs[0]], ql.w_q, ql.b_q,
                                 strides=li.strides, pads=li.pads,
                                 shift=ql.spec.requant_shift, relu=li.relu,
                                 pool=pool, groups=li.group, interpret=True)
        elif li.kind == pipe.P.POOL:
            fn = (ops.avgpool2d_nhwc if li.pool_type == "avg"
                  else ops.maxpool2d_nhwc)
            h = fn(env[li.inputs[0]], li.kernel_shape[0], li.strides[0],
                   li.pads)
        elif li.kind == pipe.P.FC:
            hin = env[li.inputs[0]]
            if hin.ndim > 2:
                hin = hin.reshape(hin.shape[0], -1)
            h = ops.qgemm(hin, ql.w_q, ql.b_q,
                          shift=ql.spec.requant_shift, relu=li.relu,
                          interpret=True)
        else:
            raise AssertionError(li.kind)  # mobilenet_tiny: no merges
        env[li.output] = h
        if li.kind in (pipe.P.CONV, pipe.P.FC):
            deq = np.asarray(h, np.float32) * 2.0 ** -tensor_m[li.output]
            want = acts[li.output]
            if want.ndim == 4:
                want = np.transpose(want, (0, 2, 3, 1))
            total += float(np.mean(np.abs(deq - want)))
    return total


def test_mobilenet_per_channel_accuracy_not_worse():
    """Fixed-seed mobilenet_tiny batch: per-channel calibration must be
    at least as accurate as per-tensor (the depthwise stacks are where
    per-channel scales pay off — the summed stage-output error drops
    ~5 % on this net for every seed tried)."""
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    err = {}
    for mode in (False, True):
        gate = _calibrated(cnn.mobilenet_tiny, x, per_channel=mode)
        err[mode] = _stagewise_dequant_error(gate, x)
    assert err[True] <= err[False], err


def test_skewed_channel_conv_per_channel_strictly_better():
    """A conv whose output channels differ by orders of magnitude:
    per-tensor quantization crushes the small channels to zero,
    per-channel keeps them — strict accuracy win, not a tie."""
    rng = np.random.default_rng(1)
    cout, cin, hw = 8, 4, 8
    w = np.stack([rng.standard_normal((cin, 3, 3)).astype(np.float32)
                  * (2.0 ** -(2 * c)) for c in range(cout)])
    x = rng.standard_normal((1, cin, hw, hw)).astype(np.float32) * 0.5
    xh = jnp.transpose(jnp.asarray(x), (0, 2, 3, 1))
    wh = jnp.transpose(jnp.asarray(w), (2, 3, 1, 0))
    acc_f = np.asarray(jax.lax.conv_general_dilated(
        xh, wh, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))

    m_x = Q.best_pow2_exponent(x)
    xq = jnp.asarray(Q.quantize_array(
        np.asarray(jnp.transpose(jnp.asarray(x), (0, 2, 3, 1))), m_x))

    def int8_out(m_w):
        spec = Q.QuantSpec(m_w=m_w, m_x=m_x, m_y=7)
        wq, _ = Q.quantize_weights(w, None, spec)
        wqh = jnp.asarray(np.transpose(wq, (2, 3, 1, 0)))
        y = ops.qconv2d_nhwc(xq, wqh, None, shift=spec.requant_shift,
                             relu=False, interpret=True)
        return np.asarray(y).astype(np.float32) * 2.0 ** -7

    err_pt = np.mean(np.abs(int8_out(Q.best_pow2_exponent(w)) - acc_f))
    err_pc = np.mean(np.abs(
        int8_out(Q.best_pow2_exponents_per_channel(w)) - acc_f))
    assert err_pc < err_pt, (err_pc, err_pt)
