"""Residual-add epilogue fusion + Cin-tiled contraction.

Two tentpole claims pinned bit-for-bit:

  * the conv band kernel's fused skip path (requant+clip to int8, then
    int32 operand alignment, add, merge requant, then fused pool) is
    exactly the unfused Conv -> Add two-stage program — swept over
    band-straddling rows, stride-2 convs, mismatched operand scales,
    skip + fused-pool ordering and ragged Cout tiles;
  * the ``block_cin`` contraction tile is a pure blocking knob (any
    tile bit-identical to the whole-Cin contraction) that bounds the
    input-band working set — the ``N_i`` axis finally changes measured
    kernel behaviour, not just the analytical report.

Plus the parser fold pass: eligibility/fallback matrix, end-to-end
fused == unfused parity on the resnet builders, and a jaxpr test that
the fused program really contains no standalone add stage.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import parser as P
from repro.core import pipeline as pipe
from repro.core import verify as V
from repro.core.quantize import QuantSpec
from repro.core.resources import conv_band_working_set
from repro.core.synthesis import CNN2Gate
from repro.kernels import ref
from repro.kernels.qconv import band_input_bytes, qconv2d, vmem_bytes
from repro.models import cnn

RNG = np.random.default_rng(23)


def i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, shape, np.int8))


def _oracle_two_stage(x, w, b, strides, shift, relu, skip, skip_shifts,
                      merge_shift, merge_relu, pool):
    """The unfused program: conv stage writes int8, add stage aligns,
    merges and requantizes, a trailing max-pool runs after the merge."""
    y1 = ref.qconv2d_ref(x, w, b, strides, shift, relu, None)
    merged = ref.qadd_ref([y1, skip], skip_shifts, shift=merge_shift,
                          relu=merge_relu)
    if pool is not None:
        merged = ref.maxpool2d_ref(merged, pool[0], pool[1])
    return merged


# ------------------------------------------------- kernel parity matrix
@pytest.mark.parametrize("cfg", [
    # (h, w, cin, cout, k, stride, pool, block_h, block_cin)
    (16, 16, 8, 16, 3, 1, None, 4, None),     # plain banding
    (17, 17, 8, 16, 3, 1, None, 3, 4),        # band-straddling rows
    (15, 15, 8, 16, 3, 2, None, 2, None),     # stride-2 conv
    (14, 14, 8, 130, 3, 1, None, 3, None),    # Cout=130 ragged tile
    (15, 15, 8, 16, 3, 1, (2, 2), 2, None),   # skip + fused pool
    (19, 19, 8, 16, 3, 1, (3, 2), 3, 4),      # overlapping pool straddle
])
@pytest.mark.parametrize("shifts", [
    ((0, 0), 0),          # already aligned, no output requant
    ((2, 0), 1),          # mismatched operand scales
    ((0, 3), 2),
])
def test_skip_fused_kernel_matches_two_stage_oracle(cfg, shifts):
    h, w_, cin, cout, k, stride, pool, bh, bci = cfg
    skip_shifts, merge_shift = shifts
    x, wt = i8(2, h, w_, cin), i8(k, k, cin, cout)
    b = jnp.asarray(RNG.integers(-500, 500, (cout,), np.int32))
    ho = (h - k) // stride + 1
    skip = i8(2, ho, ho, cout)
    got = qconv2d(x, wt, b, strides=(stride, stride), shift=5, relu=False,
                  pool=pool, block_cout=64, block_h=bh, block_cin=bci,
                  skip=skip, skip_shifts=skip_shifts,
                  merge_shift=merge_shift, merge_relu=True, interpret=True)
    want = _oracle_two_stage(x, wt, b, (stride, stride), 5, False, skip,
                             skip_shifts, merge_shift, True, pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_skip_epilogue_clips_conv_result_first():
    """The conv result must be clipped to int8 *before* the merge — the
    tensor the unfused conv stage would have written.  shift=0 with big
    accumulators makes the intermediate clip observable."""
    x = jnp.asarray(RNG.integers(-128, 128, (1, 6, 6, 32), np.int8))
    wt = jnp.asarray(RNG.integers(-128, 128, (3, 3, 32, 8), np.int8))
    skip = i8(1, 4, 4, 8)
    got = qconv2d(x, wt, None, strides=(1, 1), shift=0, relu=False,
                  block_h=2, skip=skip, skip_shifts=(0, 0),
                  merge_shift=0, merge_relu=False, interpret=True)
    want = _oracle_two_stage(x, wt, None, (1, 1), 0, False, skip,
                             (0, 0), 0, False, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ block_cin invariance
def test_block_cin_pure_blocking_knob():
    """Every Cin tile (incl. ragged Cin) gives the identical bit
    pattern as the whole-Cin contraction."""
    x, wt = i8(1, 13, 13, 130), i8(3, 3, 130, 24)
    b = jnp.asarray(RNG.integers(-500, 500, (24,), np.int32))
    outs = [np.asarray(qconv2d(x, wt, b, strides=(1, 1), shift=6,
                               relu=True, pool=(2, 2), block_h=3,
                               block_cin=bci, interpret=True))
            for bci in (None, 128, 64, 8)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_executor_invariant_to_n_i():
    """N_i now selects the kernel's real Cin tile; results must stay
    bit-identical across the option space (blocking only)."""
    gate = CNN2Gate.from_graph(cnn.resnet_tiny(batch=2))
    x = (RNG.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    outs = [np.asarray(pipe.run_int8(gate.quantized, jnp.asarray(x),
                                     n_i=ni, interpret=True))
            for ni in (1, 4, 16)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# --------------------------------------------------- parser fold pass
def test_resnet_tiny_folds_every_add():
    pm = P.parse(cnn.resnet_tiny())
    assert not any(li.kind == P.ADD for li in pm.layers)
    merged = [li for li in pm.layers if li.merge is not None]
    assert len(merged) == 2
    for li in merged:
        assert li.skip_input in li.inputs and len(li.inputs) == 2
        assert li.merge.relu  # the post-add ReLU rode along
        # the intermediate is the conv's own (pre-fold) product
        assert li.merge_intermediate not in [l.output for l in pm.layers]


@pytest.mark.parametrize("build,adds", [(cnn.resnet18, 8), (cnn.resnet50, 16)])
def test_every_residual_add_folds_at_224(build, adds):
    """At the published 224x224 every merge of the full-size resnets
    folds into a conv epilogue, parsing only: ResNet-50's 12 identity
    merges are hosted by a stride-1 1x1 expand, its 4 projection merges
    by the 1x1 projection."""
    g = build()
    assert sum(n.op_type == "Add" for n in g.nodes) == adds
    pm = P.parse(g)
    assert not any(li.kind == P.ADD for li in pm.layers)
    hosts = [li for li in pm.layers if li.merge is not None]
    assert len(hosts) == adds
    assert all(li.merge.relu and li.skip_input in li.inputs for li in hosts)


def test_projection_block_host_is_later_conv():
    """When both Add operands are single-consumer convs (projection
    block), the later-scheduled conv hosts so the skip is already
    available."""
    pm = P.parse(cnn.resnet_tiny())
    hosts = [li for li in pm.layers if li.merge is not None]
    for host in hosts:
        producers = {li.output: i for i, li in enumerate(pm.layers)}
        if host.skip_input in producers:
            assert producers[host.skip_input] < pm.layers.index(host)


def test_multi_consumer_conv_output_not_folded():
    """A conv output that fans out (feeds the Add *and* another conv)
    must stay addressable — the Add survives as a standalone stage."""
    b = cnn.GraphBuilder("fanout", (1, 3, 10, 10), 2)
    b.conv(8, 3, pad=1, relu=False)
    split = b.tap()                      # conv output: 3 consumers
    b.conv(8, 3, pad=1, relu=False)
    main = b.tap()
    b.from_tap(split).add_from(main, relu=True)  # reads split AND main
    b.from_tap(split).conv(8, 1, relu=False)     # extra consumer
    extra = b.tap()
    b.add_from(extra, relu=False)
    b.global_avgpool()
    b.fc(3, relu=False, softmax=True)
    pm = P.parse(b.build())
    # first add: 'main' conv is single-consumer -> folds; second add
    # merges two tensors whose conv producers both fan out -> survives
    adds = [li for li in pm.layers if li.kind == P.ADD]
    merged = [li for li in pm.layers if li.merge is not None]
    assert len(adds) + len(merged) == 2 and len(merged) >= 1


def test_depthwise_producer_folds_and_matches():
    """The depthwise band kernel now carries the same skip epilogue as
    the dense one: an Add whose second operand is a single-consumer
    depthwise conv folds into that conv, bit-exact vs the unfused
    program (MobileNet-v2-style inverted-residual merges)."""
    def build():
        b = cnn.GraphBuilder("dwadd", (1, 3, 12, 12), 4)
        b.conv(16, 3, pad=1)
        split = b.tap()
        b.dwconv(3, pad=1, relu=False)
        left = b.tap()
        b.from_tap(split).dwconv(3, pad=1, relu=False)
        b.add_from(left, relu=True)
        b.global_avgpool()
        b.fc(3, relu=False, softmax=True)
        return b.build()

    g = build()
    pm = P.parse(g)
    merged = [li for li in pm.layers if li.merge is not None]
    assert len(merged) == 1 and merged[0].is_dw_kernel
    assert not any(li.kind == P.ADD for li in pm.layers)
    x = np.random.default_rng(0).standard_normal(
        g.inputs[0].shape).astype(np.float32)
    gate = CNN2Gate.from_graph(g)
    gate.calibrate_quantization(x)
    y_f = pipe.run_int8(pipe.build_quantized(pm, gate.specs), x)
    y_u = pipe.run_int8(
        pipe.build_quantized(P.parse(g, fuse_skip=False), gate.specs), x)
    assert jnp.array_equal(y_f, y_u)


def test_folded_stage_absorbs_following_maxpool():
    """Conv -> Add -> ReLU -> MaxPool collapses into ONE stage: the
    epilogue pools after the merge (graph order), bit-exact vs the
    unfused program."""
    def build():
        b = cnn.GraphBuilder("addpool", (2, 3, 12, 12), 8)
        b.conv(8, 3, pad=1)
        split = b.tap()
        b.conv(8, 3, pad=1, relu=False)
        b.add_from(split, relu=True)
        b.maxpool(2, 2)
        b.fc(4, relu=False, softmax=True)
        return b.build()
    pm = P.parse(build())
    host = next(li for li in pm.layers if li.merge is not None)
    assert host.pool is not None and not any(li.kind == P.POOL
                                             for li in pm.layers)
    gate_f = CNN2Gate.from_graph(build())
    x = (RNG.standard_normal((2, 3, 12, 12)) * 0.5).astype(np.float32)
    specs = gate_f.calibrate_quantization(x)
    gate_u = CNN2Gate.from_graph(build(), fuse_skip=False)
    gate_u.apply_quantization(specs)
    y_f = np.asarray(gate_f.build("emulation")(jnp.asarray(x)))
    y_u = np.asarray(gate_u.build("emulation")(jnp.asarray(x)))
    np.testing.assert_array_equal(y_f, y_u)


def test_softmax_on_add_blocks_fold():
    """A Softmax fused into the Add stage has no home in the conv
    epilogue — folding it would silently drop the softmax.  The merge
    must stay standalone, and the fused-default program must still
    match the unfused one exactly (regression: the fold used to check
    only the host conv's softmax flag)."""
    def build():
        b = cnn.GraphBuilder("addsm", (2, 3, 8, 8), 5)
        b.conv(4, 3, pad=1)
        split = b.tap()
        b.conv(4, 3, pad=1, relu=False)
        b.add_from(split, relu=False)
        # graph ends Conv -> Add -> Softmax (channel axis)
        name = b._name("Softmax")
        out = name + "_out"
        b.nodes.append(cnn.Node("Softmax", name, [b.cur], [out],
                                {"axis": 1}))
        b.cur = out
        return b.build()
    pm = P.parse(build())
    add = next(li for li in pm.layers if li.kind == P.ADD)
    assert add.softmax and not any(li.merge is not None for li in pm.layers)
    x = (RNG.standard_normal((2, 3, 8, 8)) * 0.5).astype(np.float32)
    gate_f = CNN2Gate.from_graph(build())
    specs = gate_f.calibrate_quantization(x)
    gate_u = CNN2Gate.from_graph(build(), fuse_skip=False)
    gate_u.apply_quantization(specs)
    y_f = np.asarray(gate_f.build("emulation")(jnp.asarray(x)))
    y_u = np.asarray(gate_u.build("emulation")(jnp.asarray(x)))
    np.testing.assert_array_equal(y_f, y_u)
    assert y_f.max() <= 1.0 + 1e-6  # the softmax actually ran


# ------------------------------------------- end-to-end fused parity
@pytest.mark.parametrize("build,in_hw", [
    (cnn.resnet_tiny, 32),
    (cnn.resnet18, 32),
    (cnn.resnet50, 32),
])
def test_fused_program_bit_exact_vs_unfused(build, in_hw):
    """Acceptance: the skip-fused executor is bit-exact against the
    unfused Conv -> Add program under the same specs, on every resnet
    builder."""
    kw = dict(batch=2, in_hw=in_hw)
    gate_f = CNN2Gate.from_graph(build(**kw))
    x = (RNG.standard_normal((2, 3, in_hw, in_hw)) * 0.5
         ).astype(np.float32)
    specs = gate_f.calibrate_quantization(x)
    gate_u = CNN2Gate.from_graph(build(**kw), fuse_skip=False)
    gate_u.apply_quantization(specs)
    assert any(li.merge is not None for li in gate_f.parsed.layers)
    assert any(li.kind == P.ADD for li in gate_u.parsed.layers)
    y_f = np.asarray(gate_f.build("emulation")(jnp.asarray(x)))
    y_u = np.asarray(gate_u.build("emulation")(jnp.asarray(x)))
    np.testing.assert_array_equal(y_f, y_u)


def test_fused_specs_identical_to_unfused_calibration():
    """Calibrating the fused program must produce the same QuantSpecs
    (same names, same values) as calibrating the unfused one — fusion
    never changes the fixed-point program, only where it executes."""
    x = (RNG.standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    s_f = CNN2Gate.from_graph(
        cnn.resnet_tiny(batch=2)).calibrate_quantization(x)
    s_u = CNN2Gate.from_graph(
        cnn.resnet_tiny(batch=2),
        fuse_skip=False).calibrate_quantization(x)
    assert s_f == s_u


def test_mismatched_branch_scales_fused_bit_exact():
    """Force unequal operand positions (nonzero alignment shifts) on a
    diamond graph and check fused == unfused bit-for-bit."""
    def build():
        b = cnn.GraphBuilder("diamond", (2, 3, 12, 12), 3)
        b.conv(8, 3, pad=1)
        split = b.tap()
        b.conv(8, 3, pad=1, relu=False)
        left = b.tap()
        b.from_tap(split).conv(8, 3, pad=1, relu=False)
        b.add_from(left, relu=True)
        b.global_avgpool()
        b.fc(5, relu=False, softmax=True)
        return b.build()
    pm = P.parse(build(), fuse_skip=False)
    conv_names = [li.name for li in pm.layers if li.kind == P.CONV]
    add_name = next(li.name for li in pm.layers if li.kind == P.ADD)
    fc_name = next(li.name for li in pm.layers if li.kind == P.FC)
    specs = {
        conv_names[0]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[1]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[2]: QuantSpec(m_w=7, m_x=6, m_y=4),
        add_name: QuantSpec(m_w=0, m_x=4, m_y=3),
        fc_name: QuantSpec(m_w=7, m_x=3, m_y=7),
    }
    x = (RNG.standard_normal((2, 3, 12, 12)) * 0.5).astype(np.float32)
    gate_f = CNN2Gate.from_graph(build())
    gate_f.apply_quantization(specs)
    host = next(ql for ql in gate_f.quantized.layers
                if ql.info.merge is not None)
    assert sorted(host.operand_shifts) == [0, 2]  # real alignment work
    gate_u = CNN2Gate.from_graph(build(), fuse_skip=False)
    gate_u.apply_quantization(specs)
    y_f = np.asarray(gate_f.build("emulation")(jnp.asarray(x)))
    y_u = np.asarray(gate_u.build("emulation")(jnp.asarray(x)))
    np.testing.assert_array_equal(y_f, y_u)


def test_fused_merge_below_common_scale_rejected():
    """Shift-only alignment cannot scale up — same guard as the
    standalone merge, now raised from the fused path."""
    pm = P.parse(cnn.resnet_tiny())
    host = next(li for li in pm.layers if li.merge is not None)
    specs = {}
    for li in pm.layers:
        if li.kind in (P.CONV, P.FC):
            specs[li.name] = QuantSpec(m_w=7, m_x=6, m_y=6)
    specs[host.merge.name] = QuantSpec(m_w=0, m_x=8, m_y=8)  # above ops
    with pytest.raises(ValueError, match="alignment"):
        pipe.build_quantized(pm, specs)


# ----------------------------------------------- jaxpr: no add stage
# (the probe itself is the verifier's reusable int_add_eqns — the old
# copy-pasted walker lives in core/verify.py now)

def test_fused_program_has_no_standalone_add_stage():
    gate = CNN2Gate.from_graph(cnn.resnet_tiny(batch=1))
    x = (RNG.standard_normal((1, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    ex_f = pipe.make_executor(gate.quantized, interpret=True)
    jaxpr_f = jax.make_jaxpr(lambda v: ex_f(v))(jnp.asarray(x))
    assert V.int_add_eqns(jaxpr_f.jaxpr) == 0
    # ...and the QV501 probe agrees wholesale
    assert V.structural_probes(gate.quantized) == []
    # ...and the unfused program DOES have them (the probe is valid)
    gate_u = CNN2Gate.from_graph(cnn.resnet_tiny(batch=1),
                                 fuse_skip=False)
    gate_u.apply_quantization(gate.specs)
    ex_u = pipe.make_executor(gate_u.quantized, interpret=True)
    jaxpr_u = jax.make_jaxpr(lambda v: ex_u(v))(jnp.asarray(x))
    assert V.int_add_eqns(jaxpr_u.jaxpr) > 0


# ------------------------------------------------ working-set model
def test_cin_tile_shrinks_input_band_3x():
    """Acceptance: a 224x224x512 conv (3x3, pad 1 -> hp=wp=226) with
    block_cin=128 holds >= 3x less input band than the whole-Cin
    kernel, and the full working set drops accordingly."""
    whole = band_input_bytes(226, 226, 512, 3, 224, block_h=8)
    tiled = band_input_bytes(226, 226, 512, 3, 224, block_h=8,
                             block_cin=128)
    assert whole / tiled >= 3.0
    ws_whole = vmem_bytes(226, 226, 512, 3, 3, 128, 224, 224, block_h=8)
    ws_tiled = vmem_bytes(226, 226, 512, 3, 3, 128, 224, 224, block_h=8,
                          block_cin=128)
    assert ws_tiled < ws_whole


def test_skip_vmem_term_charged_for_fused_merge():
    """The DSE working-set rule must charge the skip band the epilogue
    holds: the fused program's peak conv working set exceeds the same
    conv without the merge."""
    assert vmem_bytes(34, 34, 64, 3, 3, 128, 32, 32, block_h=4,
                      skip=True) > \
        vmem_bytes(34, 34, 64, 3, 3, 128, 32, 32, block_h=4)
    pm_f = P.parse(cnn.resnet_tiny())
    ws = conv_band_working_set(pm_f.layers, 32, 4, n_i=16)
    assert ws > 0


def test_working_set_shrinks_with_n_i():
    """The N_i axis now bounds the measured band: a VGG-scale model's
    working set must be monotone non-increasing as N_i shrinks."""
    pm = P.parse(cnn.vgg16())
    ws = [conv_band_working_set(pm.layers, 32, 8, n_i=ni)
          for ni in (16, 8, 4)]
    assert ws[0] >= ws[1] >= ws[2]
    assert conv_band_working_set(pm.layers, 32, 8) >= ws[0]
