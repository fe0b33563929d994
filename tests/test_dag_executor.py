"""DAG stage program: parser scheduling, residual/depthwise execution.

Parity matrix for the DAG-scheduled int8 executor:

  * residual add with mismatched branch scales (per-operand alignment
    shifts), bit-exact against a reference chain built from the
    ``kernels/ref.py`` oracles;
  * multi-consumer tensor fan-out (diamond graphs);
  * depthwise conv vs the float/int reference — bit-for-bit at the
    int32 accumulator;
  * grouped convs may never execute as dense convs: valid groups run
    grouped, invalid groups raise;
  * a toposort property test over randomized DAGs (hypothesis; skipped
    cleanly when the package is absent, per conftest stub).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import parser as P
from repro.core import pipeline as pipe
from repro.core.graph import Graph, GraphError, Node, TensorInfo
from repro.core.quantize import QuantSpec
from repro.core.resources import conv_band_working_set
from repro.core.synthesis import CNN2Gate
from repro.kernels import ops, ref
from repro.kernels.qconv import qdwconv2d
from repro.models import cnn

RNG = np.random.default_rng(11)


def i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, shape, np.int8))


# ---------------------------------------------------------- parser / DAG
def test_parse_resnet_tiny_stage_program():
    # fuse_skip=False: this test pins the *unfused* stage program — the
    # fallback for ineligible merges (the skip-fusion pass has its own
    # suite in tests/test_skip_fusion.py)
    pm = P.parse(cnn.resnet_tiny(), fuse_skip=False)
    kinds = [li.kind for li in pm.layers]
    assert kinds.count(P.ADD) == 2
    adds = [li for li in pm.layers if li.kind == P.ADD]
    assert all(len(li.inputs) == 2 for li in adds)
    assert all(li.relu for li in adds)  # post-add ReLU fused into merge
    # schedule is topological: every input is produced earlier (or is
    # the graph input)
    seen = {pm.input_name}
    for li in pm.layers:
        assert all(t in seen for t in li.inputs), li.name
        seen.add(li.output)
    # multi-consumer fan-out survives as a named tensor: the block
    # input feeds both the first conv and the merge
    stem_out = pm.layers[0].output
    consumers = pm.consumer_stages(stem_out)
    assert len(consumers) == 2
    assert {c.kind for c in consumers} == {P.CONV, P.ADD}


def test_parse_mobilenet_depthwise_stages():
    pm = P.parse(cnn.mobilenet_tiny())
    dws = [li for li in pm.layers if li.is_depthwise]
    assert len(dws) == 3
    assert all(li.group == li.c_in == li.c_out for li in dws)
    # depthwise layers do not destroy the (N_i, N_l) option space
    assert 8 in pm.feasible_ni() and 8 in pm.feasible_nl()


def test_merge_stages_in_memory_schedule_and_latency():
    pm = P.parse(cnn.resnet_tiny(), fuse_skip=False)
    sched = P.memory_schedule(pm, 16, 32)
    assert len(sched) == len(pm.layers)
    assert all(s["read_vectors"] > 0 and s["lanes"] > 0 for s in sched)
    merge_rows = [s for s in sched if s["kind"] == P.ADD]
    assert merge_rows and all(s["weight_vectors"] == 0 for s in merge_rows)
    rep = CNN2Gate.from_graph(cnn.resnet_tiny(),
                              fuse_skip=False).latency_report(
        "ARRIA10", 16, 32)
    add_times = [l for l in rep.layers if l.kind == P.ADD]
    assert add_times and all(l.macs == 0 and l.time_s > 0 for l in add_times)


def test_band_working_set_covers_branch_and_depthwise():
    for g in (cnn.resnet_tiny(), cnn.mobilenet_tiny()):
        pm = P.parse(g)
        ws = [conv_band_working_set(pm.layers, 32, bh) for bh in (1, 4, 16)]
        assert all(w > 0 for w in ws)
        assert ws == sorted(ws)  # monotone in block_h, branches included


# ------------------------------------------------- residual merge parity
def _diamond_graph(seed=3):
    """One tensor fans out into two conv branches that merge in an Add —
    the smallest multi-consumer residual graph."""
    b = cnn.GraphBuilder("diamond", (2, 3, 12, 12), seed)
    b.conv(8, 3, pad=1)
    split = b.tap()
    b.conv(8, 3, pad=1, relu=False)
    left = b.tap()
    b.from_tap(split).conv(8, 3, pad=1, relu=False)
    right = b.tap()
    b.from_tap(left).add_from(right, relu=True)
    b.global_avgpool()
    b.fc(5, relu=False, softmax=True)
    return b.build()


def test_diamond_fanout_executes_and_tracks_float():
    g = _diamond_graph()
    gate = CNN2Gate.from_graph(g)
    x = (RNG.standard_normal((2, 3, 12, 12)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    assert y_q.shape == y_f.shape
    rel = np.linalg.norm(y_q - y_f) / max(np.linalg.norm(y_f), 1e-9)
    assert rel < 0.75  # the tolerance the linear tiny_cnn itself meets


def test_residual_add_mismatched_branch_scales_bit_exact():
    """Force the two branch producers onto different fixed-point
    positions and check the executor against a reference chain built
    from the ref.py oracles: the merge must align operands with
    per-operand round-half-up shifts, bit-for-bit."""
    g = _diamond_graph()
    pm = P.parse(g, fuse_skip=False)
    conv_names = [li.name for li in pm.layers if li.kind == P.CONV]
    add_name = next(li.name for li in pm.layers if li.kind == P.ADD)
    fc_name = next(li.name for li in pm.layers if li.kind == P.FC)
    # stem at m_y=6; left branch emits at m=6, right branch at m=4
    specs = {
        conv_names[0]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[1]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[2]: QuantSpec(m_w=7, m_x=6, m_y=4),
        add_name: QuantSpec(m_w=0, m_x=4, m_y=3),
        fc_name: QuantSpec(m_w=7, m_x=3, m_y=7),
    }
    gate = CNN2Gate.from_graph(g, fuse_skip=False)
    gate.apply_quantization(specs)
    qm = gate.quantized
    add_q = next(ql for ql in qm.layers if ql.info.kind == P.ADD)
    assert add_q.operand_shifts == (2, 0)  # 6-4 and 4-4

    x = (RNG.standard_normal((2, 3, 12, 12)) * 0.5).astype(np.float32)
    y_exec = np.asarray(pipe.run_int8(qm, jnp.asarray(x), interpret=True))

    # reference chain straight from the oracles (NHWC int8)
    convs = {ql.info.name: ql for ql in qm.layers if ql.info.kind == P.CONV}
    xq = jnp.clip(jnp.round(jnp.asarray(x) * 2.0 ** 6), -128, 127
                  ).astype(jnp.int8).transpose(0, 2, 3, 1)

    def run_conv(name, xin, relu):
        ql = convs[name]
        xin = jnp.pad(xin, ((0, 0), (1, 1), (1, 1), (0, 0)))
        return ref.qconv2d_ref(xin, ql.w_q, ql.b_q, (1, 1),
                               ql.spec.requant_shift, relu)

    stem = run_conv(conv_names[0], xq, True)
    left = run_conv(conv_names[1], stem, False)
    right = run_conv(conv_names[2], stem, False)
    merged = ref.qadd_ref([left, right], (2, 0), shift=1, relu=True)
    gap = ref.avgpool2d_ref(merged, merged.shape[1], 1)
    fc_q = next(ql for ql in qm.layers if ql.info.kind == P.FC)
    flat = gap.reshape(gap.shape[0], -1)
    logits_q = ref.qgemm_ref(flat, fc_q.w_q, fc_q.b_q,
                             fc_q.spec.requant_shift, relu=False)
    logits = jnp.asarray(np.asarray(logits_q, np.float32) * 2.0 ** -7)
    want = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(y_exec, want, rtol=0, atol=0)


def test_merge_below_common_scale_rejected():
    """Shift-only alignment cannot scale an operand *up*: a user spec
    that puts the merge position above an operand must raise (fused and
    unfused programs alike)."""
    g = _diamond_graph()
    pm = P.parse(g, fuse_skip=False)
    conv_names = [li.name for li in pm.layers if li.kind == P.CONV]
    add_name = next(li.name for li in pm.layers if li.kind == P.ADD)
    fc_name = next(li.name for li in pm.layers if li.kind == P.FC)
    specs = {
        conv_names[0]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[1]: QuantSpec(m_w=7, m_x=6, m_y=6),
        conv_names[2]: QuantSpec(m_w=7, m_x=6, m_y=4),
        add_name: QuantSpec(m_w=0, m_x=6, m_y=6),  # above right branch
        fc_name: QuantSpec(m_w=7, m_x=6, m_y=7),
    }
    with pytest.raises(ValueError, match="alignment"):
        pipe.build_quantized(pm, specs)


# -------------------------------------------------- depthwise conv parity
@pytest.mark.parametrize("cfg", [
    # (h, w, c, k, stride, pool, block_h)
    (14, 14, 8, 3, 1, None, 4),
    (17, 17, 16, 3, 2, None, 3),      # stride-2, ragged bands
    (15, 15, 24, 3, 1, (2, 2), 5),    # fused pool across band boundary
    (10, 10, 130, 3, 1, None, 2),     # channels past one 128 lane tile
])
@pytest.mark.parametrize("shift,relu", [(6, True), (3, False)])
def test_depthwise_band_kernel_matches_ref(cfg, shift, relu):
    h, w, c, k, stride, pool, bh = cfg
    x = i8(2, h, w, c)
    wt = i8(k, k, c)
    b = jnp.asarray(RNG.integers(-500, 500, (c,), np.int32))
    got = qdwconv2d(x, wt, b, strides=(stride, stride), shift=shift,
                    relu=relu, pool=pool, block_c=64, block_h=bh,
                    interpret=True)
    want = ref.qconv2d_ref(x, wt.reshape(k, k, 1, c), b, (stride, stride),
                           shift, relu, pool, groups=c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_depthwise_int32_accumulator_bit_exact():
    """With shift=0 and operands small enough that the accumulator fits
    int8, the kernel output IS the int32 accumulator — bit-for-bit."""
    x = jnp.asarray(RNG.integers(-3, 4, (1, 9, 9, 12), np.int8))
    wt = jnp.asarray(RNG.integers(-3, 4, (3, 3, 12), np.int8))
    got = qdwconv2d(x, wt, None, strides=(1, 1), shift=0, relu=False,
                    block_h=2, interpret=True)
    acc = np.asarray(ref.qconv2d_ref(
        x, wt.reshape(3, 3, 1, 12), None, (1, 1), 0, False, groups=12))
    # independent int32 oracle: plain lax conv at accumulator precision
    acc32 = jax.lax.conv_general_dilated(
        jnp.asarray(x, jnp.int32),
        jnp.asarray(wt.reshape(3, 3, 1, 12), jnp.int32),
        (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=12)
    assert int(jnp.abs(acc32).max()) <= 127  # nothing clipped
    np.testing.assert_array_equal(np.asarray(got), np.asarray(acc32))
    np.testing.assert_array_equal(np.asarray(got), acc)


def test_depthwise_block_h_and_block_c_invariance():
    x, wt = i8(1, 13, 13, 40), i8(3, 3, 40)
    outs = [np.asarray(qdwconv2d(x, wt, None, strides=(1, 1), shift=5,
                                 relu=True, pool=(2, 2), block_c=bc,
                                 block_h=bh, interpret=True))
            for bh, bc in ((1, 128), (3, 128), (None, 64), (4, 8))]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ------------------------------------------------ grouped conv guarantees
def test_grouped_conv_never_runs_dense():
    """A group=2 conv must execute grouped: compare against the float
    oracle (which honours feature_group_count) — a silently-dense
    execution produces garbage here because the dense conv would read
    all 8 input channels per filter instead of 4."""
    b = cnn.GraphBuilder("grouped", (2, 3, 10, 10), 5)
    b.conv(8, 3, pad=1)
    b.conv(8, 3, pad=1, group=2)
    b.global_avgpool()
    b.fc(4, relu=False, softmax=True)
    g = b.build()
    gate = CNN2Gate.from_graph(g)
    x = (RNG.standard_normal((2, 3, 10, 10)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    rel = np.linalg.norm(y_q - y_f) / max(np.linalg.norm(y_f), 1e-9)
    assert rel < 0.75


def test_invalid_group_raises_not_silent():
    pm = P.parse(cnn.tiny_cnn())
    conv = next(li for li in pm.layers if li.kind == P.CONV)
    conv.group = 3  # does not divide c_out=16
    specs = {li.name: QuantSpec(m_w=7, m_x=6, m_y=6) for li in pm.layers}
    with pytest.raises(NotImplementedError, match="group"):
        pipe.build_quantized(pm, specs)


# --------------------------------------------- end-to-end residual nets
@pytest.fixture(scope="module")
def resnet_gate():
    gate = CNN2Gate.from_graph(cnn.resnet_tiny(batch=4))
    x = (RNG.standard_normal((4, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    return gate, x


def test_resnet_tiny_emulation_matches_float(resnet_gate):
    gate, x = resnet_gate
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(cnn.resnet_tiny(batch=4),
                                   jnp.asarray(x)))
    # top-1 must agree wherever the float top-2 margin exceeds the int8
    # noise floor (untrained nets have near-tied softmax rows where
    # argmax is not a meaningful parity signal)
    top2 = np.sort(y_f, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 0.02
    assert decided.any()
    assert np.all(y_q.argmax(-1)[decided] == y_f.argmax(-1)[decided])
    rel = np.linalg.norm(y_q - y_f) / np.linalg.norm(y_f)
    assert rel < 0.75  # same tolerance the linear nets meet


def test_resnet50_emulation_matches_float():
    """ResNet-50 v1.5 at 32x32 against the float graph.  Its random
    He-normal logits are large (std ~90), so the softmax rows are
    near one-hot and flip on a small logit error; the comparison is of
    the dequantized logits, which the executor's answer is the softmax
    of.  Tolerance 0.2 relative: the per-tensor power-of-two int8
    rounding compounds along the deepest path's ~50 requantizations,
    to 0.10-0.12 on the inputs tried (resnet_tiny reads 0.03, resnet18
    0.07); a dropped skip or a misaligned merge reads far above."""
    g = cnn.resnet50(batch=2, in_hw=32)
    gate = CNN2Gate.from_graph(g)
    x = (np.random.default_rng(50).standard_normal((2, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    qm = gate.quantized
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    logits_q = np.asarray(pipe.oracle_replay(qm, jnp.asarray(x), dequantize=False),
                          np.float32).reshape(2, -1) * 2.0 ** -qm.output_m
    np.testing.assert_allclose(y_q, np.asarray(jax.nn.softmax(logits_q, axis=-1)),
                               rtol=1e-5, atol=1e-6)
    fc = [n for n in g.nodes if n.op_type == "Gemm"][-1]
    logits_f = np.asarray(cnn.run_float(g, jnp.asarray(x), return_env=True)[fc.outputs[0]])
    rel = np.linalg.norm(logits_q - logits_f) / np.linalg.norm(logits_f)
    assert rel < 0.2, rel


def test_resnet_tiny_block_h_invariant(resnet_gate):
    gate, x = resnet_gate
    outs = [np.asarray(pipe.run_int8(gate.quantized, jnp.asarray(x),
                                     interpret=True, block_h=bh))
            for bh in (None, 2, 5)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_mobilenet_tiny_emulation_tracks_float():
    gate = CNN2Gate.from_graph(cnn.mobilenet_tiny(batch=4))
    x = (RNG.standard_normal((4, 3, 32, 32)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(cnn.mobilenet_tiny(batch=4),
                                   jnp.asarray(x)))
    rel = np.linalg.norm(y_q - y_f) / np.linalg.norm(y_f)
    assert rel < 0.75


def test_branch_scales_aligned_by_calibration(resnet_gate):
    """Branch-aware calibration drives the merge operand shifts to zero
    whenever the producers' m_y caps allow it."""
    gate, _x = resnet_gate
    for ql in gate.quantized.layers:
        if ql.info.kind == P.ADD:
            assert all(s >= 0 for s in ql.operand_shifts)
            ms = [s for s in ql.operand_shifts]
            assert min(ms) == 0  # at least one operand sits at the merge m


def test_concat_stage_executes():
    b = cnn.GraphBuilder("cat", (2, 3, 8, 8), 9)
    b.conv(8, 3, pad=1)
    split = b.tap()
    b.conv(8, 1, relu=False)
    left = b.tap()
    b.from_tap(split).conv(4, 1, relu=False)
    right = b.tap()
    b.from_tap(left).concat_from(right)
    b.relu()
    b.global_avgpool()
    b.fc(3, relu=False, softmax=True)
    g = b.build()
    assert g.shape(g.nodes[-1].inputs[0])  # graph built & shaped
    gate = CNN2Gate.from_graph(g)
    x = (RNG.standard_normal((2, 3, 8, 8)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    rel = np.linalg.norm(y_q - y_f) / max(np.linalg.norm(y_f), 1e-9)
    assert y_q.shape == y_f.shape and rel < 0.75


def test_padded_maxpool_runs_standalone_and_matches_float():
    """A padded MaxPool must NOT fuse into the conv band kernel (which
    has no pool-pad path) — it runs standalone, where the int8-native
    reduce_window handles pads exactly.  This is the resnet18 stem
    shape (conv pad + 3x3/2 pool pad 1)."""
    b = cnn.GraphBuilder("padpool", (2, 3, 14, 14), 4)
    b.conv(8, 3, pad=1).maxpool(3, 2, pad=1)
    b.fc(5, relu=False, softmax=True)
    g = b.build()
    pm = P.parse(g)
    conv = next(li for li in pm.layers if li.kind == P.CONV)
    assert conv.pool is None  # padded pool did not fuse
    assert any(li.kind == P.POOL for li in pm.layers)
    gate = CNN2Gate.from_graph(g)
    x = (RNG.standard_normal((2, 3, 14, 14)) * 0.5).astype(np.float32)
    gate.calibrate_quantization(x)
    y_q = np.asarray(gate.build("emulation")(jnp.asarray(x)))
    y_f = np.asarray(cnn.run_float(g, jnp.asarray(x)))
    assert y_q.shape == y_f.shape  # shape drift was the crash signature
    rel = np.linalg.norm(y_q - y_f) / max(np.linalg.norm(y_f), 1e-9)
    assert rel < 0.75


def test_concat_fused_relu_applied():
    """A ReLU fused into a Concat stage must clamp negatives (it used
    to be parsed, marked fused, and silently dropped)."""
    xs = [i8(1, 4, 4, 3), i8(1, 4, 4, 5)]
    y = np.asarray(ops.qconcat_nhwc(xs, (0, 1), relu=True))
    assert y.shape == (1, 4, 4, 8) and y.min() >= 0
    want = np.concatenate(
        [np.maximum(np.asarray(ref.align_shift(x.astype(jnp.int32), s)), 0)
         for x, s in zip(xs, (0, 1))], axis=-1)
    np.testing.assert_array_equal(y, want.astype(np.int8))


def test_band_working_set_handles_vector_merge():
    """MLP-style (2-D) residuals must not crash the DSE feasibility
    pass."""
    nodes = [
        Node("Gemm", "g1", ["x", "w1", "b1"], ["t1"]),
        Node("Gemm", "g2", ["t1", "w2", "b2"], ["t2"]),
        Node("Add", "a", ["t1", "t2"], ["y"]),
    ]
    inits = {"w1": RNG.standard_normal((8, 8)).astype(np.float32),
             "b1": np.zeros(8, np.float32),
             "w2": RNG.standard_normal((8, 8)).astype(np.float32),
             "b2": np.zeros(8, np.float32)}
    g = Graph("mlp_skip", nodes, [TensorInfo("x", (1, 8))], ["y"], inits)
    pm = P.parse(g)
    assert conv_band_working_set(pm.layers, 8, 4) > 0


# -------------------------------------------------- toposort property
@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_toposort_property_random_dags(data):
    """Random DAGs of Relu/Add nodes, presented shuffled: Graph must
    recover a valid topological order (or raise GraphError on cycles,
    which this generator never builds)."""
    n_nodes = data.draw(st.integers(2, 12))
    tensors = ["x"]
    nodes = []
    for i in range(n_nodes):
        k = data.draw(st.integers(1, min(2, len(tensors))))
        ins = [data.draw(st.sampled_from(tensors)) for _ in range(k)]
        out = f"t{i}"
        if len(set(ins)) == 2:
            nodes.append(Node("Add", f"n{i}", ins, [out]))
        else:
            nodes.append(Node("Relu", f"n{i}", [ins[0]], [out]))
        tensors.append(out)
    perm = data.draw(st.permutations(nodes))
    g = Graph("rand", perm, [TensorInfo("x", (1, 4))], [nodes[-1].outputs[0]])
    seen = {"x"}
    for n in g.nodes:
        assert all(t in seen for t in n.inputs)
        seen.update(n.outputs)


def test_cycle_still_rejected():
    nodes = [Node("Relu", "a", ["t2"], ["t1"]),
             Node("Relu", "b", ["t1"], ["t2"])]
    with pytest.raises(GraphError):
        Graph("cyc", nodes, [TensorInfo("x", (1, 4))], ["t2"])
