"""CNN model zoo expressed in the ONNX-lite transport format.

Builders emit exactly the graphs a framework exporter would (ONNX op
names, NCHW, initializers as numpy arrays), so the front-end parser is
exercised the same way it would be on a real ONNX file.  AlexNet and
VGG-16 match the paper's workloads (Tables 1–4).  A float JAX executor
(``run_float``) serves as the accuracy oracle for the int8 pipeline.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, Node, TensorInfo


class GraphBuilder:
    """Tiny builder DSL ("the ML framework" whose export we parse).

    The builder threads one *current* tensor; ``tap()`` captures a
    handle to it and ``from_tap`` rewinds, which is how branches
    (residual skips, inception-style splits) are expressed — the emitted
    graph is a plain ONNX-style DAG either way."""

    def __init__(self, name: str, input_shape: Sequence[int], seed: int = 0):
        self.name = name
        self.nodes: List[Node] = []
        self.inits: Dict[str, np.ndarray] = {}
        self.rng = np.random.default_rng(seed)
        self.input = TensorInfo("input", tuple(input_shape))
        self.cur = "input"
        self.cur_shape: Tuple[int, ...] = tuple(input_shape)
        self._n = 0

    def _name(self, op: str) -> str:
        self._n += 1
        return f"{op.lower()}_{self._n}"

    # ------------------------------------------------- branch plumbing
    def tap(self) -> Tuple[str, Tuple[int, ...]]:
        """Handle to the current tensor (for skips/merges)."""
        return self.cur, self.cur_shape

    def from_tap(self, handle: Tuple[str, Tuple[int, ...]]) -> "GraphBuilder":
        """Rewind the builder to a tapped tensor (start a branch)."""
        self.cur, self.cur_shape = handle[0], tuple(handle[1])
        return self

    def conv(self, c_out: int, k: int, stride: int = 1, pad: int = 0,
             relu: bool = True, group: int = 1) -> "GraphBuilder":
        name = self._name("Conv")
        c_in = self.cur_shape[1]
        w = (self.rng.standard_normal((c_out, c_in // group, k, k)) *
             np.sqrt(2.0 / (c_in // group * k * k))).astype(np.float32)
        b = (self.rng.standard_normal(c_out) * 0.01).astype(np.float32)
        self.inits[name + "_w"] = w
        self.inits[name + "_b"] = b
        out = name + "_out"
        self.nodes.append(Node(
            "Conv", name, [self.cur, name + "_w", name + "_b"], [out],
            {"kernel_shape": [k, k], "strides": [stride, stride],
             "pads": [pad, pad, pad, pad], "dilations": [1, 1],
             "group": group}))
        self.cur = out
        h = (self.cur_shape[2] + 2 * pad - k) // stride + 1
        w_ = (self.cur_shape[3] + 2 * pad - k) // stride + 1
        self.cur_shape = (self.cur_shape[0], c_out, h, w_)
        if relu:
            self.relu()
        return self

    def dwconv(self, k: int, stride: int = 1, pad: int = 0,
               relu: bool = True) -> "GraphBuilder":
        """Depthwise conv (group == C, multiplier 1, MobileNet-style)."""
        return self.conv(self.cur_shape[1], k, stride=stride, pad=pad,
                         relu=relu, group=self.cur_shape[1])

    def add_from(self, handle: Tuple[str, Tuple[int, ...]],
                 relu: bool = True) -> "GraphBuilder":
        """Residual merge: current tensor + tapped tensor."""
        name = self._name("Add")
        out = name + "_out"
        self.nodes.append(Node("Add", name, [self.cur, handle[0]], [out]))
        self.cur = out
        if relu:
            self.relu()
        return self

    def concat_from(self, *handles: Tuple[str, Tuple[int, ...]]
                    ) -> "GraphBuilder":
        """Channel merge: concat current tensor with tapped tensors."""
        name = self._name("Concat")
        out = name + "_out"
        self.nodes.append(Node(
            "Concat", name, [self.cur] + [h[0] for h in handles], [out],
            {"axis": 1}))
        c = self.cur_shape[1] + sum(h[1][1] for h in handles)
        self.cur_shape = (self.cur_shape[0], c) + tuple(self.cur_shape[2:])
        self.cur = out
        return self

    def relu(self) -> "GraphBuilder":
        name = self._name("Relu")
        out = name + "_out"
        self.nodes.append(Node("Relu", name, [self.cur], [out]))
        self.cur = out
        return self

    def maxpool(self, k: int, stride: Optional[int] = None,
                pad: int = 0) -> "GraphBuilder":
        stride = stride or k
        name = self._name("MaxPool")
        out = name + "_out"
        self.nodes.append(Node(
            "MaxPool", name, [self.cur], [out],
            {"kernel_shape": [k, k], "strides": [stride, stride],
             "pads": [pad, pad, pad, pad]}))
        self.cur = out
        n, c, h, w = self.cur_shape
        self.cur_shape = (n, c, (h + 2 * pad - k) // stride + 1,
                          (w + 2 * pad - k) // stride + 1)
        return self

    def avgpool(self, k: int, stride: Optional[int] = None,
                pad: int = 0) -> "GraphBuilder":
        stride = stride or k
        name = self._name("AveragePool")
        out = name + "_out"
        self.nodes.append(Node(
            "AveragePool", name, [self.cur], [out],
            {"kernel_shape": [k, k], "strides": [stride, stride],
             "pads": [pad, pad, pad, pad]}))
        self.cur = out
        n, c, h, w = self.cur_shape
        self.cur_shape = (n, c, (h + 2 * pad - k) // stride + 1,
                          (w + 2 * pad - k) // stride + 1)
        return self

    def global_avgpool(self) -> "GraphBuilder":
        name = self._name("GlobalAveragePool")
        out = name + "_out"
        self.nodes.append(Node("GlobalAveragePool", name, [self.cur], [out]))
        self.cur = out
        n, c, _h, _w = self.cur_shape
        self.cur_shape = (n, c, 1, 1)
        return self

    def flatten(self) -> "GraphBuilder":
        name = self._name("Flatten")
        out = name + "_out"
        self.nodes.append(Node("Flatten", name, [self.cur], [out], {"axis": 1}))
        self.cur = out
        n = self.cur_shape[0]
        self.cur_shape = (n, int(np.prod(self.cur_shape[1:])))
        return self

    def fc(self, n_out: int, relu: bool = True, softmax: bool = False) -> "GraphBuilder":
        if len(self.cur_shape) != 2:
            self.flatten()
        name = self._name("Gemm")
        k = self.cur_shape[1]
        w = (self.rng.standard_normal((k, n_out)) * np.sqrt(2.0 / k)).astype(np.float32)
        b = (self.rng.standard_normal(n_out) * 0.01).astype(np.float32)
        self.inits[name + "_w"] = w
        self.inits[name + "_b"] = b
        out = name + "_out"
        self.nodes.append(Node("Gemm", name, [self.cur, name + "_w", name + "_b"],
                               [out], {"transA": 0, "transB": 0}))
        self.cur = out
        self.cur_shape = (self.cur_shape[0], n_out)
        if relu:
            self.relu()
        if softmax:
            name = self._name("Softmax")
            out = name + "_out"
            self.nodes.append(Node("Softmax", name, [self.cur], [out], {"axis": 1}))
            self.cur = out
        return self

    def build(self) -> Graph:
        return Graph(self.name, self.nodes, [self.input], [self.cur], self.inits)


def alexnet(batch: int = 1, num_classes: int = 1000, seed: int = 0,
            channels_base: int = 64) -> Graph:
    """AlexNet [36] (single-tower variant, as in torchvision / PipeCNN).

    Five conv layers (1,2,5 followed by 3x3/2 max-pool) + three FC —
    the paper's Fig. 6 structure: 5 fused conv/pool stages + 3 FC stages.
    """
    cb = channels_base
    b = GraphBuilder("alexnet", (batch, 3, 224, 224), seed)
    b.conv(cb, 11, stride=4, pad=2).maxpool(3, 2)
    b.conv(cb * 3, 5, pad=2).maxpool(3, 2)
    b.conv(cb * 6, 3, pad=1)
    b.conv(cb * 4, 3, pad=1)
    b.conv(cb * 4, 3, pad=1).maxpool(3, 2)
    b.fc(4096).fc(4096).fc(num_classes, relu=False, softmax=True)
    return b.build()


def vgg16(batch: int = 1, num_classes: int = 1000, seed: int = 0) -> Graph:
    """VGG-16 [37]: 13 conv (5 pool stages) + 3 FC."""
    b = GraphBuilder("vgg16", (batch, 3, 224, 224), seed)
    for c, reps in ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3)):
        for _ in range(reps):
            b.conv(c, 3, pad=1)
        b.maxpool(2, 2)
    b.fc(4096).fc(4096).fc(num_classes, relu=False, softmax=True)
    return b.build()


def tiny_cnn(batch: int = 1, num_classes: int = 10, seed: int = 0,
             in_hw: int = 32) -> Graph:
    """A small CIFAR-scale CNN for fast tests/examples."""
    b = GraphBuilder("tiny_cnn", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, pad=1).maxpool(2, 2)
    b.conv(32, 3, pad=1).maxpool(2, 2)
    b.fc(64).fc(num_classes, relu=False, softmax=True)
    return b.build()


def tiny_cnn_gap(batch: int = 1, num_classes: int = 10, seed: int = 0,
                 in_hw: int = 32) -> Graph:
    """Variant with average-pool + global-average-pool head (exercises
    the standalone avg-pool pipeline stages)."""
    b = GraphBuilder("tiny_cnn_gap", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, pad=1).avgpool(2, 2)
    b.conv(32, 3, pad=1).global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def _residual_merge(b: GraphBuilder, skip: Tuple[str, Tuple[int, ...]],
                    stride: int) -> None:
    """Close a residual block whose main path ends at the current tensor:
    identity skip, or a 1x1 projection (ResNet option B) drawn after the
    main path where the stride or the width changes, then add and ReLU."""
    main = b.tap()
    c_out = main[1][1]
    if stride != 1 or skip[1][1] != c_out:
        b.from_tap(skip).conv(c_out, 1, stride=stride, relu=False)
        skip = b.tap()
    b.from_tap(main).add_from(skip, relu=True)


def _basic_block(b: GraphBuilder, c_out: int, stride: int = 1) -> None:
    """ResNet basic block: two 3x3 convs + identity/projection skip,
    post-add ReLU (the canonical v1 ordering)."""
    skip = b.tap()
    b.conv(c_out, 3, stride=stride, pad=1)
    b.conv(c_out, 3, pad=1, relu=False)
    _residual_merge(b, skip, stride)


def _bottleneck_block(b: GraphBuilder, width: int, stride: int = 1) -> None:
    """ResNet v1.5 bottleneck block: 1x1 reduce to ``width``, 3x3 at the
    block's stride (v1.5 strides here, not in the first 1x1), 1x1 expand
    to ``4 * width`` without ReLU, then the skip, add and ReLU."""
    skip = b.tap()
    b.conv(width, 1)
    b.conv(width, 3, stride=stride, pad=1)
    b.conv(4 * width, 1, relu=False)
    _residual_merge(b, skip, stride)


def resnet_tiny(batch: int = 1, num_classes: int = 10, seed: int = 0,
                in_hw: int = 32) -> Graph:
    """CIFAR-scale residual net: stem + identity block + downsample
    block (strided projection) — the smallest graph that exercises
    multi-consumer fan-out, residual merge and branch requantization."""
    b = GraphBuilder("resnet_tiny", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, pad=1)
    _basic_block(b, 16)
    _basic_block(b, 32, stride=2)
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def resnet18(batch: int = 1, num_classes: int = 1000, seed: int = 0,
             in_hw: int = 224) -> Graph:
    """ResNet-18 [He et al.]: 7x7/2 stem + padded 3x3/2 max-pool, four
    basic-block groups (64/128/256/512, two blocks each, strided
    projection at each group boundary), GAP head.  ``in_hw`` shrinks
    the input for interpret-mode tests (the GAP head absorbs any size
    the five stride-2 stages leave >= 1)."""
    b = GraphBuilder("resnet18", (batch, 3, in_hw, in_hw), seed)
    b.conv(64, 7, stride=2, pad=3).maxpool(3, 2, pad=1)
    for c_out, stride in ((64, 1), (64, 1), (128, 2), (128, 1),
                          (256, 2), (256, 1), (512, 2), (512, 1)):
        _basic_block(b, c_out, stride)
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def resnet50(batch: int = 1, num_classes: int = 1000, seed: int = 0,
             in_hw: int = 224) -> Graph:
    """ResNet-50 v1.5 [He et al., Table 1, 50-layer column; the stride
    in each downsampling block's 3x3 conv, as torchvision and MLPerf
    Inference run it]: 7x7/2 stem + padded 3x3/2 max-pool, four
    bottleneck stages (width 64/128/256/512 with 3/4/6/3 blocks, a 1x1
    projection on each stage's first block), GAP head.  53 convs, 16
    residual adds.  ``in_hw`` shrinks the input for interpret-mode
    tests."""
    b = GraphBuilder("resnet50", (batch, 3, in_hw, in_hw), seed)
    b.conv(64, 7, stride=2, pad=3).maxpool(3, 2, pad=1)
    for width, blocks, stride in ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)):
        for i in range(blocks):
            _bottleneck_block(b, width, stride if i == 0 else 1)
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def mobilenet_tiny(batch: int = 1, num_classes: int = 10, seed: int = 0,
                   in_hw: int = 32) -> Graph:
    """MobileNet-v1-style separable stack: strided stem + three
    depthwise(3x3)+pointwise(1x1) pairs — exercises the depthwise band
    kernel and the grouped feasibility rules."""
    b = GraphBuilder("mobilenet_tiny", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, stride=2, pad=1)
    for c_out, stride in ((32, 1), (64, 2), (64, 1)):
        b.dwconv(3, stride=stride, pad=1)
        b.conv(c_out, 1)
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def _inception(b: GraphBuilder, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int) -> None:
    """GoogLeNet inception module: four parallel branches — 1x1, 1x1→3x3,
    1x1→5x5, 3x3-maxpool→1x1 — channel-concatenated.  Every branch ends
    in a dense conv, so the whole 4-way merge is concat-epilogue
    eligible (each branch writes its channel slice of the shared merge
    buffer in place)."""
    split = b.tap()
    b.conv(c1, 1)
    b1 = b.tap()
    b.from_tap(split).conv(c3r, 1).conv(c3, 3, pad=1)
    b2 = b.tap()
    b.from_tap(split).conv(c5r, 1).conv(c5, 5, pad=2)
    b3 = b.tap()
    b.from_tap(split).maxpool(3, 1, pad=1).conv(cp, 1)
    b4 = b.tap()
    b.from_tap(b1).concat_from(b2, b3, b4)


def googlenet_tiny(batch: int = 1, num_classes: int = 10, seed: int = 0,
                   in_hw: int = 24) -> Graph:
    """CIFAR-scale GoogLeNet: stem + two inception modules (4-way
    channel merges; a post-merge max-pool between them that the concat
    fusion absorbs into the producers' epilogues) + GAP head — the
    inception-class stress test of the toolflow surveys, small enough
    for interpret mode."""
    b = GraphBuilder("googlenet_tiny", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, pad=1).maxpool(2, 2)
    _inception(b, 8, 8, 12, 4, 6, 6)      # merge Cout 8+12+6+6 = 32
    b.maxpool(2, 2)                        # absorbed by the concat
    _inception(b, 10, 8, 12, 4, 6, 4)     # ragged offsets 0/10/22/28
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


def _fire(b: GraphBuilder, s: int, e1: int, e3: int) -> None:
    """SqueezeNet fire module: 1x1 squeeze feeding parallel 1x1 and 3x3
    expands, channel-concatenated (both expands are dense convs, so the
    2-way merge is concat-epilogue eligible)."""
    b.conv(s, 1)
    split = b.tap()
    b.conv(e1, 1)
    left = b.tap()
    b.from_tap(split).conv(e3, 3, pad=1)
    right = b.tap()
    b.from_tap(left).concat_from(right)


def squeezenet_tiny(batch: int = 1, num_classes: int = 10, seed: int = 0,
                    in_hw: int = 24) -> Graph:
    """CIFAR-scale SqueezeNet: strided stem + three fire modules (2-way
    expand concats; a post-merge max-pool after the second that the
    concat fusion absorbs) + GAP head."""
    b = GraphBuilder("squeezenet_tiny", (batch, 3, in_hw, in_hw), seed)
    b.conv(16, 3, stride=2, pad=1)
    _fire(b, 8, 12, 12)
    _fire(b, 8, 12, 12)
    b.maxpool(2, 2)                        # absorbed by fire-2's concat
    _fire(b, 12, 20, 12)                   # ragged offsets 0/20
    b.global_avgpool()
    b.fc(num_classes, relu=False, softmax=True)
    return b.build()


# ---------------------------------------------------------------------
# Float oracle: run the graph directly with lax ops (NCHW).
# ---------------------------------------------------------------------

def run_float(graph: Graph, x: jnp.ndarray, return_env: bool = False):
    """Execute the ONNX-lite graph in float32 — the emulation-mode
    accuracy oracle against which the int8 pipeline is validated."""
    env: Dict[str, jnp.ndarray] = {graph.inputs[0].name: x}
    for k, v in graph.initializers.items():
        env[k] = jnp.asarray(v)
    for n in graph.nodes:
        if n.op_type == "Conv":
            xin, w = env[n.inputs[0]], env[n.inputs[1]]
            pads = n.attr("pads", [0, 0, 0, 0])
            out = jax.lax.conv_general_dilated(
                xin, w,
                window_strides=tuple(n.attr("strides", [1, 1])),
                padding=((pads[0], pads[2]), (pads[1], pads[3])),
                rhs_dilation=tuple(n.attr("dilations", [1, 1])),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=int(n.attr("group", 1)),
            )
            if len(n.inputs) > 2:
                out = out + env[n.inputs[2]][None, :, None, None]
            env[n.outputs[0]] = out
        elif n.op_type == "MaxPool":
            xin = env[n.inputs[0]]
            k = n.attr("kernel_shape")
            s = n.attr("strides", k)
            p = n.attr("pads", [0, 0, 0, 0])
            env[n.outputs[0]] = jax.lax.reduce_window(
                xin, -jnp.inf, jax.lax.max,
                (1, 1, k[0], k[1]), (1, 1, s[0], s[1]),
                ((0, 0), (0, 0), (p[0], p[2]), (p[1], p[3])))
        elif n.op_type in ("AveragePool", "GlobalAveragePool"):
            xin = env[n.inputs[0]]
            if n.op_type == "GlobalAveragePool":
                env[n.outputs[0]] = jnp.mean(xin, axis=(2, 3), keepdims=True)
            else:
                k = n.attr("kernel_shape")
                s = n.attr("strides", k)
                p = n.attr("pads", [0, 0, 0, 0])
                padding = ((0, 0), (0, 0), (p[0], p[2]), (p[1], p[3]))
                dims, strides = (1, 1, k[0], k[1]), (1, 1, s[0], s[1])
                summed = jax.lax.reduce_window(
                    xin, 0.0, jax.lax.add, dims, strides, padding)
                if any(p):
                    # ONNX count_include_pad=0: divide by the real
                    # window population, matching the int8 path
                    counts = jax.lax.reduce_window(
                        jnp.ones_like(xin), 0.0, jax.lax.add,
                        dims, strides, padding)
                    env[n.outputs[0]] = summed / counts
                else:
                    env[n.outputs[0]] = summed / (k[0] * k[1])
        elif n.op_type == "Relu":
            env[n.outputs[0]] = jax.nn.relu(env[n.inputs[0]])
        elif n.op_type == "Softmax":
            env[n.outputs[0]] = jax.nn.softmax(env[n.inputs[0]], axis=int(n.attr("axis", -1)))
        elif n.op_type == "Gemm":
            a, w = env[n.inputs[0]], env[n.inputs[1]]
            if int(n.attr("transA", 0)):
                a = a.T
            if int(n.attr("transB", 0)):
                w = w.T
            out = a @ w
            if len(n.inputs) > 2:
                out = out + env[n.inputs[2]]
            env[n.outputs[0]] = out
        elif n.op_type == "MatMul":
            env[n.outputs[0]] = env[n.inputs[0]] @ env[n.inputs[1]]
        elif n.op_type == "Flatten":
            xin = env[n.inputs[0]]
            axis = int(n.attr("axis", 1))
            lead = int(np.prod(xin.shape[:axis])) if axis else 1
            env[n.outputs[0]] = xin.reshape(lead, -1)
        elif n.op_type == "Reshape":
            target = n.attr("shape") or env[n.inputs[1]].tolist()
            env[n.outputs[0]] = env[n.inputs[0]].reshape([int(t) for t in target])
        elif n.op_type == "Add":
            env[n.outputs[0]] = env[n.inputs[0]] + env[n.inputs[1]]
        elif n.op_type == "Concat":
            env[n.outputs[0]] = jnp.concatenate(
                [env[i] for i in n.inputs], axis=int(n.attr("axis", 1)))
        elif n.op_type in ("Dropout", "Identity"):
            env[n.outputs[0]] = env[n.inputs[0]]
        else:
            raise NotImplementedError(n.op_type)
    if return_env:
        return env
    return env[graph.outputs[0]]


def collect_activations(graph: Graph, x: np.ndarray) -> Dict[str, np.ndarray]:
    """Run float and keep every intermediate (for PTQ calibration)."""
    env = run_float(graph, jnp.asarray(x), return_env=True)
    return {k: np.asarray(v) for k, v in env.items()}
