"""Plain reference of a CNN whose layers form a graph (convs with fused
ReLU and max-pool, standalone max-pools, residual adds, a global
average pool, FC layers, softmax head) in fixed point.

It reads the schema of ``bench/counts.py`` and keeps the contract of
``sequential_cnn``: ``build(cfg, seed, x_cal, bits)`` gives an object
whose ``probabilities(images)`` runs in blocks.  It imports nothing of
the program under test and takes nothing it made.  From the seed it
makes the float weights by the seeded He-normal recipe, weight then
bias, per conv and FC in the order of the configuration's ``layers``,
so a configuration lists its layers in the order its builder draws
them.

Scales are powers of two, calibrated on the calibration image by the
rule the configuration's ``quantization`` states:

  1. each tensor's desired exponent is the largest m with
     max|x| * 2^m inside the signed range (``pow2_exponent``);
  2. the two operands of an ``add`` form a scale group pinned at the
     group's least desired exponent, repeated until nothing changes
     (stacked residuals chain their groups);
  3. a forward walk caps a conv's or FC's output exponent at
     m_w + m_x; ``maxpool`` and ``gap`` keep their input's exponent;
     an ``add`` works at m_common, the least of its operands'
     exponents, and its output exponent is at most m_common.

The integer network then runs in plain ``jax.numpy``/``lax`` in NCHW:
int8 operands, int32 accumulation, round-half-up right-shift
requantization, ReLU and saturation.  An ``add`` moves each operand to
m_common by a round-half-up right shift in int32, adds, and
requantizes with its optional ReLU; ``maxpool`` pads with -128; ``gap``
is an int32 sum, a round-half-up divide by H * W, then saturation.  The
last layer's int8 output is dequantized and put through a softmax.

``bits`` is the word length of every quantized tensor: 8 is the
configuration, 4 the control.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import WEIGHTED, layer_shapes
from bench.reference import sequential_cnn
from bench.reference.sequential_cnn import (_INT32_HI, _INT32_LO, _conv, _maxpool, _quantize,
                                            _requant, pow2_exponent)


def float_weights(shapes: List[Dict], seed: int) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """The seeded He-normal weights of every conv and FC, by layer name:
    ``standard_normal(shape) * sqrt(2 / fan_in)`` then the bias
    ``standard_normal(out) * 0.01``, both float32 (conv OIHW, FC
    (in, out)), drawn in layer order from one generator."""
    rng = np.random.default_rng(seed)
    params = {}
    for st in shapes:
        if st["kind"] == "conv":
            cin, k = st["in_chw"][0], st["kernel"]
            shape, fan_in = (st["out"], cin, k, k), cin * k * k
        elif st["kind"] == "fc":
            fan_in = st["in_features"]
            shape = (fan_in, st["out"])
        else:
            continue
        w = (rng.standard_normal(shape) * np.sqrt(2.0 / fan_in)).astype(np.float32)
        b = (rng.standard_normal(st["out"]) * 0.01).astype(np.float32)
        params[st["name"]] = (w, b)
    return params


def _standalone_maxpool(x, st, init):
    k, s, p = st["kernel"], st["stride"], st["pad"]
    return jax.lax.reduce_window(x, init, jax.lax.max, (1, 1, k, k), (1, 1, s, s),
                                 ((0, 0), (0, 0), (p, p), (p, p)))


def _align(v, shift):
    """Round-half-up arithmetic right shift of int32 ``v``, unclipped."""
    half = jnp.where(shift > 0, jnp.left_shift(1, jnp.maximum(shift - 1, 0)), 0)
    return jnp.right_shift(v + half, shift)


def _float_amax(shapes: List[Dict]) -> Callable:
    """Jitted float pass: max |x| of every layer's output in layer order
    (the logits for a softmax head), at the highest matmul precision."""

    def run(params, x):
        env, amax = {"input": x}, []
        for st in shapes:
            h = env[st["inputs"][0]]
            kind = st["kind"]
            if kind == "conv":
                w, b = params[st["name"]]
                h = _conv(h, w, st, precision=jax.lax.Precision.HIGHEST)
                h = h + b[None, :, None, None]
            elif kind == "fc":
                w, b = params[st["name"]]
                h = h.reshape(h.shape[0], -1)
                h = jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST) + b
            elif kind == "maxpool":
                h = _standalone_maxpool(h, st, -jnp.inf)
            elif kind == "add":
                h = h + env[st["inputs"][1]]
            else:  # gap
                h = jnp.mean(h, axis=(2, 3), keepdims=True)
            if st.get("relu"):
                h = jnp.maximum(h, 0.0)
            if st.get("pool"):
                h = _maxpool(h, st, -jnp.inf)
            env[st["name"]] = h
            amax.append(jnp.max(jnp.abs(h)))
        return jnp.stack(amax)

    return jax.jit(run)


def _int_forward(shapes: List[Dict], bits: int) -> Callable:
    """Jitted integer pass: float NCHW images -> softmax probabilities.
    Scales and shifts are arguments, so one program serves every seed."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1

    def run(qparams, shifts, scale_in, scale_out, x):
        env = {"input": jnp.clip(jnp.round(x * scale_in), lo, hi).astype(jnp.int8)}
        for st in shapes:
            name, kind = st["name"], st["kind"]
            h = env[st["inputs"][0]]
            if kind == "conv":
                wq, bq = qparams[name]
                acc = _conv(h, wq, st, preferred_element_type=jnp.int32)
                h = _requant(acc + bq[None, :, None, None], shifts[name][0], lo, hi, st["relu"])
                if st.get("pool"):
                    h = _maxpool(h, st, jnp.int8(-128))
            elif kind == "fc":
                wq, bq = qparams[name]
                h = h.reshape(h.shape[0], -1)
                acc = jnp.dot(h, wq, preferred_element_type=jnp.int32) + bq
                h = _requant(acc, shifts[name][0], lo, hi, st["relu"])
            elif kind == "maxpool":
                h = _standalone_maxpool(h, st, jnp.int8(-128))
            elif kind == "add":
                s = shifts[name]
                acc = sum(_align(env[t].astype(jnp.int32), s[k])
                          for k, t in enumerate(st["inputs"]))
                h = _requant(acc, s[2], lo, hi, st.get("relu", False))
            else:  # gap
                n = h.shape[2] * h.shape[3]
                total = jnp.sum(h.astype(jnp.int32), axis=(2, 3), keepdims=True)
                h = jnp.clip(jnp.floor_divide(total + n // 2, n), lo, hi).astype(jnp.int8)
            env[name] = h
        logits = env[shapes[-1]["name"]].astype(jnp.float32) * scale_out
        return jax.nn.softmax(logits.reshape(logits.shape[0], -1), axis=-1)

    return jax.jit(run)


def exponents(shapes: List[Dict], params: Dict, amax: np.ndarray, m_in: int,
              bits: int) -> Tuple[Dict[str, Dict[str, int]], Dict[str, int]]:
    """By the rule in the module's docstring: per conv, FC and add its
    ``m_w``, ``m_x`` (an add's m_common) and ``m_y``; and every tensor's
    exponent, by layer name and ``"input"``."""
    desired = {"input": m_in}
    desired.update((st["name"], pow2_exponent(float(a), bits)) for st, a in zip(shapes, amax))
    groups = [st["inputs"] for st in shapes if st["kind"] == "add"]
    changed = True
    while changed:
        changed = False
        for g in groups:
            m = min(desired[t] for t in g)
            for t in g:
                changed |= desired[t] != m
                desired[t] = m
    m = {"input": m_in}
    out = {}
    for st in shapes:
        name, kind = st["name"], st["kind"]
        if kind in WEIGHTED:
            m_w = pow2_exponent(float(np.max(np.abs(params[name][0]))), bits)
            m_x = m[st["inputs"][0]]
            out[name] = {"m_w": m_w, "m_x": m_x, "m_y": min(desired[name], m_w + m_x)}
        elif kind == "add":
            m_common = min(m[t] for t in st["inputs"])
            out[name] = {"m_w": 0, "m_x": m_common, "m_y": min(desired[name], m_common)}
        m[name] = out[name]["m_y"] if name in out else m[st["inputs"][0]]
    return out, m


class Reference(sequential_cnn.Reference):
    """The plain fixed-point network of one graph configuration and
    seed, calibrated on ``x_cal`` (one NCHW float32 image).  Weights are
    made and quantized on the host and the float pass runs on the host's
    CPU device; the integer pass runs on the default device."""

    def __init__(self, cfg: Dict, seed: int, x_cal: np.ndarray, bits: int = 8):
        self.shapes = shapes = layer_shapes(cfg)
        lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
        params = float_weights(shapes, seed)
        with jax.default_device(jax.devices("cpu")[0]):
            amax = np.asarray(_float_amax(shapes)(params, x_cal))
        self.m_in = pow2_exponent(float(np.max(np.abs(x_cal))), bits)
        self.exponents, m = exponents(shapes, params, amax, self.m_in, bits)
        self.qparams, shifts = {}, {}
        for st in shapes:
            name = st["name"]
            if name not in self.exponents:
                continue
            e = self.exponents[name]
            if st["kind"] == "add":
                shifts[name] = [m[t] - e["m_x"] for t in st["inputs"]] + [e["m_x"] - e["m_y"]]
            else:
                w, b = params[name]
                m_acc = e["m_w"] + e["m_x"]
                self.qparams[name] = (
                    jnp.asarray(_quantize(w, e["m_w"], lo, hi, np.int8)),
                    jnp.asarray(_quantize(b, m_acc, _INT32_LO, _INT32_HI, np.int32)))
                shifts[name] = [m_acc - e["m_y"]]
        self.shifts = {k: jnp.asarray(v, jnp.int32) for k, v in shifts.items()}
        self.m_out = m[shapes[-1]["name"]]
        self._fwd = _int_forward(shapes, bits)


def build(cfg: Dict, seed: int, x_cal: np.ndarray, bits: int = 8) -> Reference:
    return Reference(cfg, seed, x_cal, bits)
