"""Plain reference of a sequential CNN (convs with fused ReLU and
max-pool, then FC layers, softmax head) in fixed point.

It imports nothing of the program under test and takes nothing it made.
From the seed it makes the configuration's float weights (the seeded
He-normal recipe its file states), calibrates power-of-two scales on the
calibration image by the rule its file states, quantizes, and runs the
integer network in plain ``jax.numpy``/``lax`` in NCHW: int8 operands,
int32 accumulation, round-half-up right-shift requantization, then
ReLU, saturation and the max-pool; the int8 logits are dequantized and
put through a softmax.

``bits`` is the word length of every quantized tensor: 8 is the
configuration, 4 the control (the same rule computed one precision
below, which the comparison has to refuse).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import layer_shapes

#: images per block of the reference's integer pass
BLOCK = 16
_INT32_LO, _INT32_HI = -2.0 ** 31, 2.0 ** 31 - 128  # float32-exact int32 bounds


def float_weights(cfg: Dict, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The configuration's seeded He-normal weights: per layer in order,
    ``standard_normal(shape) * sqrt(2 / fan_in)`` then the bias
    ``standard_normal(out) * 0.01``, both float32 (conv OIHW, FC
    (in, out))."""
    rng = np.random.default_rng(seed)
    params = []
    for st in layer_shapes(cfg):
        if st["kind"] == "conv":
            cin, k = st["in_chw"][0], st["kernel"]
            shape, fan_in = (st["out"], cin, k, k), cin * k * k
        else:
            fan_in = st["in_features"]
            shape = (fan_in, st["out"])
        w = (rng.standard_normal(shape) * np.sqrt(2.0 / fan_in)).astype(np.float32)
        b = (rng.standard_normal(st["out"]) * 0.01).astype(np.float32)
        params.append((w, b))
    return params


def pow2_exponent(amax: float, bits: int) -> int:
    """Largest m with ``amax * 2^m`` inside the signed ``bits`` range,
    clamped to [-(bits - 1), 24]; an all-zero tensor gets bits - 1."""
    if amax == 0.0:
        return bits - 1
    m = int(np.floor(np.log2((2 ** (bits - 1) - 1) / amax)))
    return max(-(bits - 1), min(m, 24))


def _conv(x, w, st, **kw):
    p = st["pad"]
    return jax.lax.conv_general_dilated(
        x, w, (st["stride"],) * 2, ((p, p), (p, p)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), **kw)


def _maxpool(x, st, init):
    pk, ps = st["pool"]
    return jax.lax.reduce_window(x, init, jax.lax.max, (1, 1, pk, pk),
                                 (1, 1, ps, ps), "VALID")


def _float_amax(shapes: List[Dict]) -> Callable:
    """Jitted float pass: max |x| of every stage output (the logits for
    a softmax head), at the highest matmul precision."""

    def run(params, x):
        amax = []
        h = x
        for st, (w, b) in zip(shapes, params):
            if st["kind"] == "conv":
                h = _conv(h, w, st, precision=jax.lax.Precision.HIGHEST)
                h = h + b[None, :, None, None]
            else:
                h = h.reshape(h.shape[0], -1)
                h = jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST) + b
            if st["relu"]:
                h = jnp.maximum(h, 0.0)
            if st.get("pool"):
                h = _maxpool(h, st, -jnp.inf)
            amax.append(jnp.max(jnp.abs(h)))
        return jnp.stack(amax)

    return jax.jit(run)


def _requant(acc, shift, lo: int, hi: int, relu: bool):
    half = jnp.where(shift > 0, jnp.left_shift(1, jnp.maximum(shift - 1, 0)), 0)
    acc = jnp.right_shift(acc + half, shift)  # arithmetic on int32
    if relu:
        acc = jnp.maximum(acc, 0)
    return jnp.clip(acc, lo, hi).astype(jnp.int8)


def _int_forward(shapes: List[Dict], bits: int) -> Callable:
    """Jitted integer pass: float NCHW images -> softmax probabilities.
    Scales and shifts are arguments, so one program serves every seed."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1

    def run(qparams, shifts, scale_in, scale_out, x):
        h = jnp.clip(jnp.round(x * scale_in), lo, hi).astype(jnp.int8)
        for i, (st, (wq, bq)) in enumerate(zip(shapes, qparams)):
            if st["kind"] == "conv":
                acc = _conv(h, wq, st, preferred_element_type=jnp.int32)
                acc = acc + bq[None, :, None, None]
            else:
                h = h.reshape(h.shape[0], -1)
                acc = jnp.dot(h, wq, preferred_element_type=jnp.int32) + bq
            h = _requant(acc, shifts[i], lo, hi, st["relu"])
            if st.get("pool"):
                h = _maxpool(h, st, jnp.int8(-128))
        logits = h.astype(jnp.float32) * scale_out
        return jax.nn.softmax(logits, axis=-1)

    return jax.jit(run)


def _quantize(x: np.ndarray, m: int, lo: float, hi: float, dtype) -> np.ndarray:
    """Round-to-nearest-even at scale 2^m (exact in float32: a power of
    two), saturated to [lo, hi]."""
    return np.clip(np.rint(x * np.float32(2.0 ** m)), lo, hi).astype(dtype)


class Reference:
    """The plain fixed-point network of one configuration and seed,
    calibrated on ``x_cal`` (one NCHW float32 image).  Weights are made
    and quantized on the host and the float pass runs on the host's CPU
    device; the integer pass runs on the default device."""

    def __init__(self, cfg: Dict, seed: int, x_cal: np.ndarray, bits: int = 8):
        self.shapes = layer_shapes(cfg)
        lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
        params = float_weights(cfg, seed)
        with jax.default_device(jax.devices("cpu")[0]):
            amax = np.asarray(_float_amax(self.shapes)(params, x_cal))
        m = m_in = pow2_exponent(float(np.max(np.abs(x_cal))), bits)
        qparams, shifts = [], []
        for (w, b), a in zip(params, amax):
            m_w = pow2_exponent(float(np.max(np.abs(w))), bits)
            m_y = min(pow2_exponent(float(a), bits), m_w + m)
            qparams.append((jnp.asarray(_quantize(w, m_w, lo, hi, np.int8)),
                            jnp.asarray(_quantize(b, m_w + m, _INT32_LO, _INT32_HI, np.int32))))
            shifts.append(m_w + m - m_y)
            m = m_y
        self.m_in, self.m_out = m_in, m
        self.qparams = qparams
        self.shifts = jnp.asarray(shifts, jnp.int32)
        self._fwd = _int_forward(self.shapes, bits)

    def __call__(self, x) -> jax.Array:
        """Probabilities for a batch of NCHW float32 images."""
        return self._fwd(self.qparams, self.shifts, jnp.float32(2.0 ** self.m_in),
                         jnp.float32(2.0 ** -self.m_out), x)

    def probabilities(self, images: np.ndarray) -> np.ndarray:
        """``__call__`` over ``images`` (N, C, H, W) in blocks of
        :data:`BLOCK`, on the host."""
        n = images.shape[0]
        out = []
        for i in range(0, n, BLOCK):
            blk = images[i:i + BLOCK]
            pad = BLOCK - blk.shape[0]
            if pad:
                blk = np.concatenate([blk, np.zeros((pad,) + blk.shape[1:], blk.dtype)])
            out.append(np.asarray(self(jnp.asarray(blk)))[:BLOCK - pad])
        return np.concatenate(out)


def build(cfg: Dict, seed: int, x_cal: np.ndarray, bits: int = 8) -> Reference:
    return Reference(cfg, seed, x_cal, bits)
