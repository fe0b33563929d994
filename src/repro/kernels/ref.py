"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *exact* semantics the kernels must reproduce
(``tests/test_kernels_*.py`` sweep shapes/dtypes and assert_allclose
against these).  All integer arithmetic follows the paper's fixed-point
rules: int8 operands, int32 accumulation, round-half-up arithmetic
right-shift requantization (shift = m_w + m_x - m_y), fused ReLU.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

INT8_MIN, INT8_MAX = -128, 127


def _is_scalar_shift(shift) -> bool:
    return isinstance(shift, int) or (
        hasattr(shift, "ndim") and getattr(shift, "ndim", 1) == 0)


def round_shift(v: jnp.ndarray, shift) -> jnp.ndarray:
    """Round-half-up arithmetic right shift (no clip/relu).  ``shift``
    is a Python int (per-tensor) or an int32 vector broadcast against
    the **last axis** of ``v`` (per-output-channel lanes) — the shared
    requant primitive of every oracle and both epilogue modes."""
    if _is_scalar_shift(shift):
        if shift > 0:
            v = jax.lax.shift_right_arithmetic(
                v + (1 << (shift - 1)), shift)
        return v
    s = jnp.asarray(shift, jnp.int32)
    half = jnp.where(s > 0, jnp.left_shift(1, jnp.maximum(s - 1, 0)), 0)
    # jnp.right_shift broadcasts and is arithmetic for signed ints
    return jnp.right_shift(v + half, s)


def requant(acc: jnp.ndarray, shift, relu: bool) -> jnp.ndarray:
    """int32 accumulator -> int8: round-half-up shift, relu, clip.
    ``shift`` may be a per-lane int32 vector (per-channel quantization);
    lanes ride the last axis of ``acc``."""
    acc = round_shift(acc, shift)
    if relu:
        acc = jnp.maximum(acc, 0)
    return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


def align_shift(v: jnp.ndarray, shift: int) -> jnp.ndarray:
    """Round-half-up arithmetic right shift (no clip) — the operand
    alignment step of a residual merge: an int8 operand at fixed-point
    position m is moved to position m - shift."""
    if shift > 0:
        v = jax.lax.shift_right_arithmetic(v + (1 << (shift - 1)), shift)
    return v


def qgemm_ref(
    x: jnp.ndarray,  # (M, K) int8
    w: jnp.ndarray,  # (K, N) int8
    b: Optional[jnp.ndarray],  # (N,) int32
    shift: int,
    relu: bool = False,
) -> jnp.ndarray:
    acc = jnp.dot(x, w, preferred_element_type=jnp.int32)
    if b is not None:
        acc = acc + b.astype(jnp.int32)[None, :]
    return requant(acc, shift, relu)


def qconv2d_ref(
    x: jnp.ndarray,  # (N, H, W, Cin) int8, already zero-padded
    w: jnp.ndarray,  # (KH, KW, Cin/groups, Cout) int8
    b: Optional[jnp.ndarray],  # (Cout,) int32
    strides: Tuple[int, int],
    shift: int,
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,  # (window, stride)
    groups: int = 1,
) -> jnp.ndarray:
    """Fused conv+ReLU+maxpool, NHWC/HWIO, VALID padding (pad upstream).
    ``groups`` follows ONNX Conv semantics (groups == Cin == Cout is
    depthwise); the int32 accumulator is exact, so this is the
    bit-for-bit oracle for both band kernels and the grouped fallback.
    The int8 operands go in as they are, accumulating in int32: a TPU
    runs that on its integer matrix unit, where an int32-operand conv
    is slow or refused."""
    acc = jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32,
    )
    if b is not None:
        acc = acc + b.astype(jnp.int32)[None, None, None, :]
    y = requant(acc, shift, relu)
    if pool is not None:
        pw, ps = pool
        y = jax.lax.reduce_window(
            y, jnp.int8(INT8_MIN), jax.lax.max,
            (1, pw, pw, 1), (1, ps, ps, 1), "VALID")
    return y


def qadd_ref(
    xs,                      # sequence of int8 operands, same shape
    align_shifts,            # per-operand right shifts to a common scale
    shift: int,              # requant shift from the common scale to m_y
    relu: bool = False,
) -> jnp.ndarray:
    """Residual-merge oracle: align each int8 operand to the common
    fixed-point position (round-half-up right shift in int32), add, then
    requantize to the output scale.  With all shifts zero this is a pure
    saturating int8 add."""
    acc = None
    for x, s in zip(xs, align_shifts):
        v = align_shift(x.astype(jnp.int32), s)
        acc = v if acc is None else acc + v
    return requant(acc, shift, relu)


def qconcat_ref(
    xs,                      # sequence of int8 operands
    align_shifts,            # per-operand right shifts to the common scale
    axis: int = -1,
    relu: bool = False,
) -> jnp.ndarray:
    """Channel-merge oracle: align each int8 operand to the common
    fixed-point position (round-half-up right shift in int32, clipped
    back to int8 — a zero shift is the identity), concatenate, then
    apply the optional fused post-merge ReLU.  Concatenation itself
    never changes values, so this per-operand alignment is the *entire*
    fixed-point semantics of a ``Concat`` stage — and therefore exactly
    what a producer conv's concat epilogue must apply before writing
    its channel slice of the shared merge buffer."""
    aligned = [
        jnp.clip(align_shift(x.astype(jnp.int32), s),
                 INT8_MIN, INT8_MAX).astype(jnp.int8)
        if s else x
        for x, s in zip(xs, align_shifts)
    ]
    y = jnp.concatenate(aligned, axis=axis)
    if relu:
        y = jnp.maximum(y, 0)
    return y


def maxpool2d_ref(x: jnp.ndarray, window: int, stride: int) -> jnp.ndarray:
    """Standalone int8 NHWC max-pool."""
    return jax.lax.reduce_window(
        x, jnp.int8(INT8_MIN), jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")


def avgpool2d_ref(x: jnp.ndarray, window: int, stride: int,
                  pads: Tuple[int, int, int, int] = (0, 0, 0, 0)
                  ) -> jnp.ndarray:
    """Standalone int8 NHWC average-pool: int32 sum, round-half-up
    divide (fixed-point semantics — the scale is unchanged).  Padded
    windows divide by the real window population (the ONNX
    ``count_include_pad=0`` default): the per-window divisor is the
    number of non-pad taps, computed by pooling an all-ones plane with
    zero padding."""
    padding = ((0, 0), (pads[0], pads[2]), (pads[1], pads[3]), (0, 0))
    dims, strides = (1, window, window, 1), (1, stride, stride, 1)
    summed = jax.lax.reduce_window(
        x.astype(jnp.int32), jnp.int32(0), jax.lax.add,
        dims, strides, padding)
    if any(pads):
        counts = jax.lax.reduce_window(
            jnp.ones(x.shape[1:3], jnp.int32)[None, :, :, None],
            jnp.int32(0), jax.lax.add, dims, strides, padding)
        q = jnp.floor_divide(summed + counts // 2, counts)
    else:
        count = window * window
        q = jnp.floor_divide(summed + count // 2, count)
    return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8)


def attention_ref(
    q: jnp.ndarray,  # (B, H, Sq, D)
    k: jnp.ndarray,  # (B, HKV, Skv, D)
    v: jnp.ndarray,  # (B, HKV, Skv, D)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Grouped-query attention oracle.  ``q_offset`` is the absolute
    position of q[0] (for decode/prefill continuation).  ``window`` is a
    sliding-attention span: key j visible to query i iff i-window < j <= i.
    """
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


def ssd_ref(
    x: jnp.ndarray,   # (B, L, H, P)
    dt: jnp.ndarray,  # (B, L, H)  -- positive (post-softplus)
    a: jnp.ndarray,   # (H,)       -- negative
    b: jnp.ndarray,   # (B, L, G, N)
    c: jnp.ndarray,   # (B, L, G, N)
    d: Optional[jnp.ndarray] = None,  # (H,) skip connection
    init_state: Optional[jnp.ndarray] = None,  # (B, H, P, N)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential state-space-duality oracle (Mamba-2 §SSD):
        S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T ;  y_t = S_t C_t + D x_t
    Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    B_, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    g = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = jnp.repeat(b.astype(jnp.float32), g, axis=2)  # (B,L,H,N)
    cf = jnp.repeat(c.astype(jnp.float32), g, axis=2)

    def step(s, t):
        decay = jnp.exp(dtf[:, t] * a[None, :])  # (B,H)
        contrib = jnp.einsum("bh,bhp,bhn->bhpn", dtf[:, t], xf[:, t], bf[:, t])
        s = decay[..., None, None] * s + contrib
        y = jnp.einsum("bhpn,bhn->bhp", s, cf[:, t])
        return s, y

    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((B_, H, P, N), jnp.float32))
    s_fin, ys = jax.lax.scan(step, s0, jnp.arange(L))
    y = jnp.moveaxis(ys, 0, 1)  # (B,L,H,P)
    if d is not None:
        y = y + d[None, None, :, None] * xf
    return y.astype(x.dtype), s_fin
