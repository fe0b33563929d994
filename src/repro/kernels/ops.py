"""Public jit'd wrappers for the Pallas kernels.

Two families of entry points (see DESIGN.md §3):

  * ``*_nhwc`` — TPU-native layouts (NHWC activations, HWIO weights).
    These are what the whole-network fused executor calls: activations
    stay NHWC int8 from network ingress to egress, so no per-layer
    transposes ever reach XLA.
  * ``*_nchw`` — ONNX-layout compatibility wrappers (NCHW / OIHW) that
    transpose around the NHWC paths.  Kept for direct callers and
    layout-parity tests; the executor does not use them.

The wrappers also handle zero-padding for convolution pads (zero ==
symmetric quantization zero-point; max-pool pads with INT8_MIN) and the
interpret-mode switch: on this CPU container every kernel runs with
``interpret=True`` (Python-evaluated, bit-exact semantics); on a real
TPU the same calls lower to Mosaic.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import qconv as _qconv
from . import qgemm as _qgemm
from . import flash_attention as _flash
from . import ssd_scan as _ssd
from . import ref as ref  # re-export oracles for callers/tests


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def qgemm(x, w, b=None, *, shift, relu: bool = False,
          block_m: Optional[int] = None, block_n: Optional[int] = None,
          block_k: Optional[int] = None, interpret: Optional[bool] = None):
    """``shift`` is an int (per-tensor) or a length-N tuple (per-output-
    channel weight scales — the per-lane shift vector path); a block left
    at None is chosen from the shape (``qgemm.fc_tiles``)."""
    interpret = default_interpret() if interpret is None else interpret
    return _qgemm.qgemm(x, w, b, shift=shift, relu=relu, block_m=block_m,
                        block_n=block_n, block_k=block_k, interpret=interpret)


# ------------------------------------------------------ NHWC-native paths

def qconv2d_nhwc(
    x: jnp.ndarray,  # (N, H, W, Cin) int8, unpadded
    w: jnp.ndarray,  # (KH, KW, Cin/groups, Cout) int8 (HWIO)
    b: Optional[jnp.ndarray],
    *,
    strides: Tuple[int, int] = (1, 1),
    pads: Tuple[int, int, int, int] = (0, 0, 0, 0),
    shift=0,
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,
    groups: int = 1,
    block_cout: int = 128,
    block_h: Optional[int] = None,
    block_cin: Optional[int] = None,
    skip: Optional[jnp.ndarray] = None,
    skip_shifts: Tuple[int, int] = (0, 0),
    merge_shift: int = 0,
    merge_relu: bool = False,
    out_buf: Optional[jnp.ndarray] = None,
    out_off: int = 0,
    concat_shift: int = 0,
    concat_relu: bool = False,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """TPU-layout entry point for the fused conv+ReLU+pool row-band
    kernels.  Returns NHWC int8 (post-pool when ``pool`` is given).

    Dispatch on ``groups`` (ONNX Conv semantics):
      * 1 — dense row-band MXU kernel (:func:`qconv.qconv2d`);
      * Cin with integer channel multiplier (Cout = m·Cin, 1×1 filter
        slice) — depthwise row-band VPU kernel (:func:`qconv.qdwconv2d`);
      * anything else (ragged groups) — the grouped row-band kernel
        (:func:`qconv.qgconv2d`), one group per grid step.

    ``shift`` is an int (per-tensor requant) or a length-Cout tuple
    (per-output-channel weight scales: the band epilogue applies a
    per-lane shift vector — every dispatch target supports it).
    ``block_cin`` tiles the dense kernel's Cin contraction (the DSE's
    ``N_i`` axis); ``skip`` fuses a residual add into the epilogue and
    ``out_buf``/``out_off``/``concat_shift``/``concat_relu`` write the
    result into a channel slice of a shared concat merge buffer (dense
    and depthwise kernels — the parser never folds merges onto ragged
    grouped producers)."""
    interpret = default_interpret() if interpret is None else interpret
    cin = x.shape[-1]
    cout = w.shape[-1]
    if any(pads):
        x = jnp.pad(x, ((0, 0), (pads[0], pads[2]), (pads[1], pads[3]),
                        (0, 0)))
    if groups == 1:
        return _qconv.qconv2d(x, w, b, strides=strides, shift=shift,
                              relu=relu, pool=pool, block_cout=block_cout,
                              block_h=block_h, block_cin=block_cin,
                              skip=skip, skip_shifts=skip_shifts,
                              merge_shift=merge_shift, merge_relu=merge_relu,
                              out_buf=out_buf, out_off=out_off,
                              concat_shift=concat_shift,
                              concat_relu=concat_relu,
                              interpret=interpret)
    if groups == cin and cout % cin == 0 and w.shape[2] == 1:
        return _qconv.qdwconv2d(x, w.reshape(w.shape[0], w.shape[1], cout),
                                b, strides=strides, shift=shift, relu=relu,
                                pool=pool, block_c=block_cout,
                                block_h=block_h,
                                skip=skip, skip_shifts=skip_shifts,
                                merge_shift=merge_shift,
                                merge_relu=merge_relu,
                                out_buf=out_buf, out_off=out_off,
                                concat_shift=concat_shift,
                                concat_relu=concat_relu,
                                interpret=interpret)
    # ragged grouped conv: banded Pallas path, group on its own grid axis
    assert skip is None and out_buf is None, \
        "merge fusion requires the dense or depthwise band kernel"
    return _qconv.qgconv2d(x, w, b, groups=groups, strides=strides,
                           shift=shift, relu=relu, pool=pool,
                           block_h=block_h, interpret=interpret)


def qadd_nhwc(xs, align_shifts, *, shift: int = 0,
              relu: bool = False) -> jnp.ndarray:
    """Residual-merge stage: align int8 operands to a common fixed-point
    position, add in int32, requantize back to int8.  Elementwise VPU
    work with no reduction — XLA fuses it into the surrounding int8
    dataflow, so a dedicated Pallas kernel would buy nothing."""
    return ref.qadd_ref(xs, align_shifts, shift, relu)


def qconcat_nhwc(xs, align_shifts, *, axis: int = -1,
                 relu: bool = False) -> jnp.ndarray:
    """Channel-merge stage: align each int8 operand to the common scale,
    then concatenate (values are unchanged by concat, so there is no
    output requant beyond the per-operand alignment).  ``relu`` applies
    a fused post-merge ReLU (relu∘concat == concat∘relu per operand).
    Delegates to :func:`ref.qconcat_ref` — ONE definition of the merge
    semantics, shared with the producer-epilogue concat fusion."""
    return ref.qconcat_ref(xs, align_shifts, axis=axis, relu=relu)


def maxpool2d_nhwc(x: jnp.ndarray, window: int, stride: int,
                   pads: Tuple[int, int, int, int] = (0, 0, 0, 0)
                   ) -> jnp.ndarray:
    """Standalone int8-native NHWC max-pool (pools not fused behind a
    conv).  Stays in the executor's no-transpose NHWC dataflow; the
    reduction runs directly on int8 (identity = INT8_MIN)."""
    return jax.lax.reduce_window(
        x, jnp.int8(ref.INT8_MIN), jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1),
        ((0, 0), (pads[0], pads[2]), (pads[1], pads[3]), (0, 0)))


def avgpool2d_nhwc(x: jnp.ndarray, window: int, stride: int,
                   pads: Tuple[int, int, int, int] = (0, 0, 0, 0)
                   ) -> jnp.ndarray:
    """Standalone int8-native NHWC average-pool (AveragePool /
    GlobalAveragePool): int32 window sum, round-half-up divide — the
    fixed-point scale is unchanged, so the result feeds the next int8
    stage directly.

    Padded windows divide by the **real** window population (the ONNX
    ``count_include_pad=0`` default), not by ``window*window`` — a
    border window that covers only 4 of 9 taps averages those 4, so pad
    pixels never drag the mean toward zero."""
    return ref.avgpool2d_ref(x, window, stride, pads)


# -------------------------------------- ONNX-layout (NCHW) compatibility

def qconv2d_nchw(
    x: jnp.ndarray,  # (N, Cin, H, W) int8
    w: jnp.ndarray,  # (Cout, Cin, KH, KW) int8 (OIHW, ONNX layout)
    b: Optional[jnp.ndarray],
    *,
    strides: Tuple[int, int] = (1, 1),
    pads: Tuple[int, int, int, int] = (0, 0, 0, 0),
    shift: int = 0,
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,
    block_cout: int = 128,
    block_h: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """ONNX-layout wrapper around :func:`qconv2d_nhwc`.  Returns NCHW
    int8 (post-pool when ``pool`` is given)."""
    xh = jnp.transpose(x, (0, 2, 3, 1))          # NHWC
    wh = jnp.transpose(w, (2, 3, 1, 0))          # HWIO
    y = qconv2d_nhwc(xh, wh, b, strides=strides, pads=pads, shift=shift,
                     relu=relu, pool=pool, block_cout=block_cout,
                     block_h=block_h, interpret=interpret)
    return jnp.transpose(y, (0, 3, 1, 2))


def maxpool2d_nchw(x: jnp.ndarray, window: int, stride: int,
                   pads: Tuple[int, int, int, int] = (0, 0, 0, 0)) -> jnp.ndarray:
    """ONNX-layout wrapper around :func:`maxpool2d_nhwc`."""
    xh = jnp.transpose(x, (0, 2, 3, 1))
    return jnp.transpose(maxpool2d_nhwc(xh, window, stride, pads),
                         (0, 3, 1, 2))


def avgpool2d_nchw(x: jnp.ndarray, window: int, stride: int,
                   pads: Tuple[int, int, int, int] = (0, 0, 0, 0)) -> jnp.ndarray:
    """ONNX-layout wrapper around :func:`avgpool2d_nhwc`."""
    xh = jnp.transpose(x, (0, 2, 3, 1))
    return jnp.transpose(avgpool2d_nhwc(xh, window, stride, pads),
                         (0, 3, 1, 2))


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    interpret = default_interpret() if interpret is None else interpret
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


def ssd_scan(x, dt, a, b, c, d=None, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    interpret = default_interpret() if interpret is None else interpret
    return _ssd.ssd_scan(x, dt, a, b, c, d, chunk=chunk, interpret=interpret)
