"""int8 GEMM + bias + requantize Pallas kernel (the paper's fused
conv/fully-connected matrix unit, §3.2.3: "convolution kernel and the
fully connected kernel can be fused together as a single 3-D
matrix-matrix multiplication unit").

TPU mapping: int8 operands feed the MXU with int32 accumulation; block
shapes default to (128, 128, 128) tiles — multiples of the (32, 128)
int8 native tile — and the DSE's ``N_i``/``N_l`` map to the contraction
and output tile widths.  ``shift`` may be a length-N tuple (per-output-
channel quantized FC layers): the counts are staged as a ``(1, N)``
int32 operand sharing the bias row's BlockSpec and the epilogue
applies a per-lane round-half-up shift vector; a scalar ``shift``
compiles the exact per-tensor kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

INT8_MIN, INT8_MAX = -128, 127

#: Round-half-up shift (scalar or per-lane row) + relu + int8 clip —
#: the oracle's own implementation (ref.py imports only jax/jnp, so no
#: cycle): the kernel epilogue cannot drift from what tests pin.
_requant = ref.requant


def _qgemm_kernel(x_ref, w_ref, b_ref, *rest, k_steps: int,
                  has_shift_vec: bool, shift: int, relu: bool):
    rest = list(rest)
    s_ref = rest.pop(0) if has_shift_vec else None
    o_ref, acc_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        acc = acc_ref[...] + b_ref[...].astype(jnp.int32)
        s = s_ref[...] if s_ref is not None else shift
        o_ref[...] = _requant(acc, s, relu)


@functools.partial(
    jax.jit,
    static_argnames=("shift", "relu", "block_m", "block_n", "block_k", "interpret"),
)
def qgemm(
    x: jnp.ndarray,  # (M, K) int8
    w: jnp.ndarray,  # (K, N) int8
    b: Optional[jnp.ndarray],  # (N,) int32 or None
    *,
    shift,           # int | length-N tuple (per-channel shift vector)
    relu: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked int8 GEMM; shapes need not divide blocks (zero padding is
    applied and sliced off — zero is the symmetric quantization zero)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if b is None:
        b = jnp.zeros((n,), jnp.int32)
    per_channel = isinstance(shift, tuple)
    if per_channel:
        assert len(shift) == n, (len(shift), n)
    # N and K ride the 128-wide lane axis of the int8 tiles: whole lane
    # tiles only (the exact integer contraction is tile-independent)
    bm = min(block_m, _rup(m, 8))
    bn = min(_rup(block_n, 128), _rup(n, 128))
    bk = min(_rup(block_k, 128), _rup(k, 128))
    mp, np_, kp = _rup(m, bm), _rup(n, bn), _rup(k, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    bp = jnp.pad(b, (0, np_ - n)).reshape(1, np_)
    k_steps = kp // bk
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    operands = [xp, wp, bp]
    if per_channel:
        svec = jnp.pad(jnp.asarray(shift, jnp.int32),
                       (0, np_ - n)).reshape(1, np_)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(svec)
    out = pl.pallas_call(
        functools.partial(_qgemm_kernel, k_steps=k_steps,
                          has_shift_vec=per_channel,
                          shift=0 if per_channel else shift, relu=relu),
        grid=(mp // bm, np_ // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        # M/N tiles are independent; only the K walk carries the
        # accumulator — lets Mosaic double-buffer the K-tile DMAs
        # behind the current tile's matmul (the conv kernels already
        # declare this; the FC kernel was the only one missing it)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


def _rup(x: int, mult: int) -> int:
    return -(-x // mult) * mult
