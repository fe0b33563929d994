"""int8 GEMM + bias + requantize Pallas kernel (the paper's fused
conv/fully-connected matrix unit, §3.2.3: "convolution kernel and the
fully connected kernel can be fused together as a single 3-D
matrix-matrix multiplication unit").

TPU mapping: int8 operands feed the MXU with int32 accumulation.  The
tiles are chosen from the operands' shapes by :func:`fc_tiles`, not by
the DSE's ``N_i``/``N_l`` (those tile the conv kernels): at batch <= 32
an FC layer does at most 64 int8 ops per weight byte, far below the
chip's ridge, so its time is the weight stream's, and each grid step
streams one multi-MiB block of whole 128-lane weight rows.  ``shift``
may be a length-N tuple (per-output-channel quantized FC layers): the
counts are staged as a ``(1, N)`` int32 operand sharing the bias row's
BlockSpec and the epilogue applies a per-lane round-half-up shift
vector; a scalar ``shift`` compiles the exact per-tensor kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.resources import VMEM_BUDGET_BYTES
from . import ref

INT8_MIN, INT8_MAX = -128, 127

#: Weight bytes one grid step streams, at most: multi-MiB blocks keep
#: HBM busy where 16 KB ones each paid the step's fixed cost, and the
#: first block's copy, which nothing overlaps, stays short.  On a v5e,
#: 2 MiB streamed VGG-16's FC1 at 749 GB/s, 4 MiB at 745, 16 KB at 67.
WEIGHT_BLOCK_BYTES = 2 * 1024 ** 2
#: VMEM the blocks of one grid step may take; the rest of the scoped
#: VMEM is left to Mosaic's own scratch.
BLOCK_VMEM_BYTES = VMEM_BUDGET_BYTES * 3 // 4
#: Rows of one activation block: the whole 8-rounded batch up to this,
#: which bounds the int32 accumulator (``bm * bn * 4`` bytes).
MAX_BLOCK_M = 128

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


@dataclasses.dataclass(frozen=True)
class FcTiles:
    """One ``qgemm`` call's blocks (``bm, bk, bn``) and the padded
    operand dims (``mp, kp, np_``) they divide."""
    bm: int
    bk: int
    bn: int
    mp: int
    kp: int
    np_: int

    @property
    def grid(self):
        return (self.mp // self.bm, self.np_ // self.bn, self.kp // self.bk)

    @property
    def grid_steps(self) -> int:
        i, j, kk = self.grid
        return i * j * kk

    @property
    def weight_block_bytes(self) -> int:
        return self.bk * self.bn


def fc_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """VMEM bytes of one grid step: the double-buffered int8 activation,
    weight and output blocks and int32 bias and shift rows, plus the
    int32 accumulator and the matmul's int32 result.  Rows round up to
    the native tiles, (32, 128) for int8 and (8, 128) for int32."""
    rows8, rows32 = _rup(bm, 32), _rup(bm, 8)
    blocks = rows8 * bk + bk * bn + rows8 * bn + 2 * 8 * bn * 4
    return 2 * blocks + 2 * rows32 * bn * 4


def fc_tiles(m: int, k: int, n: int, block_m: Optional[int] = None,
             block_n: Optional[int] = None,
             block_k: Optional[int] = None) -> FcTiles:
    """The blocks of ``qgemm`` on an (M, K) x (K, N) product.

    By default they follow from the shape: ``bm`` is the whole 8-rounded
    M (at most :data:`MAX_BLOCK_M`); ``bn`` the whole 128-rounded N and
    ``bk`` the largest multiple of 128 dividing the 128-rounded K, each
    as large as a weight block of :data:`WEIGHT_BLOCK_BYTES` and the
    VMEM budget allow.  ``bn`` and ``bk`` divide the 128-rounded dims, so
    no weight pads beyond whole lane tiles.  An explicit ``block_*``
    overrides its dim (rounded to whole lane tiles for N and K; the dim
    then pads to a multiple of it)."""
    kp0, np0 = _rup(k, 128), _rup(n, 128)
    if block_m is None:
        block_m = MAX_BLOCK_M
    bm = min(block_m, _rup(m, 8))
    if block_n is None:
        bn = max((d for d in _lane_divisors(np0)
                  if 128 * d <= WEIGHT_BLOCK_BYTES
                  and fc_vmem_bytes(bm, 128, d) <= BLOCK_VMEM_BYTES),
                 default=128)
    else:
        bn = min(_rup(block_n, 128), np0)
    if block_k is None:
        bk = max((d for d in _lane_divisors(kp0)
                  if d * bn <= WEIGHT_BLOCK_BYTES
                  and fc_vmem_bytes(bm, d, bn) <= BLOCK_VMEM_BYTES),
                 default=128)
    else:
        bk = min(_rup(block_k, 128), kp0)
    return FcTiles(bm=bm, bk=bk, bn=bn, mp=_rup(m, bm), kp=_rup(k, bk),
                   np_=_rup(n, bn))


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
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked int8 GEMM on the blocks of :func:`fc_tiles`; shapes need
    not divide blocks (zero padding is applied and sliced off — zero is
    the symmetric quantization zero)."""
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
    t = fc_tiles(m, k, n, block_m, block_n, block_k)
    bm, bn, bk, mp, np_, kp = t.bm, t.bn, t.bk, t.mp, t.np_, t.kp
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    bp = jnp.pad(b, (0, np_ - n)).reshape(1, np_)
    k_steps = kp // bk
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    if not interpret:   # the interpreter takes no memory-space constraint
        wp = _in_hbm(wp)
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
        grid=t.grid,
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


@jax.custom_batching.custom_vmap
def _in_hbm(w):
    """``w`` kept in HBM as the kernel's operand.  Left free, XLA may
    stage a whole weight matrix into VMEM with a copy of its own ahead
    of the kernel (it does for a 4 MiB one, and for larger ones where
    conv kernels run long enough to hide the copy): the weight stream
    then leaves the kernel's device time, and the blocks double-buffer
    nothing."""
    return pltpu.with_memory_space_constraint(w, pltpu.HBM)


@_in_hbm.def_vmap
def _in_hbm_vmap(axis_size, in_batched, w):
    # the constraint has no batching rule: a batch of weight images (the
    # fault trials of core/ser.py) goes to the kernel unconstrained
    del axis_size
    return w, in_batched[0]


def _rup(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _lane_divisors(dim: int):
    """The multiples of 128 that divide ``dim`` (itself one)."""
    lanes = dim // 128
    return [128 * d for d in range(1, lanes + 1) if lanes % d == 0]
