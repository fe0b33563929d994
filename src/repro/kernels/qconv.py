"""Fused int8 conv + ReLU + max-pool Pallas kernel — the flagship
"pipelined kernel" of the paper (§3.2.3, Fig. 5), adapted to TPU.

FPGA -> TPU adaptation (see DESIGN.md §2): the paper streams a
line-buffer convolution through OpenCL pipes; the TPU-native equivalent
keeps the conv -> ReLU -> requantize -> max-pool chain resident in VMEM
inside ONE kernel (fusion = pipes: the intermediate feature map never
round-trips through HBM) and expresses the convolution as kh*kw
shifted int8 matmuls on the MXU (im2col-free sliced dot products).

Parallelism parameters map onto the paper's degrees of freedom
(DESIGN.md §2 table):
  * ``N_l`` (compute lanes)      -> ``block_cout`` (output-channel tile)
  * ``N_i`` (input vector width) -> ``block_cin`` (input-channel
    contraction tile, ``8·N_i``: eight int8 elements per lane-vector
    word feed one MXU column — a real grid axis, not just a model knob)
  * line-buffer depth            -> ``block_h`` (row-band tile)

Grid: ``(batch, H/block_h, Cout/block_cout, Cin/block_cin)``, iterated
with the Cin contraction tile innermost.  Each step sees one **row
band** of the input — ``block_h`` output rows plus the halo the band
needs (kh-1 conv rows, and when a max-pool is fused, the pool-window
carry rows, so the fused pool stays bit-exact across band boundaries)
— restricted to one ``block_cin`` channel slice, so per-step VMEM no
longer scales with the whole Cin (wide VGG/ResNet layers fit deeper
bands).  The band window *overlaps* its neighbours by the halo, which
a blocked BlockSpec cannot express; the input spec therefore uses
element-offset (``pl.Element``) indexing.  Strided convs read their
taps from a *phase-split* band — ``(sh, rows, sw, cols, C)``, one plane
per stride phase — so every tap is a unit-stride window (Mosaic refuses
strided value slices); a narrow strided first layer is folded to a
stride-1 conv over space-to-depth channels instead, where its
phase-split band would not fit VMEM (:func:`_folds_to_depth`).
Because the input index map
ignores the Cout grid axis, the band slice stays resident in VMEM
while the weight tiles cycle — the old whole-plane kernel re-fetched
the entire input per Cout tile.  The int32 accumulator lives in
explicit VMEM scratch and is carried across the Cin steps
(qgemm-style ``pl.when`` init/accumulate/finish), and
``dimension_semantics`` tells Mosaic the batch/band axes are parallel
so it double-buffers the next band's DMA behind the current band's
matmuls.

Epilogue skip operand (residual-add fusion): the final Cin step may
add an int8 **skip** feature map into the band before the merge
requantization — the residual ``Add`` of a ResNet block executed
inside the conv kernel's epilogue instead of as a standalone stage
(one whole feature-map HBM write+read saved per skip connection; the
paper's §3.2.3 "never leave the pipe" argument applied to the skip
path).  The math replicates the unfused two-stage program bit-for-bit:
the conv result is requantized and *clipped to int8* first (exactly
the tensor the standalone conv stage would have produced), then both
operands are alignment-shifted in int32, added, and requantized to
the merge output scale — see ``_band_epilogue``.

Concat-epilogue output (inception-class merges, DESIGN.md §10): with
``out_buf`` the kernel writes its Cout tiles directly into a
channel-offset slice ``[out_off, out_off + Cout)`` of a shared merge
buffer instead of materializing its own tensor — the channel ``Concat``
of a GoogLeNet/SqueezeNet branch merge becomes an *output BlockSpec*,
not a copy.  The buffer rides ``input_output_aliases`` (unwritten
channels pass through untouched) and every output-side BlockSpec uses
element offsets with **clamped** index maps
(``min(i*tile, size-tile)``): a ragged final row band or Cout tile
re-computes its overlap with the previous tile — identical values, so
the revisit is benign — instead of writing padding into neighbouring
branches' channels.  The per-operand concat alignment shift and the
merge's fused ReLU run inside the epilogue (monotone per-element maps,
so they commute exactly with the fused max-pool that still runs last).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

INT8_MIN, INT8_MAX = -128, 127

#: Round-half-up arithmetic right shift (the paper's requant and the
#: merge alignment step share this primitive).  ``shift`` is a static
#: Python int (per-tensor requant) or an int32 row vector — ``(1,
#: bco)``, one count per output-channel lane — for per-channel weight
#: scales.  ONE implementation for oracle and kernels (ref.py imports
#: only jax/jnp, so no cycle): a rounding-rule change cannot drift
#: between them.
_round_shift = ref.round_shift


def _band_epilogue(
    acc,      # (conv_rows * cols, bco) int32 accumulator
    b_row,    # (1, bco) int32 bias
    shift,                           # int | (1, bco) int32 per-lane row
    relu: bool,
    skip=None,                       # (conv_rows * cols, bco) int8 or None
    skip_shifts: Tuple[int, int] = (0, 0),
    merge_shift: int = 0,
    merge_relu: bool = False,
    concat_shift: int = 0,
    concat_relu: bool = False,
):
    """Shared bias/requant/ReLU tail of both band kernels — identical
    fixed-point semantics for dense and depthwise convs.  Returns the
    int8-range result on its int32 carrier; :func:`_band_store` pools
    and writes it.

    With a per-channel quantized layer ``shift`` is a ``(1, bco)``
    int32 row (one count per Cout lane, staged as a kernel operand
    alongside the bias) instead of a static scalar; the merge
    alignment/requant shifts below stay scalar either way (activations
    are always per-tensor).

    With ``skip`` the tail replicates the unfused Conv→Add two-stage
    program exactly: the conv accumulator is requantized and clipped to
    int8 (the tensor the standalone conv would have written to HBM),
    then conv result and skip are alignment-shifted to the merge's
    common fixed-point position in int32, added, and requantized with
    ``merge_shift``/``merge_relu``.  A fused max-pool always runs last
    (post-merge), matching the graph order Conv→Add→(ReLU)→MaxPool.

    With ``concat_shift``/``concat_relu`` the tail additionally applies
    this operand's channel-``Concat`` alignment — exactly
    ``ops.qconcat_nhwc``'s per-operand ``clip(round_shift(x, s))`` (a
    zero shift is the identity on values already clipped to int8 range,
    so it is skipped) and the merge's fused ReLU — before the pool.
    Both maps are monotone and per-element, so running them pre-pool is
    bit-identical to pooling the concatenated tensor."""
    acc = acc + b_row.astype(jnp.int32)          # (1,bco) broadcasts
    acc = _round_shift(acc, shift)
    if relu:
        acc = jnp.maximum(acc, 0)
    acc = jnp.clip(acc, INT8_MIN, INT8_MAX)      # int8 range, int32 carrier
    if skip is not None:
        a_conv, a_skip = skip_shifts
        acc = (_round_shift(acc, a_conv)
               + _round_shift(skip.astype(jnp.int32), a_skip))
        acc = _round_shift(acc, merge_shift)
        if merge_relu:
            acc = jnp.maximum(acc, 0)
        acc = jnp.clip(acc, INT8_MIN, INT8_MAX)
    if concat_shift:
        acc = jnp.clip(_round_shift(acc, concat_shift), INT8_MIN, INT8_MAX)
    if concat_relu:
        acc = jnp.maximum(acc, 0)
    return acc


def _band_store(o_ref, pool_ref, y, conv_hw: Tuple[int, int], wo: int,
                pool: Optional[Tuple[int, int]]) -> None:
    """Write the epilogue result ``y`` — ``(rows * cols, lanes)`` int32
    holding int8 values, ``cols >= wo`` computed columns — into the
    output block.  A fused max-pool reads its windows back from the
    ``(ceil(lanes/128), rows * cols, 128)`` int32 ``pool_ref`` scratch
    with strided loads, one pooled row and one 128-lane chunk at a
    time: the pool stride lands on the sublane axis, where Mosaic takes
    strided loads from a 128-lane ref but refuses strided value slices.
    The max runs on the int32 carrier, which holds exactly the int8
    values, so the result is bit-identical to pooling the int8 tensor."""
    rows, cols = conv_hw
    if pool is None:
        y = y.astype(jnp.int8).reshape(rows, cols, -1)
        o_ref[0] = y[:, :wo] if cols != wo else y
        return
    pw, ps = pool
    pho, pwo = (rows - pw) // ps + 1, (wo - pw) // ps + 1
    lanes = y.shape[-1]
    for k in range(pool_ref.shape[0]):
        lo, width = 128 * k, min(128, lanes - 128 * k)
        pool_ref[k, :, :width] = y[:, lo:lo + width]

        def pooled_row(a, carry, k=k, lo=lo, width=width):
            row = None
            for pi in range(pw):
                for pj in range(pw):
                    win = pool_ref[k, pl.ds((a * ps + pi) * cols + pj, pwo,
                                            stride=ps), :]
                    row = win if row is None else jnp.maximum(row, win)
            o_ref[0, a, :, lo:lo + width] = row[:, :width].astype(jnp.int8)
            return carry

        jax.lax.fori_loop(0, pho, pooled_row, 0)


def _scratch_shapes(conv_hw: Tuple[int, int], lanes: int,
                    pool: Optional[Tuple[int, int]]):
    """Shapes of a band kernel's int32 VMEM scratch: the accumulator,
    plus the 128-lane pooling buffer of :func:`_band_store` when a pool
    is fused."""
    rows, cols = conv_hw
    shapes = [(rows * cols, lanes)]
    if pool is not None:
        shapes.append((-(-lanes // 128), rows * cols, 128))
    return shapes


def _scratch(conv_hw: Tuple[int, int], lanes: int,
             pool: Optional[Tuple[int, int]]):
    return [pltpu.VMEM(s, jnp.int32)
            for s in _scratch_shapes(conv_hw, lanes, pool)]


def scratch_bytes(conv_hw: Tuple[int, int], lanes: int,
                  pool: Optional[Tuple[int, int]]) -> int:
    """Bytes of the scratch :func:`_scratch` allocates."""
    return sum(4 * math.prod(s) for s in _scratch_shapes(conv_hw, lanes, pool))


def _tap(x_ref, i: int, j: int, strides: Tuple[int, int],
         conv_hw: Tuple[int, int]):
    """Tap ``(i, j)`` of a phase-split band ``(1, sh, rows, sw, cols,
    C)`` as a ``(conv_rows, cols, C)`` load: input row ``r*sh + i`` is
    phase ``i % sh``, row ``r + i // sh`` — a unit-stride window, read
    from the ref so that only one tap is live at a time."""
    sh, sw = strides
    ho, cols = conv_hw
    return x_ref[0, i % sh, pl.ds(i // sh, ho), j % sw, pl.ds(j // sw, cols), :]


def _qconv_band_kernel(
    x_ref,    # (1, sh, band_rows, sw, cols_in, bci) int8 — halo band
    w_ref,    # (KH, KW, bci, bco) int8
    b_ref,    # (1, bco) int32
    *rest,    # [shift_ref (1, bco) int32,]
              # [skip_ref (1, conv_rows, cols, bco) int8,]
              # [buf_ref (aliased merge buffer, write-only via o_ref),]
              # o_ref, acc_ref[, pool_ref]
    strides: Tuple[int, int],
    conv_hw: Tuple[int, int],   # conv rows/cols computed by this band
    wo: int,                    # conv output columns (<= computed cols)
    cin_steps: int,
    has_shift_vec: bool,
    has_skip: bool,
    shift: int,
    relu: bool,
    pool: Optional[Tuple[int, int]],
    skip_shifts: Tuple[int, int],
    merge_shift: int,
    merge_relu: bool,
    has_out_buf: bool = False,
    concat_shift: int = 0,
    concat_relu: bool = False,
):
    rest = list(rest)
    shift_ref = rest.pop(0) if has_shift_vec else None
    skip_ref = rest.pop(0) if has_skip else None
    if has_out_buf:
        rest.pop(0)   # aliased merge buffer: never read in-kernel
    o_ref, acc_ref, pool_ref = rest if pool is not None else (*rest, None)
    kh, kw = w_ref.shape[0], w_ref.shape[1]
    ho, cols = conv_hw

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        for i in range(kh):          # static unroll: kh*kw MXU matmuls
            for j in range(kw):
                patch = _tap(x_ref, i, j, strides, conv_hw)
                acc_ref[...] += jnp.dot(
                    patch.reshape(ho * cols, patch.shape[-1]),
                    w_ref[i, j],
                    preferred_element_type=jnp.int32,
                )

    def _finish():
        skip = (skip_ref[0].reshape(acc_ref.shape[0], -1)
                if skip_ref is not None else None)
        s = shift_ref[...] if shift_ref is not None else shift
        y = _band_epilogue(acc_ref[...], b_ref[...], s, relu, skip=skip,
                           skip_shifts=skip_shifts,
                           merge_shift=merge_shift,
                           merge_relu=merge_relu,
                           concat_shift=concat_shift,
                           concat_relu=concat_relu)
        _band_store(o_ref, pool_ref, y, conv_hw, wo, pool)

    if cin_steps == 1:
        # whole-Cin contraction: straight-line, no per-step conditionals
        _init()
        _accumulate()
        _finish()
    else:
        ci = pl.program_id(3)         # Cin contraction step (innermost)
        pl.when(ci == 0)(_init)
        _accumulate()
        pl.when(ci == cin_steps - 1)(_finish)


def _qdwconv_band_kernel(
    x_ref,    # (1, sh, band_rows, sw, cols_in, bc // multiplier) int8
    w_ref,    # (KH, KW, bc) int8 — one filter tap per output channel
    b_ref,    # (1, bc) int32
    *rest,    # [shift_ref (1, bc) int32,]
              # [skip_ref (1, conv_rows, cols, bc) int8,]
              # [buf_ref (aliased merge buffer, write-only via o_ref),]
              # o_ref, acc_ref[, pool_ref]
    strides: Tuple[int, int],
    conv_hw: Tuple[int, int],
    wo: int,
    has_shift_vec: bool,
    has_skip: bool,
    multiplier: int,
    shift: int,
    relu: bool,
    pool: Optional[Tuple[int, int]],
    skip_shifts: Tuple[int, int],
    merge_shift: int,
    merge_relu: bool,
    has_out_buf: bool = False,
    concat_shift: int = 0,
    concat_relu: bool = False,
):
    """Depthwise variant of the row-band kernel: each output channel is
    its own group, so the "per-group Cout tile" degenerates to a channel
    tile and the kh*kw contraction becomes VPU multiply-accumulates
    (channels ride the 128-wide lane axis; there is no cross-channel
    reduction to feed the MXU).  Per-channel requant rides a
    ``(1, bc)`` int32 shift row exactly as in the dense kernel — the
    channel tile IS the lane dim, so depthwise layers (the biggest
    per-channel accuracy winners) pay one row per tile.

    With a channel ``multiplier`` m > 1 (ONNX group=Cin, Cout=m·Cin)
    the input tile holds ``bc // m`` channels and each feeds the m
    adjacent output lanes — ``jnp.repeat`` on the lane axis reproduces
    ONNX's group→output-channel order (output channel c convolves input
    channel c // m).  The channel tile is always a multiple of m, so
    every tile maps to a whole input-channel slice.  The residual-skip
    and concat-merge epilogues are identical to the dense kernel's."""
    rest = list(rest)
    shift_ref = rest.pop(0) if has_shift_vec else None
    skip_ref = rest.pop(0) if has_skip else None
    if has_out_buf:
        rest.pop(0)   # aliased merge buffer: never read in-kernel
    o_ref, acc_ref, pool_ref = rest if pool is not None else (*rest, None)
    kh, kw = w_ref.shape[0], w_ref.shape[1]
    ho, cols = conv_hw

    acc_ref[...] = jnp.zeros_like(acc_ref)
    for i in range(kh):              # static unroll: kh*kw VPU FMAs
        for j in range(kw):
            patch = _tap(x_ref, i, j, strides, conv_hw)
            if multiplier > 1:
                patch = jnp.repeat(patch, multiplier, axis=-1)
            acc_ref[...] += (patch.reshape(ho * cols, -1).astype(jnp.int32)
                             * w_ref[i, j].astype(jnp.int32))

    skip = (skip_ref[0].reshape(acc_ref.shape[0], -1)
            if skip_ref is not None else None)
    s = shift_ref[...] if shift_ref is not None else shift
    y = _band_epilogue(acc_ref[...], b_ref[...], s, relu, skip=skip,
                       skip_shifts=skip_shifts,
                       merge_shift=merge_shift,
                       merge_relu=merge_relu,
                       concat_shift=concat_shift,
                       concat_relu=concat_relu)
    _band_store(o_ref, pool_ref, y, conv_hw, wo, pool)


def _espec(block_shape, index_map) -> pl.BlockSpec:
    """BlockSpec whose index map returns *element* offsets.  Mosaic
    takes element windows on every dim of a spec or on none, so every
    dim is a ``pl.Element``: the halo row band, the clamped row and
    Cout offsets of the concat-into path and the concat channel
    offsets.  An offset on the lane (channel) dim must be a provable
    multiple of 128 — pass a literal 0 where the tile is the whole dim
    (:func:`_tile_off`)."""
    return pl.BlockSpec(tuple(pl.Element(d) for d in block_shape), index_map)


def _tile_off(i, tile: int, n_tiles: int):
    """Element offset of tile ``i``: a literal 0 for a single tile, so
    that Mosaic can prove the lane alignment of a whole-dim block."""
    return 0 if n_tiles == 1 else i * tile


def _lane_tile(block: Optional[int], size: int) -> int:
    """Channel tile on the 128-wide lane axis: the whole dim when the
    block covers it, else the block rounded up to whole lane tiles
    (Mosaic moves a channel slice of a wider array only in whole lane
    tiles).  Tiling the exact integer contraction never changes the
    result."""
    if block is None or block >= size:
        return size
    return min(_rup(block, 128), size)


def _out_cols(wo: int, lanes_in: int, lanes_out: int) -> int:
    """Conv columns a band computes.  Mosaic folds an int8
    ``(rows, cols, C)`` tap into ``(rows*cols, C)`` only when ``cols``
    is a multiple of 8 or ``C`` fills whole lane tiles, so narrow-channel
    layers compute up to 7 extra (discarded) columns."""
    if wo % 8 == 0 or (lanes_in % 128 == 0 and lanes_out % 128 == 0):
        return wo
    return _rup(wo, 8)


def _phase_split(x, strides: Tuple[int, int], rows: int, cols: int):
    """``(N, H, W, C) -> (N, sh, rows, sw, cols, C)`` with input pixel
    ``(r*sh + a, c*sw + b)`` at ``[:, a, r, b, c]``: every conv tap of a
    strided conv becomes a unit-stride window of one phase plane.  The
    input is zero-padded (zero == the symmetric quantization zero) or
    cropped to ``(sh*rows, sw*cols)``; at stride 1 this is a reshape."""
    sh, sw = strides
    n, h, w, c = x.shape
    hp, wp = sh * rows, sw * cols
    if hp > h or wp > w:
        x = jnp.pad(x, ((0, 0), (0, max(0, hp - h)), (0, max(0, wp - w)),
                        (0, 0)))
    if x.shape[1] > hp or x.shape[2] > wp:
        x = x[:, :hp, :wp]
    if sh == sw == 1:
        return x.reshape(n, 1, rows, 1, cols, c)
    return (x.reshape(n, rows, sh, cols, sw, c)
            .transpose(0, 2, 1, 4, 3, 5))


def _folds_to_depth(strides: Tuple[int, int], cin: int) -> bool:
    """Whether :func:`qconv2d` runs a strided dense conv as a stride-1
    conv over space-to-depth channels: where the ``sh*sw*Cin`` folded
    channels fit the one 128-lane tile that a narrower channel dim is
    padded to in VMEM anyway.  The phase-split band keeps ``Cin`` on the
    lanes, so for a narrow first layer it is mostly lane padding: at
    AlexNet's conv_1 (11x11/4, Cin 3) under the default tiles Mosaic
    needs 18.59M of scoped VMEM for it, over v5e's 16M limit, and
    refuses the kernel.  Folded, the same band fills its lanes and
    compiles."""
    return strides != (1, 1) and strides[0] * strides[1] * cin <= 128


def _s2d_shape(hp: int, wp: int, cin: int, kh: int, kw: int,
               strides: Tuple[int, int]):
    """``(rows, cols, channels, kh, kw)`` of the space-to-depth conv
    that :func:`_space_to_depth` folds a strided conv into."""
    sh, sw = strides
    kh2, kw2 = -(-kh // sh), -(-kw // sw)
    return ((hp - kh) // sh + kh2, (wp - kw) // sw + kw2, sh * sw * cin,
            kh2, kw2)


def _space_to_depth(x, w, strides: Tuple[int, int]):
    """Fold a strided dense conv into a stride-1 one over stride-phase
    channels: ``x[:, r*sh + a, c*sw + b, ch]`` moves to channel
    ``(a*sw + b)*C + ch`` of pixel ``(r, c)``, and the filter to
    ``ceil(kh/sh) x ceil(kw/sw)`` taps whose extra entries are zero
    (AlexNet's 11x11/4 conv on 3 channels becomes a 3x3 conv on 48).
    The int32 sum gains only zero terms, so the result is
    bit-identical.  See :func:`_folds_to_depth` for where it is used."""
    sh, sw = strides
    n, hp, wp, c = x.shape
    kh, kw, _c, cout = w.shape
    rows, cols, _c2, kh2, kw2 = _s2d_shape(hp, wp, c, kh, kw, strides)
    x = jnp.pad(x, ((0, 0), (0, max(0, sh * rows - hp)),
                    (0, max(0, sw * cols - wp)), (0, 0)))
    x = (x[:, :sh * rows, :sw * cols]
         .reshape(n, rows, sh, cols, sw, c)
         .transpose(0, 1, 3, 2, 4, 5)
         .reshape(n, rows, cols, sh * sw * c))
    w = jnp.pad(w, ((0, sh * kh2 - kh), (0, sw * kw2 - kw), (0, 0), (0, 0)))
    w = (w.reshape(kh2, sh, kw2, sw, c, cout)
         .transpose(0, 2, 1, 3, 4, 5)
         .reshape(kh2, kw2, sh * sw * c, cout))
    return x, w


def _halo_band(x, strides, kh: int, kw: int, conv_rows: int, cols: int,
               last_start: int):
    """Phase-split ``x`` for row bands of ``conv_rows`` conv rows and
    ``cols`` computed columns whose last band starts at conv row
    ``last_start``; returns the split input and its band block shape
    (without the channel dim)."""
    sh, sw = strides
    band_rows = conv_rows + (kh - 1) // sh
    cols_in = cols + (kw - 1) // sw
    x6 = _phase_split(x, strides, last_start + band_rows, cols_in)
    return x6, (1, sh, band_rows, sw, cols_in)


def band_geometry(block_h: int, kh: int, sh: int,
                  pool: Optional[Tuple[int, int]]) -> Tuple[int, int, int]:
    """Row-band halo arithmetic shared by the kernel and the DSE
    resource model.

    For a band of ``block_h`` *final* output rows (post-pool when a pool
    is fused) returns ``(conv_rows, in_rows, in_step)``:

      conv_rows — conv output rows the band must compute
                  (= ``(block_h-1)*ps + pw`` with a fused pool: the last
                  pool window carries ``pw-ps`` rows past the stride);
      in_rows   — input rows the band must read (conv halo ``kh-1``);
      in_step   — input-row distance between consecutive band starts
                  (< in_rows: the difference is the halo overlap).
    """
    if pool is not None:
        pw, ps = pool
        conv_rows = (block_h - 1) * ps + pw
        conv_step = block_h * ps
    else:
        conv_rows = block_h
        conv_step = block_h
    in_rows = (conv_rows - 1) * sh + kh
    in_step = conv_step * sh
    return conv_rows, in_rows, in_step


def default_block_h(oh: int, wo: int) -> int:
    """Default row-band height: enough rows that each band's matmul has
    a healthy M dimension (targets >= ~1024 conv pixels per band, the
    MXU sweet spot) without approaching the whole-plane working set."""
    target_rows = max(1, -(-1024 // max(wo, 1)))
    return min(oh, target_rows, 32)


def _qconv2d_into(
    x, w, b, out_buf, *,
    strides, shift, relu, pool, block_cout, block_h, block_cin,
    skip, skip_shifts, merge_shift, merge_relu,
    out_off, concat_shift, concat_relu, interpret,
):
    """Concat-epilogue variant of the dense band call: writes the conv's
    Cout tiles into channels ``[out_off, out_off + Cout)`` of the shared
    merge buffer ``out_buf`` and returns the whole (aliased) buffer.

    The buffer has the *exact* merge geometry — no Cout or row padding
    is allowed to leak into it — so output-side tiles use **clamped**
    element-offset index maps (``min(i*tile, size-tile)``): a ragged final
    tile re-computes part of its predecessor's rows/channels with
    identical values instead of writing padding.  Unwritten channels
    (the other producers' slices) pass through untouched via
    ``input_output_aliases``."""
    n, hp, wp, cin = x.shape
    kh, kw, _cin2, cout = w.shape
    sh, sw = strides
    ho = (hp - kh) // sh + 1
    wo = (wp - kw) // sw + 1
    if b is None:
        b = jnp.zeros((cout,), jnp.int32)
    per_channel = isinstance(shift, tuple)
    if per_channel:
        assert len(shift) == cout, (len(shift), cout)

    if pool is not None:
        pwin, pstr = pool
        oh, ow = (ho - pwin) // pstr + 1, (wo - pwin) // pstr + 1
    else:
        oh, ow = ho, wo
    ps = pool[1] if pool is not None else 1
    nb, ohb, owb, c_tot = out_buf.shape
    assert (nb, ohb, owb) == (n, oh, ow), (out_buf.shape, (n, oh, ow))
    assert out_off + cout <= c_tot, (out_off, cout, c_tot)

    bco = min(block_cout, cout)
    n_co = -(-cout // bco)

    bci = _lane_tile(block_cin, cin)
    cinp = _rup(cin, bci)
    cin_steps = cinp // bci
    if cinp > cin:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cinp - cin)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, cinp - cin), (0, 0)))

    bh = min(block_h or default_block_h(oh, wo), oh)
    conv_rows = band_geometry(bh, kh, sh, pool)[0]
    n_bands = -(-oh // bh)
    cols = _out_cols(wo, bci, bco)
    x, band = _halo_band(x, strides, kh, kw, conv_rows, cols,
                         (oh - bh) * ps)

    def ostart(hi):          # clamped band start (final-output rows)
        return jnp.minimum(hi * bh, oh - bh)

    def cstart(co):          # clamped Cout-tile start
        return jnp.minimum(co * bco, cout - bco)

    brow = b.reshape(1, cout)
    in_specs = [
        _espec(band + (bci,),
               lambda ni, hi, co, ci: (ni, 0, ostart(hi) * ps, 0, 0,
                                       _tile_off(ci, bci, cin_steps))),
        _espec((kh, kw, bci, bco),
               lambda ni, hi, co, ci: (0, 0, _tile_off(ci, bci, cin_steps),
                                       cstart(co))),
        _espec((1, bco), lambda ni, hi, co, ci: (0, cstart(co))),
    ]
    operands = [x, w, brow]
    if per_channel:
        svec = jnp.asarray(shift, jnp.int32).reshape(1, cout)
        in_specs.append(
            _espec((1, bco), lambda ni, hi, co, ci: (0, cstart(co))))
        operands.append(svec)
    if skip is not None:
        assert skip.shape == (n, ho, wo, cout), (skip.shape,
                                                 (n, ho, wo, cout))
        skip_rows = (oh - bh) * ps + conv_rows
        skip = jnp.pad(skip, ((0, 0), (0, max(0, skip_rows - ho)),
                              (0, cols - wo), (0, 0)))
        in_specs.append(
            _espec((1, conv_rows, cols, bco),
                   lambda ni, hi, co, ci: (ni, ostart(hi) * ps, 0,
                                           cstart(co))))
        operands.append(skip)

    out_spec = _espec(
        (1, bh, ow, bco),
        lambda ni, hi, co, ci: (ni, ostart(hi), 0, out_off + cstart(co)))
    in_specs.append(out_spec)        # aliased merge buffer (same tiles)
    operands.append(out_buf)

    return pl.pallas_call(
        functools.partial(
            _qconv_band_kernel,
            strides=strides,
            conv_hw=(conv_rows, cols),
            wo=wo,
            cin_steps=cin_steps,
            has_shift_vec=per_channel,
            has_skip=skip is not None,
            has_out_buf=True,
            shift=0 if per_channel else shift,
            relu=relu,
            pool=pool,
            skip_shifts=skip_shifts,
            merge_shift=merge_shift,
            merge_relu=merge_relu,
            concat_shift=concat_shift,
            concat_relu=concat_relu,
        ),
        grid=(n, n_bands, n_co, cin_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_buf.shape, jnp.int8),
        scratch_shapes=_scratch((conv_rows, cols), bco, pool),
        input_output_aliases={len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            # ragged tiles revisit rows/channels (same values), so the
            # band and Cout axes are not parallel-safe here
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("strides", "shift", "relu", "pool", "block_cout",
                     "block_h", "block_cin", "skip_shifts", "merge_shift",
                     "merge_relu", "out_off", "concat_shift", "concat_relu",
                     "interpret"),
)
def qconv2d(
    x: jnp.ndarray,  # (N, Hp, Wp, Cin) int8, pre-padded (VALID conv)
    w: jnp.ndarray,  # (KH, KW, Cin, Cout) int8
    b: Optional[jnp.ndarray],  # (Cout,) int32
    *,
    strides: Tuple[int, int] = (1, 1),
    shift=0,         # int | length-Cout tuple (per-channel shift vector)
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,
    block_cout: int = 128,
    block_h: Optional[int] = None,
    block_cin: Optional[int] = None,
    skip: Optional[jnp.ndarray] = None,  # (N, Ho, Wo, Cout) int8 residual
    skip_shifts: Tuple[int, int] = (0, 0),
    merge_shift: int = 0,
    merge_relu: bool = False,
    out_buf: Optional[jnp.ndarray] = None,  # shared concat merge buffer
    out_off: int = 0,
    concat_shift: int = 0,
    concat_relu: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Row-banded fused int8 conv.  ``block_cin=None`` contracts the
    whole Cin per grid step (the pre-tiling behaviour); otherwise the
    contraction runs in ``block_cin``-channel slices on an extra
    (innermost) grid axis.  ``skip`` is an optional residual operand in
    the *conv output* geometry (pre-pool); see ``_band_epilogue``.

    ``shift`` as a length-Cout tuple selects the per-channel requant
    path: the counts are staged as a ``(1, Cout)`` int32 operand with a
    per-Cout-block BlockSpec (the bias row's twin) and the epilogue
    applies a per-lane round-half-up shift vector.  A scalar ``shift``
    compiles the exact pre-existing per-tensor kernel (no extra
    operand, same jaxpr).

    ``out_buf`` selects the concat-epilogue path (``_qconv2d_into``):
    the result lands in channels ``[out_off, out_off + Cout)`` of the
    shared merge buffer — after this operand's ``concat_shift``
    alignment and the merge's ``concat_relu`` — and the *whole buffer*
    is returned instead of a standalone tensor."""
    if _folds_to_depth(strides, x.shape[-1]):
        x, w = _space_to_depth(x, w, strides)
        strides = (1, 1)
    if out_buf is not None:
        return _qconv2d_into(
            x, w, b, out_buf, strides=strides, shift=shift, relu=relu,
            pool=pool, block_cout=block_cout, block_h=block_h,
            block_cin=block_cin, skip=skip, skip_shifts=skip_shifts,
            merge_shift=merge_shift, merge_relu=merge_relu,
            out_off=out_off, concat_shift=concat_shift,
            concat_relu=concat_relu, interpret=interpret)
    n, hp, wp, cin = x.shape
    kh, kw, cin2, cout = w.shape
    assert cin == cin2, (x.shape, w.shape)
    sh, sw = strides
    ho = (hp - kh) // sh + 1
    wo = (wp - kw) // sw + 1
    if b is None:
        b = jnp.zeros((cout,), jnp.int32)

    per_channel = isinstance(shift, tuple)
    if per_channel:
        assert len(shift) == cout, (len(shift), cout)

    bco = min(_rup(block_cout, 128), _rup(cout, 128))
    coutp = _rup(cout, bco)
    n_co = coutp // bco
    wpad = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, coutp - cout)))
    bpad = jnp.pad(b, (0, coutp - cout)).reshape(1, coutp)

    bci = _lane_tile(block_cin, cin)
    cinp = _rup(cin, bci)
    cin_steps = cinp // bci
    if cinp > cin:  # zero channels contribute nothing to the dot
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cinp - cin)))
        wpad = jnp.pad(wpad, ((0, 0), (0, 0), (0, cinp - cin), (0, 0)))

    if pool is not None:
        pwin, pstr = pool
        oh, ow = (ho - pwin) // pstr + 1, (wo - pwin) // pstr + 1
    else:
        oh, ow = ho, wo

    bh = min(block_h or default_block_h(oh, wo), oh)
    conv_rows = band_geometry(bh, kh, sh, pool)[0]
    n_bands = -(-oh // bh)
    ohp = n_bands * bh
    # conv-row distance between band starts (the input row distance
    # over the conv stride)
    conv_step = bh * (pool[1] if pool is not None else 1)
    cols = _out_cols(wo, bci, bco)
    # Rows past the last valid output row read zero-padding (zero ==
    # symmetric quantization zero-point); their outputs are sliced off.
    x, band = _halo_band(x, strides, kh, kw, conv_rows, cols,
                         (n_bands - 1) * conv_step)

    in_specs = [
        # Overlapping halo bands: element-offset indexing; the map
        # ignores `co`, so the band slice stays resident across the Cout
        # tiles (no per-tile input re-read).
        _espec(band + (bci,),
               lambda ni, hi, co, ci: (ni, 0, hi * conv_step, 0, 0,
                                       _tile_off(ci, bci, cin_steps))),
        pl.BlockSpec((kh, kw, bci, bco),
                     lambda ni, hi, co, ci: (0, 0, ci, co)),
        pl.BlockSpec((1, bco), lambda ni, hi, co, ci: (0, co)),
    ]
    operands = [x, wpad, bpad]
    if per_channel:
        # per-lane shift counts ride next to the bias row (same
        # per-Cout-block spec; padded lanes shift by 0 and are sliced)
        svec = jnp.pad(jnp.asarray(shift, jnp.int32),
                       (0, coutp - cout)).reshape(1, coutp)
        in_specs.append(
            pl.BlockSpec((1, bco), lambda ni, hi, co, ci: (0, co)))
        operands.append(svec)
    if skip is not None:
        assert skip.shape == (n, ho, wo, cout), (skip.shape, (n, ho, wo, cout))
        # Conv-row band of the residual operand.  Bands of conv rows
        # overlap when a pool is fused (the pool-window carry), so the
        # skip spec takes element offsets too; its rows step by the
        # *conv* row distance between bands.
        skip_rows = (n_bands - 1) * conv_step + conv_rows
        skip = jnp.pad(skip, ((0, 0), (0, max(0, skip_rows - ho)),
                              (0, cols - wo), (0, coutp - cout)))
        in_specs.append(
            _espec((1, conv_rows, cols, bco),
                   lambda ni, hi, co, ci: (ni, hi * conv_step, 0,
                                           _tile_off(co, bco, n_co))))
        operands.append(skip)

    out = pl.pallas_call(
        functools.partial(
            _qconv_band_kernel,
            strides=strides,
            conv_hw=(conv_rows, cols),
            wo=wo,
            cin_steps=cin_steps,
            has_shift_vec=per_channel,
            has_skip=skip is not None,
            shift=0 if per_channel else shift,
            relu=relu,
            pool=pool,
            skip_shifts=skip_shifts,
            merge_shift=merge_shift,
            merge_relu=merge_relu,
        ),
        grid=(n, n_bands, n_co, cin_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, ow, bco),
                               lambda ni, hi, co, ci: (ni, hi, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, ohp, ow, coutp), jnp.int8),
        scratch_shapes=_scratch((conv_rows, cols), bco, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:, :oh, :, :cout]


@functools.partial(
    jax.jit,
    static_argnames=("strides", "shift", "relu", "pool", "block_c",
                     "block_h", "skip_shifts", "merge_shift", "merge_relu",
                     "out_off", "concat_shift", "concat_relu", "interpret"),
)
def qdwconv2d(
    x: jnp.ndarray,  # (N, Hp, Wp, Cin) int8, pre-padded (VALID conv)
    w: jnp.ndarray,  # (KH, KW, Cout) int8 — one 2-D filter per out channel
    b: Optional[jnp.ndarray],  # (Cout,) int32
    *,
    strides: Tuple[int, int] = (1, 1),
    shift=0,         # int | length-Cout tuple (per-channel shift vector)
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,
    block_c: int = 128,
    block_h: Optional[int] = None,
    skip: Optional[jnp.ndarray] = None,  # (N, Ho, Wo, Cout) int8 residual
    skip_shifts: Tuple[int, int] = (0, 0),
    merge_shift: int = 0,
    merge_relu: bool = False,
    out_buf: Optional[jnp.ndarray] = None,  # shared concat merge buffer
    out_off: int = 0,
    concat_shift: int = 0,
    concat_relu: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Depthwise (group == Cin, Cout = m·Cin for integer channel
    multiplier m ≥ 1) row-banded int8 conv with the same fused
    ReLU/requant/max-pool/skip/concat tail as :func:`qconv2d`.  Grid is
    ``(batch, H/block_h, Cout/block_c)`` — the channel tile is the
    per-group Cout tile; with m > 1 each tile reads the matching
    ``block_c / m`` input channels (the tile is kept a multiple of m).
    ``shift`` as a length-Cout tuple stages the per-channel shift row,
    ``skip`` fuses a residual add, and ``out_buf``/``out_off`` write the
    result into a channel-offset slice of a shared concat merge buffer,
    all exactly as in :func:`qconv2d`."""
    n, hp, wp, c_in = x.shape
    kh, kw, cout = w.shape
    assert cout % c_in == 0, (x.shape, w.shape)
    m = cout // c_in
    sh, sw = strides
    ho = (hp - kh) // sh + 1
    wo = (wp - kw) // sw + 1
    if b is None:
        b = jnp.zeros((cout,), jnp.int32)

    per_channel = isinstance(shift, tuple)
    if per_channel:
        assert len(shift) == cout, (len(shift), cout)

    if pool is not None:
        pwin, pstr = pool
        oh, ow = (ho - pwin) // pstr + 1, (wo - pwin) // pstr + 1
    else:
        oh, ow = ho, wo
    ps = pool[1] if pool is not None else 1

    bh = min(block_h or default_block_h(oh, wo), oh)
    conv_rows = band_geometry(bh, kh, sh, pool)[0]
    n_bands = -(-oh // bh)
    conv_step = bh * ps

    if out_buf is not None:
        # Concat-epilogue path: exact merge geometry, clamped tiles
        # (see _qconv2d_into for the revisit-consistency argument).
        nb, ohb, owb, c_tot = out_buf.shape
        assert (nb, ohb, owb) == (n, oh, ow), (out_buf.shape, (n, oh, ow))
        assert out_off + cout <= c_tot, (out_off, cout, c_tot)
        bc = min(block_c, cout)
        bc = max(bc - bc % m, m)     # whole input channels per tile
        n_c = -(-cout // bc)
        cols = _out_cols(wo, bc // m, bc)
        x, band = _halo_band(x, strides, kh, kw, conv_rows, cols,
                             (oh - bh) * ps)

        def ostart(hi):
            return jnp.minimum(hi * bh, oh - bh)

        def cstart(ci):
            # m | bc and m | cout, so the clamped start stays a whole
            # input-channel boundary
            return jnp.minimum(ci * bc, cout - bc)

        brow = b.reshape(1, cout)
        in_specs = [
            _espec(band + (bc // m,),
                   lambda ni, hi, ci: (ni, 0, ostart(hi) * ps, 0, 0,
                                       cstart(ci) // m)),
            _espec((kh, kw, bc), lambda ni, hi, ci: (0, 0, cstart(ci))),
            _espec((1, bc), lambda ni, hi, ci: (0, cstart(ci))),
        ]
        operands = [x, w, brow]
        if per_channel:
            svec = jnp.asarray(shift, jnp.int32).reshape(1, cout)
            in_specs.append(
                _espec((1, bc), lambda ni, hi, ci: (0, cstart(ci))))
            operands.append(svec)
        if skip is not None:
            assert skip.shape == (n, ho, wo, cout), (skip.shape,
                                                     (n, ho, wo, cout))
            skip_rows = (oh - bh) * ps + conv_rows
            skip = jnp.pad(skip, ((0, 0), (0, max(0, skip_rows - ho)),
                                  (0, cols - wo), (0, 0)))
            in_specs.append(
                _espec((1, conv_rows, cols, bc),
                       lambda ni, hi, ci: (ni, ostart(hi) * ps, 0,
                                           cstart(ci))))
            operands.append(skip)
        out_spec = _espec(
            (1, bh, ow, bc),
            lambda ni, hi, ci: (ni, ostart(hi), 0, out_off + cstart(ci)))
        in_specs.append(out_spec)
        operands.append(out_buf)
        return pl.pallas_call(
            functools.partial(
                _qdwconv_band_kernel,
                strides=strides,
                conv_hw=(conv_rows, cols),
                wo=wo,
                has_shift_vec=per_channel,
                has_skip=skip is not None,
                has_out_buf=True,
                multiplier=m,
                shift=0 if per_channel else shift,
                relu=relu,
                pool=pool,
                skip_shifts=skip_shifts,
                merge_shift=merge_shift,
                merge_relu=merge_relu,
                concat_shift=concat_shift,
                concat_relu=concat_relu,
            ),
            grid=(n, n_bands, n_c),
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(out_buf.shape, jnp.int8),
            scratch_shapes=_scratch((conv_rows, cols), bc, pool),
            input_output_aliases={len(operands) - 1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(*operands)

    bc = min(block_c, _rup(cout, 128))
    bc = max(bc - bc % m, m)         # whole input channels per tile
    cp = _rup(cout, bc)              # m | bc  =>  m | cp
    n_c = cp // bc
    if cp > cout:  # zero channels: zero weights/bias keep them inert
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp // m - c_in)))
    wpad = jnp.pad(w, ((0, 0), (0, 0), (0, cp - cout)))
    bpad = jnp.pad(b, (0, cp - cout)).reshape(1, cp)

    ohp = n_bands * bh
    cols = _out_cols(wo, bc // m, bc)
    x, band = _halo_band(x, strides, kh, kw, conv_rows, cols,
                         (n_bands - 1) * conv_step)

    in_specs = [
        # Halo band, channel-tiled: element offsets (rows overlap
        # between bands; channels advance by whole tiles).
        _espec(band + (bc // m,),
               lambda ni, hi, ci: (ni, 0, hi * conv_step, 0, 0,
                                   _tile_off(ci, bc // m, n_c))),
        pl.BlockSpec((kh, kw, bc), lambda ni, hi, ci: (0, 0, ci)),
        pl.BlockSpec((1, bc), lambda ni, hi, ci: (0, ci)),
    ]
    operands = [x, wpad, bpad]
    if per_channel:
        svec = jnp.pad(jnp.asarray(shift, jnp.int32),
                       (0, cp - cout)).reshape(1, cp)
        in_specs.append(pl.BlockSpec((1, bc), lambda ni, hi, ci: (0, ci)))
        operands.append(svec)
    if skip is not None:
        assert skip.shape == (n, ho, wo, cout), (skip.shape,
                                                 (n, ho, wo, cout))
        # Conv-row band of the residual operand (see qconv2d): bands of
        # conv rows overlap when a pool is fused, so element-offset rows
        # stepping by the conv row stride; channels pad to the tile grid.
        skip_rows = (n_bands - 1) * conv_step + conv_rows
        skip = jnp.pad(skip, ((0, 0), (0, max(0, skip_rows - ho)),
                              (0, cols - wo), (0, cp - cout)))
        in_specs.append(
            _espec((1, conv_rows, cols, bc),
                   lambda ni, hi, ci: (ni, hi * conv_step, 0,
                                       _tile_off(ci, bc, n_c))))
        operands.append(skip)

    out = pl.pallas_call(
        functools.partial(
            _qdwconv_band_kernel,
            strides=strides,
            conv_hw=(conv_rows, cols),
            wo=wo,
            has_shift_vec=per_channel,
            has_skip=skip is not None,
            multiplier=m,
            shift=0 if per_channel else shift,
            relu=relu,
            pool=pool,
            skip_shifts=skip_shifts,
            merge_shift=merge_shift,
            merge_relu=merge_relu,
        ),
        grid=(n, n_bands, n_c),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, ow, bc),
                               lambda ni, hi, ci: (ni, hi, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((n, ohp, ow, cp), jnp.int8),
        scratch_shapes=_scratch((conv_rows, cols), bc, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:, :oh, :, :cout]


@functools.partial(
    jax.jit,
    static_argnames=("groups", "strides", "shift", "relu", "pool",
                     "block_h", "interpret"),
)
def qgconv2d(
    x: jnp.ndarray,  # (N, Hp, Wp, Cin) int8, pre-padded (VALID conv)
    w: jnp.ndarray,  # (KH, KW, Cin/groups, Cout) int8
    b: Optional[jnp.ndarray],  # (Cout,) int32
    *,
    groups: int,
    strides: Tuple[int, int] = (1, 1),
    shift=0,         # int | length-Cout tuple (per-channel shift vector)
    relu: bool = True,
    pool: Optional[Tuple[int, int]] = None,
    block_h: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged grouped conv (1 < groups < Cin, or any group count the
    dense/depthwise kernels don't cover): row-banded Pallas path that
    puts the *group* on its own grid axis.  The channels are laid out
    group-major — group ``g`` of image ``i`` is batch row ``i*groups +
    g`` — so each grid step ``(batch*groups, H/block_h)`` contracts one
    group's whole ``Cin/groups`` channel dim against its ``Cout/groups``
    filter tile: the dense band kernel body with a single Cin step, so
    the group tile rides the MXU exactly like a dense Cout tile.  (A
    group's channel slice of the interleaved tensor is narrower than a
    128-lane tile, which Mosaic cannot move as a block.)"""
    n, hp, wp, cin = x.shape
    kh, kw, cin_g, cout = w.shape
    assert cin == cin_g * groups, (x.shape, w.shape, groups)
    assert cout % groups == 0, (cout, groups)
    cout_g = cout // groups
    sh, sw = strides
    ho = (hp - kh) // sh + 1
    wo = (wp - kw) // sw + 1
    if b is None:
        b = jnp.zeros((cout,), jnp.int32)

    per_channel = isinstance(shift, tuple)
    if per_channel:
        assert len(shift) == cout, (len(shift), cout)

    if pool is not None:
        pwin, pstr = pool
        oh, ow = (ho - pwin) // pstr + 1, (wo - pwin) // pstr + 1
    else:
        oh, ow = ho, wo

    bh = min(block_h or default_block_h(oh, wo), oh)
    conv_rows = band_geometry(bh, kh, sh, pool)[0]
    n_bands = -(-oh // bh)
    ohp = n_bands * bh
    conv_step = bh * (pool[1] if pool is not None else 1)
    cols = _out_cols(wo, cin_g, cout_g)
    xg = (x.reshape(n, hp, wp, groups, cin_g).transpose(0, 3, 1, 2, 4)
          .reshape(n * groups, hp, wp, cin_g))
    xg, band = _halo_band(xg, strides, kh, kw, conv_rows, cols,
                          (n_bands - 1) * conv_step)

    def per_group(shape):    # the group's slab of a group-major operand
        return pl.BlockSpec((pl.Squeezed(),) + shape,
                            lambda gi, hi: (gi % groups,) + (0,) * len(shape))

    in_specs = [
        _espec(band + (cin_g,),
               lambda gi, hi: (gi, 0, hi * conv_step, 0, 0, 0)),
        per_group((kh, kw, cin_g, cout_g)),
        per_group((1, cout_g)),
    ]
    operands = [xg, w.reshape(kh, kw, cin_g, groups, cout_g)
                .transpose(3, 0, 1, 2, 4),
                b.reshape(groups, 1, cout_g)]
    if per_channel:
        in_specs.append(per_group((1, cout_g)))
        operands.append(jnp.asarray(shift, jnp.int32)
                        .reshape(groups, 1, cout_g))

    out = pl.pallas_call(
        functools.partial(
            _qconv_band_kernel,
            strides=strides,
            conv_hw=(conv_rows, cols),
            wo=wo,
            cin_steps=1,
            has_shift_vec=per_channel,
            has_skip=False,
            shift=0 if per_channel else shift,
            relu=relu,
            pool=pool,
            skip_shifts=(0, 0),
            merge_shift=0,
            merge_relu=False,
        ),
        grid=(n * groups, n_bands),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, ow, cout_g),
                               lambda gi, hi: (gi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n * groups, ohp, ow, cout_g),
                                       jnp.int8),
        scratch_shapes=_scratch((conv_rows, cols), cout_g, pool),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    out = (out.reshape(n, groups, ohp, ow, cout_g).transpose(0, 2, 3, 1, 4)
           .reshape(n, ohp, ow, cout))
    return out[:, :oh]


def band_input_bytes(hp: int, wp: int, cin: int, kh: int, ho: int, *,
                     sh: int = 1,
                     block_h: Optional[int] = None,
                     pool: Optional[Tuple[int, int]] = None,
                     block_cin: Optional[int] = None) -> int:
    """int8 bytes of the input halo band one grid step holds in VMEM —
    the term the Cin contraction tile bounds (``block_cin=None`` means
    the whole-Cin contraction: the band carries every input channel)."""
    bh = min(block_h or ho, ho)
    _conv_rows, band_in_rows, _step = band_geometry(bh, kh, sh, pool)
    band_in_rows = min(band_in_rows, hp)
    return band_in_rows * wp * _lane_tile(block_cin, cin)


def vmem_bytes(hp: int, wp: int, cin: int, kh: int, kw: int, bco: int,
               ho: int, wo: int, *,
               sh: int = 1,
               sw: Optional[int] = None,
               block_h: Optional[int] = None,
               pool: Optional[Tuple[int, int]] = None,
               block_cin: Optional[int] = None,
               skip: bool = False,
               per_channel: bool = False) -> int:
    """Per-grid-step working-set estimate used by the DSE resource
    model: one halo row band (one Cin slice of it when ``block_cin`` is
    set) + weight tile + int32 scratch + output band, plus the residual
    skip band (``skip_vmem_bytes``) when a residual add is fused into
    the epilogue and the int32 per-lane shift row (``shift_vec_bytes``)
    when the layer is per-channel quantized.  ``ho``/``wo`` are *final*
    output rows/cols (post-pool when ``pool`` is fused);
    ``block_h=None`` means untiled (the whole plane in one band — the
    old kernel's working set).

    The tiles are the ones :func:`qconv2d` builds: a narrow strided
    conv folded to depth (:func:`_folds_to_depth`), Cin and Cout tiles
    of whole 128-lane tiles (:func:`_lane_tile`; ``bco`` rounds up the
    same way), the computed columns of :func:`_out_cols` and the
    scratch of :func:`scratch_bytes`, fused-pool buffer included."""
    sw = sw or sh
    conv_wo = (wp - kw) // sw + 1
    if _folds_to_depth((sh, sw), cin):
        hp, wp, cin, kh, kw = _s2d_shape(hp, wp, cin, kh, kw, (sh, sw))
        sh = sw = 1
    bh = min(block_h or ho, ho)
    conv_rows = band_geometry(bh, kh, sh, pool)[0]
    bci = _lane_tile(block_cin, cin)
    bco = _rup(bco, 128)
    cols = _out_cols(conv_wo, bci, bco)
    return (band_input_bytes(hp, wp, cin, kh, ho, sh=sh, block_h=block_h,
                             pool=pool, block_cin=block_cin)  # x band int8
            + kh * kw * bci * bco            # w tile int8
            + scratch_bytes((conv_rows, cols), bco, pool)
            + bh * wo * bco                  # y band int8
            + skip_vmem_bytes(conv_rows, cols, bco, skip)
            + shift_vec_bytes(bco, per_channel))


def skip_vmem_bytes(conv_rows: int, conv_wo: int, bco: int,
                    skip: bool = True) -> int:
    """int8 bytes of the residual skip band a fused-merge grid step
    holds alongside the conv working set (conv-output geometry,
    pre-pool)."""
    return conv_rows * conv_wo * bco if skip else 0


def shift_vec_bytes(lanes: int, per_channel: bool = True) -> int:
    """int32 bytes of the per-lane requant-shift row a per-channel
    quantized grid step holds next to the bias row (the epilogue's
    shift-vector operand; zero in per-tensor mode, where the shift is
    a compile-time constant)."""
    return 4 * lanes if per_channel else 0


def dw_vmem_bytes(wp: int, c: int, kh: int, kw: int, bc: int,
                  ho: int, wo: int, *,
                  sh: int = 1,
                  sw: Optional[int] = None,
                  block_h: Optional[int] = None,
                  pool: Optional[Tuple[int, int]] = None,
                  per_channel: bool = False,
                  multiplier: int = 1,
                  skip: bool = False) -> int:
    """Per-grid-step working set of the depthwise row-band kernel.  The
    input band is channel-tiled (unlike the dense kernel, which must see
    every Cin for the contraction), so ``bc`` bounds every term
    (including the per-channel shift row in per-channel mode).  ``c`` is
    the *output* channel count; with a channel ``multiplier`` m > 1 the
    input band carries only ``bc / m`` channels (each feeds m output
    lanes in-register), and ``skip`` adds the fused residual band in
    conv-output geometry, as in :func:`vmem_bytes`.  The channel tile,
    computed columns and scratch are the ones :func:`qdwconv2d`
    builds."""
    bh = min(block_h or ho, ho)
    conv_rows, band_in_rows, _step = band_geometry(bh, kh, sh, pool)
    conv_wo = (wp - kw) // (sw or sh) + 1
    bc = min(bc, _rup(c, 128))
    bc = max(bc - bc % multiplier, multiplier)
    bc_in = bc // multiplier
    cols = _out_cols(conv_wo, bc_in, bc)
    return (band_in_rows * wp * bc_in        # x band int8 (channel tile)
            + kh * kw * bc                   # per-channel taps int8
            + scratch_bytes((conv_rows, cols), bc, pool)
            + bh * wo * bc                   # y band int8
            + skip_vmem_bytes(conv_rows, cols, bc, skip)
            + shift_vec_bytes(bc, per_channel))


def gconv_vmem_bytes(wp: int, cin_g: int, cout_g: int, kh: int, kw: int,
                     ho: int, wo: int, *,
                     sh: int = 1,
                     sw: Optional[int] = None,
                     block_h: Optional[int] = None,
                     pool: Optional[Tuple[int, int]] = None,
                     per_channel: bool = False) -> int:
    """Per-grid-step working set of the ragged grouped-conv band kernel
    (:func:`qgconv2d`): one group's input-channel slice of the halo
    band, its filter tile, the int32 accumulator, and the group's
    output band — the group axis is a grid axis, so per-step VMEM never
    scales with the group count.  Computed columns and scratch are the
    ones :func:`qgconv2d` builds."""
    bh = min(block_h or ho, ho)
    conv_rows, band_in_rows, _step = band_geometry(bh, kh, sh, pool)
    cols = _out_cols((wp - kw) // (sw or sh) + 1, cin_g, cout_g)
    return (band_in_rows * wp * cin_g        # x band int8 (group slice)
            + kh * kw * cin_g * cout_g       # w tile int8
            + scratch_bytes((conv_rows, cols), cout_g, pool)
            + bh * wo * cout_g               # y band int8
            + shift_vec_bytes(cout_g, per_channel))


def _rup(x: int, mult: int) -> int:
    return -(-x // mult) * mult
