"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached (the chip's own compiler runs here; nothing executes).

Interpret mode cannot see what Mosaic refuses: strided value slices,
channel offsets off the 128-lane tiling, reshapes off the int8 tile,
scoped-VMEM overruns.  These compiles can.  They cover every conv
layer shape of the paper's AlexNet and VGG-16 at 224x224 with the
executor's default tiles (fused pools included), the Cin tile at every
``8*N_i`` the DSE can choose, ResNet-18's strided convs (the
phase-split band: AlexNet's narrow strided conv_1 is folded to depth),
every FC shape of both at batch 1 and 32 (with the weights kept in
HBM, also under ``vmap``), and the depthwise, grouped and concat-into
band kernels at a ``*_tiny`` shape.

The topology is described inside a fixture, never while a module is
imported: only one process may hold the TPU library, and with several
test workers each of them imports this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.kernels import ops

#: (name, input H=W, Cin, Cout, k, stride, pad, fused pool) — the conv
#: stages of models/cnn.py's ``alexnet()``; the parser fuses each
#: following 3x3/2 max-pool into the conv before it.
ALEXNET_CONVS = [
    ("conv_1", 224, 3, 64, 11, 4, 2, (3, 2)),
    ("conv_4", 27, 64, 192, 5, 1, 2, (3, 2)),
    ("conv_7", 13, 192, 384, 3, 1, 1, None),
    ("conv_9", 13, 384, 256, 3, 1, 1, None),
    ("conv_11", 13, 256, 256, 3, 1, 1, (3, 2)),
]


def _vgg16_convs():
    """The conv stages of ``vgg16()``, named as its builder names them
    (one counter over Conv, Relu and MaxPool nodes)."""
    out, hw, cin, node = [], 224, 3, 0
    for c, reps in ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3)):
        for r in range(reps):
            node += 1
            pool = (2, 2) if r == reps - 1 else None
            out.append((f"conv_{node}", hw, cin, c, 3, 1, 1, pool))
            node += 1                 # relu
            cin = c
        node += 1                     # maxpool
        hw //= 2
    return out


VGG16_CONVS = _vgg16_convs()

#: ``resnet18()``'s first strided block at 224x224: the 3x3/2 conv and
#: the 1x1/2 projection on 64 channels, ``sh*sw*Cin`` = 256 > 128, so
#: they read a phase-split band rather than folding to depth
RESNET18_STRIDED = [
    ("block3-conv3x3s2", 56, 64, 128, 3, 2, 1, None),
    ("block3-proj1x1s2", 56, 64, 128, 1, 2, 0, None),
]

#: the executor's default tiles: ``make_executor(n_i=16, n_l=32)``
DEFAULT_BLOCK_CIN, DEFAULT_BLOCK_COUT = 8 * 16, 8 * 32
#: every ``N_i`` the DSE offers these models (``feasible_ni`` under the
#: framework cap of 16)
DSE_NI = (1, 2, 4, 8, 16)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pallas_calls(fn, *shapes):
    """Every ``pallas_call`` in ``fn``'s jaxpr, as the text of its
    parameters: the kernel body, grid, block specs and compiler
    parameters, which are all Mosaic is given."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(str(eqn.params))
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    walk(v.jaxpr)
                elif isinstance(v, Jaxpr):
                    walk(v)

    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return found


def _compile_conv(sharding, hw, cin, cout, k, stride, pad, pool,
                  block_cin=DEFAULT_BLOCK_CIN, groups=1, depthwise=False,
                  out_buf=None, out_off=0, kernels_only=False):
    w_shape = (k, k, 1 if depthwise else cin // groups, cout)
    shapes = [_sds((1, hw, hw, cin), jnp.int8, sharding),
              _sds(w_shape, jnp.int8, sharding),
              _sds((cout,), jnp.int32, sharding)]
    if out_buf is not None:
        shapes.append(_sds(out_buf, jnp.int8, sharding))

    def conv(x, w, b, *buf):
        kw = dict(out_buf=buf[0], out_off=out_off) if buf else {}
        return ops.qconv2d_nhwc(
            x, w, b, strides=(stride, stride), pads=(pad,) * 4, shift=7,
            relu=True, pool=pool, groups=cin if depthwise else groups,
            block_cout=DEFAULT_BLOCK_COUT, block_cin=block_cin,
            interpret=False, **kw)

    if kernels_only:
        return _pallas_calls(conv, *shapes)
    return _compile(conv, *shapes)


@pytest.mark.parametrize(
    "layer", ALEXNET_CONVS + VGG16_CONVS + RESNET18_STRIDED,
    ids=[f"alexnet-{c[0]}" for c in ALEXNET_CONVS]
    + [f"vgg16-{c[0]}" for c in VGG16_CONVS]
    + [f"resnet18-{c[0]}" for c in RESNET18_STRIDED])
def test_conv_layer_compiles_for_v5e(one_chip, layer):
    _name, *geometry = layer
    _compile_conv(one_chip, *geometry)


@pytest.mark.parametrize("n_i", DSE_NI)
@pytest.mark.parametrize("layer", [ALEXNET_CONVS[2], VGG16_CONVS[2]],
                         ids=["alexnet-conv_7", "vgg16-conv_6"])
def test_conv_compiles_at_every_dse_cin_tile(one_chip, layer, n_i):
    """Cin 192 (a ragged second Cin tile) and Cin 64 (narrower than a
    lane tile) at ``block_cin = 8*N_i``.  Every such tile rounds up to
    one 128-lane tile or the whole Cin, so each ``N_i`` must build the
    very kernel that ``test_conv_layer_compiles_for_v5e`` compiles at
    the default tile; the test holds it to that."""
    _name, *geometry = layer
    got = _compile_conv(one_chip, *geometry, block_cin=8 * n_i,
                        kernels_only=True)
    default = _compile_conv(one_chip, *geometry, kernels_only=True)
    assert len(got) == 1 and got == default


#: (K, N) of every FC layer of ``alexnet()`` and ``vgg16()``
FC_SHAPES = [(25088, 4096), (9216, 4096), (4096, 4096), (4096, 1000)]


@pytest.mark.parametrize("m", [1, 32])
@pytest.mark.parametrize("k,n", FC_SHAPES,
                         ids=[f"{k}x{n}" for k, n in FC_SHAPES])
def test_qgemm_vgg16_fc1_compiles_for_v5e(one_chip, m, k, n):
    """Every FC shape of AlexNet and VGG-16 at batch 1 and 32 with the
    tiles chosen from the shape: multi-MiB weight blocks, which Mosaic
    refuses if they overrun the scoped VMEM."""
    _compile(lambda x, w, b: ops.qgemm(x, w, b, shift=7, relu=True,
                                       interpret=False),
             _sds((m, k), jnp.int8, one_chip),
             _sds((k, n), jnp.int8, one_chip),
             _sds((n,), jnp.int32, one_chip))


def _operand_layouts(hlo: str, target: str = "tpu_custom_call"):
    """Type and layout of each operand of the first custom call to
    ``target`` in a compiled module's text."""
    call = next(ln for ln in hlo.splitlines() if f'"{target}"' in ln)
    names = re.search(r"custom-call\(([^)]*)\)", call).group(1).split(", ")
    return [re.search(rf"^\s*(?:ROOT )?{re.escape(nm)} = (\S+)", hlo,
                      re.M).group(1) for nm in names]


def test_qgemm_weights_stream_from_hbm(one_chip):
    """The weight operand stays in HBM (no ``S(1)``, VMEM, in its
    layout): XLA stages a free 4 MiB weight (the last FC of both
    networks) into VMEM ahead of the kernel, and the kernel's device
    time would leave its stream out."""
    k, n = 4096, 1000
    compiled = _compile(
        lambda x, w, b: ops.qgemm(x, w, b, shift=7, relu=True,
                                  interpret=False),
        _sds((1, k), jnp.int8, one_chip), _sds((k, n), jnp.int8, one_chip),
        _sds((n,), jnp.int32, one_chip))
    w_layout = _operand_layouts(compiled.as_text())[1]
    assert w_layout.startswith("s8[") and "S(1)" not in w_layout, w_layout


def test_qgemm_compiles_over_a_batch_of_weight_images(one_chip):
    """Fault trials vmap weight images through one executor
    (core/ser.py); the HBM constraint has no batching rule, so the batch
    goes to the kernel without it."""
    k, n = 4096, 1000
    _compile(jax.vmap(lambda x, w, b: ops.qgemm(x, w, b, shift=7, relu=True,
                                                interpret=False),
                      in_axes=(None, 0, None)),
             _sds((1, k), jnp.int8, one_chip),
             _sds((3, k, n), jnp.int8, one_chip),
             _sds((n,), jnp.int32, one_chip))


def test_depthwise_band_kernel_compiles_for_v5e(one_chip):
    """mobilenet_tiny's strided depthwise stage: 3x3/2 on 16x16x32."""
    _compile_conv(one_chip, 16, 32, 32, 3, 2, 1, None, depthwise=True)


def test_grouped_band_kernel_compiles_for_v5e(one_chip):
    """A ragged grouped conv (4 groups of 8 channels) on 16x16x32."""
    _compile_conv(one_chip, 16, 32, 32, 3, 1, 1, None, groups=4)


#: Mosaic's block rule: the last two block dims are multiples of
#: (8, 128) or the whole array dims.  A concat operand's channel slice
#: of a wider merge buffer is neither.
_LANE_SLICE_REFUSED = (
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the "
    "overall array")


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason=_LANE_SLICE_REFUSED + " (12-channel slice of 32)")
def test_concat_into_band_kernel_compiles_for_v5e(one_chip):
    """googlenet_tiny's second inception: the 3x3 branch (8 -> 12
    channels on 6x6) writes channels [10, 22) of the 32-channel merge
    buffer."""
    _compile_conv(one_chip, 6, 8, 12, 3, 1, 1, None,
                  out_buf=(1, 6, 6, 32), out_off=10)
