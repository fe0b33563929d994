"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.core.resources import VMEM_BUDGET_BYTES
from repro.kernels.qgemm import (BLOCK_VMEM_BYTES, WEIGHT_BLOCK_BYTES,
                                 fc_tiles, fc_vmem_bytes, qgemm)
from repro.kernels.qconv import qconv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, shape, np.int8))


# --------------------------------------------------------------- qgemm
@pytest.mark.parametrize("m,k,n", [(1, 16, 8), (7, 33, 65), (128, 256, 128),
                                   (200, 100, 300), (1, 9216, 64)])
@pytest.mark.parametrize("shift,relu", [(0, False), (7, True), (12, False)])
def test_qgemm_matches_ref(m, k, n, shift, relu):
    x, w = i8(m, k), i8(k, n)
    b = jnp.asarray(RNG.integers(-(1 << 20), 1 << 20, (n,), np.int32))
    got = qgemm(x, w, b, shift=shift, relu=relu, interpret=True,
                block_m=32, block_n=128, block_k=128)
    want = ref.qgemm_ref(x, w, b, shift, relu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [1, 32])
@pytest.mark.parametrize("k", [300, 9216])    # 128-rounded; 6 K blocks
@pytest.mark.parametrize("per_channel", [False, True])
@pytest.mark.parametrize("relu", [False, True])
def test_qgemm_default_tiles_match_ref(m, k, per_channel, relu):
    """The tiles chosen from the shape, N = 1000 padded to 1024."""
    n = 1000
    x, w = i8(m, k), i8(k, n)
    b = jnp.asarray(RNG.integers(-(1 << 20), 1 << 20, (n,), np.int32))
    shift = (tuple(int(s) for s in RNG.integers(4, 14, n)) if per_channel
             else 9)
    got = qgemm(x, w, b, shift=shift, relu=relu, interpret=True)
    want = ref.qgemm_ref(x, w, b, shift, relu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [1, 8, 32, 128, 200])
@pytest.mark.parametrize("k,n", [(25088, 4096), (9216, 4096), (4096, 4096),
                                 (4096, 1000), (512, 10), (300, 130),
                                 (49, 100000)])
def test_fc_tiles_rule(m, k, n):
    """Whole lane tiles dividing the 128-rounded K and N (no weight pads
    further), double-buffered blocks and the accumulator within the
    scoped VMEM, and weight blocks within their budget."""
    t = fc_tiles(m, k, n)
    kp0, np0 = -(-k // 128) * 128, -(-n // 128) * 128
    assert t.bk % 128 == 0 and t.bn % 128 == 0
    assert (t.kp, t.np_) == (kp0, np0)
    assert kp0 % t.bk == 0 and np0 % t.bn == 0
    assert t.bm % 8 == 0 and t.mp >= m and t.mp % t.bm == 0
    assert t.weight_block_bytes <= WEIGHT_BLOCK_BYTES
    assert fc_vmem_bytes(t.bm, t.bk, t.bn) <= BLOCK_VMEM_BYTES \
        <= VMEM_BUDGET_BYTES
    # as deep as the budgets allow: the next lane divisor of K breaks one
    deeper = [d for d in range(t.bk + 128, kp0 + 1, 128) if kp0 % d == 0]
    if deeper:
        d = deeper[0]
        assert (d * t.bn > WEIGHT_BLOCK_BYTES
                or fc_vmem_bytes(t.bm, d, t.bn) > BLOCK_VMEM_BYTES)


def test_fc_tiles_stream_vgg16_fc1_in_few_steps():
    t = fc_tiles(1, 25088, 4096)
    assert t.grid_steps <= 64
    assert (t.bm, t.bk, t.bn, t.grid_steps) == (8, 512, 4096, 49)
    assert fc_tiles(1, 9216, 4096).grid_steps == 18
    assert fc_tiles(1, 4096, 4096).grid_steps == 8
    assert fc_tiles(1, 4096, 1000).grid_steps == 2


def test_qgemm_no_bias():
    x, w = i8(17, 40), i8(40, 10)
    got = qgemm(x, w, None, shift=6, interpret=True)
    want = ref.qgemm_ref(x, w, None, 6, False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------- qconv
@pytest.mark.parametrize("cfg", [
    # (h, w, cin, cout, k, stride, pool)
    (12, 12, 4, 8, 3, 1, None),
    (16, 16, 3, 16, 3, 1, (2, 2)),
    (23, 23, 8, 32, 5, 2, None),
    (27, 27, 16, 24, 3, 1, (3, 2)),     # AlexNet-style overlapping pool
    (14, 14, 32, 130, 3, 1, (2, 2)),    # cout not a multiple of block
])
@pytest.mark.parametrize("shift,relu", [(8, True), (5, False)])
def test_qconv_matches_ref(cfg, shift, relu):
    h, w, cin, cout, k, stride, pool = cfg
    x = i8(2, h, w, cin)
    wt = i8(k, k, cin, cout)
    b = jnp.asarray(RNG.integers(-1000, 1000, (cout,), np.int32))
    got = qconv2d(x, wt, b, strides=(stride, stride), shift=shift, relu=relu,
                  pool=pool, block_cout=64, interpret=True)
    want = ref.qconv2d_ref(x, wt, b, (stride, stride), shift, relu, pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qconv_nchw_wrapper_pads():
    # ONNX-layout wrapper with explicit pads vs lax conv on padded input
    x = i8(1, 3, 10, 10)
    w = i8(8, 3, 3, 3)  # OIHW
    b = jnp.zeros((8,), jnp.int32)
    got = ops.qconv2d_nchw(x, w, b, strides=(1, 1), pads=(1, 1, 1, 1),
                           shift=7, relu=True, interpret=True)
    xh = jnp.pad(jnp.transpose(x, (0, 2, 3, 1)), ((0, 0), (1, 1), (1, 1), (0, 0)))
    want = ref.qconv2d_ref(xh, jnp.transpose(w, (2, 3, 1, 0)), b, (1, 1), 7, True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.transpose(want, (0, 3, 1, 2))))


# ----------------------------------------------------------- attention
@pytest.mark.parametrize("b,h,hkv,sq,skv,d", [
    (1, 4, 4, 64, 64, 32),     # MHA
    (2, 8, 2, 128, 128, 64),   # GQA 4:1
    (1, 2, 1, 100, 100, 64),   # ragged seq (padding path)
    (1, 4, 2, 32, 160, 64),    # cross/continuation: skv > sq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, hkv, sq, skv, d, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, sq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, skv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, skv, d)), dtype)
    off = skv - sq
    got = flash_attention(q, k, v, causal=True, q_offset=off,
                          block_q=32, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=off)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=1e-2)


def test_flash_attention_sliding_window():
    q = jnp.asarray(RNG.standard_normal((1, 4, 96, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 96, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 96, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=24,
                          block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-3)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 40, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 72, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 72, 64)), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=16, block_k=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-3)


# ----------------------------------------------------------------- ssd
@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 100, 4, 32, 2, 32, 32),   # ragged chunks, grouped B/C
    (1, 128, 8, 64, 1, 64, 64),
])
def test_ssd_matches_ref(b, l, h, p, g, n, chunk):
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, l, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    d = jnp.asarray(RNG.standard_normal((h,)), jnp.float32)
    got = ssd_scan(x, dt, a, bb, cc, d, chunk=chunk, interpret=True)
    want, _ = ref.ssd_ref(x, dt, a, bb, cc, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


def test_ssd_chunk_invariance():
    """Different chunk sizes must agree — the scan decomposition is exact."""
    b, l, h, p, g, n = 1, 96, 2, 16, 1, 16
    x = jnp.asarray(RNG.standard_normal((b, l, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, l, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b, l, g, n)) * 0.3, jnp.float32)
    y16 = ssd_scan(x, dt, a, bb, cc, chunk=16, interpret=True)
    y48 = ssd_scan(x, dt, a, bb, cc, chunk=48, interpret=True)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y48),
                               atol=1e-4, rtol=1e-3)


# ------------------------------------------------ property sweeps
from hypothesis import given, settings, strategies as st


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 64), k=st.integers(1, 96), n=st.integers(1, 96),
       shift=st.integers(0, 14), relu=st.booleans())
def test_qgemm_property_random_shapes(m, k, n, shift, relu):
    rng = np.random.default_rng(m * 10007 + k * 101 + n)
    x = jnp.asarray(rng.integers(-128, 128, (m, k), np.int8))
    w = jnp.asarray(rng.integers(-128, 128, (k, n), np.int8))
    b = jnp.asarray(rng.integers(-(1 << 16), 1 << 16, (n,), np.int32))
    got = qgemm(x, w, b, shift=shift, relu=relu, interpret=True,
                block_m=16, block_n=32, block_k=32)
    want = ref.qgemm_ref(x, w, b, shift, relu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(1, 48), skv=st.integers(1, 80),
       h=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2]))
def test_flash_attention_property(sq, skv, h, g):
    if skv < sq:
        skv = sq  # causal continuation requires cache >= query span
    hkv = max(1, h // g)
    hq = hkv * g
    rng = np.random.default_rng(sq * 131 + skv)
    q = jnp.asarray(rng.standard_normal((1, hq, sq, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, hkv, skv, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hkv, skv, 16)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, q_offset=skv - sq,
                          block_q=16, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=skv - sq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=1e-3)
