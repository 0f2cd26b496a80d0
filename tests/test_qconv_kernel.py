"""Q-Conv kernel parity suite: ops vs oracle, Pallas vs XLA taps,
integer-path conv2d_apply vs the fake-quant reference, and the
serve-vs-eval Q-vector bit-parity the packed path guarantees."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fxp import QTensor
from repro.core.policy import get_policy
from repro.core.quantizer import quantize_params
from repro.kernels.glue import resolve_interpret
from repro.kernels.qconv import ops, ref
from repro.nn.conv import conv2d_apply, conv2d_init, qconv_block
from repro.nn.module import unbox

# (B, H, W, C, N, k, stride, padding): stem-like shapes plus odd
# spatial sizes, frame-stack channel counts, and non-3x3 filters.
SHAPES = [
    (4, 10, 5, 4, 16, 3, 2, "SAME"),     # catch stem, stride 2
    (2, 32, 32, 12, 16, 3, 2, "SAME"),   # keydoor k=4 frame stack
    (3, 9, 7, 16, 32, 3, 1, "SAME"),     # odd spatial, stride 1
    (2, 8, 8, 8, 8, 3, 2, "VALID"),
    (1, 5, 5, 3, 5, 2, 1, "VALID"),      # even kernel
    (2, 7, 11, 1, 4, 3, 2, "SAME"),      # single channel
]


def _quantized_operands(shape, seed=0):
    b, h, w, c, n, k, _, _ = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (b, h, w, c))
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.maximum(amax, 1e-12) / 127.0
    qx = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    wgt = jax.random.normal(k2, (k, k, c, n)) * 0.1
    wa = jnp.max(jnp.abs(wgt), axis=(0, 1, 2), keepdims=True)
    sw = (jnp.maximum(wa, 1e-12) / 127.0).reshape(-1)
    qw = jnp.clip(jnp.round(wgt / sw), -127, 127).astype(jnp.int8)
    bias = jax.random.normal(k3, (n,)) * 0.01
    return qx, sx, qw, sw, bias


@pytest.mark.parametrize("shape", SHAPES)
def test_ops_xla_matches_oracle_bitwise(shape):
    """Eager tap-dot path == independent broadcast-sum oracle, exactly."""
    qx, sx, qw, sw, b = _quantized_operands(shape)
    stride, pad = shape[6], shape[7]
    out = ops.qconv2d_i8(qx, sx, qw, sw, b, stride=stride, padding=pad)
    want = ref.qconv2d_i8(qx, sx, qw, sw, b, stride=stride, padding=pad)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("shape", SHAPES)
def test_exact_f32_embedding_matches_int32(shape):
    """fp32-embedded integer dots == true int32 dots, bitwise (jit)."""
    qx, sx, qw, sw, b = _quantized_operands(shape, seed=1)
    stride, pad = shape[6], shape[7]
    f = functools.partial(ops.qconv2d_i8, stride=stride, padding=pad)
    a = jax.jit(functools.partial(f, exact_f32=True))(qx, sx, qw, sw, b)
    c = jax.jit(functools.partial(f, exact_f32=False))(qx, sx, qw, sw, b)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("fuse_relu", [False, True])
def test_pallas_kernel_matches_taps(shape, fuse_relu):
    """Pallas kernel (interpret on CPU) vs tap-dot path: same integer
    program, fp accumulation within 1 ulp (FMA regrouping only)."""
    qx, sx, qw, sw, b = _quantized_operands(shape, seed=2)
    stride, pad = shape[6], shape[7]
    f = functools.partial(ops.qconv2d_i8, stride=stride, padding=pad,
                          fuse_relu=fuse_relu)
    out_k = f(qx, sx, qw, sw, b, kernel=True)
    out_x = f(qx, sx, qw, sw, b)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               rtol=1e-6, atol=1e-6)


def test_kernel_interpret_fallback_on_cpu():
    """interpret=None resolves to interpreter mode off-TPU."""
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    qx, sx, qw, sw, b = _quantized_operands(SHAPES[0], seed=3)
    out = ops.qconv2d_i8(qx, sx, qw, sw, b, stride=2, kernel=True,
                         interpret=None)
    assert out.shape == (4, 5, 3, 16)


def test_fused_relu_equals_relu_of_unfused():
    qx, sx, qw, sw, b = _quantized_operands(SHAPES[1], seed=4)
    fused = ops.qconv2d_i8(qx, sx, qw, sw, b, stride=2, fuse_relu=True)
    plain = ops.qconv2d_i8(qx, sx, qw, sw, b, stride=2)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(jnp.maximum(plain, 0.0)))


def test_conv2d_apply_integer_path_matches_fake_quant():
    """Dispatch sanity: fxp8 integer path vs the ref-backend fake-quant
    conv.  Same quantization grids, different accumulation order."""
    fxp8 = get_policy("fxp8")
    p = unbox(conv2d_init(jax.random.PRNGKey(0), 4, 16, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 10, 5, 4))
    y_int = conv2d_apply(p, x, stride=2, policy=fxp8)
    y_ref = conv2d_apply(p, x, stride=2,
                         policy=dataclasses.replace(fxp8, backend="ref"))
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_conv2d_apply_pallas_backend():
    fxp8 = get_policy("fxp8")
    pal = dataclasses.replace(fxp8, backend="pallas")
    p = unbox(conv2d_init(jax.random.PRNGKey(0), 4, 16, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 10, 5, 4))
    y_pl = conv2d_apply(p, x, stride=2, policy=pal)
    y_x = conv2d_apply(p, x, stride=2, policy=fxp8)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_x),
                               rtol=1e-6, atol=1e-6)


def test_packed_weights_bit_identical_to_eval():
    """The serve-vs-eval contract at the Q-vector level: QTensor
    weights through the kernel == fp weights quantized on the fly,
    bitwise, eager and jitted."""
    fxp8 = get_policy("fxp8")
    p = unbox(conv2d_init(jax.random.PRNGKey(0), 12, 16, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 10, 10, 12))
    pq = quantize_params(p, dataclasses.replace(fxp8, per_channel=True))
    assert isinstance(pq["w"], QTensor)
    y_eval = conv2d_apply(p, x, stride=2, policy=fxp8)
    y_srv = conv2d_apply(pq, x, stride=2, policy=fxp8)
    np.testing.assert_array_equal(np.asarray(y_srv), np.asarray(y_eval))
    f = jax.jit(lambda pp, xx: conv2d_apply(pp, xx, stride=2,
                                            policy=fxp8))
    np.testing.assert_array_equal(np.asarray(f(pq, x)),
                                  np.asarray(f(p, x)))


def test_qconv_block_integer_path_gradients_match_ste():
    """The custom-vjp backward must reproduce the fake-quant STE
    gradients exactly (same dequantized operands, same fp conv vjp)."""
    fxp8 = get_policy("fxp8")
    ref_pol = dataclasses.replace(fxp8, backend="ref")
    p = unbox(conv2d_init(jax.random.PRNGKey(0), 4, 16, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 10, 5, 4))
    g = jax.grad(lambda p_, x_: qconv_block(p_, x_, policy=fxp8).sum())(
        p, x)
    g_ref = jax.grad(
        lambda p_, x_: qconv_block(p_, x_, policy=ref_pol).sum())(p, x)
    for k in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(g[k]),
                                      np.asarray(g_ref[k]))


def test_wide_policy_stays_on_fp_path():
    """w8 (a_bits=32) must keep the fake-quant fallback — integer
    activations need a quantized-activation policy."""
    from repro.nn.conv import _use_integer_conv
    w8 = get_policy("w8")
    p = unbox(conv2d_init(jax.random.PRNGKey(0), 4, 16, 3))
    assert not _use_integer_conv(w8, p["w"])
    assert _use_integer_conv(get_policy("fxp8"), p["w"])
    assert _use_integer_conv(get_policy("w4a8"), p["w"])
