"""Public wrappers for Q-Conv: tap extraction, padding, backend glue.

Two interchangeable executions of the same integer program:

* ``kernel=False`` (default) — per-tap ``dot_general`` contractions.
  On TPU these are int8 -> int32 MXU dots; off-TPU the integer dot is
  embedded *exactly* in fp32 (every product and channel partial sum is
  an integer < 2^24, so fp32 sgemm returns the same bits as int32
  accumulation — and is the fast CPU path).
* ``kernel=True`` — the Pallas tap-blocked kernel
  (:func:`repro.kernels.qconv.qconv.qconv_i8_taps_kernel`), run in
  interpreter mode automatically off-TPU.

Both run the identical integer program and accumulate dequantized
taps in fp32 in the same (kh-major, kw) order.  Within one execution
context the result is bitwise reproducible — the serve-vs-eval parity
guarantee rides on both sides calling this same function.  Across
backends (Pallas vs XLA lowering) the fp tap accumulation may differ
by FMA contraction, so cross-backend agreement is to ~1 ulp (the
qconv parity suite pins this at rtol=1e-6, matching kernels/qmac).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.glue import (LANE, fit_block, on_tpu, pad_to,
                                resolve_interpret, sublane_tile)
from repro.kernels.qconv import qconv as _k
from repro.kernels.qconv import ref as _ref

# exact fp32 embedding of the int dot needs every channel partial sum
# below 2^24: C * 127 * 127 <= 2^24  =>  C <= 1040
_EXACT_F32_MAX_C = 1040


def _tap_views(qx, sx, kh, kw, stride, ho, wo):
    """The KH*KW shifted strided views of the (padded) input, in the
    kernel's (kh-major, kw) tap order."""
    taps = []
    for di in range(kh):
        for dj in range(kw):
            sl = (slice(None),
                  slice(di, di + (ho - 1) * stride + 1, stride),
                  slice(dj, dj + (wo - 1) * stride + 1, stride),
                  slice(None))
            taps.append((qx[sl], sx[sl]))
    return taps


def _padded(qx, sx, kh, kw, stride, padding):
    b, h, w, _ = qx.shape
    if padding == "SAME":
        ho, (pt, pb) = _ref.same_pads(h, kh, stride)
        wo, (plf, prt) = _ref.same_pads(w, kw, stride)
        pads = ((0, 0), (pt, pb), (plf, prt), (0, 0))
        return jnp.pad(qx, pads), jnp.pad(sx, pads), ho, wo
    if padding == "VALID":
        return qx, sx, _ref.valid_out(h, kh, stride), \
            _ref.valid_out(w, kw, stride)
    raise ValueError(f"unsupported padding {padding!r}")


def qconv2d_i8(qx: jax.Array, sx: jax.Array, qw: jax.Array,
               sw: jax.Array, b: jax.Array, *, stride: int = 1,
               padding: str = "SAME", fuse_relu: bool = False,
               kernel: bool = False,
               interpret: Optional[bool] = None,
               exact_f32: Optional[bool] = None) -> jax.Array:
    """Integer Q-Conv with fused dequant + bias (+ ReLU) epilogue.

    Dtype contract: int8 operands, int32 (or exactly-embedded fp32)
    channel accumulation, fp32 output.  Shapes:

      qx [B, H, W, C] int8      per-pixel quantized activations
      sx [B, H, W, 1] fp32      their per-pixel (rowwise) scales
      qw [KH, KW, C, N] int8    per-out-channel quantized filters
      sw fp32, size 1 or N      the per-out-channel weight scales
      b  [N] fp32               bias
      -> [B, H', W', N] fp32

    ``padding`` is "SAME" or "VALID"; any stride / odd spatial size /
    channel count is handled (the Pallas path auto-pads to tile
    multiples and slices the result back).
    """
    bsz, _, _, c = qx.shape
    kh, kw, _, n = qw.shape
    sw2 = jnp.asarray(sw, jnp.float32).reshape(1, -1)
    b2 = b.astype(jnp.float32).reshape(1, -1)
    qxp, sxp, ho, wo = _padded(qx, sx.astype(jnp.float32), kh, kw,
                               stride, padding)
    taps = _tap_views(qxp, sxp, kh, kw, stride, ho, wo)

    if kernel:
        m = bsz * ho * wo
        bm = fit_block(m, _k.DEFAULT_BM, sublane_tile(jnp.int8))
        bn = fit_block(n, _k.DEFAULT_BN, LANE)
        qxt = jnp.stack([t[0].reshape(m, c) for t in taps])
        sxt = jnp.stack([t[1].reshape(m, 1) for t in taps])
        qwt = qw.reshape(kh * kw, c, n)
        out = _k.qconv_i8_taps_kernel(
            pad_to(qxt, 1, bm, 8), pad_to(sxt, 1, bm),
            pad_to(qwt, 1, 8, bn),
            pad_to(jnp.broadcast_to(sw2, (1, n)), 1, bn),
            pad_to(b2, 1, bn), bm=bm, bn=bn, fuse_relu=fuse_relu,
            interpret=resolve_interpret(interpret))
        return out[:m, :n].reshape(bsz, ho, wo, n)

    if exact_f32 is None:
        exact_f32 = not on_tpu() and c <= _EXACT_F32_MAX_C
    dn = (((3,), (0,)), ((), ()))
    acc = jnp.zeros((bsz, ho, wo, n), jnp.float32)
    for t, (xt, st) in enumerate(taps):
        wt = qw.reshape(kh * kw, c, n)[t]
        if exact_f32:
            d = jax.lax.dot_general(xt.astype(jnp.float32),
                                    wt.astype(jnp.float32), dn)
        else:
            d = jax.lax.dot_general(
                xt, wt, dn,
                preferred_element_type=jnp.int32).astype(jnp.float32)
        acc = acc + d * st
    out = acc * sw2.reshape(1, 1, 1, -1) + b2.reshape(1, 1, 1, -1)
    return jnp.maximum(out, 0.0) if fuse_relu else out


# re-export oracle for test convenience
ref_qconv2d_i8 = _ref.qconv2d_i8
