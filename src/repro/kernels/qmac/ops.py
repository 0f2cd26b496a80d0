"""jit'd public wrappers for the Q-MAC kernel (padding + backend glue)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.glue import (LANE, fit_block, pad_to, resolve_interpret,
                                sublane_tile)
from repro.kernels.qmac import qmac as _k
from repro.kernels.qmac import ref as _ref


def _blocks(m, k, n, bm, bn, bk):
    """Default tiles: a whole axis when it fits one block, else the
    kernel's 128-wide tiles over a zero-padded axis."""
    bm = bm or fit_block(m, _k.DEFAULT_BM, sublane_tile(jnp.int8))
    bn = bn or fit_block(n, _k.DEFAULT_BN, LANE)
    bk = bk or fit_block(k, _k.DEFAULT_BK, LANE)
    return bm, bn, bk


def qmac_i8(qx: jax.Array, qw: jax.Array, *, bm=None, bn=None, bk=None,
            interpret=None) -> jax.Array:
    """Q-MAC int8 matmul: int8 [M,K] x int8 [K,N] -> int32 [M,N].

    Dtype contract: int8 operands, int32 accumulation, int32 out (no
    epilogue).  ``bm``/``bn``/``bk`` are the M/N/K tile sizes (default:
    the whole dim up to 128, else 128); any M/K/N is accepted —
    operands are zero-padded to tile multiples and the result sliced
    back.  |acc| <= K*127*128 must fit int32, i.e. K <= 131072.
    ``interpret=None`` runs the Pallas interpreter off-TPU.
    """
    m, k = qx.shape
    _, n = qw.shape
    bm, bn, bk = _blocks(m, k, n, bm, bn, bk)
    out = _k.qmac_i8_kernel(pad_to(qx, bm, bk), pad_to(qw, bk, bn),
                            bm=bm, bn=bn, bk=bk,
                            interpret=resolve_interpret(interpret))
    return out[:m, :n]


def qmac_i8_deq(qx, sx, qw, sw, *, bm=None, bn=None, bk=None,
                interpret=None) -> jax.Array:
    """Fused dequantizing Q-MAC matmul: (qx . qw) * sx * sw -> fp32.

    Dtype contract: int8 operands, int32 MAC accumulation, fp32 out of
    the fused per-row x per-channel dequant epilogue.  Shapes:
    qx [M, K] int8, sx [M, 1] fp32 per-row (per-token) scales,
    qw [K, N] int8, sw [1, N] fp32 per-out-channel scales -> [M, N].
    Blocking and padding as in :func:`qmac_i8`.
    """
    m, k = qx.shape
    _, n = qw.shape
    bm, bn, bk = _blocks(m, k, n, bm, bn, bk)
    out = _k.qmac_i8_deq_kernel(
        pad_to(qx, bm, bk), pad_to(sx.astype(jnp.float32), bm),
        pad_to(qw, bk, bn), pad_to(sw.astype(jnp.float32), 1, bn),
        bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret))
    return out[:m, :n]


# re-export oracle for test convenience
ref_qmac_i8 = _ref.qmac_i8
ref_qmac_i8_deq = _ref.qmac_i8_deq
