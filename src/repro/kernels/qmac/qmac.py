"""Q-MAC: int8 SIMD matmul Pallas TPU kernel (paper Sec. III-A).

TPU adaptation of the paper's 16x-8-bit-multiplier MAC array: the MXU
consumes int8 operand tiles at 2x the bf16 rate, so the "16 MACs/cycle
at FxP8" configuration becomes an int8 matmul whose operand tiles live
in VMEM and accumulate in int32 — with dequantization fused into the
epilogue so the fp32 result never costs an extra HBM round trip.

Blocking: (bm x bk) int8 activation tile, (bk x bn) int8 weight tile,
(bm x bn) int32 VMEM accumulator.  The K grid axis is innermost and
sequential; the accumulator is zeroed at k==0 and flushed at the last
k step (classic Pallas matmul pattern).  A tile side is either the
whole (unpadded) dim or 128, a multiple of both the 128-lane width and
int8's 32-row sublane packing (``repro.kernels.glue.fit_block``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref):
    """int8 x int8 -> int32 tile matmul with K-loop accumulation."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _mm_deq_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref):
    """Same, with fused dequant epilogue: out = acc * sx * sw (fp32)."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[...] * sw_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qmac_i8_kernel(qx, qw, *, bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                   interpret=False):
    """[M,K]i8 x [K,N]i8 -> [M,N]i32; M,K,N must be multiples of tiles."""
    m, k = qx.shape
    k2, n = qw.shape
    assert k == k2, (qx.shape, qw.shape)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(qx, qw)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qmac_i8_deq_kernel(qx, sx, qw, sw, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                       bk=DEFAULT_BK, interpret=False):
    """Fused int8 matmul + dequant.  sx: [M,1] fp32, sw: [1,N] fp32."""
    m, k = qx.shape
    _, n = qw.shape
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _mm_deq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(qx, qw, sx, sw)
