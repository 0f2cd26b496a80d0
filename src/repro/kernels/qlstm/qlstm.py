"""Fused Q-LSTM cell Pallas kernel (paper's Q-LSTM block).

The paper's Q-LSTM block wires two Q-MACs (x- and h- paths) directly
into V-ACT sigmoid/tanh stages with the cell state held in local
memory.  The TPU analogue is a single Pallas kernel: both int8 gate
matmuls hit the MXU, all four gate activations run on the VPU via the
CORDIC pipeline, and c/h never leave VMEM within a step.

Grid: batch tiles only; each program computes the full 4H gate stripe
for its batch rows (RL-scale hidden sizes — the paper's agent uses
H = 32 — easily fit VMEM; the wrapper asserts the footprint).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vact.vact import _sigmoid_tile

DEFAULT_BB = 128


def _tanh_tile(x, n_iters):
    return 2.0 * _sigmoid_tile(2.0 * x, n_iters) - 1.0


def _qlstm_kernel(qx_ref, sx_ref, qh_ref, sh_ref, qw_ref, sw_ref,
                  qu_ref, su_ref, b_ref, c_ref, h_out_ref, c_out_ref,
                  *, hidden, n_iters):
    acc_x = jax.lax.dot_general(
        qx_ref[...], qw_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_h = jax.lax.dot_general(
        qh_ref[...], qu_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    gates = (acc_x.astype(jnp.float32) * sx_ref[0, 0] * sw_ref[...]
             + acc_h.astype(jnp.float32) * sh_ref[0, 0] * su_ref[...]
             + b_ref[...])
    H = hidden
    i = _sigmoid_tile(gates[:, 0 * H:1 * H], n_iters)
    f = _sigmoid_tile(gates[:, 1 * H:2 * H], n_iters)
    g = _tanh_tile(gates[:, 2 * H:3 * H], n_iters)
    o = _sigmoid_tile(gates[:, 3 * H:4 * H], n_iters)
    c_new = f * c_ref[...] + i * g
    h_out_ref[...] = _tanh_tile(c_new, n_iters) * o
    c_out_ref[...] = c_new


@functools.partial(jax.jit,
                   static_argnames=("n_iters", "bb", "interpret"))
def qlstm_cell_kernel(qx, sx, qh, sh, qw, sw, qu, su, b, c, *,
                      n_iters, bb=DEFAULT_BB, interpret=False):
    B, Din = qx.shape
    H = c.shape[-1]
    grid = (B // bb,)
    kern = functools.partial(_qlstm_kernel, hidden=H, n_iters=n_iters)
    h_new, c_new = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, Din), lambda i: (i, 0)),        # qx
            pl.BlockSpec((1, 1), lambda i: (0, 0)),           # sx
            pl.BlockSpec((bb, H), lambda i: (i, 0)),          # qh
            pl.BlockSpec((1, 1), lambda i: (0, 0)),           # sh
            pl.BlockSpec((Din, 4 * H), lambda i: (0, 0)),     # qw
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),       # sw
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),       # qu
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),       # su
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),       # b
            pl.BlockSpec((bb, H), lambda i: (i, 0)),          # c
        ],
        out_specs=[
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(qx, sx, qh, sh, qw, sw, qu, su, b, c)
    return h_new, c_new
