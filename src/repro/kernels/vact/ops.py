"""Public wrappers for V-ACT: shape-agnostic, auto-padded, backend glue."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.glue import (LANE, fit_block, pad_to, resolve_interpret,
                                sublane_tile)
from repro.kernels.vact import vact as _k
from repro.kernels.vact import ref as _ref


def _as2d(x):
    if x.ndim == 1:
        return x[None, :], x.shape
    return x.reshape(-1, x.shape[-1]), x.shape


def _tiles(x2):
    """(rows, features) blocks of a 2-D operand: whole dims up to the
    kernel's caps, else cap-sized blocks over a zero-padded axis."""
    bm = fit_block(x2.shape[0], _k.DEFAULT_BM, sublane_tile(x2.dtype))
    bn = fit_block(x2.shape[1], _k.DEFAULT_BN, LANE)
    return bm, bn


def vact(x: jax.Array, kind: str, n_iters: int,
         interpret: Optional[bool] = None) -> jax.Array:
    """V-ACT CORDIC activation on any-shaped fp input.

    ``kind`` is one of the CORDIC-approximated nonlinearities (tanh,
    sigmoid, softmax, ...) evaluated in ``n_iters`` shift-add rounds.
    The input is flattened to [rows, features] (last axis = features);
    rows tile at <= 256 and features at <= 128 (except softmax, whose
    row reduction must see the whole feature axis in one block).  fp32
    compute, fp32 out, original shape restored.
    """
    interpret = resolve_interpret(interpret)
    x2, shape = _as2d(x.astype(jnp.float32))
    bm, bn = _tiles(x2)
    if kind == "softmax":
        # pad rows only; columns must stay exact for the reduction
        out = _k.vact_softmax_kernel(pad_to(x2, bm), n_iters=n_iters,
                                     bm=bm, interpret=interpret)
    else:
        out = _k.vact_ew_kernel(pad_to(x2, bm, bn), kind=kind,
                                n_iters=n_iters, bm=bm, bn=bn,
                                interpret=interpret)
    return out[: x2.shape[0], : x2.shape[1]].reshape(shape)


def vact_q8(qx: jax.Array, sx: jax.Array, kind: str, n_iters: int,
            interpret: Optional[bool] = None) -> jax.Array:
    """Fused int8 -> int8 V-ACT activation (requantizing).

    Dtype contract: qx int8 with per-tensor scale ``sx`` (fp32 scalar),
    dequant + CORDIC ``kind`` + requant all inside the kernel; output
    is int8 on the fixed 1/127 grid (activations land in [-1, 1]).
    Same tiling as :func:`vact`.
    """
    x2, shape = _as2d(qx)
    bm, bn = _tiles(x2)
    s = jnp.asarray(sx, jnp.float32).reshape(1, 1)
    out = _k.vact_ew_q8_kernel(pad_to(x2, bm, bn), s, kind=kind,
                               n_iters=n_iters, bm=bm, bn=bn,
                               interpret=resolve_interpret(interpret))
    return out[: x2.shape[0], : x2.shape[1]].reshape(shape)


ref_vact = _ref.vact
ref_vact_q8 = _ref.vact_q8
