"""Public wrapper for the fused Q-LSTM cell kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.glue import (fit_block, pad_to, resolve_interpret,
                                sublane_tile)
from repro.kernels.qlstm import qlstm as _k
from repro.kernels.qlstm import ref as _ref

# VMEM budget guard for the full-stripe blocking (per-core VMEM ~ 8 MiB;
# leave generous headroom for double buffering).
_VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def qlstm_cell(qx, sx, qh, sh, qw, sw, qu, su, b, c, *,
               n_iters: int = 13, interpret: Optional[bool] = None):
    """Fused quantized LSTM cell step (one timestep, full stripe).

    Dtype contract: int8 input/hidden (qx [B, Din], qh [B, H]) with
    per-tensor fp32 scales, int8 gate weights (qw [Din, 4H],
    qu [H, 4H]) with per-column fp32 scales, fp32 bias b [4H] and cell
    state c [B, H]; int32 MACs, CORDIC gate nonlinearities
    (``n_iters`` rounds), fp32 (h', c') out.  The whole [Din + H, 4H]
    weight stripe must fit VMEM (checked; tile H or fall back to
    qmac+vact otherwise); the batch is one block up to
    ``_k.DEFAULT_BB`` rows, else ``DEFAULT_BB``-row blocks over a
    zero-padded batch.
    """
    B, Din = qx.shape
    H = c.shape[-1]
    footprint = (Din * 4 * H) + (H * 4 * H) + 4 * (4 * H) * 4
    if footprint > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"qlstm full-stripe blocking needs {footprint} B of VMEM "
            f"(> {_VMEM_BUDGET_BYTES}); tile H or fall back to qmac+vact")
    bb = fit_block(B, _k.DEFAULT_BB, sublane_tile(jnp.int8))
    qx, qh, c = pad_to(qx, bb), pad_to(qh, bb), pad_to(c, bb)
    sx = jnp.asarray(sx, jnp.float32).reshape(1, 1)
    sh = jnp.asarray(sh, jnp.float32).reshape(1, 1)
    sw = jnp.asarray(sw, jnp.float32).reshape(1, 4 * H)
    su = jnp.asarray(su, jnp.float32).reshape(1, 4 * H)
    b = jnp.asarray(b, jnp.float32).reshape(1, 4 * H)
    h_new, c_new = _k.qlstm_cell_kernel(qx, sx, qh, sh, qw, sw, qu, su,
                                        b, c, n_iters=n_iters, bb=bb,
                                        interpret=resolve_interpret(
                                            interpret))
    return h_new[:B], c_new[:B]


ref_qlstm_cell = _ref.qlstm_cell
