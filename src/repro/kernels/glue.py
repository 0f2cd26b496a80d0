"""Glue shared by the kernel wrappers: where a ``pallas_call`` runs, and
how an operand axis is cut into blocks the TPU lowering accepts.

The Mosaic lowering takes a block whose last two dims are either the
whole array dims or whole multiples of the dtype's native
(sublane x 128-lane) tile — (8, 128) at 32 bits, (16, 128) at 16 bits,
(32, 128) for int8.  :func:`fit_block` keeps every block on one side of
that rule: an axis that fits in one block is taken whole (no padding),
a longer one is cut into ``cap``-sized blocks, ``cap`` being a multiple
of the tile, over an axis zero-padded to a multiple of ``cap``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

LANE = 128


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; ``None`` runs the Pallas
    interpreter everywhere but on a TPU."""
    return not on_tpu() if interpret is None else interpret


def sublane_tile(dtype) -> int:
    """Rows of one native tile for ``dtype``: 8 / 16 / 32 at 4 / 2 / 1
    bytes per element."""
    return 32 // jnp.dtype(dtype).itemsize


def fit_block(dim: int, cap: int, tile: int) -> int:
    """Block length for one axis of length ``dim``: the whole axis when
    it fits in ``cap``, else ``cap`` (the caller zero-pads the axis to
    a multiple of it with :func:`pad_to`).

    ``tile`` is the axis's native tile: :data:`LANE` for a last dim,
    :func:`sublane_tile` for the dim before it (the larger of the two
    where one axis plays both roles across operands).
    """
    if cap % tile:
        raise ValueError(f"block cap {cap} is not a multiple of the "
                         f"{tile}-wide tile")
    return min(dim, cap)


def pad_to(x: jax.Array, *multiples: int) -> jax.Array:
    """Zero-pad leading axis ``i`` of ``x`` up to a multiple of
    ``multiples[i]``; axes past the given multiples stay as they are."""
    pads = [(0, (-d) % m) for d, m in zip(x.shape, multiples, strict=False)]
    pads += [(0, 0)] * (x.ndim - len(pads))
    if not any(p for _, p in pads):
        return x
    return jnp.pad(x, pads)
