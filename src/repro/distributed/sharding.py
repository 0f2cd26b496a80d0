"""Logical-axis sharding (MaxText-style) for the production meshes.

Parameters are annotated with *logical* axis names at init time
(nn/module.Param).  A per-(arch, mesh) rule table maps logical names to
mesh axes; ``make_shardings`` turns an axes tree into NamedShardings,
and ``constrain`` applies in-graph sharding constraints to activations
(used for sequence-parallel activations and MoE dispatch buffers).

Rule resolution handles the two mesh flavours transparently:
("data","model") single-pod and ("pod","data","model") multi-pod — the
"batch" logical axis maps to all data-like axes present.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fxp import QTensor

AxisName = Union[str, Tuple[str, ...], None]

# Base logical->mesh rules.  Per-arch overrides replace entries (e.g.
# kv_heads -> "model" only when divisible; experts -> "model" for EP).
BASE_RULES: Dict[str, AxisName] = {
    "batch": "__data__",      # expands to ("pod","data") when present
    "seq": None,              # flip to "model" for sequence parallelism
    # FSDP/ZeRO-3: the d_model dim of every weight is sharded over the
    # data axis; XLA all-gathers weights per layer inside the scan and
    # reduce-scatters their gradients.  Without this, params+optimizer
    # of the 72B arch are 65 GiB/device; with it they are ~2.5 GiB.
    "d_model": "data",
    "heads": "model",
    "kv_heads": None,
    "d_ff": "model",
    "d_ff_expert": "model",
    "experts": None,
    "d_inner": "model",
    "vocab": "model",
    "layers": None,
}


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_axis_size(mesh: Mesh) -> int:
    """Total number of data-parallel slots (product of data-like axes)."""
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def resolve(rules: Dict[str, AxisName], name: Optional[str],
            mesh: Mesh) -> AxisName:
    if name is None:
        return None
    r = rules.get(name, None)
    if r == "__data__":
        ax = data_axes(mesh)
        return ax if ax else None
    if isinstance(r, str) and r not in mesh.axis_names:
        return None
    return r


def spec_for(axes, rules: Dict[str, AxisName], mesh: Mesh) -> P:
    if axes is None:
        return P()
    resolved = []
    used = set()
    for a in axes:
        r = resolve(rules, a, mesh)
        # a mesh axis may appear once per spec (e.g. seq->model under
        # SP collides with vocab->model): first occurrence wins
        flat = r if isinstance(r, tuple) else (r,) if r else ()
        if any(f in used for f in flat):
            r = None
        else:
            used.update(flat)
        resolved.append(r)
    return P(*resolved)


def make_shardings(params_like, axes_tree, mesh: Mesh,
                   rules: Optional[Dict[str, AxisName]] = None):
    """NamedSharding tree matching ``params_like`` (handles QTensor).

    ``params_like`` may be concrete arrays or ShapeDtypeStructs; the
    axes tree holds logical-axis tuples at the positions of (pre-
    quantization) weights.
    """
    rules = dict(BASE_RULES, **(rules or {}))

    def one(leaf, axes):
        if isinstance(leaf, QTensor):
            q_spec = spec_for(axes, rules, mesh)
            # scale: broadcast dims unsharded, last dim follows weight
            n = leaf.scale.ndim
            last = q_spec[-1] if len(q_spec) else None
            s_spec = P(*([None] * (n - 1) + [last])) if n else P()
            return QTensor(NamedSharding(mesh, q_spec),
                           NamedSharding(mesh, s_spec), leaf.bits)
        return NamedSharding(mesh, spec_for(axes, rules, mesh))

    return jax.tree.map(one, params_like, axes_tree,
                        is_leaf=lambda l: isinstance(l, QTensor))


# ---------------------------------------------------------------------------
# activation constraints via a thread-local mesh/rules context
# ---------------------------------------------------------------------------

_ctx = threading.local()


@contextlib.contextmanager
def mesh_rules(mesh: Optional[Mesh],
               rules: Optional[Dict[str, AxisName]] = None):
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, dict(BASE_RULES, **(rules or {}))) if mesh else None
    try:
        yield
    finally:
        _ctx.state = prev



def current_mesh() -> Optional[Mesh]:
    state = getattr(_ctx, "state", None)
    return state[0] if state else None


def constrain(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    """Apply a logical sharding constraint if a mesh context is active."""
    state = getattr(_ctx, "state", None)
    if state is None:
        return x
    mesh, rules = state
    spec = spec_for(axes, rules, mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def batch_spec(mesh: Mesh, extra_dims: int = 1,
               batch_size: Optional[int] = None) -> P:
    """PartitionSpec for [batch, ...] inputs: batch over all data axes.

    If ``batch_size`` is given and does not divide the data axes
    (long_500k runs with global_batch=1), the batch dim is replicated —
    pjit argument shardings require exact divisibility.
    """
    ax = data_axes(mesh)
    if ax and batch_size is not None:
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        if batch_size % n != 0:
            ax = ()
    return P(ax if ax else None, *([None] * extra_dims))
