"""shard_map MoE dispatch — explicit EP / TP-within-expert execution.

XLA's SPMD partitioner cannot partition the capacity-buffer scatter of
a global-view MoE dispatch (it falls back to replicating the [E, C, D]
buffers — 100+ GiB/device at 1M-token steps).  Here the data movement
is *written down* with shard_map + lax collectives instead of inferred:

  EP  (E % model == 0, qwen3-moe):
      local dispatch -> all_to_all over "model" (split experts, concat
      capacity) -> each device runs its E/m experts over m*C_loc slots
      -> all_to_all back -> local combine.
  TPE (E < model, mixtral):
      experts replicated, d_ff model-sharded: local dispatch -> local
      partial FFN -> psum over "model" -> local combine.

Expert weights arrive FSDP-sharded on d_model ("data") and are
all-gathered inside the body — the same per-layer weight traffic the
dense layers get from the SPMD partitioner.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.policy import QuantPolicy
from repro.core.qmatmul import q_batched_matmul
from repro.core.vact import activation

Array = jax.Array


def _local_dispatch(x_rep, e_flat, n_experts: int, capacity: int):
    """Group this shard's (token, k) pairs by expert id — GATHER
    formulation: slot (e, c) pulls sorted-token starts[e]+c.  The index
    tensors stay [E, C] / [Tk] (a few MB); the scatter formulation's
    backward materializes u32/f32 [E, C, D] index/operand buffers
    (~4 GB each at 1M-token steps, measured 3x step traffic).

    x_rep: [Tk_loc, D] -> (buf [E, C, D], pos_c [Tk_loc], keep)."""
    tk = e_flat.shape[0]
    order = jnp.argsort(e_flat)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(tk) - starts[sorted_e]
    pos = jnp.zeros_like(ranks).at[order].set(ranks)
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, capacity)

    slot = starts[:, None] + jnp.arange(capacity)[None]      # [E, C]
    valid = jnp.arange(capacity)[None] < counts[:, None]     # [E, C]
    token = order[jnp.clip(slot, 0, tk - 1)]                 # [E, C]
    buf = x_rep[token] * valid[..., None].astype(x_rep.dtype)
    return buf, pos_c, keep


def _expert_ffn(buf, w_gate, w_up, w_down, policy, act):
    g = q_batched_matmul(buf, w_gate, policy)
    u = q_batched_matmul(buf, w_up, policy)
    h = activation(g, act, policy) * u
    return q_batched_matmul(h, w_down, policy)


def moe_shard_map(x, router_w, w_gate, w_up, w_down, mesh, *,
                  top_k: int, capacity_factor: float,
                  policy: Optional[QuantPolicy], act: str) -> Array:
    """x: [B, S, D] (batch-sharded over the data axes) -> [B, S, D]."""
    B, S, D = x.shape
    E = w_gate.shape[0]
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in dax:
        n_data *= mesh.shape[a]
    m = mesh.shape.get("model", 1)
    ep = E % m == 0 and E >= m and m > 1
    t_loc = (B * S) // n_data
    cap = max(int(math.ceil(t_loc * top_k / E * capacity_factor)), 4)

    from repro.core.fxp import QTensor, as_dense
    serve = isinstance(w_gate, QTensor)      # PTQ int8 weights loaded
    fsdp = (dax if not serve else None) or None
    if ep:
        w_in_spec = P("model", fsdp, None)
        w_out_spec = P("model", None, fsdp)
    else:
        w_in_spec = P(None, fsdp, "model")
        w_out_spec = P(None, "model", fsdp)
    rw_spec = P(fsdp, None)

    def leaf_spec(w, qv_spec):
        """QTensor weights carry their own scale spec (broadcast dims
        unsharded)."""
        if isinstance(w, QTensor):
            sspec = P(*[qv_spec[i] if w.scale.shape[i] > 1 else None
                        for i in range(w.scale.ndim)])
            return QTensor(qv_spec, sspec, w.bits)
        return qv_spec

    def body(xb, rw, wg, wu, wd):
        b_loc = xb.shape[0]
        xf = xb.reshape(-1, D)
        cdt = policy.compute_dtype if policy else jnp.float32
        if serve:
            rw = as_dense(rw, jnp.float32)
            wg, wu, wd = (as_dense(t, cdt) for t in (wg, wu, wd))
        elif dax:
            # FSDP gather of the d_model shards (per-layer, like dense)
            wg = jax.lax.all_gather(wg, dax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, dax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, dax, axis=2, tiled=True)
            rw = jax.lax.all_gather(rw, dax, axis=0, tiled=True)

        # routing: fp32, local (replicated across "model")
        logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
        e_flat = gate_idx.reshape(-1)
        w_flat = gate_vals.reshape(-1)
        x_rep = jnp.repeat(xf, top_k, axis=0)

        buf, pos_c, keep = _local_dispatch(x_rep, e_flat, E, cap)

        if ep:
            # [E, C, D] --(split experts, concat slots)--> [E/m, mC, D]
            buf = jax.lax.all_to_all(buf, "model", split_axis=0,
                                     concat_axis=1, tiled=True)
            out_buf = _expert_ffn(buf, wg, wu, wd, policy, act)
            # [E/m, mC, D] --(split slots, concat experts)--> [E, C, D]
            out_buf = jax.lax.all_to_all(out_buf, "model", split_axis=1,
                                         concat_axis=0, tiled=True)
        else:
            # TPE: d_ff sharded -> partial d_model products, reduce
            out_buf = _expert_ffn(buf, wg, wu, wd, policy, act)
            out_buf = jax.lax.psum(out_buf, "model")

        gathered = out_buf[e_flat, jnp.minimum(pos_c, cap - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        weighted = gathered * w_flat[:, None].astype(gathered.dtype)
        out = weighted.reshape(-1, top_k, D).sum(axis=1)
        return out.reshape(b_loc, S, D).astype(xb.dtype)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(dax if dax else None, None, None),
                                 leaf_spec(router_w, rw_spec),
                                 leaf_spec(w_gate, w_in_spec),
                                 leaf_spec(w_up, w_in_spec),
                                 leaf_spec(w_down, w_out_spec)),
                       out_specs=P(dax if dax else None, None, None),
                       check_vma=False)
    return fn(x, router_w, w_gate, w_up, w_down)


def shardable(x, mesh, n_experts: int) -> bool:
    """Can this call drop to the shard_map path?"""
    if mesh is None or "model" not in mesh.axis_names:
        return False
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in dax:
        n_data *= mesh.shape[a]
    B = x.shape[0]
    return B % max(n_data, 1) == 0
