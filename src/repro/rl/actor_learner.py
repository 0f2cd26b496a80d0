"""Q-Actor distributed actor-learner (paper Fig. 2), TPU-native.

Learner: full-precision PPO updates.
Actors:  rollouts under a *quantized* copy of the policy (FxP8 by
default) — the paper's core speed/comm lever.

Sync is modeled exactly as the paper argues it matters:
  learner -> actor: int8 payload + fp scales (``pack_weights``), a
      ~4x wire-byte cut measured by ``sync_bytes``;
  actor -> learner: trajectories, aggregated with a liveness mask —
      a dead/straggling actor's slot is masked out of the PPO loss
      (timeout semantics), so the step never blocks on one actor.
Policy lag: ``FleetSync`` is a versioned mailbox of packed weights —
the learner pushes, slots fetch at a chosen lag (0 lock-step, 1
double-buffered overlap), and per-slot staleness drives the ``alive``
straggler mask (asynchrony via dispatch overlap, not threads — the
math, staleness and payloads are faithful; transport is jit-internal).

On a real mesh the actor fleet is shard_map'd over the data axes by
``collect_sharded``: the packed int8 weights are broadcast once per
sync, each device dequantizes locally and rolls B/n_devices
environments, and the outputs come back as one global (batch-sharded)
``RolloutResult`` — see launch/rl_train.py for the driver.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fxp import QTensor
from repro.core.policy import QuantPolicy
from repro.core.quantizer import (dequantize_params, quantize_params,
                                  quantized_nbytes)
from repro.distributed.sharding import data_axes, data_axis_size
from repro.rl.dists import ActionDist, distribution_for
from repro.rl.envs.base import Environment
from repro.rl.rollout import RolloutResult, rollout

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ActorLearnerConfig:
    n_actors: int = 4
    envs_per_actor: int = 16
    rollout_len: int = 64
    comm_bits: int = 8           # learner->actor payload precision
    max_lag: int = 1             # staleness window (versions)


# -- weight sync ------------------------------------------------------------

def pack_weights(params, comm_bits: int):
    """Quantize the param tree for the wire (QTensor leaves)."""
    if comm_bits >= 32:
        return params
    return quantize_params(params, QuantPolicy(w_bits=comm_bits,
                                               per_channel=True))


def unpack_weights(packed):
    return dequantize_params(packed)


def sync_bytes(packed) -> Tuple[int, int]:
    """(payload_bytes, fp32_equivalent_bytes) for one sync."""
    stored, fp32 = quantized_nbytes(packed)
    return stored, fp32


# -- the actor fleet ---------------------------------------------------------

class FleetSync:
    """Versioned int8 weight mailbox between the learner and the fleet.

    The learner ``push``es each new packed version; actor slots
    ``fetch`` with a chosen lag (0 = lock-step, 1 = double-buffered:
    the collect for iteration k+1 runs against version k while the
    learner's k+1 update is still in flight).  Each fetch is recorded
    per slot, so ``staleness``/``alive`` are *derived* from what the
    fleet actually read — a slot that stops fetching (straggler /
    dead actor) drops out of ``alive()`` once it falls more than
    ``max_lag`` versions behind, and the driver masks its batch out of
    the loss via ``fleet_mask`` instead of blocking on it.
    """

    def __init__(self, n_slots: int, max_lag: int = 1, depth: int = 2):
        self.n_slots = max(n_slots, 1)
        self.max_lag = max(max_lag, 1)
        self.depth = max(depth, max_lag + 1, 2)
        self._buf: List = []                      # [(version, packed)]
        self._version = -1
        self._seen = [-1] * self.n_slots

    @property
    def version(self) -> int:
        """Latest published version id (-1 before the first push)."""
        return self._version

    def push(self, packed) -> int:
        self._version += 1
        self._buf.append((self._version, packed))
        if len(self._buf) > self.depth:
            self._buf.pop(0)
        return self._version

    def fetch(self, lag: int = 0, slots: Optional[List[int]] = None):
        """Read the version ``lag`` behind the newest (clamped to the
        oldest retained) and record the read for ``slots`` (default:
        the whole fleet)."""
        idx = max(len(self._buf) - 1 - max(lag, 0), 0)
        version, packed = self._buf[idx]
        for s in (range(self.n_slots) if slots is None else slots):
            self._seen[s] = version
        return packed

    def staleness(self) -> Array:
        """Versions-behind-newest per slot, [n_slots] int32."""
        return jnp.asarray([self._version - s for s in self._seen],
                           jnp.int32)

    def alive(self) -> Array:
        """[n_slots] bool — slots within the staleness budget."""
        return self.staleness() <= self.max_lag


def collect(packed, env: Environment, apply_fn: Callable,
            actor_policy: Optional[QuantPolicy], key: Array,
            env_state, obs, n_steps: int,
            dist: Optional[ActionDist] = None) -> RolloutResult:
    """One actor's contribution: dequantize the synced weights, roll."""
    with jax.named_scope("actor"):
        params = unpack_weights(packed)
    fn = (lambda p, o: apply_fn(p, o, actor_policy))
    return rollout(params, env, fn, key, env_state, obs, n_steps, dist)


def fleet_mask(alive: Array, envs_per_slot: int) -> Array:
    """Env-level float mask [n_slots * envs_per_slot] from a per-slot
    liveness vector (slot = actor in the emulation, device on a mesh)."""
    return jnp.repeat(alive.astype(jnp.float32), envs_per_slot)


def merge_results(results: List[RolloutResult],
                  alive: Array) -> Tuple[RolloutResult, Array]:
    """Stack per-actor results along the env axis; return (merged,
    env-level mask [n_actors*B]) for the masked PPO loss.

    ``alive`` [n_actors] bool — False marks a straggler whose batch is
    present (shape-stable) but masked to zero weight.

    The merged result honors the full ``RolloutResult`` contract: the
    env-state leaves are tree-concatenated along the env axis, so the
    merged ``final_env``/``final_obs`` resume collection directly.
    """
    traj = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                        *[r.traj for r in results])
    last_value = jnp.concatenate([r.last_value for r in results])
    final_env = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *[r.final_env for r in results])
    n_envs = results[0].last_value.shape[0]
    mask = fleet_mask(alive, n_envs)
    merged = RolloutResult(traj, last_value, final_env,
                           jnp.concatenate([r.final_obs for r in results]))
    return merged, mask


# -- sharded execution on a device mesh --------------------------------------

def collect_sharded(packed, env: Environment, apply_fn: Callable,
                    actor_policy: Optional[QuantPolicy], key: Array,
                    env_state, obs, n_steps: int, mesh: Mesh,
                    dist: Optional[ActionDist] = None) -> RolloutResult:
    """shard_map the actor fleet over the mesh's data axes.

    Global [B, ...] ``env_state``/``obs`` in, one global (batch-sharded)
    ``RolloutResult`` out.  The packed int8 weights and the key are
    broadcast; device ``d`` dequantizes locally and rolls envs
    ``[d*B/n, (d+1)*B/n)`` under the stream ``fold_in(key, d)`` — so the
    per-device RNG streams are independent by construction, and on a
    1-device mesh the result is bit-identical to
    ``collect(..., key=fold_in(key, 0), ...)``.
    """
    axes = data_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no data axes to "
                         "shard the actor fleet over")
    n_slots = data_axis_size(mesh)
    B = jax.tree.leaves(obs)[0].shape[0]
    if B % n_slots != 0:
        raise ValueError(
            f"n_envs={B} does not divide evenly over the mesh's "
            f"{n_slots} data slot(s) "
            f"({dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))})")
    if dist is None:
        dist = distribution_for(env.action_space)

    def slot_index():
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def body(packed, key, est, obs):
        key = jax.random.fold_in(key, slot_index())
        return collect(packed, env, apply_fn, actor_policy, key, est, obs,
                       n_steps, dist)

    batch = P(axes)             # env axis (axis 0) over the data axes
    time_major = P(None, axes)  # trajectory leaves are [T, B, ...]
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), batch, batch),
                       out_specs=RolloutResult(traj=time_major,
                                               last_value=batch,
                                               final_env=batch,
                                               final_obs=batch),
                       check_vma=False)
    return fn(packed, key, env_state, obs)


# -- value-family collection (eps-greedy / noisy behaviour actors) ------------

def slot_keys(key: Array, n_slots: int) -> Array:
    """Per-slot RNG key stack [n_slots, key_shape].

    Slot 0 keeps the caller's raw key so a 1-slot sharded run consumes
    exactly the stream the single-device path does (bit-exact by
    construction); slots d > 0 fold in the slot index for independent
    streams.  Note this differs from the on-policy ``collect_sharded``
    convention, which folds the index into every slot including 0.
    """
    ks = [key] + [jax.random.fold_in(key, d) for d in range(1, n_slots)]
    return jnp.stack(ks)


def slot_key(key: Array, idx) -> Array:
    """In-graph counterpart of ``slot_keys`` for a *traced* slot index
    (``lax.axis_index`` inside shard_map): slot 0 keeps the raw key,
    others fold the index in — bitwise the same per-slot streams as
    ``slot_keys(key, n)[idx]``."""
    return jnp.where(idx == 0, key, jax.random.fold_in(key, idx))


def collect_value(packed, env: Environment, behave_fn: Callable,
                  actor_policy: Optional[QuantPolicy], key: Array,
                  env_state, obs, n_steps: int, eps: Array):
    """One value-family actor's contribution: dequantize the synced
    weights once, scan ``n_steps`` behaviour-policy env steps.

    Returns ``((est, obs), (O, A, R, D, Tr, FO))`` with time-major
    [T, B, ...] trajectory leaves — the exact scan the value iteration
    ran inline before this was extracted, bit for bit.
    """
    with jax.named_scope("actor"):
        actor_params = unpack_weights(packed)

    def one_full(carry, k):
        est, o = carry
        with jax.named_scope("actor"):
            a = behave_fn(actor_params, o, k, eps, actor_policy)
        with jax.named_scope("env"):
            est, nxt, r, d, tr, fo = jax.vmap(env.step)(est, a)
        return (est, nxt), (o, a, r, d, tr, fo)

    keys = jax.random.split(key, n_steps)
    return jax.lax.scan(one_full, (env_state, obs), keys)


def collect_value_sharded(packed, env: Environment, behave_fn: Callable,
                          actor_policy: Optional[QuantPolicy], key: Array,
                          env_state, obs, n_steps: int, eps: Array,
                          mesh: Mesh):
    """shard_map the value-family fleet over the mesh's data axes.

    The packed int8 weights and epsilon are broadcast; device ``d``
    dequantizes locally and rolls its envs under ``slot_keys(key)[d]``.
    On a 1-device mesh the output is bit-identical to
    ``collect_value(..., key, ...)`` — slot 0 keeps the raw stream.
    """
    axes = data_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no data axes to "
                         "shard the actor fleet over")
    n_slots = data_axis_size(mesh)
    B = jax.tree.leaves(obs)[0].shape[0]
    if B % n_slots != 0:
        raise ValueError(
            f"n_envs={B} does not divide evenly over the mesh's "
            f"{n_slots} data slot(s) "
            f"({dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))})")
    keys = slot_keys(key, n_slots)

    def body(packed, keys, eps, est, obs):
        return collect_value(packed, env, behave_fn, actor_policy,
                             keys[0], est, obs, n_steps, eps)

    batch = P(axes)             # env axis (axis 0) over the data axes
    time_major = P(None, axes)  # trajectory leaves are [T, B, ...]
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), batch, P(), batch, batch),
                       out_specs=((batch, batch), (time_major,) * 6),
                       check_vma=False)
    return fn(packed, keys, eps, env_state, obs)
