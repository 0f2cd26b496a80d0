"""Vectorized experience collection (B envs x T steps, one jit).

``apply_fn(params, obs) -> (dparams, value)`` is the *actor policy* —
``dparams`` parameterizes whatever :class:`~repro.rl.dists.ActionDist`
matches the env's action space (logits for Discrete, mean/log_std for
Box).  Pass quantized params + an FxP8 QuantPolicy and this is the
paper's quantized actor; the rollout code is precision- and
distribution-agnostic.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.rl.dists import ActionDist, distribution_for
from repro.rl.envs.base import Environment

Array = jax.Array


class Trajectory(NamedTuple):
    obs: Array          # [T, B, ...]
    actions: Array      # [T, B] (Discrete) or [T, B, d] (Box)
    log_probs: Array    # [T, B]
    values: Array       # [T, B]
    rewards: Array      # [T, B]
    dones: Array        # [T, B] terminations (no bootstrap across)
    truncated: Array    # [T, B] pure timeouts (bootstrap through)
    next_obs: Array     # [T, B, ...] true successor obs (pre-reset)

    @property
    def boundary(self) -> Array:
        """Episode boundaries — what auto-reset/episode stats key off."""
        return self.dones | self.truncated


class RolloutResult(NamedTuple):
    traj: Trajectory
    last_value: Array   # [B]
    final_env: Any      # env state carry (resume collection)
    final_obs: Array


def init_envs(env: Environment, key: Array, n_envs: int, mesh=None):
    """Reset ``n_envs`` environments; with ``mesh``, place every state
    leaf sharded over the mesh's data axes (env axis 0) so the sharded
    collection path starts without a reshard."""
    keys = jax.random.split(key, n_envs)
    state, obs = jax.vmap(env.reset)(keys)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import data_axes
        sharding = NamedSharding(mesh, P(data_axes(mesh) or None))
        state, obs = jax.tree.map(
            lambda x: jax.device_put(x, sharding), (state, obs))
    return state, obs


def rollout(params, env: Environment, apply_fn: Callable, key: Array,
            env_state, obs, n_steps: int,
            dist: Optional[ActionDist] = None) -> RolloutResult:
    """Collect ``n_steps`` transitions from every env (scan over time)."""
    if dist is None:
        dist = distribution_for(env.action_space)

    def one(carry, step_key):
        state, obs = carry
        dparams, value = apply_fn(params, obs)
        dparams = dparams.astype(jnp.float32)
        action = dist.sample(step_key, dparams)
        logp = dist.log_prob(dparams, action)
        state, next_obs, reward, done, truncated, final_obs = \
            jax.vmap(env.step)(state, action)
        tr = Trajectory(_rows(obs), action, logp, value, reward, done,
                        truncated, _rows(final_obs))
        return (state, next_obs), tr

    keys = jax.random.split(key, n_steps)
    (env_state, obs), traj = jax.lax.scan(one, (env_state, obs), keys)
    shape = (n_steps,) + obs.shape
    traj = traj._replace(obs=traj.obs.reshape(shape),
                         next_obs=traj.next_obs.reshape(shape))
    last_value = apply_fn(params, obs)[1]
    return RolloutResult(traj, last_value, env_state, obs)


def _rows(obs: Array) -> Array:
    """[B, ...] -> [B, prod(...)]: the scan stacks observations one
    flat row per env.  Stacked as [T, B, H, W, C], a small channel
    count C sits on the TPU's 128-wide lane axis, and the buffer pads
    to 128/C times its size (43x, 16 GiB, for the E2HRL fleet)."""
    return obs.reshape(obs.shape[0], -1)


def episode_returns(traj: Trajectory) -> Tuple[Array, Array]:
    """Mean undiscounted return and count of COMPLETED episodes.

    An episode completes at any boundary — termination OR truncation
    (a timed-out episode still has a return; only its value targets
    differ).
    """
    return episode_returns_from(traj.rewards, traj.boundary)


def episode_returns_from(rewards: Array, boundary: Array
                         ) -> Tuple[Array, Array]:
    """``episode_returns`` on raw [T, B] arrays (for collection loops
    that don't build a :class:`Trajectory`, e.g. the replay drivers)."""

    def per_env(rew, done):
        def f(carry, x):
            acc, total, n = carry
            r, d = x
            acc = acc + r
            total = total + jnp.where(d, acc, 0.0)
            n = n + d.astype(jnp.int32)
            acc = jnp.where(d, 0.0, acc)
            return (acc, total, n), None

        (_, total, n), _ = jax.lax.scan(f, (0.0, 0.0, 0), (rew, done))
        return total, n

    totals, ns = jax.vmap(per_env, in_axes=1)(rewards, boundary)
    n = ns.sum()
    return totals.sum() / jnp.maximum(n, 1), n
