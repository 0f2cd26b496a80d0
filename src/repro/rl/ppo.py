"""PPO (clipped) — the paper's training algorithm — plus A2C.

Supports the paper's *two-stage* HRL schedule: stage "action" trains
stem+action+value with the sub-goal frozen; stage "subgoal" fine-tunes
the sub-goal module with everything else frozen (Sec. III: "Once the
action module is trained, its weights are frozen, and the sub-goal
module is fine-tuned independently").  Freezing = zeroing grads by
subtree, which keeps optimizer state layout stable across stages.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.rl.dists import ActionDist, Categorical
from repro.rl.gae import gae, normalize
from repro.rl.rollout import Trajectory

Array = jax.Array

_CATEGORICAL = Categorical()


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    gamma: float = 0.99
    lam: float = 0.95
    clip_eps: float = 0.2
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    epochs: int = 4
    minibatches: int = 4
    normalize_adv: bool = True


def ppo_loss(params, apply_fn: Callable, batch: dict, cfg: PPOConfig,
             dist: Optional[ActionDist] = None) -> Tuple[Array, dict]:
    """batch: flat dict of [N, ...] tensors (obs, actions, log_probs,
    advantages, returns, mask).  ``dist`` defaults to Categorical; pass
    the env's ActionDist (e.g. TanhGaussian) for continuous control.
    """
    dist = dist or _CATEGORICAL
    dparams, values = apply_fn(params, batch["obs"])
    dparams = dparams.astype(jnp.float32)
    logp = dist.log_prob(dparams, batch["actions"])

    mask = batch.get("mask")
    mean = (lambda x: (x * mask).sum() / jnp.maximum(mask.sum(), 1)) \
        if mask is not None else jnp.mean

    ratio = jnp.exp(logp - batch["log_probs"])
    adv = batch["advantages"]
    pg = -jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv)
    pg_loss = mean(pg)

    v_loss = 0.5 * mean(jnp.square(values - batch["returns"]))
    entropy = mean(dist.entropy(dparams))

    loss = pg_loss + cfg.vf_coef * v_loss - cfg.ent_coef * entropy
    stats = {"loss": loss, "pg_loss": pg_loss, "v_loss": v_loss,
             "entropy": entropy,
             "approx_kl": mean(batch["log_probs"] - logp)}
    return loss, stats


def a2c_loss(params, apply_fn: Callable, batch: dict, cfg: PPOConfig,
             dist: Optional[ActionDist] = None) -> Tuple[Array, dict]:
    dist = dist or _CATEGORICAL
    dparams, values = apply_fn(params, batch["obs"])
    dparams = dparams.astype(jnp.float32)
    logp = dist.log_prob(dparams, batch["actions"])

    # same liveness-mask contract as ppo_loss: a masked (dead/straggler)
    # slot contributes zero loss
    mask = batch.get("mask")
    mean = (lambda x: (x * mask).sum() / jnp.maximum(mask.sum(), 1)) \
        if mask is not None else jnp.mean

    pg_loss = -mean(logp * batch["advantages"])
    v_loss = 0.5 * mean(jnp.square(values - batch["returns"]))
    entropy = mean(dist.entropy(dparams))
    loss = pg_loss + cfg.vf_coef * v_loss - cfg.ent_coef * entropy
    return loss, {"loss": loss, "pg_loss": pg_loss, "v_loss": v_loss,
                  "entropy": entropy}


def batch_from_traj(traj: Trajectory, last_value: Array,
                    cfg: PPOConfig,
                    actor_mask: Optional[Array] = None,
                    value_fn: Optional[Callable] = None) -> dict:
    """GAE over [T, B] then flatten to [T*B, ...].

    ``actor_mask`` [B] (1 = actor delivered, 0 = straggler/dead): masked
    actors contribute zero loss — the aggregator's timeout semantics —
    and are excluded from the advantage-normalization statistics so a
    dead slot's stale trajectory cannot skew the live envs' updates.

    ``value_fn`` (obs [N, ...] -> values [N]) prices the truncation
    bootstrap: one extra forward over ``traj.next_obs`` so timed-out
    rows bootstrap from V(final_obs) instead of being cut like
    terminations.  Pass the learner's value head (the rollout hot path
    stays untouched).  Without it, truncations fall back to the legacy
    cut-at-boundary targets (biased at timeouts).
    """
    if value_fn is not None:
        T, B = traj.rewards.shape
        nobs = traj.next_obs.reshape((T * B,) + traj.next_obs.shape[2:])
        boot = value_fn(nobs).reshape(T, B)
        advs, rets = gae(traj.rewards, traj.values, traj.dones,
                         last_value, cfg.gamma, cfg.lam,
                         truncated=traj.truncated, bootstrap_values=boot)
    else:
        advs, rets = gae(traj.rewards, traj.values, traj.boundary,
                         last_value, cfg.gamma, cfg.lam)
    if cfg.normalize_adv:
        if actor_mask is not None:
            w = jnp.broadcast_to(actor_mask[None].astype(jnp.float32),
                                 advs.shape)
            n = jnp.maximum(w.sum(), 1.0)
            mu = (advs * w).sum() / n
            std = jnp.sqrt(jnp.maximum(
                (jnp.square(advs - mu) * w).sum() / n, 0.0))
            advs = (advs - mu) / (std + 1e-8)
        else:
            advs = normalize(advs)
    T, B = traj.rewards.shape
    flat = lambda x: x.reshape((T * B,) + x.shape[2:])
    batch = {
        "obs": flat(traj.obs),
        "actions": flat(traj.actions),
        "log_probs": flat(traj.log_probs),
        "advantages": flat(advs),
        "returns": flat(rets),
    }
    if actor_mask is not None:
        batch["mask"] = flat(
            jnp.broadcast_to(actor_mask[None].astype(jnp.float32),
                             (T, B)))
    return batch


# ---------------------------------------------------------------------------
# two-stage freezing
# ---------------------------------------------------------------------------

def stage_mask(params, stage: str):
    """1/0 pytree: which leaves train in this stage.

    stage "action":  stem + action head + value head (sub-goal frozen)
    stage "subgoal": sub-goal module only
    stage "all":     everything (non-hierarchical nets)
    """
    if stage == "all":
        return jax.tree.map(lambda _: 1.0, params)

    def mask_subtree(tree, on):
        return jax.tree.map(lambda _: 1.0 if on else 0.0, tree)

    out = {}
    for name, sub in params.items():
        trainable = (name == "subgoal") == (stage == "subgoal")
        out[name] = mask_subtree(sub, trainable)
    return out


def apply_stage_mask(grads, mask):
    return jax.tree.map(lambda g, m: g * m, grads, mask)


def _take_rows(v: Array, idx: Array) -> Array:
    """``v[idx]`` taken as whole flat samples: gather rows of the
    [N, prod(...)] view, then give them back the leaf's own shape."""
    return v.reshape(v.shape[0], -1)[idx].reshape(idx.shape + v.shape[1:])


def minibatch_epochs(key, params, opt_state, batch, apply_fn, cfg,
                     optimizer_step, loss_fn=ppo_loss, grad_mask=None,
                     dist: Optional[ActionDist] = None):
    """Standard PPO epochs x minibatches loop (python loop: trace-time
    constants, jit the caller).  The permutation and gather run under
    the named scope ``shuffle``, the loss forward and backward under
    ``grad``; ``optimizer_step`` names its own.

    Each minibatch gathers whole samples as flat, sample-major rows and
    takes the leaf's shape only after the gather.  Gathered as images,
    a conv's batch-minor input layout (3 channels leave the lanes to
    the batch) is pushed back onto the whole buffer, and every gather
    becomes a lane gather; as rows, it reads the rollout's own
    [T*B, H*W*C] buffer and the relayout is paid per minibatch."""
    n = batch["obs"].shape[0]
    if n % cfg.minibatches != 0:
        raise ValueError(
            f"minibatch_epochs: batch of {n} samples (rollout T*B) does "
            f"not divide into cfg.minibatches={cfg.minibatches} — the "
            f"tail {n % cfg.minibatches} samples would be silently "
            "dropped every epoch. Pick n_envs*rollout_len divisible by "
            "the minibatch count, or adjust PPOConfig.minibatches.")
    mb = n // cfg.minibatches
    stats = None
    # keep the historical 4-arg loss_fn contract intact when no dist
    # is supplied (custom losses need not know about ActionDist)
    extra = () if dist is None else (dist,)
    for _ in range(cfg.epochs):
        with jax.named_scope("shuffle"):
            key, sub = jax.random.split(key)
            perm = jax.random.permutation(sub, n)
        for i in range(cfg.minibatches):
            with jax.named_scope("shuffle"):
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                mbatch = {k: _take_rows(v, idx)
                          for k, v in batch.items()}
            with jax.named_scope("grad"):
                (_, stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, apply_fn, mbatch,
                                           cfg, *extra)
                if grad_mask is not None:
                    grads = apply_stage_mask(grads, grad_mask)
            params, opt_state = optimizer_step(params, opt_state, grads)
    return params, opt_state, stats
