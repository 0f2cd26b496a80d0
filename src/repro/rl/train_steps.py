"""Jitted per-iteration step functions for both training families.

Factories, not loose functions: each returns the *already-jitted*
iteration with the donation contract baked in, closing over everything
that is static for a run (env, nets, optimizer config, replay
backend).  Extracted from ``launch/rl_train.py`` so that

* the drivers stay orchestration-only (checkpoint flow, logging,
  weight-sync bookkeeping), and
* the trace audit (:mod:`repro.analysis.trace_audit`) can lower the
  real step functions abstractly — the exact programs training runs —
  and assert dtype/donation invariants on them without running a
  single iteration.

Donation contracts (QF401):

* on-policy ``iteration(params, opt, est, obs, packed, key, gmask,
  alive)`` donates ``opt``/``est``/``obs`` (argnums 1-3) — the
  threaded state.  ``params`` is NOT donated: ``packed`` aliases its
  unquantized leaves (biases, or the whole tree under fp32 actors),
  and a buffer cannot be both donated and passed again.
* value-based ``iteration(params, target, opt, buf, packed, est, obs,
  key, it)`` donates ``target``/``opt``/``buf``/``est``/``obs``
  (argnums 1, 2, 3, 5, 6) — without it XLA copies the whole replay
  buffer (capacity x obs, the dominant allocation) every iteration
  just to apply the circular write.  Same ``params``/``packed``
  aliasing caveat.
* the sharded value step (``make_sharded_value_iteration``) appends a
  per-slot ``alive`` arg but keeps the identical donation contract —
  the audit asserts donation survives the shard_map'd lowering too.

Telemetry (``metrics=...``): each factory optionally threads a
:mod:`repro.obs.metrics` buffer through the jitted step — appended as
the LAST argument, donated, and returned last, exactly like replay
state.  The metric updates consume already-computed traced values
(``ret``/``n_ep``/replay fill) and feed nothing back into the training
math, so the instrumented step stays bitwise identical to the
uninstrumented one (docs/observability.md contract; test-asserted).
With ``metrics=None`` (the default, and what the trace audit lowers)
signatures and donation contracts are exactly the historical ones
above.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import data_axes
from repro.obs.metrics import counter_add, gauge_max, gauge_set
from repro.optim import adamw_update
from repro.rl.actor_learner import (collect_sharded, collect_value,
                                    collect_value_sharded, fleet_mask,
                                    slot_key)
from repro.rl.ppo import batch_from_traj, minibatch_epochs
from repro.rl.replay import (normalize_weights, per_global_weights,
                             replay_size)
from repro.rl.rollout import episode_returns, episode_returns_from
from repro.rl.value import (ddpg_actor_loss, ddpg_critic_loss_td,
                            epsilon, nstep_targets, polyak)


def make_onpolicy_iteration(env, apply_fn, a_policy, mesh, dist, pcfg,
                            loss_fn, sched, ocfg, *, rollout_len: int,
                            n_envs: int, n_slots: int, metrics=None):
    """One sharded-collect + minibatch-update step (ppo / a2c)."""
    learner_apply = lambda p, o: apply_fn(p, o, None)  # noqa: E731

    def update(params, opt, est, obs, packed, key, gmask, alive):
        k1, k2 = jax.random.split(key)
        res = collect_sharded(packed, env, apply_fn, a_policy, k1, est,
                              obs, rollout_len, mesh, dist)
        mask = fleet_mask(alive, n_envs // n_slots)
        # the learner's fp32 value head prices the truncation bootstrap
        batch = batch_from_traj(res.traj, res.last_value, pcfg,
                                actor_mask=mask,
                                value_fn=lambda o: learner_apply(params,
                                                                 o)[1])

        def opt_step(p, s, g):
            p, s, _ = adamw_update(g, s, p, sched, ocfg)
            return p, s

        params, opt, stats = minibatch_epochs(
            k2, params, opt, batch, learner_apply, pcfg, opt_step,
            loss_fn=loss_fn, grad_mask=gmask, dist=dist)
        ret, n_ep = episode_returns(res.traj)
        return (params, opt, res.final_env, res.final_obs, ret,
                n_ep), stats["loss"]

    def body(params, opt, est, obs, packed, key, gmask, alive):
        return update(params, opt, est, obs, packed, key, gmask,
                      alive)[0]

    if metrics is None:
        return jax.jit(body, donate_argnums=(1, 2, 3))

    @partial(jax.jit, donate_argnums=(1, 2, 3, 8))
    def iteration(params, opt, est, obs, packed, key, gmask, alive,
                  mbuf):
        (params, opt, est, obs, ret, n_ep), loss = update(
            params, opt, est, obs, packed, key, gmask, alive)
        mbuf = counter_add(mbuf, "env_steps", rollout_len * n_envs)
        mbuf = counter_add(mbuf, "episodes", n_ep)
        mbuf = gauge_set(mbuf, "return_mean", ret)
        mbuf = gauge_set(mbuf, "loss", loss)
        mbuf = gauge_set(mbuf, "alive_frac",
                         jnp.mean(alive.astype(jnp.float32)))
        return params, opt, est, obs, ret, n_ep, mbuf

    return iteration


def _value_metric_updates(mbuf, rb, *, env_steps, n_ep, ret, eps, buf):
    """The value-family metric writes, shared by the single-device and
    sharded steps (replay_size already sums a slot-leading state)."""
    mbuf = counter_add(mbuf, "env_steps", env_steps)
    mbuf = counter_add(mbuf, "episodes", n_ep)
    mbuf = gauge_set(mbuf, "return_mean", ret)
    mbuf = gauge_set(mbuf, "epsilon", eps)
    mbuf = gauge_set(mbuf, "replay_size", replay_size(buf))
    if rb.prioritized:
        mbuf = gauge_max(mbuf, "replay_max_priority",
                         jnp.max(buf.max_p))
    return mbuf


def make_value_iteration(env, agent, rb, a_policy, sched, ocfg, *,
                         algo: str, rollout_len: int,
                         updates_per_iter: int, per_beta0: float,
                         beta_iters: int, metrics=None):
    """One collect-into-replay + sampled-updates step (dqn / qrdqn /
    ddpg)."""
    cfg = agent.cfg
    discrete = agent.discrete

    def body(params, target, opt, buf, packed, est, obs, key, it):
        k_collect, k_update = jax.random.split(key)
        eps = (epsilon(it * rollout_len, cfg) if discrete
               else jnp.zeros(()))
        (est, obs), (O, A, R, D, Tr, FO) = collect_value(
            packed, env, agent.behave, a_policy, k_collect, est, obs,
            rollout_len, eps)

        rets, nxt, disc = nstep_targets(R, D, Tr, FO, cfg.gamma,
                                        cfg.n_step)
        T, B = R.shape
        flat = lambda x: x.reshape((T * B,) + x.shape[2:])  # noqa: E731
        buf = rb.add(buf, flat(O), flat(A), flat(rets), flat(nxt),
                     flat(disc))

        # PER bias correction anneals toward full (beta=1) over the
        # run; uniform ignores it (python literal, compiles away)
        beta = (per_beta0 + (1.0 - per_beta0)
                * jnp.clip(it / beta_iters, 0.0, 1.0)
                if rb.prioritized else 1.0)

        def opt_step(p, s, g):
            p, s, _ = adamw_update(g, s, p, sched, ocfg)
            return p, s

        for _ in range(updates_per_iter):
            k_update, k_s, k_n = jax.random.split(k_update, 3)
            batch = rb.sample(buf, k_s, cfg.batch_size,
                              min_size=cfg.learn_start, beta=beta)
            if algo == "ddpg":
                g_c, td = jax.grad(ddpg_critic_loss_td, has_aux=True)(
                    params["critic"], target["critic"], target["actor"],
                    agent.critic_apply, agent.act, batch, cfg, k_n)
                c_p, c_s = opt_step(params["critic"], opt["critic"], g_c)
                g_a = jax.grad(ddpg_actor_loss)(
                    params["actor"], c_p, agent.critic_apply, agent.act,
                    batch)
                a_p, a_s = opt_step(params["actor"], opt["actor"], g_a)
                params = {"actor": a_p, "critic": c_p}
                opt = {"actor": a_s, "critic": c_s}
                target = polyak(target, params, cfg.tau)
            else:
                g, td = jax.grad(agent.loss_fn, has_aux=True)(
                    params, target,
                    lambda p, o: agent.q_apply(p, o, None), batch, cfg)
                params, opt = opt_step(params, opt, g)
                target = polyak(target, params, cfg.target_tau)
            # priority refresh from the fresh TD errors (uniform: no-op)
            buf = rb.update(buf, batch["indices"], td)

        ret, n_ep = episode_returns_from(R, D | Tr)
        return params, target, opt, buf, est, obs, ret, n_ep

    if metrics is None:
        return jax.jit(body, donate_argnums=(1, 2, 3, 5, 6))

    @partial(jax.jit, donate_argnums=(1, 2, 3, 5, 6, 9))
    def iteration(params, target, opt, buf, packed, est, obs, key, it,
                  mbuf):
        n_envs = obs.shape[0]
        eps = (epsilon(it * rollout_len, cfg) if discrete
               else jnp.zeros(()))
        params, target, opt, buf, est, obs, ret, n_ep = body(
            params, target, opt, buf, packed, est, obs, key, it)
        mbuf = _value_metric_updates(
            mbuf, rb, env_steps=rollout_len * n_envs, n_ep=n_ep,
            ret=ret, eps=eps, buf=buf)
        return params, target, opt, buf, est, obs, ret, n_ep, mbuf

    return iteration


def make_sharded_value_iteration(env, agent, srb, a_policy, sched, ocfg,
                                 mesh, *, algo: str, rollout_len: int,
                                 updates_per_iter: int, per_beta0: float,
                                 beta_iters: int, metrics=None):
    """The value-family step shard_mapped over the mesh's data axes.

    Device ``d`` collects its envs under its own behaviour stream,
    writes into *its* local replay slot, samples its stratified share
    of the global batch, and contributes a local gradient; the learner
    is the explicit ``psum`` over the data axes (divided by the alive
    count), so every device applies the identical optimizer step and
    the params stay replicated.  The PER bias correction goes global
    the same way: ``psum`` of the local sizes and ``pmax`` of the local
    weight maxima feed :func:`per_global_weights`/
    :func:`normalize_weights` — the exact math the host-side
    ``make_sharded_replay`` facade computes.

    A straggler slot (``alive[d]`` False, derived from ``FleetSync``
    staleness) still runs shape-stably but its batch weights are zeroed
    and the psum denominator counts only live slots.

    At ``n_slots=1`` the whole step is bit-exact vs
    :func:`make_value_iteration`: slot 0 keeps the raw RNG streams,
    1-device ``psum``/``pmax`` are identities, and ``/ 1.0`` and
    ``* 1.0`` are IEEE-exact.  Signature adds the per-slot ``alive``
    vector; donation contract is unchanged (argnums 1, 2, 3, 5, 6).
    """
    cfg = agent.cfg
    discrete = agent.discrete
    rb = srb.local if srb.local is not None else srb
    n_slots = srb.n_slots
    axes = data_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no data axes to "
                         "shard the value fleet over")
    if cfg.batch_size % n_slots != 0:
        raise ValueError(
            f"batch size {cfg.batch_size} does not divide evenly over "
            f"{n_slots} replay slot(s) (--batch-size)")
    n_local = cfg.batch_size // n_slots
    learn_min = max(int(cfg.learn_start), 1)
    batch_spec = P(axes)

    def psum_mean(tree, n_alive):
        return jax.tree.map(
            lambda x: jax.lax.psum(x, axes) / n_alive, tree)

    def opt_step(p, s, g):
        p, s, _ = adamw_update(g, s, p, sched, ocfg)
        return p, s

    def update_shard(params, target, opt, buf, trans, key, it, alive):
        # leading slot axis arrives sharded to size 1: take local views
        lbuf = jax.tree.map(lambda x: x[0], buf)
        O, A, rets, nxt, disc = (x[0] for x in trans)
        a_live = alive[0].astype(jnp.float32)
        n_alive = jnp.maximum(
            jax.lax.psum(a_live, axes), 1.0)

        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)

        lbuf = rb.add(lbuf, O, A, rets, nxt, disc)
        # global underfill gate: learn_start counts total transitions
        size_g = jax.lax.psum(replay_size(lbuf), axes)
        ok = (size_g >= learn_min).astype(jnp.float32)

        beta = (per_beta0 + (1.0 - per_beta0)
                * jnp.clip(it / beta_iters, 0.0, 1.0)
                if rb.prioritized else 1.0)

        k_update = key
        for _ in range(updates_per_iter):
            k_update, k_s, k_n = jax.random.split(k_update, 3)
            k_s, k_n = slot_key(k_s, idx), slot_key(k_n, idx)
            batch = rb.sample(lbuf, k_s, n_local, min_size=1, beta=beta)
            if rb.prioritized:
                w = per_global_weights(batch["probs"], size_g, beta,
                                       n_slots)
                w = normalize_weights(
                    w, jax.lax.pmax(jnp.max(w), axes))
                batch["weight"] = w * ok * a_live
            else:
                batch["weight"] = jnp.broadcast_to(ok * a_live,
                                                   (n_local,))
            if algo == "ddpg":
                g_c, td = jax.grad(ddpg_critic_loss_td, has_aux=True)(
                    params["critic"], target["critic"], target["actor"],
                    agent.critic_apply, agent.act, batch, cfg, k_n)
                c_p, c_s = opt_step(params["critic"], opt["critic"],
                                    psum_mean(g_c, n_alive))
                g_a = jax.grad(ddpg_actor_loss)(
                    params["actor"], c_p, agent.critic_apply, agent.act,
                    batch)
                a_p, a_s = opt_step(params["actor"], opt["actor"],
                                    psum_mean(g_a, n_alive))
                params = {"actor": a_p, "critic": c_p}
                opt = {"actor": a_s, "critic": c_s}
                target = polyak(target, params, cfg.tau)
            else:
                g, td = jax.grad(agent.loss_fn, has_aux=True)(
                    params, target,
                    lambda p, o: agent.q_apply(p, o, None), batch, cfg)
                params, opt = opt_step(params, opt,
                                       psum_mean(g, n_alive))
                target = polyak(target, params, cfg.target_tau)
            lbuf = rb.update(lbuf, batch["indices"], td)

        buf = jax.tree.map(lambda x: x[None], lbuf)
        return params, target, opt, buf

    update_fn = jax.shard_map(
        update_shard, mesh=mesh,
        in_specs=(P(), P(), P(), batch_spec, batch_spec, P(), P(),
                  batch_spec),
        out_specs=(P(), P(), P(), batch_spec),
        check_vma=False)

    def body(params, target, opt, buf, packed, est, obs, key, it,
             alive):
        k_collect, k_update = jax.random.split(key)
        eps = (epsilon(it * rollout_len, cfg) if discrete
               else jnp.zeros(()))
        (est, obs), (O, A, R, D, Tr, FO) = collect_value_sharded(
            packed, env, agent.behave, a_policy, k_collect, est, obs,
            rollout_len, eps, mesh)

        rets, nxt, disc = nstep_targets(R, D, Tr, FO, cfg.gamma,
                                        cfg.n_step)
        T, B = R.shape
        Bl = B // n_slots

        def slotted(x):
            # [T, B, ...] -> [n_slots, T*Bl, ...]: slot d's rows in
            # the same t-major order the single-device flat() produced
            x = x.reshape((T, n_slots, Bl) + x.shape[2:])
            x = jnp.swapaxes(x, 0, 1)
            return x.reshape((n_slots, T * Bl) + x.shape[3:])

        trans = tuple(slotted(x) for x in (O, A, rets, nxt, disc))
        params, target, opt, buf = update_fn(params, target, opt, buf,
                                             trans, k_update, it, alive)
        ret, n_ep = episode_returns_from(R, D | Tr)
        return params, target, opt, buf, est, obs, ret, n_ep

    if metrics is None:
        return jax.jit(body, donate_argnums=(1, 2, 3, 5, 6))

    @partial(jax.jit, donate_argnums=(1, 2, 3, 5, 6, 10))
    def iteration(params, target, opt, buf, packed, est, obs, key, it,
                  alive, mbuf):
        n_envs = obs.shape[0]
        eps = (epsilon(it * rollout_len, cfg) if discrete
               else jnp.zeros(()))
        params, target, opt, buf, est, obs, ret, n_ep = body(
            params, target, opt, buf, packed, est, obs, key, it, alive)
        mbuf = _value_metric_updates(
            mbuf, srb, env_steps=rollout_len * n_envs, n_ep=n_ep,
            ret=ret, eps=eps, buf=buf)
        mbuf = gauge_set(mbuf, "alive_frac",
                         jnp.mean(alive.astype(jnp.float32)))
        return params, target, opt, buf, est, obs, ret, n_ep, mbuf

    return iteration
