"""RL subsystem tests: envs, GAE, PPO, DQN, dists, actor-learner sync."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.policy import FXP8, QuantPolicy
from repro.nn.module import unbox
from repro.rl import PPOConfig, batch_from_traj, gae, init_envs, rollout
from repro.rl.actor_learner import (merge_results, pack_weights,
                                    sync_bytes, unpack_weights)
from repro.rl.dists import Categorical, TanhGaussian, distribution_for
from repro.rl.envs import Box, Discrete, Environment, make
from repro.rl.envs.spaces import head_dim
from repro.rl.nets import (mlp_ac_apply, mlp_ac_init, mlp_pi_apply,
                           mlp_pi_init, mlp_q_apply, mlp_q_init,
                           mlp_qr_apply, mlp_qr_init, mlp_twin_q_apply,
                           mlp_twin_q_init)
from repro.rl.value import (DDPGConfig, DQNConfig, QRDQNConfig,
                            ddpg_actor_loss, ddpg_critic_loss, dqn_loss,
                            egreedy, epsilon, nstep_targets, polyak,
                            qrdqn_loss, replay_add, replay_init,
                            replay_sample)
from repro.rl.ppo import (a2c_loss, apply_stage_mask, minibatch_epochs,
                          ppo_loss, stage_mask)
from repro.rl.rollout import episode_returns


# -- envs (spot checks; the per-env contract lives in test_envs.py) ----------

def test_make_returns_typed_environment():
    env = make("cartpole")
    assert isinstance(env, Environment)
    assert env.spec.name == "cartpole"
    assert isinstance(env.action_space, Discrete)
    assert env.spec.n_actions == 2
    assert env.obs_shape == (4,)


def test_make_unknown_env_lists_registry():
    with pytest.raises(ValueError, match="cartpole"):
        make("nope")


def test_cartpole_terminates_on_angle():
    env = make("cartpole")
    s, _ = env.reset(jax.random.PRNGKey(0))
    done = False
    for _ in range(500):          # always push right -> falls over
        s, _, _, d, tr, _ = jax.jit(env.step)(s, jnp.asarray(1))
        done = done or bool(d)
        if done:
            break
        assert not bool(tr)       # falls well before the 500-step limit
    assert done


def test_keydoor_subgoal_then_goal():
    """Walking to key then door yields both bonuses and terminates."""
    from repro.rl.envs import keydoor
    s, _ = keydoor.reset(jax.random.PRNGKey(3))
    step = jax.jit(keydoor.step)

    def walk_to(s, target):
        total = 0.0
        for _ in range(2 * keydoor.GRID):
            dr = target[0] - s.agent[0]
            dc = target[1] - s.agent[1]
            if dr < 0:
                a = 0
            elif dr > 0:
                a = 1
            elif dc < 0:
                a = 2
            elif dc > 0:
                a = 3
            else:
                break
            s, _, r, d, tr, _ = step(s, jnp.asarray(a))
            total += float(r)
            if bool(d | tr):
                break
        return s, total

    key_pos = np.asarray(s.key_pos)
    s, r1 = walk_to(s, key_pos)
    assert bool(s.has_key)
    assert r1 > 0.3                       # +0.5 pickup minus step costs
    door = np.asarray(s.door)
    s2, r2 = walk_to(s, door)
    assert r2 > 0.8                       # +1.0 open minus step costs


def test_vectorized_rollout_and_returns():
    env = make("cartpole")
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    est, obs = init_envs(env, jax.random.PRNGKey(1), 8)
    res = jax.jit(lambda p, e, o: rollout(
        p, env, fn, jax.random.PRNGKey(2), e, o, 64))(params, est, obs)
    assert res.traj.rewards.shape == (64, 8)
    ret, n = episode_returns(res.traj)
    assert int(n) > 0 and float(ret) > 5.0     # random policy survives >5


# -- action distributions -----------------------------------------------

def test_distribution_for_space_kinds():
    assert isinstance(distribution_for(Discrete(4)), Categorical)
    d = distribution_for(Box(-2.0, 2.0, (1,)))
    assert isinstance(d, TanhGaussian)
    with pytest.raises(ValueError):
        distribution_for(Box(-np.inf, np.inf, (1,)))


def test_head_dim():
    assert head_dim(Discrete(6)) == 6
    assert head_dim(Box(-1.0, 1.0, (3,))) == 6


def test_categorical_matches_log_softmax():
    dist = Categorical()
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 3))
    a = jnp.array([0, 2, 1, 2, 0])
    expect = jax.nn.log_softmax(logits)[jnp.arange(5), a]
    np.testing.assert_allclose(np.asarray(dist.log_prob(logits, a)),
                               np.asarray(expect), rtol=1e-6)
    ent = dist.entropy(jnp.zeros((2, 4)))
    np.testing.assert_allclose(np.asarray(ent), np.log(4.0), rtol=1e-5)


def test_tanh_gaussian_samples_in_bounds_and_logprob_finite():
    dist = TanhGaussian(-2.0, 2.0)
    dparams = jax.random.normal(jax.random.PRNGKey(0), (64, 2))  # d=1
    a = dist.sample(jax.random.PRNGKey(1), dparams)
    assert a.shape == (64, 1)
    # fp32 tanh saturates to exactly +/-1, so the bounds are closed
    assert bool(jnp.all((a >= -2.0) & (a <= 2.0)))
    lp = dist.log_prob(dparams, a)
    assert lp.shape == (64,)
    assert bool(jnp.all(jnp.isfinite(lp)))
    assert bool(jnp.all(jnp.isfinite(dist.entropy(dparams))))


def test_tanh_gaussian_logprob_integrates_to_one():
    """Riemann-integrate exp(log_prob) over the support: ~1."""
    dist = TanhGaussian(-2.0, 2.0)
    dparams = jnp.array([0.3, -0.5])      # mu=0.3, log_std=-0.5
    xs = jnp.linspace(-1.999, 1.999, 4001).reshape(-1, 1)
    lp = jax.vmap(lambda x: dist.log_prob(dparams, x))(xs)
    mass = float(jnp.sum(jnp.exp(lp)) * (xs[1, 0] - xs[0, 0]))
    assert mass == pytest.approx(1.0, abs=2e-2)


def test_continuous_rollout_and_ppo_loss():
    """Pendulum actions flow through rollout + PPO without reshaping."""
    env = make("pendulum")
    dist = distribution_for(env.action_space)
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 3,
                               head_dim(env.action_space)))
    fn = lambda p, o: mlp_ac_apply(p, o)
    est, obs = init_envs(env, jax.random.PRNGKey(1), 4)
    res = jax.jit(lambda p, e, o: rollout(
        p, env, fn, jax.random.PRNGKey(2), e, o, 16,
        dist))(params, est, obs)
    assert res.traj.actions.shape == (16, 4, 1)
    batch = batch_from_traj(res.traj, res.last_value, PPOConfig())
    (loss, stats), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, fn, batch, PPOConfig(), dist)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0


# -- GAE ----------------------------------------------------------------

def test_gae_matches_manual_single_env():
    r = jnp.array([[1.0], [1.0], [1.0]])
    v = jnp.array([[0.5], [0.5], [0.5]])
    d = jnp.zeros((3, 1), bool)
    lastv = jnp.array([0.5])
    adv, ret = gae(r, v, d, lastv, gamma=0.9, lam=1.0)
    # lam=1: adv_t = sum_k gamma^k r_{t+k} + gamma^{T-t} v_T - v_t
    expect0 = 1 + 0.9 + 0.81 + 0.729 * 0.5 - 0.5
    assert float(adv[0, 0]) == pytest.approx(expect0, rel=1e-5)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(adv + v))


def test_gae_stops_at_done():
    r = jnp.ones((2, 1))
    v = jnp.zeros((2, 1))
    d = jnp.array([[True], [False]])
    adv, _ = gae(r, v, d, jnp.array([10.0]), gamma=0.9, lam=0.95)
    assert float(adv[0, 0]) == pytest.approx(1.0)  # no bootstrap past done


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10**6))
def test_gae_zero_when_values_consistent(seed):
    """If v exactly equals discounted return, advantages are ~0."""
    key = jax.random.PRNGKey(seed)
    r = jax.random.uniform(key, (5, 2))
    lastv = jnp.zeros((2,))
    d = jnp.zeros((5, 2), bool)
    # v_t = r_t + g*v_{t+1}
    g = 0.9
    vs = []
    nxt = lastv
    for t in range(4, -1, -1):
        nxt = r[t] + g * nxt
        vs.append(nxt)
    v = jnp.stack(vs[::-1])
    # v here includes r_t; GAE defines delta = r + g*v' - v, so feed
    # v_t as value BEFORE reward: shift
    adv, _ = gae(r, v, d, lastv, gamma=g, lam=0.95)
    # delta_t = r_t + g v_{t+1} - v_t = 0 by construction
    np.testing.assert_allclose(np.asarray(adv), 0.0, atol=1e-4)


# -- PPO / A2C ----------------------------------------------------------

def _tiny_batch(n=16):
    key = jax.random.PRNGKey(0)
    return {
        "obs": jax.random.normal(key, (n, 4)),
        "actions": jnp.zeros((n,), jnp.int32),
        "log_probs": jnp.full((n,), -0.69),
        "advantages": jnp.ones((n,)),
        "returns": jnp.ones((n,)),
    }


def test_ppo_loss_finite_and_grads_flow():
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    (loss, stats), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, fn, _tiny_batch(), PPOConfig())
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree.leaves(grads))
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0


def test_ppo_clipping_caps_ratio_gradient():
    """With a huge positive advantage and ratio far above 1+eps, the
    pg gradient wrt logits must vanish (clip active)."""
    cfg = PPOConfig(ent_coef=0.0, vf_coef=0.0)
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    b = _tiny_batch(4)
    b["log_probs"] = jnp.full((4,), -20.0)   # ratio = e^(logp+20) >> 1.2
    b["advantages"] = jnp.ones((4,)) * 5.0
    grads = jax.grad(lambda p: ppo_loss(p, fn, b, cfg)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert gnorm < 1e-5


def test_minibatch_epochs_rejects_indivisible_batch():
    """A batch that does not divide into cfg.minibatches would silently
    drop the tail every epoch — it must be a loud error instead."""
    from repro.optim import AdamWConfig, adamw_init, adamw_update, constant
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    batch = _tiny_batch(n=10)                 # 10 % 4 != 0
    opt = adamw_init(params)
    sched = constant(1e-3)
    ocfg = AdamWConfig()

    def opt_step(p, s, g):
        p, s, _ = adamw_update(g, s, p, sched, ocfg)
        return p, s

    with pytest.raises(ValueError, match="silently"):
        minibatch_epochs(jax.random.PRNGKey(0), params, opt, batch, fn,
                         PPOConfig(), opt_step)
    # the divisible case still runs
    out = minibatch_epochs(jax.random.PRNGKey(0), params, opt,
                           _tiny_batch(n=16), fn, PPOConfig(), opt_step)
    assert len(out) == 3


def _minibatch_epochs_image_gather(key, params, opt_state, batch,
                                   apply_fn, cfg, optimizer_step,
                                   loss_fn):
    """The loop as it gathered before flat rows: ``v[idx]`` on each
    leaf's own shape, images included."""
    n = batch["obs"].shape[0]
    mb = n // cfg.minibatches
    stats = None
    for _ in range(cfg.epochs):
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, n)
        for i in range(cfg.minibatches):
            idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
            mbatch = {k: v[idx] for k, v in batch.items()}
            (_, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, apply_fn, mbatch, cfg)
            params, opt_state = optimizer_step(params, opt_state, grads)
    return params, opt_state, stats


@pytest.mark.parametrize("loss_fn", [ppo_loss, a2c_loss],
                         ids=["ppo", "a2c"])
@pytest.mark.parametrize("obs_shape", [(8, 8, 3), (4,)],
                         ids=["image", "rank2"])
def test_minibatch_epochs_row_gather_is_bit_identical(loss_fn, obs_shape):
    """Gathering each minibatch as flat sample-major rows puts the same
    samples in the same minibatch as gathering the leaf as it is:
    params, optimizer state and stats agree bit for bit."""
    from repro.optim import AdamWConfig, adamw_init, adamw_update, constant
    from repro.rl.nets import conv_ac_apply, conv_ac_init
    n, key = 64, jax.random.PRNGKey(7)
    ks = jax.random.split(key, 6)
    if len(obs_shape) == 3:
        params = unbox(conv_ac_init(ks[0], obs_shape, 3,
                                    channels=(4, 8), hidden=16))
        fn = conv_ac_apply
    else:
        params = unbox(mlp_ac_init(ks[0], obs_shape[0], 3))
        fn = mlp_ac_apply
    batch = {
        "obs": jax.random.normal(ks[1], (n,) + obs_shape),
        "actions": jax.random.randint(ks[2], (n,), 0, 3),
        "log_probs": -jax.random.uniform(ks[3], (n,), minval=0.5,
                                         maxval=1.5),
        "advantages": jax.random.normal(ks[4], (n,)),
        "returns": jax.random.normal(ks[5], (n,)),
    }
    cfg = PPOConfig(epochs=2, minibatches=4)
    sched, ocfg = constant(1e-2), AdamWConfig(max_grad_norm=0.5)

    def opt_step(p, s, g):
        p, s, _ = adamw_update(g, s, p, sched, ocfg)
        return p, s

    opt = adamw_init(params)
    got = jax.jit(lambda k, p, o, b: minibatch_epochs(
        k, p, o, b, fn, cfg, opt_step, loss_fn=loss_fn))(
            key, params, opt, batch)
    want = jax.jit(lambda k, p, o, b: _minibatch_epochs_image_gather(
        k, p, o, b, fn, cfg, opt_step, loss_fn))(key, params, opt, batch)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the update moved the weights, so equal is not trivially equal
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(params),
                               jax.tree.leaves(got[0]), strict=True))


def test_a2c_loss_finite():
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    loss, _ = a2c_loss(params, fn, _tiny_batch(), PPOConfig())
    assert np.isfinite(float(loss))


def test_stage_mask_freezes_subgoal():
    params = {"stem": {"w": jnp.ones(3)}, "subgoal": {"w": jnp.ones(3)},
              "action": {"w": jnp.ones(3)}, "value": {"w": jnp.ones(3)}}
    grads = jax.tree.map(jnp.ones_like, params)
    m1 = stage_mask(params, "action")
    g1 = apply_stage_mask(grads, m1)
    assert float(jnp.sum(g1["subgoal"]["w"])) == 0
    assert float(jnp.sum(g1["stem"]["w"])) == 3
    m2 = stage_mask(params, "subgoal")
    g2 = apply_stage_mask(grads, m2)
    assert float(jnp.sum(g2["subgoal"]["w"])) == 3
    assert float(jnp.sum(g2["action"]["w"])) == 0


def test_two_stage_grad_mask_freezes_offstage_subtree():
    """The exact wiring rl_train --two-stage uses: minibatch_epochs with
    a stage_mask grad mask bitwise-freezes the off-stage subtree while
    the on-stage subtrees train (param-delta test on the real agent)."""
    from repro.launch.rl_train import make_agent
    from repro.optim import AdamWConfig, adamw_init, adamw_update, constant

    env = make("catch")                      # smallest image env
    dist = distribution_for(env.action_space)
    params, apply_fn = make_agent("hrl", env, jax.random.PRNGKey(0), None)
    fn = lambda p, o: apply_fn(p, o, None)
    est, obs = init_envs(env, jax.random.PRNGKey(1), 4)
    res = rollout(params, env, fn, jax.random.PRNGKey(2), est, obs, 8,
                  dist)
    batch = batch_from_traj(res.traj, res.last_value, PPOConfig())
    opt = adamw_init(params)
    sched = constant(3e-3)
    ocfg = AdamWConfig(weight_decay=0.0, max_grad_norm=0.5)

    def opt_step(p, s, g):
        p, s, _ = adamw_update(g, s, p, sched, ocfg)
        return p, s

    for stage, frozen, trained in (("action", "subgoal", "action"),
                                   ("subgoal", "action", "subgoal")):
        gmask = stage_mask(params, stage)
        new_params, _, _ = minibatch_epochs(
            jax.random.PRNGKey(3), params, opt, batch, fn, PPOConfig(),
            opt_step, grad_mask=gmask, dist=dist)
        for a, b in zip(jax.tree.leaves(params[frozen]),
                        jax.tree.leaves(new_params[frozen]), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        delta = sum(float(jnp.sum(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(params[trained]),
                                    jax.tree.leaves(new_params[trained]), strict=True))
        assert delta > 0, f"stage {stage} did not train {trained}"


def test_two_stage_checkpoint_records_stage_and_resumes_in_stage(
        tmp_path, capsys):
    """Two-stage steps are namespaced (g = stage*iters + it) and tagged
    with the stage, so a resume lands mid-stage-2 instead of silently
    restarting stage 1."""
    from repro.checkpoint import CheckpointManager
    from repro.launch.rl_train import make_agent, rl_train
    from repro.optim import adamw_init

    d = str(tmp_path / "ck")
    kw = dict(env_name="catch", agent="hrl", iters=2, n_envs=4,
              rollout_len=4, two_stage=True, ckpt_dir=d, save_every=1)
    rl_train(verbose=False, **kw)
    capsys.readouterr()

    mgr = CheckpointManager(d)
    assert mgr.latest_step() == 3            # 2 stages x 2 iters - 1
    env = make("catch")
    params, _ = make_agent("hrl", env, jax.random.PRNGKey(0), "fxp8")
    est0, obs0 = init_envs(env, jax.random.PRNGKey(1), 4)
    from repro.rl.trainer import onpolicy_state
    _, md = mgr.restore(onpolicy_state(params, adamw_init(params),
                                       est0, obs0))
    assert md["stage"] == "subgoal"
    assert md["stage_iter"] == 1

    # simulate preemption right after g=2 (stage 2, iter 0) and
    # relaunch with the same command line: must resume inside stage 2
    # at g=3, never re-running stage 1 or the checkpointed step
    import os
    for sfx in (".npz", ".npz.json"):
        os.unlink(os.path.join(d, f"step_3{sfx}"))
    _, hist = rl_train(verbose=True, **kw)
    out = capsys.readouterr().out
    assert "resumed at global iter 3 (stage subgoal, iter 0 done)" in out
    assert "[stage=action]" not in out
    assert "[stage=subgoal]" in out
    assert len(hist) == 1                    # exactly the missing iter

    # resuming a two-stage checkpoint without --two-stage must refuse
    # loudly, not silently reinterpret the step in single-stage terms
    with pytest.raises(ValueError, match="saved in stage"):
        rl_train(verbose=False, **{**kw, "two_stage": False})


def test_two_stage_requires_hrl_agent():
    from repro.launch.rl_train import rl_train
    with pytest.raises(ValueError, match="requires --agent hrl"):
        rl_train(env_name="cartpole", agent="mlp", iters=1,
                 two_stage=True, verbose=False)


def test_masked_batch_zeroes_straggler_loss():
    """A batch whose mask is all-zero produces zero pg/v loss."""
    from repro.rl.rollout import Trajectory
    T, B = 8, 4
    traj = Trajectory(
        obs=jnp.zeros((T, B, 4)), actions=jnp.zeros((T, B), jnp.int32),
        log_probs=jnp.zeros((T, B)), values=jnp.zeros((T, B)),
        rewards=jnp.ones((T, B)), dones=jnp.zeros((T, B), bool),
        truncated=jnp.zeros((T, B), bool), next_obs=jnp.zeros((T, B, 4)))
    batch = batch_from_traj(traj, jnp.zeros((B,)), PPOConfig(),
                            actor_mask=jnp.zeros((B,)))
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_ac_apply(p, o)
    cfg = PPOConfig(ent_coef=0.0)
    loss, stats = ppo_loss(params, fn, batch, cfg)
    assert float(stats["pg_loss"]) == 0.0
    assert float(stats["v_loss"]) == 0.0
    # a2c honours the same liveness-mask contract (--algo a2c runs
    # through the identical masked sharded driver)
    loss, stats = a2c_loss(params, fn, batch, cfg)
    assert float(stats["pg_loss"]) == 0.0
    assert float(stats["v_loss"]) == 0.0


# -- truncation-aware GAE (the headline bugfix) --------------------------

def test_gae_bootstraps_through_truncation_not_termination():
    """Identical rewards/values, one env truncated vs one terminated at
    t=0: the truncated row's advantage must include the discounted
    bootstrap value of its final (pre-reset) observation; the
    terminated row must not."""
    r = jnp.array([[1.0, 1.0], [1.0, 1.0]])
    v = jnp.zeros((2, 2))
    dones = jnp.array([[False, True], [False, False]])
    trunc = jnp.array([[True, False], [False, False]])
    boot = jnp.full((2, 2), 10.0)          # V(final_obs) everywhere
    lastv = jnp.zeros((2,))
    adv, _ = gae(r, v, dones, lastv, gamma=0.9, lam=0.95,
                 truncated=trunc, bootstrap_values=boot)
    # env 0 truncated at t=0: adv = r + gamma * V(final_obs)
    assert float(adv[0, 0]) == pytest.approx(1.0 + 0.9 * 10.0)
    # env 1 terminated at t=0: no bootstrap
    assert float(adv[0, 1]) == pytest.approx(1.0)
    # the advantage chain still breaks at the truncation: row 1 of
    # env 0 (the fresh episode) must not leak into row 0 beyond the
    # bootstrap — identical to a lam=0 one-step target here
    adv_no_chain, _ = gae(r, v, dones, lastv, gamma=0.9, lam=0.0,
                          truncated=trunc, bootstrap_values=boot)
    assert float(adv[0, 0]) == pytest.approx(float(adv_no_chain[0, 0]))

    # truncated without bootstrap values is a loud error, not a bias
    with pytest.raises(ValueError, match="bootstrap_values"):
        gae(r, v, dones, lastv, truncated=trunc)


def test_gae_truncation_end_to_end_on_pendulum():
    """A pendulum rollout across the 200-step horizon: dones stay
    False, the boundary row is truncated, and batch_from_traj with a
    value_fn produces targets that bootstrap V(final_obs) there."""
    env = make("pendulum")
    dist = distribution_for(env.action_space)
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 3,
                               head_dim(env.action_space)))
    fn = lambda p, o: mlp_ac_apply(p, o)
    est, obs = init_envs(env, jax.random.PRNGKey(1), 2)
    res = jax.jit(lambda p, e, o: rollout(
        p, env, fn, jax.random.PRNGKey(2), e, o, 202,
        dist))(params, est, obs)
    assert not bool(res.traj.dones.any())
    assert bool(res.traj.truncated.any())
    t, b = map(int, np.argwhere(np.asarray(res.traj.truncated))[0])
    # next_obs at the truncation is the pre-reset state, not the fresh
    # episode's first observation (which the next row acts on)
    assert not np.allclose(np.asarray(res.traj.next_obs[t, b]),
                           np.asarray(res.traj.obs[t + 1, b]))

    cfg = PPOConfig(gamma=0.9, lam=0.95)
    value_fn = lambda o: fn(params, o)[1]
    batch = batch_from_traj(res.traj, res.last_value, cfg,
                            value_fn=value_fn)
    T, B = res.traj.rewards.shape
    rets = batch["returns"].reshape(T, B)
    boot = value_fn(res.traj.next_obs.reshape(T * B, 3)).reshape(T, B)
    # at the truncation row return = r + gamma * V(final_obs) exactly
    # (the recursion restarts there, so lam plays no role in that row)
    expect = float(res.traj.rewards[t, b] + 0.9 * boot[t, b])
    assert float(rets[t, b]) == pytest.approx(expect, rel=1e-5)


def test_nstep_targets_windows_and_discounts():
    """3-step windows stop at boundaries: termination zeroes the
    discount, truncation keeps gamma^K, the tail degrades to shorter
    valid windows."""
    g = 0.5
    T, B = 5, 1
    r = jnp.arange(1.0, 6.0).reshape(T, B)          # 1..5
    dones = jnp.array([[False], [True], [False], [False], [False]])
    trunc = jnp.array([[False], [False], [False], [True], [False]])
    nobs = jnp.arange(10.0, 15.0).reshape(T, B, 1)  # distinct markers
    rets, nxt, disc = nstep_targets(r, dones, trunc, nobs, g, 3)
    rets, nxt, disc = (np.asarray(rets)[:, 0], np.asarray(nxt)[:, 0, 0],
                       np.asarray(disc)[:, 0])
    # t=0: window hits the termination at t=1 -> K=2, no bootstrap
    assert rets[0] == pytest.approx(1.0 + g * 2.0)
    assert disc[0] == 0.0 and nxt[0] == 11.0
    # t=1: terminated immediately -> K=1, no bootstrap
    assert rets[1] == pytest.approx(2.0) and disc[1] == 0.0
    # t=2: window hits the truncation at t=3 -> K=2, bootstrap gamma^2
    assert rets[2] == pytest.approx(3.0 + g * 4.0)
    assert disc[2] == pytest.approx(g ** 2) and nxt[2] == 13.0
    # t=3: truncated immediately -> K=1, bootstrap gamma
    assert disc[3] == pytest.approx(g) and nxt[3] == 13.0
    # t=4: chunk tail -> K=1 one-step target
    assert rets[4] == pytest.approx(5.0)
    assert disc[4] == pytest.approx(g) and nxt[4] == 14.0


# -- replay + value-based losses -----------------------------------------

def test_replay_circular_and_sample():
    buf = replay_init(8, (4,))
    obs = jnp.arange(24.0).reshape(6, 4)
    buf = replay_add(buf, obs, jnp.zeros(6, jnp.int32), jnp.ones(6),
                     obs, jnp.full(6, 0.99))
    assert int(buf.size) == 6 and int(buf.ptr) == 6
    buf = replay_add(buf, obs, jnp.zeros(6, jnp.int32), jnp.ones(6),
                     obs, jnp.full(6, 0.99))
    assert int(buf.size) == 8 and int(buf.ptr) == 4   # wrapped
    s = replay_sample(buf, jax.random.PRNGKey(0), 16)
    assert s["obs"].shape == (16, 4)
    np.testing.assert_array_equal(np.asarray(s["weight"]), 1.0)


def test_replay_sample_guards_underfilled_buffer():
    """The empty/underfilled buffer is never silently trained on:
    eager sampling raises, and under jit the weight column masks the
    whole batch (so a weighted loss is exactly zero)."""
    buf = replay_init(8, (4,))
    with pytest.raises(ValueError, match="min_size"):
        replay_sample(buf, jax.random.PRNGKey(0), 4)
    obs = jnp.ones((2, 4))
    buf = replay_add(buf, obs, jnp.zeros(2, jnp.int32), jnp.ones(2),
                     obs, jnp.zeros(2))
    with pytest.raises(ValueError, match="min_size"):
        replay_sample(buf, jax.random.PRNGKey(0), 4, min_size=4)
    # under jit size is a tracer: the guard becomes a zero weight...
    s = jax.jit(lambda b, k: replay_sample(b, k, 4, min_size=4))(
        buf, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(s["weight"]), 0.0)
    # ...which zeroes the masked losses
    params = unbox(mlp_q_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_q_apply(p, o)
    assert float(dqn_loss(params, params, fn, s, DQNConfig())) == 0.0
    # and once filled past min_size the same call trains normally
    obs = jnp.ones((6, 4))
    buf = replay_add(buf, obs, jnp.zeros(6, jnp.int32), jnp.ones(6),
                     obs, jnp.zeros(6))
    s = jax.jit(lambda b, k: replay_sample(b, k, 4, min_size=4))(
        buf, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(s["weight"]), 1.0)
    assert float(dqn_loss(params, params, fn, s, DQNConfig())) > 0.0


def test_dqn_shim_is_gone():
    """The deprecated ``repro.rl.dqn`` compatibility shim (a PR-3
    re-export of the replay/value split) is deleted: the import path
    must fail loudly, and nothing in the source tree may still spell
    it."""
    with pytest.raises(ModuleNotFoundError):
        import repro.rl.dqn  # noqa: F401
    import pathlib
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    hits = [p for p in src.rglob("*.py")
            if "repro.rl.dqn" in p.read_text()]
    assert not hits, f"stale repro.rl.dqn references: {hits}"


def test_replay_add_overflow_keeps_last_capacity_deterministically():
    """B >= capacity: only the newest `capacity` transitions survive, at
    well-defined slots (duplicate scatter indices have unspecified write
    order in XLA — the overflow path must never produce them)."""
    cap = 4
    buf = replay_init(cap, (1,))
    obs = jnp.arange(6.0).reshape(6, 1)
    add = jax.jit(replay_add)
    buf = add(buf, obs, jnp.arange(6, dtype=jnp.int32), jnp.arange(6.0),
              obs + 100.0, jnp.zeros(6))
    assert int(buf.size) == cap
    assert int(buf.ptr) == 6 % cap            # ptr advances by full B
    # transitions 2..5 land at slots (0+2..5) % 4 = [2, 3, 0, 1]
    np.testing.assert_array_equal(np.asarray(buf.obs[:, 0]),
                                  [4.0, 5.0, 2.0, 3.0])
    np.testing.assert_array_equal(np.asarray(buf.actions), [4, 5, 2, 3])
    np.testing.assert_array_equal(np.asarray(buf.next_obs[:, 0]),
                                  [104.0, 105.0, 102.0, 103.0])
    # and a non-zero ptr start still wraps correctly
    buf = add(buf, obs, jnp.arange(6, dtype=jnp.int32), jnp.arange(6.0),
              obs, jnp.zeros(6))
    assert int(buf.ptr) == (6 + 6) % cap
    np.testing.assert_array_equal(np.asarray(buf.obs[:, 0]),
                                  [2.0, 3.0, 4.0, 5.0])


def test_dqn_loss_and_epsilon_schedule():
    params = unbox(mlp_q_init(jax.random.PRNGKey(0), 4, 2))
    fn = lambda p, o: mlp_q_apply(p, o)
    # legacy batches carry `dones`; discount-encoded ones `discounts` —
    # both must produce finite losses with gradients
    legacy = {"obs": jnp.zeros((8, 4)),
              "actions": jnp.zeros((8,), jnp.int32),
              "rewards": jnp.ones((8,)), "next_obs": jnp.zeros((8, 4)),
              "dones": jnp.zeros((8,), bool)}
    cfg = DQNConfig()
    for batch in (legacy,
                  {**{k: v for k, v in legacy.items() if k != "dones"},
                   "discounts": jnp.full((8,), 0.99)}):
        loss = dqn_loss(params, params, fn, batch, cfg)
        assert np.isfinite(float(loss))
    # Double-DQN selects with the ONLINE argmax but prices with the
    # target net: with q(obs) = obs + params, online argmax on
    # next_obs=[1, 0] is action 0, where the (shifted) target net says
    # 1.0 — vanilla max over the target net would say 2.0
    table_fn = lambda p, o: o + p
    tbatch = {"obs": jnp.zeros((1, 2)),
              "actions": jnp.zeros((1,), jnp.int32),
              "rewards": jnp.zeros((1,)),
              "next_obs": jnp.array([[1.0, 0.0]]),
              "discounts": jnp.ones((1,))}
    online_p = jnp.zeros((2,))
    target_p = jnp.array([0.0, 2.0])
    l_double = dqn_loss(online_p, target_p, table_fn, tbatch, cfg)
    l_vanilla = dqn_loss(online_p, target_p, table_fn, tbatch,
                         DQNConfig(double=False))
    assert float(l_double) == pytest.approx(1.0)    # (0 - 1*1.0)^2
    assert float(l_vanilla) == pytest.approx(4.0)   # (0 - 1*2.0)^2
    assert float(epsilon(jnp.asarray(0), cfg)) == pytest.approx(1.0)
    assert float(epsilon(jnp.asarray(10**6), cfg)) == pytest.approx(0.05)
    acts = egreedy(jax.random.PRNGKey(0),
                   jnp.array([[0.0, 9.9]] * 100), jnp.asarray(0.0))
    assert int(acts.sum()) == 100          # greedy when eps=0


def test_qrdqn_loss_finite_and_head_shape():
    n_act, n_q = 3, 8
    params = unbox(mlp_qr_init(jax.random.PRNGKey(0), 4, n_act, n_q))
    fn = lambda p, o: mlp_qr_apply(p, o, n_act, n_q)
    out = fn(params, jnp.zeros((5, 4)))
    assert out.shape == (5, n_act, n_q)
    batch = {"obs": jax.random.normal(jax.random.PRNGKey(1), (8, 4)),
             "actions": jnp.zeros((8,), jnp.int32),
             "rewards": jnp.ones((8,)),
             "next_obs": jax.random.normal(jax.random.PRNGKey(2), (8, 4)),
             "discounts": jnp.full((8,), 0.99)}
    cfg = QRDQNConfig(n_quantiles=n_q)
    (loss, ), grads = (qrdqn_loss(params, params, fn, batch, cfg),), \
        jax.grad(qrdqn_loss)(params, params, fn, batch, cfg)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0


def test_ddpg_losses_and_polyak():
    obs_dim, act_dim = 3, 1
    ka, kc = jax.random.split(jax.random.PRNGKey(0))
    cfg = DDPGConfig(low=-2.0, high=2.0)
    actor = unbox(mlp_pi_init(ka, obs_dim, act_dim))
    critic = unbox(mlp_twin_q_init(kc, obs_dim, act_dim))
    actor_apply = lambda p, o, pol=None: mlp_pi_apply(p, o, cfg.low,
                                                      cfg.high, pol)
    critic_apply = lambda p, o, a, pol=None: mlp_twin_q_apply(p, o, a,
                                                              pol)
    a = actor_apply(actor, jnp.zeros((4, obs_dim)))
    assert a.shape == (4, act_dim)
    assert bool(jnp.all((a >= cfg.low) & (a <= cfg.high)))
    batch = {"obs": jax.random.normal(jax.random.PRNGKey(1), (8, obs_dim)),
             "actions": jax.random.uniform(jax.random.PRNGKey(2),
                                           (8, act_dim), minval=-2.0,
                                           maxval=2.0),
             "rewards": jnp.ones((8,)),
             "next_obs": jax.random.normal(jax.random.PRNGKey(3),
                                           (8, obs_dim)),
             "discounts": jnp.full((8,), 0.99)}
    c_loss = ddpg_critic_loss(critic, critic, actor, critic_apply,
                              actor_apply, batch, cfg,
                              jax.random.PRNGKey(4))
    assert np.isfinite(float(c_loss))
    g = jax.grad(ddpg_actor_loss)(actor, critic, critic_apply,
                                  actor_apply, batch)
    gnorm = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert gnorm > 0
    # polyak moves the target a tau-fraction toward the online params
    tgt = jax.tree.map(jnp.zeros_like, actor)
    moved = polyak(tgt, actor, 0.25)
    for t, o in zip(jax.tree.leaves(moved), jax.tree.leaves(actor), strict=True):
        np.testing.assert_allclose(np.asarray(t), 0.25 * np.asarray(o),
                                   rtol=1e-6)


# -- actor-learner sync --------------------------------------------------

def test_sync_bytes_4x_reduction():
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2, hidden=128))
    packed = pack_weights(params, 8)
    payload, fp32 = sync_bytes(packed)
    assert payload < 0.35 * fp32          # int8 + scales < 35% of fp32


def test_pack_unpack_roundtrip_error_bounded():
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    rec = unpack_weights(pack_weights(params, 8))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rec), strict=True):
        scale = float(jnp.max(jnp.abs(a))) / 127.0
        assert float(jnp.max(jnp.abs(a - b))) <= scale * 0.51 + 1e-8


def test_quantized_actor_rollout_runs():
    """Rollout under the FXP8 actor policy with int8-packed weights."""
    from repro.rl.actor_learner import collect
    env = make("cartpole")
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    packed = pack_weights(params, 8)
    est, obs = init_envs(env, jax.random.PRNGKey(1), 4)
    res = collect(packed, env, mlp_ac_apply, FXP8,
                  jax.random.PRNGKey(2), est, obs, 16)
    assert res.traj.rewards.shape == (16, 4)
    assert np.all(np.isfinite(np.asarray(res.traj.log_probs)))


def test_merge_results_masks_stragglers():
    from repro.rl.actor_learner import collect
    env = make("cartpole")
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    packed = pack_weights(params, 8)
    results = []
    for i in range(3):
        est, obs = init_envs(env, jax.random.PRNGKey(i), 4)
        results.append(collect(packed, env, mlp_ac_apply, FXP8,
                               jax.random.PRNGKey(10 + i), est, obs, 8))
    merged, mask = merge_results(results, jnp.array([True, False, True]))
    assert merged.traj.rewards.shape == (8, 12)
    np.testing.assert_array_equal(
        np.asarray(mask), np.repeat([1.0, 0.0, 1.0], 4))


def test_merge_results_final_env_resumes_collection():
    """merged.final_env honors the RolloutResult contract: env-state
    leaves are tree-concatenated along the env axis (not a python list)
    and resume a rollout at the merged fleet size."""
    from repro.rl.actor_learner import collect, unpack_weights
    env = make("cartpole")
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    packed = pack_weights(params, 8)
    results, states = [], []
    for i in range(2):
        est, obs = init_envs(env, jax.random.PRNGKey(i), 4)
        results.append(collect(packed, env, mlp_ac_apply, FXP8,
                               jax.random.PRNGKey(10 + i), est, obs, 8))
        states.append(results[-1].final_env)
    merged, _ = merge_results(results, jnp.array([True, True]))
    # same tree structure as a batched env state, leaves stacked [8, ...]
    assert (jax.tree.structure(merged.final_env)
            == jax.tree.structure(states[0]))
    for leaf, a, b in zip(jax.tree.leaves(merged.final_env),
                          jax.tree.leaves(states[0]),
                          jax.tree.leaves(states[1]), strict=True):
        assert leaf.shape[0] == 8
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.concatenate([np.asarray(a),
                                                      np.asarray(b)]))
    # resume: roll the merged fleet onward without any re-reset
    fn = lambda p, o: mlp_ac_apply(p, o, FXP8)
    res = rollout(unpack_weights(packed), env, fn, jax.random.PRNGKey(7),
                  merged.final_env, merged.final_obs, 4)
    assert res.traj.rewards.shape == (4, 8)
    assert np.all(np.isfinite(np.asarray(res.traj.log_probs)))
