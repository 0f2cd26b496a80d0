"""Sharded actor-fleet tests (shard_map over the mesh's data axes).

The multi-device cases need forced host devices, which must be set
before the jax backend initializes — CI runs this file in its own job
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
.github/workflows/ci.yml); in a plain single-device tier-1 run those
cases skip and the subprocess test below still exercises the full
8-device training path end-to-end.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import FXP8
from repro.launch.mesh import make_host_mesh
from repro.nn.module import unbox
from repro.rl import init_envs
from repro.rl.actor_learner import (collect, collect_sharded, fleet_mask,
                                    pack_weights)
from repro.rl.envs import make
from repro.rl.nets import mlp_ac_apply, mlp_ac_init

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _fleet(n_envs, key_seed=1, mesh=None):
    env = make("cartpole")
    params = unbox(mlp_ac_init(jax.random.PRNGKey(0), 4, 2))
    packed = pack_weights(params, 8)
    est, obs = init_envs(env, jax.random.PRNGKey(key_seed), n_envs,
                         mesh=mesh)
    return env, packed, est, obs


# -- always-on (any device count) ----------------------------------------

def test_one_device_shard_map_bit_exact_vs_plain_rollout():
    """The 1-device sharded path degenerates to the plain collect:
    bit-exact on every leaf (same key stream: fold_in(key, 0))."""
    mesh = make_host_mesh(1)
    env, packed, est, obs = _fleet(8, mesh=mesh)
    key = jax.random.PRNGKey(2)
    res = collect_sharded(packed, env, mlp_ac_apply, FXP8, key, est, obs,
                          16, mesh)
    ref = collect(packed, env, mlp_ac_apply, FXP8,
                  jax.random.fold_in(key, 0), est, obs, 16)
    for a, b in zip(jax.tree.leaves(res), jax.tree.leaves(ref), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_collect_sharded_composes_with_jit():
    mesh = make_host_mesh(1)
    env, packed, est, obs = _fleet(4, mesh=mesh)
    fn = jax.jit(lambda p, k, e, o: collect_sharded(
        p, env, mlp_ac_apply, FXP8, k, e, o, 8, mesh))
    res = fn(packed, jax.random.PRNGKey(2), est, obs)
    assert res.traj.rewards.shape == (8, 4)
    assert np.all(np.isfinite(np.asarray(res.traj.log_probs)))


def test_fleet_mask_layout():
    m = fleet_mask(jnp.array([True, False, True]), 4)
    np.testing.assert_array_equal(np.asarray(m),
                                  np.repeat([1.0, 0.0, 1.0], 4))


@pytest.mark.skipif(
    jax.device_count() >= 8,
    reason="already multi-device: the in-process tests below cover this "
           "without paying for a second jax startup")
def test_rl_train_forced_8dev_subprocess():
    """End-to-end acceptance path: rl_train on a forced 8-device host
    mesh, sharded actors, int8 sync — run in a subprocess because the
    device count must be fixed before the jax backend initializes."""
    code = (
        "from repro.launch.rl_train import rl_train\n"
        "import jax\n"
        "assert jax.device_count() == 8, jax.device_count()\n"
        "params, hist = rl_train(env_name='cartpole', iters=2,\n"
        "                        n_envs=16, rollout_len=8)\n"
        "assert len(hist) == 2\n"
        "print('SHARDED_TRAIN_OK')\n"
    )
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=root, capture_output=True, text=True,
                          timeout=540)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED_TRAIN_OK" in proc.stdout
    assert "8 devices" in proc.stdout          # mesh banner printed


@pytest.mark.skipif(
    jax.device_count() >= 8,
    reason="already multi-device: the in-process tests below cover this "
           "without paying for a second jax startup")
def test_value_train_forced_8dev_subprocess():
    """The value-family counterpart: qrdqn over 8 sharded actor slots,
    per-slot PER shards, double-buffered int8 weight sync."""
    code = (
        "from repro.launch.rl_train import value_train\n"
        "import jax\n"
        "assert jax.device_count() == 8, jax.device_count()\n"
        "params, hist = value_train('qrdqn', 'cartpole', iters=3,\n"
        "                           n_envs=16, rollout_len=8,\n"
        "                           replay='per', replay_capacity=2048,\n"
        "                           learn_start=64, mesh_kind='host',\n"
        "                           sync='doublebuf')\n"
        "assert len(hist) == 3\n"
        "print('SHARDED_VALUE_OK')\n"
    )
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=root, capture_output=True, text=True,
                          timeout=540)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED_VALUE_OK" in proc.stdout
    assert "8 actor slot(s) x 2 envs" in proc.stdout


# -- forced multi-device ---------------------------------------------------

@multi_device
def test_uneven_envs_raise():
    mesh = make_host_mesh(8)
    env, packed, est, obs = _fleet(12)
    with pytest.raises(ValueError, match="does not divide"):
        collect_sharded(packed, env, mlp_ac_apply, FXP8,
                        jax.random.PRNGKey(2), est, obs, 4, mesh)


@multi_device
def test_rl_train_rejects_uneven_envs_on_explicit_mesh():
    """--mesh-devices is a hard constraint; only the default host mesh
    auto-fits its device count to n_envs."""
    from repro.launch.rl_train import rl_train
    with pytest.raises(ValueError, match="divisible"):
        rl_train(env_name="cartpole", iters=1, n_envs=12, rollout_len=4,
                 mesh_devices=8, verbose=False)


@multi_device
def test_rl_train_default_mesh_autofits_odd_n_envs(capsys):
    """n_envs=12 on an 8-device host degrades to the largest dividing
    prefix (6 slots) instead of failing."""
    from repro.launch.rl_train import rl_train
    _, hist = rl_train(env_name="cartpole", iters=1, n_envs=12,
                       rollout_len=4, verbose=True)
    out = capsys.readouterr().out
    assert "6 actor slot(s) x 2 envs" in out
    assert len(hist) == 1


@multi_device
def test_eight_device_parity_vs_manual_per_device_collect():
    """The sharded fleet must equal 8 independent per-device collects
    (fold_in(key, d) streams) concatenated along the env axis —
    bit-exact, including the resumable final env state."""
    mesh = make_host_mesh(8)
    n_envs, T = 16, 12
    env, packed, est, obs = _fleet(n_envs, mesh=mesh)
    key = jax.random.PRNGKey(2)
    res = collect_sharded(packed, env, mlp_ac_apply, FXP8, key, est, obs,
                          T, mesh)
    per = n_envs // 8
    for d in range(8):
        sl = slice(d * per, (d + 1) * per)
        est_d = jax.tree.map(lambda x: x[sl], est)
        ref = collect(packed, env, mlp_ac_apply, FXP8,
                      jax.random.fold_in(key, d), est_d, obs[sl], T)
        np.testing.assert_array_equal(np.asarray(res.traj.obs[:, sl]),
                                      np.asarray(ref.traj.obs))
        np.testing.assert_array_equal(np.asarray(res.traj.actions[:, sl]),
                                      np.asarray(ref.traj.actions))
        np.testing.assert_array_equal(np.asarray(res.last_value[sl]),
                                      np.asarray(ref.last_value))
        for a, b in zip(jax.tree.leaves(res.final_env),
                        jax.tree.leaves(ref.final_env), strict=True):
            np.testing.assert_array_equal(np.asarray(a)[sl],
                                          np.asarray(b))


@multi_device
def test_sharded_result_resumes_collection():
    """final_env/final_obs of a sharded collect feed straight back in."""
    mesh = make_host_mesh(8)
    env, packed, est, obs = _fleet(16, mesh=mesh)
    r1 = collect_sharded(packed, env, mlp_ac_apply, FXP8,
                         jax.random.PRNGKey(2), est, obs, 8, mesh)
    r2 = collect_sharded(packed, env, mlp_ac_apply, FXP8,
                         jax.random.PRNGKey(3), r1.final_env,
                         r1.final_obs, 8, mesh)
    assert r2.traj.rewards.shape == (8, 16)
    assert np.all(np.isfinite(np.asarray(r2.traj.log_probs)))


@multi_device
def test_sharded_train_smoke_in_process():
    from repro.launch.rl_train import rl_train
    params, hist = rl_train(env_name="cartpole", iters=2, n_envs=16,
                            rollout_len=8, verbose=False)
    assert len(hist) == 2
    assert all(np.isfinite(h) for h in hist)


@multi_device
def test_eight_device_value_collect_parity_vs_per_slot():
    """The sharded value-family fleet must equal 8 independent
    per-slot ``collect_value`` runs under the ``slot_keys`` streams
    (slot 0 the raw key, others fold_in) concatenated along the env
    axis — bit-exact, final env state included."""
    from repro.core.policy import get_policy
    from repro.rl.actor_learner import (collect_value,
                                        collect_value_sharded, slot_keys)
    from repro.rl.inference import build_env, make_value_agent

    mesh = make_host_mesh(8)
    n_envs, T = 16, 12
    env = build_env("cartpole", "mlp")
    agent = make_value_agent("dqn", env.spec, jax.random.PRNGKey(0))
    packed = pack_weights(agent.behaviour_subtree(agent.params), 8)
    pol = get_policy("fxp8")
    key = jax.random.PRNGKey(2)
    est, obs = init_envs(env, jax.random.PRNGKey(1), n_envs, mesh=mesh)
    eps = jnp.asarray(0.2)
    (est_s, obs_s), traj_s = collect_value_sharded(
        packed, env, agent.behave, pol, key, est, obs, T, eps, mesh)
    ks = slot_keys(key, 8)
    per = n_envs // 8
    for d in range(8):
        sl = slice(d * per, (d + 1) * per)
        est_d = jax.tree.map(lambda x: x[sl], est)
        (est_r, obs_r), traj_r = collect_value(
            packed, env, agent.behave, pol, ks[d], est_d, obs[sl], T,
            eps)
        np.testing.assert_array_equal(np.asarray(obs_s[sl]),
                                      np.asarray(obs_r))
        for a, b in zip(jax.tree.leaves(est_s),
                        jax.tree.leaves(est_r), strict=True):
            np.testing.assert_array_equal(np.asarray(a)[sl],
                                          np.asarray(b))
        for a, b in zip(jax.tree.leaves(traj_s),
                        jax.tree.leaves(traj_r), strict=True):
            np.testing.assert_array_equal(np.asarray(a)[:, sl],
                                          np.asarray(b))


@multi_device
def test_sharded_value_train_smoke_in_process():
    """qrdqn + per-slot PER shards + doublebuf int8 sync over the full
    8-slot mesh, in process (CI's multidevice job runs this file under
    forced 8 host devices)."""
    from repro.rl.trainer import value_train
    params, hist = value_train("qrdqn", "cartpole", iters=3, n_envs=16,
                               rollout_len=8, verbose=False,
                               replay="per", replay_capacity=2048,
                               learn_start=64, mesh_kind="host",
                               sync="doublebuf")
    assert len(hist) == 3
    assert all(np.isfinite(h) for h in hist)
