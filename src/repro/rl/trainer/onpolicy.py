"""The on-policy trainer (ppo / a2c, mlp / conv / hrl agents).

The actor fleet is shard_map'd over the data axes of a real device
mesh; each device dequantizes the broadcast int8 weight sync locally
and rolls ``n_envs/n_devices`` environments.  Per-device trajectories
come back as one global batch whose per-device slots carry the
FleetSync ``alive`` mask into the PPO loss (and out of the advantage
statistics) — an async aggregator only has to flip mask bits to drop a
straggler, it never has to reshape the loss.  Truncated episodes
bootstrap through the timeout (GAE consumes the env's
terminated/truncated split).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.configs.e2hrl import HRLConfig
from repro.core.policy import get_policy
from repro.models import hrl
from repro.nn.module import unbox
from repro.obs import MetricSpec
from repro.optim import AdamWConfig, adamw_init, constant
from repro.rl import PPOConfig, init_envs
from repro.rl.actor_learner import pack_weights
from repro.rl.dists import distribution_for
from repro.rl.envs import Environment, make
from repro.rl.envs.spaces import head_dim
from repro.rl.inference import (ON_POLICY_ALGOS, VALUE_ALGOS, build_env)
from repro.rl.nets import (conv_ac_apply, conv_ac_init, mlp_ac_apply,
                           mlp_ac_init)
from repro.rl.ppo import a2c_loss, ppo_loss, stage_mask
from repro.rl.train_steps import make_onpolicy_iteration
from repro.rl.trainer.base import Trainer, resolve_mesh
from repro.rl.trainer.evaluation import greedy_action, greedy_eval
from repro.rl.trainer.state import TrainState, onpolicy_state


def make_agent(agent: str, env: Environment, key,
               policy_name: Optional[str], net: str = "mlp"):
    spec = env.spec
    if agent == "mlp":
        if net == "conv":
            if len(spec.obs_shape) != 3:
                raise ValueError(
                    f"{spec.name} has obs shape {spec.obs_shape}; "
                    "--net conv needs image (H, W, C) observations")
            params = unbox(conv_ac_init(key, spec.obs_shape,
                                        head_dim(spec.action_space)))
            return params, conv_ac_apply
        if len(spec.obs_shape) != 1:
            raise ValueError(
                f"{spec.name} has obs shape {spec.obs_shape}; use "
                "--net conv for the Q-Conv pixel stem, wrap with "
                "envs.wrappers.flatten_observation for the mlp agent, "
                "or use --agent hrl")
        params = unbox(mlp_ac_init(key, spec.obs_shape[0],
                                   head_dim(spec.action_space)))
        apply_fn = mlp_ac_apply
        return params, apply_fn
    if net != "mlp":
        raise ValueError("--net conv selects the standalone conv "
                         "actor-critic; the hrl agent has its own conv "
                         "stem — drop --net")
    if len(spec.obs_shape) != 3:
        raise ValueError(
            f"{spec.name} has obs shape {spec.obs_shape}; the hrl agent "
            "needs image (H, W, C) observations — use --agent mlp")
    cfg = HRLConfig(obs_shape=spec.obs_shape, n_actions=spec.n_actions)
    params = unbox(hrl.init(key, cfg))

    def apply_fn(p, obs, policy=None):
        logits, value, _ = hrl.apply(p, obs, cfg, policy)
        return logits, value

    return params, apply_fn


class OnPolicyTrainer(Trainer):
    family = "onpolicy"

    def __init__(self, env_name: str = "cartpole", agent: str = "mlp",
                 iters: int = 40, n_envs: int = 32,
                 rollout_len: int = 128,
                 actor_policy: Optional[str] = "fxp8", lr: float = 3e-3,
                 comm_bits: int = 8, max_lag: int = 1, seed: int = 0,
                 two_stage: bool = False,
                 ckpt_dir: Optional[str] = None, save_every: int = 10,
                 mesh_kind: str = "host",
                 mesh_devices: Optional[int] = None,
                 log_every: int = 5, verbose: bool = True,
                 algo: str = "ppo", net: str = "mlp",
                 frame_stack_k: int = 1,
                 metrics_dir: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 profile_start: int = 0, profile_steps: int = 1):
        if algo not in ON_POLICY_ALGOS:
            raise ValueError(f"rl_train drives the on-policy family "
                             f"{ON_POLICY_ALGOS}; use value_train for "
                             f"{VALUE_ALGOS} (or the --algo CLI "
                             "dispatch)")
        if two_stage and agent != "hrl":
            raise ValueError("--two-stage trains the HRL sub-goal "
                             "curriculum and requires --agent hrl")
        # legacy on-policy sync: actors run (max_lag - 1) versions
        # behind the freshest push — lock-step at the default lag 1
        super().__init__(iters=iters, seed=seed, ckpt_dir=ckpt_dir,
                         save_every=save_every, log_every=log_every,
                         verbose=verbose, max_lag=max_lag,
                         fetch_lag=max_lag - 1, barrier=False,
                         metrics_dir=metrics_dir,
                         profile_dir=profile_dir,
                         profile_start=profile_start,
                         profile_steps=profile_steps)
        if net == "conv":
            self.env = build_env(env_name, net, frame_stack_k)
        else:
            # the mlp/hrl agents keep the historical raw-env view
            # (make_agent validates the obs shape)
            if frame_stack_k > 1:
                raise ValueError("--frame-stack is a pixel-pipeline "
                                 "knob and requires --net conv")
            self.env = make(env_name)
        self.env_name, self.n_envs = env_name, n_envs
        self.algo = algo
        self.rollout_len = rollout_len
        self.dist = distribution_for(self.env.action_space)
        self._init_params, self.apply_fn = make_agent(
            agent, self.env, self.key, actor_policy, net)
        self.a_policy = get_policy(actor_policy) if actor_policy else None
        self.comm = comm_bits
        self.mesh, self.n_slots = resolve_mesh(mesh_kind, mesh_devices,
                                               n_envs, verbose=verbose)
        self.ocfg = AdamWConfig(weight_decay=0.0, max_grad_norm=0.5)
        # a2c: one pass over the whole batch, no clipping surrogate
        self.pcfg = (PPOConfig() if algo == "ppo"
                     else PPOConfig(epochs=1, minibatches=1))
        self.loss_fn = ppo_loss if algo == "ppo" else a2c_loss
        self.sched = constant(lr)
        self.stage_list = ["action", "subgoal"] if two_stage else [None]
        self.stage_names = [s or "all" for s in self.stage_list]

    # ---- trainer seams ---------------------------------------------------
    def init_state(self) -> TrainState:
        est, obs = init_envs(self.env, jax.random.PRNGKey(self.seed + 1),
                             self.n_envs, mesh=self.mesh)
        return onpolicy_state(self._init_params,
                              adamw_init(self._init_params), est, obs)

    def build_iteration(self):
        return make_onpolicy_iteration(
            self.env, self.apply_fn, self.a_policy, self.mesh,
            self.dist, self.pcfg, self.loss_fn, self.sched, self.ocfg,
            rollout_len=self.rollout_len, n_envs=self.n_envs,
            n_slots=self.n_slots, metrics=self.metrics)

    def metric_spec(self) -> MetricSpec:
        return MetricSpec(counters=("env_steps", "episodes"),
                          gauges=("return_mean", "alive_frac", "loss"))

    def run_meta(self) -> dict:
        meta = super().run_meta()
        meta.update(algo=self.algo, env=self.env_name,
                    n_envs=self.n_envs, rollout_len=self.rollout_len)
        return meta

    def pack(self, state):
        with self.clock("pack"):
            return pack_weights(state.params, self.comm)

    def step(self, iteration, state, packed, key, g, stage_ctx, alive,
             mbuf=None):
        args = (state.params, state.opt, state.est, state.obs, packed,
                key, stage_ctx, alive)
        if mbuf is not None:
            args = args + (mbuf,)
        with self.clock("dispatch", g=g):
            out = iteration(*args)
        params, opt, est, obs, ret, n_ep = out[:6]
        new = onpolicy_state(params, opt, est, obs)
        return ((new, ret, n_ep) if mbuf is None
                else (new, ret, n_ep, out[6]))

    def stage_setup(self, state, stage):
        # the stage grad-mask actually freezes the off-stage subtree
        # (zero grads keep adam state at zero -> bitwise-frozen params)
        return stage_mask(state.params, stage) if stage else None

    def eval_policy(self, params, n_envs: int = 16,
                    n_steps: Optional[int] = None, seed: int = 0):
        spec = self.env.spec
        n_steps = n_steps or spec.max_steps + spec.max_steps // 4

        def act(p, o):
            dparams, _ = self.apply_fn(p, o, None)
            return greedy_action(self.dist, dparams)

        return greedy_eval(self.env, act, params,
                           jax.random.PRNGKey(seed + 17), n_envs,
                           n_steps)

    # ---- checkpoint seams ------------------------------------------------
    def validate_metadata(self, md: dict) -> None:
        md_stage = str(md.get("stage", "all"))
        if md_stage not in self.stage_names:
            raise ValueError(
                f"checkpoint in {self.ckpt_dir} was saved in stage "
                f"{md_stage!r} but this run's stages are "
                f"{self.stage_names} — relaunch with the original "
                "--two-stage/--agent flags")

    def legacy_template(self, state: TrainState):
        return (state.params, state.opt, state.est, state.obs)

    def state_from_legacy(self, restored) -> TrainState:
        return onpolicy_state(*restored)

    def metadata(self, it: int, stage) -> dict:
        return {"stage": stage or "all", "stage_iter": it}

    def resume_start(self, md: dict) -> int:
        # the checkpoint holds post-update state for its step, so
        # training continues at the NEXT step (re-running the saved one
        # would apply its optimizer update twice); the global step is
        # rebuilt from the recorded (stage, stage_iter) so a changed
        # --iters cannot land the resume in the wrong stage; the clamp
        # covers a shrunken --iters (the recorded stage already met the
        # new budget — continue at the next stage rather than skipping
        # past the end of the whole run)
        md_stage = str(md.get("stage", "all"))
        it = int(md.get("stage_iter", md.get("step", 0)))
        return (self.stage_names.index(md_stage) * self.iters
                + min(it + 1, self.iters))

    def resume_message(self, md, state, start: int) -> str:
        md_stage = str(md.get("stage", "all"))
        it = int(md.get("stage_iter", md.get("step", 0)))
        return (f"resumed at global iter {start} "
                f"(stage {md_stage}, iter {it} done)")

    def log_line(self, it, ret, n_ep, metrics: dict, stage):
        sfx = f" [stage={stage}]" if stage else ""
        return (f"iter {it:4d}  return {float(ret):8.2f}  "
                f"episodes {int(n_ep):4d}  "
                f"sync {metrics['sync_payload_bytes'] / 2**20:.2f} MiB "
                f"(fp32 {metrics['sync_fp32_bytes'] / 2**20:.2f}){sfx}")

    def export_state(self, state, state_out) -> None:
        if state_out is not None:
            state_out.update(env_state=state.est, obs=state.obs)


def rl_train(env_name: str = "cartpole", agent: str = "mlp",
             iters: int = 40, n_envs: int = 32, rollout_len: int = 128,
             actor_policy: Optional[str] = "fxp8", lr: float = 3e-3,
             comm_bits: int = 8, max_lag: int = 1, seed: int = 0,
             two_stage: bool = False, ckpt_dir: Optional[str] = None,
             save_every: int = 10, mesh_kind: str = "host",
             mesh_devices: Optional[int] = None,
             log_every: int = 5, verbose: bool = True,
             algo: str = "ppo", net: str = "mlp",
             frame_stack_k: int = 1,
             state_out: Optional[dict] = None,
             metrics_dir: Optional[str] = None,
             profile_dir: Optional[str] = None,
             profile_start: int = 0, profile_steps: int = 1):
    """On-policy training (paper Fig. 2 split over a device mesh) —
    see :class:`OnPolicyTrainer`.  Returns (params, history)."""
    trainer = OnPolicyTrainer(
        env_name, agent, iters=iters, n_envs=n_envs,
        rollout_len=rollout_len, actor_policy=actor_policy, lr=lr,
        comm_bits=comm_bits, max_lag=max_lag, seed=seed,
        two_stage=two_stage, ckpt_dir=ckpt_dir, save_every=save_every,
        mesh_kind=mesh_kind, mesh_devices=mesh_devices,
        log_every=log_every, verbose=verbose, algo=algo, net=net,
        frame_stack_k=frame_stack_k, metrics_dir=metrics_dir,
        profile_dir=profile_dir, profile_start=profile_start,
        profile_steps=profile_steps)
    state, history = trainer.train(state_out=state_out)
    return state.params, history
