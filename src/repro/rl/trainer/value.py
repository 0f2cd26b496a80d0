"""The off-policy value-based trainer (dqn / qrdqn / ddpg).

Single-device (``mesh_kind=None``, the historical default) the loop is
bit-exact with the pre-trainer ``value_train``: same RNG stream
(``fold_in(seed_key, it)``), same replay backend, same jitted
iteration.  With a mesh (``--mesh host``) collection AND learning
shard over the data axes: per-device ``collect_value_sharded``
rollouts feed per-device local replay shards
(:func:`repro.rl.replay.make_sharded_replay` — stratified global
sampling, globally-normalized PER weights), the learner's grads
``psum`` over the data axis, and the int8 weight sync runs through
FleetSync in ``lockstep`` (fetch lag 0 + a per-iteration dispatch
barrier) or ``doublebuf`` mode (fetch lag 1, no barrier: collect k+1
runs against version k while the learner's k+1 update is in flight).
At 1 mesh device the sharded path is bit-exact with the single-device
path (slot 0 keeps the identical RNG stream; 1-device psum/pmax are
identities).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policy import get_policy
from repro.obs import MetricSpec
from repro.optim import AdamWConfig, adamw_init, constant
from repro.rl.actor_learner import pack_weights
from repro.rl.envs import make
from repro.rl.envs.wrappers import NormStats
from repro.rl.inference import (ON_POLICY_ALGOS, VALUE_ALGOS, build_env,
                                make_value_agent)
from repro.rl.replay import make_replay, make_sharded_replay, replay_size
from repro.rl.rollout import init_envs
from repro.rl.train_steps import (make_sharded_value_iteration,
                                  make_value_iteration)
from repro.rl.trainer.base import Trainer, flag_mismatch, resolve_mesh
from repro.rl.trainer.evaluation import greedy_eval
from repro.rl.trainer.state import TrainState

SYNC_MODES = ("lockstep", "doublebuf")


def value_eval(algo: str, env_name: str, params,
               n_envs: int = 16, n_steps: Optional[int] = None,
               actor_policy: Optional[str] = None, seed: int = 0,
               net: str = "mlp", frame_stack_k: int = 1,
               norm_stats: Optional[NormStats] = None):
    """Greedy-policy evaluation: (mean episode return, episode count).

    ``net="conv"`` evaluates over the pixel pipeline with the running
    normalizer *frozen*: pass the training run's merged stats as
    ``norm_stats`` (see ``wrappers.norm_stats_of``/``merge_norm_stats``;
    None falls back to the identity transform).
    """
    if net == "conv":
        from repro.rl.envs.wrappers import init_norm_stats
        frozen = (norm_stats if norm_stats is not None
                  else init_norm_stats(make(env_name).obs_shape))
        env = build_env(env_name, net, frame_stack_k, norm_stats=frozen)
    else:
        env = build_env(env_name, net, frame_stack_k)
    spec = env.spec
    agent = make_value_agent(algo, spec, net=net)  # closures, no init
    policy = get_policy(actor_policy) if actor_policy else None
    n_steps = n_steps or spec.max_steps + spec.max_steps // 4
    return greedy_eval(env, lambda p, o: agent.greedy(p, o, policy),
                       params, jax.random.PRNGKey(seed + 17), n_envs,
                       n_steps)


class ValueTrainer(Trainer):
    family = "value"

    def __init__(self, algo: str = "dqn", env_name: str = "cartpole",
                 iters: int = 300, n_envs: int = 32,
                 rollout_len: int = 8,
                 actor_policy: Optional[str] = "fxp8", lr: float = 1e-3,
                 comm_bits: int = 8, seed: int = 0,
                 ckpt_dir: Optional[str] = None, save_every: int = 50,
                 replay_capacity: int = 50_000, n_step: int = 3,
                 updates_per_iter: int = 4, log_every: int = 20,
                 verbose: bool = True,
                 learn_start: Optional[int] = None, net: str = "mlp",
                 frame_stack_k: int = 1,
                 replay: str = "uniform", per_alpha: float = 0.6,
                 per_beta0: float = 0.4,
                 per_beta_iters: Optional[int] = None,
                 tqc_drop: int = 0,
                 mesh_kind: Optional[str] = None,
                 mesh_devices: Optional[int] = None,
                 sync: str = "lockstep", max_lag: int = 1,
                 metrics_dir: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 profile_start: int = 0, profile_steps: int = 1):
        if algo not in VALUE_ALGOS:
            raise ValueError(f"value_train drives {VALUE_ALGOS}, got "
                             f"{algo!r}; use rl_train for "
                             f"{ON_POLICY_ALGOS}")
        if sync not in SYNC_MODES:
            raise ValueError(f"unknown sync mode {sync!r} "
                             f"(expected one of {SYNC_MODES})")
        if mesh_kind is None and mesh_devices is not None:
            raise ValueError("--mesh-devices restricts a device mesh; "
                             "the value loop is single-device without "
                             "--mesh host")
        super().__init__(iters=iters, seed=seed, ckpt_dir=ckpt_dir,
                         save_every=save_every, log_every=log_every,
                         verbose=verbose, max_lag=max_lag,
                         fetch_lag=1 if sync == "doublebuf" else 0,
                         barrier=(sync == "lockstep"
                                  and mesh_kind is not None),
                         metrics_dir=metrics_dir,
                         profile_dir=profile_dir,
                         profile_start=profile_start,
                         profile_steps=profile_steps)
        self.algo, self.env_name, self.net = algo, env_name, net
        self.n_envs, self.rollout_len = n_envs, rollout_len
        self.frame_stack_k = frame_stack_k
        self.replay, self.per_alpha = replay, per_alpha
        self.per_beta0, self.tqc_drop = per_beta0, tqc_drop
        self.sync_mode = sync
        self.actor_policy_name = actor_policy
        self.env = build_env(env_name, net, frame_stack_k)
        spec = self.env.spec
        self.a_policy = get_policy(actor_policy) if actor_policy else None
        self.comm = comm_bits if self.a_policy else 32
        # epsilon anneals over the first half of the step budget
        decay = max((iters * rollout_len) // 2, 1)
        self.agent = make_value_agent(algo, spec, self.key,
                                      n_step=n_step,
                                      eps_decay_steps=decay,
                                      learn_start=learn_start, net=net,
                                      tqc_drop=tqc_drop)
        if mesh_kind is not None:
            self.mesh, self.n_slots = resolve_mesh(
                mesh_kind, mesh_devices, n_envs, verbose=verbose)
        else:
            self.mesh = None
        act = ((spec.action_space.shape, jnp.float32)
               if algo == "ddpg" else ((), jnp.int32))
        if self.mesh is not None:
            self.rb = make_sharded_replay(replay, self.n_slots,
                                          replay_capacity, spec.obs_shape,
                                          act[0], act[1],
                                          alpha=per_alpha)
        else:
            self.rb = make_replay(replay, replay_capacity,
                                  spec.obs_shape, act[0], act[1],
                                  alpha=per_alpha)
        self.beta_iters = max(per_beta_iters if per_beta_iters is not None
                              else iters, 1)
        self.n_step = n_step
        self.updates_per_iter = updates_per_iter
        self.ocfg = AdamWConfig(weight_decay=0.0, max_grad_norm=10.0)
        self.sched = constant(lr)

    # ---- trainer seams ---------------------------------------------------
    def init_state(self) -> TrainState:
        params = self.agent.params
        # fresh buffers, not an alias: params and target are both
        # donated to the jitted iteration, and a shared buffer cannot
        # donate twice
        target = jax.tree.map(jnp.copy, params)
        if self.algo == "ddpg":
            opt = {"actor": adamw_init(params["actor"]),
                   "critic": adamw_init(params["critic"])}
        else:
            opt = adamw_init(params)
        est, obs = init_envs(self.env, jax.random.PRNGKey(self.seed + 1),
                             self.n_envs, mesh=self.mesh)
        return TrainState(params, target, opt, self.rb.init(), est, obs)

    def build_iteration(self):
        if self.mesh is not None:
            return make_sharded_value_iteration(
                self.env, self.agent, self.rb, self.a_policy,
                self.sched, self.ocfg, self.mesh, algo=self.algo,
                rollout_len=self.rollout_len,
                updates_per_iter=self.updates_per_iter,
                per_beta0=self.per_beta0, beta_iters=self.beta_iters,
                metrics=self.metrics)
        return make_value_iteration(
            self.env, self.agent, self.rb, self.a_policy, self.sched,
            self.ocfg, algo=self.algo, rollout_len=self.rollout_len,
            updates_per_iter=self.updates_per_iter,
            per_beta0=self.per_beta0, beta_iters=self.beta_iters,
            metrics=self.metrics)

    def metric_spec(self) -> MetricSpec:
        gauges = ["return_mean", "epsilon", "replay_size"]
        if self.rb.prioritized:
            gauges.append("replay_max_priority")
        if self.mesh is not None:
            gauges.append("alive_frac")
        return MetricSpec(counters=("env_steps", "episodes"),
                          gauges=tuple(gauges))

    def run_meta(self) -> dict:
        meta = super().run_meta()
        meta.update(algo=self.algo, env=self.env_name, net=self.net,
                    n_envs=self.n_envs, rollout_len=self.rollout_len,
                    replay=self.replay, sync=self.sync_mode)
        return meta

    def pack(self, state):
        # only the behaviour net ships to the fleet (ddpg: the actor
        # alone — syncing the twin critics would triple the payload)
        with self.clock("pack"):
            return pack_weights(
                self.agent.behaviour_subtree(state.params), self.comm)

    def step(self, iteration, state, packed, key, g, stage_ctx, alive,
             mbuf=None):
        args = (state.params, state.target, state.opt, state.replay,
                packed, state.est, state.obs, key, jnp.asarray(g))
        if self.mesh is not None:
            args = args + (alive,)
        if mbuf is not None:
            args = args + (mbuf,)
        with self.clock("dispatch", g=g):
            out = iteration(*args)
        p, t, o, b, est, obs, ret, n_ep = out[:8]
        new = TrainState(p, t, o, b, est, obs)
        return ((new, ret, n_ep) if mbuf is None
                else (new, ret, n_ep, out[8]))

    def eval_policy(self, params, n_envs: int = 16,
                    n_steps: Optional[int] = None,
                    actor_policy: Optional[str] = None, seed: int = 0,
                    norm_stats: Optional[NormStats] = None):
        return value_eval(self.algo, self.env_name, params,
                          n_envs=n_envs, n_steps=n_steps,
                          actor_policy=actor_policy, seed=seed,
                          net=self.net,
                          frame_stack_k=self.frame_stack_k,
                          norm_stats=norm_stats)

    # ---- checkpoint seams ------------------------------------------------
    def validate_metadata(self, md: dict) -> None:
        d = self.ckpt_dir
        md_net = str(md.get("net", self.net))
        if md_net != self.net:
            raise flag_mismatch(d, "net", repr(md_net), repr(self.net),
                                "the torso family (and the obs "
                                "pipeline) differs")
        md_env = str(md.get("env", self.env_name))
        if md_env != self.env_name:
            raise flag_mismatch(d, "env", repr(md_env),
                                repr(self.env_name))
        md_algo = str(md.get("algo", ""))
        if md_algo != self.algo:
            raise flag_mismatch(d, "algo", repr(md_algo),
                                repr(self.algo))
        md_replay = str(md.get("replay", "uniform"))
        if md_replay != self.replay:
            raise flag_mismatch(d, "replay", repr(md_replay),
                                repr(self.replay),
                                "the sampling stream (and the PER tree "
                                "state) is part of the run")
        md_tqc = int(md.get("tqc_drop", 0))
        if md_tqc != self.tqc_drop:
            raise flag_mismatch(d, "tqc-drop", md_tqc, self.tqc_drop,
                                "the critic head shape differs "
                                "(restore does not shape-check)")
        # the sharded buffer's slot layout (and the doublebuf fetch
        # stream) are part of the run: a mismatched mesh cannot restore
        # the [n_slots]-leading replay tree bitwise
        md_slots = int(md.get("replay_slots", 1))
        if md_slots != self.n_slots:
            raise ValueError(
                f"checkpoint in {d} was saved with {md_slots} replay "
                f"slot(s), but this run's mesh shards {self.n_slots} — "
                "the sharded buffer layout differs; relaunch with the "
                "original --mesh/--mesh-devices flags")
        md_sync = str(md.get("sync", self.sync_mode))
        if md_sync != self.sync_mode:
            raise flag_mismatch(d, "sync", repr(md_sync),
                                repr(self.sync_mode),
                                "the weight-sync fetch stream differs",
                                verb="saved with")
        if self.replay == "per":
            # the priority exponent and beta schedule shape every
            # subsequent draw: a silent change would diverge from the
            # uninterrupted run's sampling stream
            for flag, have in (("per_alpha", self.per_alpha),
                               ("per_beta0", self.per_beta0),
                               ("per_beta_iters", self.beta_iters)):
                saved = md.get(flag)
                if saved is not None and float(saved) != float(have):
                    raise flag_mismatch(
                        d, flag.replace("_", "-"), saved, have,
                        "the prioritized sampling stream depends on it",
                        verb="saved with")

    def legacy_template(self, state: TrainState):
        return tuple(state)

    def state_from_legacy(self, restored) -> TrainState:
        return TrainState(*restored)

    def metadata(self, it: int, stage) -> dict:
        # env/net/frame_stack/n_envs make the checkpoint self-
        # describing for the serving loader (repro.serve.load_policy
        # rebuilds the net — and for conv policies the env-state
        # template — from these alone)
        md = {"algo": self.algo, "it": it, "replay": self.replay,
              "tqc_drop": self.tqc_drop, "env": self.env_name,
              "net": self.net, "frame_stack": self.frame_stack_k,
              "n_envs": self.n_envs, "n_step": self.n_step,
              "actor_policy": self.actor_policy_name or "fp32",
              "replay_slots": self.n_slots, "sync": self.sync_mode}
        if self.rb.prioritized:
            md.update(per_alpha=self.per_alpha,
                      per_beta0=self.per_beta0,
                      per_beta_iters=self.beta_iters)
        return md

    def resume_start(self, md: dict) -> int:
        return int(md.get("it", md.get("step", 0))) + 1

    def resume_message(self, md, state, start: int) -> str:
        return (f"resumed at iter {start} "
                f"(replay size {int(replay_size(state.replay))})")

    def header(self, state) -> str:
        pol = self.actor_policy_name if self.a_policy else "fp32"
        rep = (f"per(alpha={self.per_alpha}, beta {self.per_beta0}->1/"
               f"{self.beta_iters}it)" if self.rb.prioritized
               else "uniform")
        return (f"{self.algo} on {self.env.spec.name}: {self.n_envs} "
                f"envs x {self.rollout_len} steps/iter, "
                f"n_step={self.agent.cfg.n_step}, {pol} behaviour "
                f"actor, {rep} replay")

    def host_metrics(self, state, metrics: dict) -> dict:
        # without the jit-threaded buffer the window record still
        # carries the replay fill (one scalar host read, same value
        # the gauge reports)
        if "replay_size" in metrics:
            return {}
        return {"replay_size": int(replay_size(state.replay))}

    def log_line(self, it, ret, n_ep, metrics: dict, stage):
        return (f"iter {it:4d}  return {float(ret):8.2f}  "
                f"episodes {int(n_ep):4d}  "
                f"replay {int(metrics['replay_size']):6d}")

    def export_state(self, state, state_out) -> None:
        if state_out is not None:
            state_out.update(env_state=state.est, obs=state.obs,
                             replay=state.replay)


def value_train(algo: str = "dqn", env_name: str = "cartpole",
                iters: int = 300, n_envs: int = 32, rollout_len: int = 8,
                actor_policy: Optional[str] = "fxp8", lr: float = 1e-3,
                comm_bits: int = 8, seed: int = 0,
                ckpt_dir: Optional[str] = None, save_every: int = 50,
                replay_capacity: int = 50_000, n_step: int = 3,
                updates_per_iter: int = 4, log_every: int = 20,
                verbose: bool = True,
                learn_start: Optional[int] = None, net: str = "mlp",
                frame_stack_k: int = 1,
                replay: str = "uniform", per_alpha: float = 0.6,
                per_beta0: float = 0.4,
                per_beta_iters: Optional[int] = None,
                tqc_drop: int = 0,
                state_out: Optional[dict] = None,
                mesh_kind: Optional[str] = None,
                mesh_devices: Optional[int] = None,
                sync: str = "lockstep", max_lag: int = 1,
                metrics_dir: Optional[str] = None,
                profile_dir: Optional[str] = None,
                profile_start: int = 0, profile_steps: int = 1):
    """Off-policy value-based training (paper Fig. 2 split, replay
    flavour) — see :class:`ValueTrainer`.  Returns (params, history);
    ``state_out`` (optional dict) receives the final
    ``env_state``/``obs``/``replay`` state."""
    trainer = ValueTrainer(
        algo, env_name, iters=iters, n_envs=n_envs,
        rollout_len=rollout_len, actor_policy=actor_policy, lr=lr,
        comm_bits=comm_bits, seed=seed, ckpt_dir=ckpt_dir,
        save_every=save_every, replay_capacity=replay_capacity,
        n_step=n_step, updates_per_iter=updates_per_iter,
        log_every=log_every, verbose=verbose, learn_start=learn_start,
        net=net, frame_stack_k=frame_stack_k, replay=replay,
        per_alpha=per_alpha, per_beta0=per_beta0,
        per_beta_iters=per_beta_iters, tqc_drop=tqc_drop,
        mesh_kind=mesh_kind, mesh_devices=mesh_devices, sync=sync,
        max_lag=max_lag, metrics_dir=metrics_dir,
        profile_dir=profile_dir, profile_start=profile_start,
        profile_steps=profile_steps)
    state, history = trainer.train(state_out=state_out)
    return state.params, history
