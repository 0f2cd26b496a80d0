"""On-policy training cells (PPO / A2C): the program's own trainer,
driven one whole iteration at a time for ``--seconds``.

Each iteration is ``Trainer.train``'s sequence: pack the learner's
weights, push them through ``FleetSync`` and fetch at the trainer's
lag, run ``Trainer.step`` on the state from ``init_state`` +
``place``, and read the return on the host.  Set-up builds the trainer
once with the benchmark's weights from the seed and runs the first
``compared_steps`` iterations through that same loop; their losses,
the optimizer's first moment after the first and the weights after the
last are what the configuration's plain reference is compared with,
once the window has closed and the program's state is freed.

The configuration names the program's agent (``agent``, and ``net``
where it gives one, as ``make_agent`` takes them) and states the
loss's settings (``ppo``) and the optimizer's (``optimizer``); the
traffic file gives ``algo``, ``epochs``, ``minibatches``, ``n_envs``,
``rollout_len``, ``compared_steps`` and ``trace_iters``.  The trainer
is built from these, and refused where it still departs from them.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Callable, Optional

import jax
import numpy as np

from harness import (Cell, Outcome, Spans, device_info, peaks, traced,
                     trace_reducer)


def _check_states(trainer, cell: Cell, params0) -> None:
    """Refuse a run in which the program would depart from what the
    configuration states.  The ``ppo`` and ``optimizer`` blocks and the
    traffic's epochs and minibatches are set on the trainer by
    ``make_trainer``; what the trainer takes otherwise is checked here."""
    cfg = cell.config
    pol = trainer.a_policy
    want = dict(actor_w_bits=cfg["actor_w_bits"],
                actor_a_bits=cfg["actor_a_bits"],
                comm_bits=cfg["comm_bits"], lr=cfg["optimizer"]["lr"])
    have = dict(actor_w_bits=pol.w_bits, actor_a_bits=pol.a_bits,
                comm_bits=trainer.comm, lr=float(trainer.sched(1)))
    # the learner's matmuls run at the platform's default precision,
    # as the configuration states, unless JAX is told otherwise
    want["matmul_precision"] = cfg["learner_matmul_precision"]
    have["matmul_precision"] = (jax.config.jax_default_matmul_precision
                                or "default")
    shapes = lambda t: jax.tree.map(lambda x: tuple(x.shape), t)  # noqa: E731
    if shapes(trainer._init_params) != shapes(params0):
        raise ValueError("the program's agent does not have the "
                         "configuration's shapes")
    bad = {k: (have[k], v) for k, v in want.items()
           if (have[k] != v if isinstance(v, str)
               else not np.isclose(have[k], v, rtol=1e-6))}
    if bad:
        raise ValueError(f"program departs from the configuration "
                         f"(have, stated): {bad}")


def make_trainer(cell: Cell, seed: int, chips: int):
    """The program's trainer for the agent the configuration names,
    with the loss and optimizer settings that the configuration and the
    traffic state; ``build_iteration`` reads them when it builds."""
    from repro.rl.trainer.onpolicy import OnPolicyTrainer
    cfg, job = cell.config, cell.traffic
    if "agent" not in cfg:
        raise ValueError(f"configuration {cell.config_name!r} names no "
                         f"'agent' for the program to build")
    agent = {k: cfg[k] for k in ("agent", "net") if k in cfg}
    trainer = OnPolicyTrainer(
        env_name=cfg["env"], iters=1, n_envs=job["n_envs"],
        rollout_len=job["rollout_len"], actor_policy=cfg["actor_policy"],
        lr=cfg["optimizer"]["lr"], comm_bits=cfg["comm_bits"], max_lag=1,
        seed=seed, mesh_kind="host", mesh_devices=chips, verbose=False,
        algo=job["algo"], **agent)
    trainer.pcfg = dataclasses.replace(
        trainer.pcfg, epochs=job["epochs"], minibatches=job["minibatches"],
        **cfg["ppo"])
    trainer.ocfg = dataclasses.replace(
        trainer.ocfg, **{k: v for k, v in cfg["optimizer"].items()
                         if k != "lr"})
    return trainer


def build(cell: Cell, seed: int, chips: int,
          patch: Optional[Callable] = None):
    """The trainer with the benchmark's weights, its placed state and
    its jitted iteration; ``patch(trainer)`` may replace a part of the
    program before the iteration is built (the fault tests do)."""
    cfg = cell.config
    trainer = make_trainer(cell, seed, chips)
    ref = cell.reference()
    params0 = jax.jit(functools.partial(ref.init_params, cfg=cfg))(
        jax.random.PRNGKey(seed))
    _check_states(trainer, cell, params0)
    trainer._init_params = params0
    trainer.metrics = trainer.metric_spec()   # the loss gauge
    if patch is not None:
        patch(trainer)
    state = trainer.place(trainer.init_state())
    return trainer, state, trainer.build_iteration(), params0


def host_copy(tree):
    """A copy on the host that no later donation can overwrite."""
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


class Loop:
    """One whole iteration of ``Trainer.train``, as the window runs it."""

    def __init__(self, trainer, state, iteration, spans: Spans):
        from repro.rl.actor_learner import FleetSync
        self.trainer, self.state, self.iteration = trainer, state, iteration
        self.mbuf = trainer.metrics.init()
        self.sync = FleetSync(trainer.n_slots, max_lag=trainer.max_lag)
        self.spans = spans
        self.g = 0

    def __call__(self) -> float:
        t = self.trainer
        with self.spans("sync"):
            self.sync.push(t.pack(self.state))
            stale = self.sync.fetch(t.fetch_lag)
        sub = jax.random.fold_in(t.key, self.g)
        with self.spans("step"):
            self.state, ret, _, self.mbuf = t.step(
                self.iteration, self.state, stale, sub, self.g, None,
                self.sync.alive(), self.mbuf)
            ret = float(ret)
        self.g += 1
        return ret


def run(cell: Cell, seed: int, seconds: float, trace: bool, devs,
        t_start: float, *, patch: Optional[Callable] = None) -> Outcome:
    job = cell.traffic
    ref = cell.reference()
    spans = Spans()
    trainer, state, iteration, params0 = build(cell, seed, len(devs),
                                               patch)
    params0 = host_copy(params0)
    loop = Loop(trainer, state, iteration, spans)
    del state

    # set-up: the first iterations compile and are the ones compared
    prog = {"losses": []}
    for g in range(job["compared_steps"]):
        loop()
        prog["losses"].append(float(loop.mbuf["gauges"]["loss"]))
        if g == 0:
            prog.update(host_copy({"mu1": loop.state.opt["mu"],
                                   "nu1": loop.state.opt["nu"],
                                   "obs1": loop.state.obs}))
    prog["params"] = host_copy(loop.state.params)
    setup_s = time.perf_counter() - t_start

    spans.clear()
    rets = []
    t0 = time.perf_counter()
    while True:
        rets.append(loop())
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    window = {"iters": len(rets), "elapsed_s": elapsed,
              "sync_s": list(spans.times["sync"]),
              "step_s": list(spans.times["step"])}

    summary = None
    if trace:
        spans.clear()
        with traced(cell.name) as d:
            for _ in range(job["trace_iters"]):
                loop()
        summary = trace_reducer().reduce_dir(d, spans=("sync", "step"))
    device = device_info(devs)
    steps_per_iter = job["n_envs"] * job["rollout_len"]
    failed = sum(1 for r in rets if not np.isfinite(r))

    # the program's state goes before the reference runs
    del loop, trainer, iteration
    gc.collect()
    want = ref.steps(params0, seed, cell.config, job,
                     job["compared_steps"])
    compared = ref.compare(prog, want, params0)

    ctx = None
    breakdown = None
    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        ctx = {"kind": "train", "window": window, "trace": summary,
               "trace_iters": job["trace_iters"],
               "work": cell.work().per_iteration(cell.config, job),
               "peaks": peaks(device["kind"]), "chips": len(devs)}
    return Outcome(
        end_to_end={"train_env_steps_per_s":
                    len(rets) * steps_per_iter / elapsed,
                    "setup_s": setup_s},
        attempted=len(rets), failed=failed, compared=compared,
        per_layer_ctx=ctx, device=device, breakdown=breakdown)


SIDES = ("program", "control", "half_batch", "altered", "unchanged")
# the size at which the CPU tests run a cell of this driver
TINY = {"n_envs": 64, "rollout_len": 16}


def reading(cell: Cell, seed: int, side: str, devs) -> dict:
    """The compared numbers of one side at the cell's own size: the
    program (no window), or a variant of the reference put in its
    place (the control, or a planted fault)."""
    if side == "program":
        return run(cell, seed, 0.0, False, devs, time.perf_counter()).compared
    ref = cell.reference()
    job = cell.traffic
    params0 = host_copy(jax.jit(functools.partial(
        ref.init_params, cfg=cell.config))(jax.random.PRNGKey(seed)))
    n = job["compared_steps"]
    want = ref.steps(params0, seed, cell.config, job, n)
    got = ref.steps(params0, seed, cell.config, job, n, side)
    return ref.compare(got, want, params0)
