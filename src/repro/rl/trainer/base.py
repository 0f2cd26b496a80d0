"""The unified Trainer layer: one loop, one checkpoint flow, one RNG
convention for both training families.

``Trainer`` is the protocol the drivers plug into —

  * ``init``      -> :meth:`Trainer.init_state` (a :class:`TrainState`)
  * ``iteration`` -> :meth:`Trainer.build_iteration` /
    :meth:`Trainer.step` (the jitted step factories in
    :mod:`repro.rl.train_steps`)
  * ``save``      -> :meth:`Trainer.train`'s checkpoint writes (the
    ``TrainState`` plus family metadata and the ``schema`` tag)
  * ``restore``   -> :meth:`Trainer.restore` (metadata validated
    *before* the tree restore; schema-dispatched legacy templates)
  * ``eval_policy`` -> the family's greedy head over
    :func:`repro.rl.trainer.evaluation.greedy_eval`

so checkpoint metadata validation, fold_in RNG derivation
(``sub = fold_in(base_key, g)`` — a resumed run draws exactly the
stream the uninterrupted run would have), resume reconstruction, the
FleetSync weight-sync bookkeeping and the straggler ``alive`` mask are
implemented once here instead of twice in ``launch/rl_train.py``.

Weight sync runs through :class:`repro.rl.actor_learner.FleetSync`:
every iteration the learner pushes the freshly packed int8 weights and
the fleet fetches at the trainer's ``fetch_lag`` — 0 is lock-step
(optionally with a per-iteration ``block_until_ready`` barrier), 1 is
the double-buffered overlap (the next collect runs against version k
while the learner's k+1 update is still in flight in the async
dispatch stream).  ``alive`` is derived from per-slot fetch staleness,
not hardcoded all-true.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.distributed.sharding import data_axes, data_axis_size
from repro.launch.mesh import (describe, make_host_mesh,
                               make_production_mesh)
from repro.obs import (Console, MetricSpec, ProfileWindow,
                       RunTelemetry, SpanClock, flush)
from repro.rl.actor_learner import FleetSync, sync_bytes
from repro.rl.trainer.state import STATE_SCHEMA, TrainState


def build_mesh(mesh_kind: str = "host",
               mesh_devices: Optional[int] = None):
    if mesh_kind == "production":
        if mesh_devices is not None:
            raise ValueError("--mesh-devices restricts the host mesh "
                             "only; the production mesh shape is fixed")
        return make_production_mesh()
    if mesh_kind == "host":
        return make_host_mesh(mesh_devices)
    raise ValueError(f"unknown mesh kind {mesh_kind!r} "
                     "(expected 'host' or 'production')")


def resolve_mesh(mesh_kind: str, mesh_devices: Optional[int],
                 n_envs: int, verbose: bool = False):
    """Mesh construction + the env-divisibility contract, shared by
    both families: the default host mesh auto-fits its device count to
    the largest prefix dividing ``n_envs`` (odd host device counts
    degrade to fewer slots); an explicit ``--mesh-devices`` stays a
    hard error."""
    if mesh_kind == "host" and mesh_devices is None:
        mesh_devices = len(jax.devices())
        while mesh_devices > 1 and n_envs % mesh_devices != 0:
            mesh_devices -= 1
    mesh = build_mesh(mesh_kind, mesh_devices)
    n_slots = data_axis_size(mesh)
    if n_envs % n_slots != 0:
        raise ValueError(f"--n-envs {n_envs} must be divisible by the "
                         f"mesh's {n_slots} data slot(s)")
    Console(verbose).info(f"{describe(mesh)}: {n_slots} actor slot(s) "
                          f"x {n_envs // n_slots} envs")
    return mesh, n_slots


def flag_mismatch(ckpt_dir, flag: str, saved, have, reason: str = "",
                  verb: str = "saved by") -> ValueError:
    """The one checkpoint-vs-flags error format (metadata is validated
    BEFORE the tree restore, so a mismatched template fails with this
    and never a missing-leaf KeyError)."""
    why = f"{reason}; " if reason else ""
    return ValueError(
        f"checkpoint in {ckpt_dir} was {verb} --{flag} {saved}, not "
        f"{have} — {why}relaunch with the original flags")


class Trainer:
    """Base driver: subclasses supply the family-specific seams, this
    class owns the loop, the checkpoint flow and the weight sync."""

    family = "?"

    def __init__(self, *, iters: int, seed: int,
                 ckpt_dir: Optional[str], save_every: int,
                 log_every: int, verbose: bool, n_slots: int = 1,
                 max_lag: int = 1, fetch_lag: int = 0,
                 barrier: bool = False,
                 metrics_dir: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 profile_start: int = 0, profile_steps: int = 1):
        self.iters = iters
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self.ckpt_dir = ckpt_dir
        self.save_every = save_every
        self.log_every = log_every
        self.verbose = verbose
        self.console = Console(verbose)
        self.n_slots = n_slots
        self.max_lag = max_lag
        self.fetch_lag = fetch_lag
        self.barrier = barrier
        self.metrics_dir = metrics_dir
        self.profile_dir = profile_dir
        self.profile_start = profile_start
        self.profile_steps = profile_steps
        # the family metric spec, resolved in train() when telemetry
        # is on; None keeps the historical (uninstrumented) programs
        self.metrics: Optional[MetricSpec] = None
        self.stage_list = [None]
        self.stage_names = ["all"]

    # ---- family seams ----------------------------------------------------
    def init_state(self) -> TrainState:
        raise NotImplementedError

    def build_iteration(self):
        raise NotImplementedError

    def step(self, iteration, state, packed, key, g: int, stage_ctx,
             alive, mbuf=None):
        """Run one jitted iteration; returns ``(state, ret, n_ep)``,
        plus the updated metric buffer when ``mbuf`` is threaded."""
        raise NotImplementedError

    def pack(self, state):
        """The packed (int8) weight payload the fleet syncs."""
        raise NotImplementedError

    def eval_policy(self, params, **kw):
        raise NotImplementedError

    def stage_setup(self, state, stage):
        return None

    def validate_metadata(self, md: dict) -> None:
        pass

    def legacy_template(self, state: TrainState):
        """Restore template for schema-less (pre-TrainState) ckpts."""
        raise NotImplementedError

    def state_from_legacy(self, restored) -> TrainState:
        raise NotImplementedError

    def metadata(self, it: int, stage) -> dict:
        return {}

    def resume_start(self, md: dict) -> int:
        raise NotImplementedError

    def resume_message(self, md: dict, state, start: int) -> str:
        return f"resumed at iter {start}"

    def header(self, state) -> Optional[str]:
        return None

    def metric_spec(self) -> Optional[MetricSpec]:
        """The family's jit-threaded metric shape (None: no threaded
        buffer even with telemetry on)."""
        return None

    def run_meta(self) -> dict:
        """The ``meta`` record's ``run`` block."""
        return {"family": self.family, "seed": self.seed,
                "iters": self.iters, "n_slots": self.n_slots}

    def host_metrics(self, state, metrics: dict) -> dict:
        """Host-side gauges merged into each window record (families
        add what the jit buffer does not carry, e.g. replay fill when
        metrics are not threaded)."""
        return {}

    def log_line(self, it, ret, n_ep, metrics: dict, stage) -> str:
        """Render the console line from the window's structured
        metrics record."""
        raise NotImplementedError

    def export_state(self, state, state_out: Optional[dict]) -> None:
        pass

    # ---- the one driver --------------------------------------------------
    def place(self, state: TrainState) -> TrainState:
        """Lay the state out on the mesh as the jitted iteration
        returns it — env state and the slot-major replay sharded over
        the data axes, the rest replicated — so iteration 1 reuses
        iteration 0's program instead of tracing and compiling it
        again.  Without a mesh the state stays where it is."""
        mesh = getattr(self, "mesh", None)
        if mesh is None:
            return state
        rep = NamedSharding(mesh, P())
        slots = NamedSharding(mesh, P(data_axes(mesh) or None))
        put = jax.device_put
        return TrainState(put(state.params, rep), put(state.target, rep),
                          put(state.opt, rep), put(state.replay, slots),
                          put(state.est, slots), put(state.obs, slots))

    def restore(self, mgr: CheckpointManager, state: TrainState):
        """Schema-dispatched restore: flags are validated against the
        sidecar metadata first; ``trainstate/v1`` checkpoints restore
        straight into the :class:`TrainState` template, schema-less
        ones go through the family's legacy tuple template, and any
        other schema fails naming both."""
        md = mgr.metadata()
        schema = md.get("schema")
        if schema is not None and schema != STATE_SCHEMA:
            raise ValueError(
                f"checkpoint in {self.ckpt_dir} records state schema "
                f"{schema!r}, but this launcher reads {STATE_SCHEMA!r} "
                "(or the legacy schema-less tuple layout) — regenerate "
                "the checkpoint or use a matching launcher version")
        self.validate_metadata(md)
        if schema == STATE_SCHEMA:
            return mgr.restore(state)
        legacy, md = mgr.restore(self.legacy_template(state))
        return self.state_from_legacy(legacy), md

    def train(self, state_out: Optional[dict] = None):
        con = self.console
        state = self.init_state()
        start, mgr = 0, None
        if self.ckpt_dir:
            mgr = CheckpointManager(self.ckpt_dir, keep=2,
                                    save_every=self.save_every)
            if mgr.latest_step() is not None:
                state, md = self.restore(mgr, state)
                start = self.resume_start(md)
                con.info(self.resume_message(md, state, start))
        state = self.place(state)
        tel = None
        self.metrics = None
        if self.metrics_dir:
            # telemetry opens AFTER restore so the first window starts
            # at the resume step — the sink appends, keeping windows
            # contiguous across a restart
            self.metrics = self.metric_spec()
            tel = RunTelemetry(self.metrics_dir, run=self.run_meta(),
                               start=start)
        prof = (ProfileWindow(self.profile_dir, self.profile_start,
                              self.profile_steps)
                if self.profile_dir else None)
        clock = tel.clock if tel else SpanClock()
        iteration = self.build_iteration()
        mbuf = self.metrics.init() if self.metrics else None
        sync = FleetSync(self.n_slots, max_lag=self.max_lag)
        head = self.header(state)
        if head:
            con.info(head)
        history = []
        total_payload = 0
        w_payload = w_fp32 = 0
        t0 = time.time()
        t_win = time.perf_counter()
        for si, stage in enumerate(self.stage_list):
            ctx = self.stage_setup(state, stage)
            for it in range(self.iters):
                g = si * self.iters + it  # global step: stages never
                if g < start:             # collide; resume lands
                    continue              # mid-stage, not at stage 1
                if prof:
                    win = prof.tick(g)
                    if win:
                        if tel:
                            tel.profile(prof.dir, win)
                        con.info(f"profiler trace for steps "
                                 f"[{win[0]}, {win[1]}] -> {prof.dir}")
                with clock("sync"):
                    sync.push(self.pack(state))
                    stale = sync.fetch(self.fetch_lag)
                payload, fp32_eq = sync_bytes(stale)
                total_payload += payload
                w_payload += payload
                w_fp32 += fp32_eq
                # key derived from the global step, not a running
                # split: a resumed run at step g draws the same stream
                # the uninterrupted run would have
                sub = jax.random.fold_in(self.key, g)
                with clock("step"):
                    if mbuf is not None:
                        state, ret, n_ep, mbuf = self.step(
                            iteration, state, stale, sub, g, ctx,
                            sync.alive(), mbuf)
                    else:
                        state, ret, n_ep = self.step(
                            iteration, state, stale, sub, g, ctx,
                            sync.alive())
                    if self.barrier:
                        # lock-step: fence the dispatch stream so the
                        # next collect cannot overlap this learner
                        # update (the double-buffered mode omits
                        # exactly this)
                        jax.block_until_ready((state, ret))
                    # the host read of ret is the loop's pre-existing
                    # per-iteration sync point — time it as the step
                    ret_f = float(ret)
                history.append(ret_f)
                if it % self.log_every == 0 or it == self.iters - 1:
                    metrics = {}
                    hists = None
                    if mbuf is not None:
                        metrics, hists, mbuf = flush(self.metrics,
                                                     mbuf)
                    metrics.update(self.host_metrics(state, metrics))
                    metrics["sync_payload_bytes"] = w_payload
                    metrics["sync_fp32_bytes"] = w_fp32
                    metrics["staleness_max"] = int(
                        jax.device_get(sync.staleness()).max())
                    metrics.setdefault(
                        "alive_frac",
                        float(jax.device_get(sync.alive()).mean()))
                    wall = time.perf_counter() - t_win
                    if "env_steps" in metrics and wall > 0:
                        metrics["steps_per_s"] = round(
                            metrics["env_steps"] / wall, 2)
                    if tel:
                        tel.step_flush(g, metrics, hists)
                    con.info(self.log_line(it, ret_f, int(n_ep),
                                           metrics, stage))
                    w_payload = w_fp32 = 0
                    t_win = time.perf_counter()
                if mgr and mgr.should_save(g):
                    with clock("checkpoint"):
                        mgr.save(g, state,
                                 metadata={**self.metadata(it, stage),
                                           "schema": STATE_SCHEMA})
        if prof:
            win = prof.stop()
            if win:
                if tel:
                    tel.profile(prof.dir, win)
                con.info(f"profiler trace for steps "
                         f"[{win[0]}, {win[1]}] -> {prof.dir}")
        if tel:
            tel.close()
        con.info(f"done in {time.time() - t0:.0f}s; "
                 f"total sync payload {total_payload / 2**20:.1f} MiB")
        self.export_state(state, state_out)
        return state, history
