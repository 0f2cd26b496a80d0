"""Smoke run of the main path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip: phases 1-4 below
    python chip_smoke.py --chips 4   # four chips: the sharded fleets only

One process runs every phase in order, and any failure ends the run
with a non-zero exit code; nothing falls back to the CPU.

1. Device check: refuses any platform but ``tpu``.
2. E2HRL training (``rl_train``): the paper's agent at its published
   32x32x3 input and (16, 32, 32) conv channels, fxp8 actors, PPO on
   keydoor, 512 envs x 128 steps, 3 iterations on one device.
3. Quantized serving: a short dqn catch conv run writes a checkpoint,
   ``serve_policy`` serves it at w8 after checking that the served
   greedy actions equal the evaluation path's bit for bit.
4. Every Pallas kernel with ``interpret=False``, against its oracle.

``--chips 4`` runs instead the sharded E2HRL actor fleet on a 4-device
mesh against per-device rollouts (bit-exact), and a 3-iteration
qrdqn + PER run sharded over 4 devices with double-buffered sync.

Timings are printed as set-up or information, never as metrics.  The
last line of stdout is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.policy import get_policy  # noqa: E402
from repro.kernels.qconv import ops as qconv_ops  # noqa: E402
from repro.kernels.qlstm import ops as qlstm_ops  # noqa: E402
from repro.kernels.qmac import ops as qmac_ops  # noqa: E402
from repro.kernels.vact import ops as vact_ops  # noqa: E402
from repro.launch import rl_train as rl_cli  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve_policy import serve_policy  # noqa: E402

OUT = ROOT / "chiprun_out" / "chip_smoke"
E2HRL_ENV = "keydoor"
TRAIN_POLICY = "fxp8"
SERVE_PRECISION = "w8"
SERVING_BUCKETS = (1, 2, 4, 8, 16, 32)


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(dev) -> int:
    """``peak_bytes_in_use`` of ``dev`` as its backend reports it."""
    return dev.memory_stats()["peak_bytes_in_use"]


def _finite(tree) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(tree))


# ---- phase 1 ----------------------------------------------------------------

def check_device(n_chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's devices are {d.platform!r}, "
                         "not 'tpu' — this check runs on the chip only")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} but JAX sees "
                         f"{len(devs)} device(s)")
    log(f"[device] {d.device_kind} x {len(devs)}; jax {jax.__version__}, "
        f"jaxlib {importlib.metadata.version('jaxlib')}, "
        f"libtpu {importlib.metadata.version('libtpu')}")
    return d


# ---- phase 2 ----------------------------------------------------------------

def phase_train(out: Path, n_envs: int, rollout_len: int, iters: int):
    """E2HRL fxp8 PPO through ``rl_train`` on one device."""
    from repro.obs import read_records
    from repro.rl.envs import make

    metrics = out / "train_metrics"
    params0, _ = rl_cli.make_agent("hrl", make(E2HRL_ENV),
                                   jax.random.PRNGKey(0), TRAIN_POLICY)
    params0 = jax.device_get(params0)
    params, history = rl_cli.rl_train(
        E2HRL_ENV, "hrl", iters=iters, n_envs=n_envs,
        rollout_len=rollout_len, actor_policy=TRAIN_POLICY, seed=0,
        mesh_kind="host", mesh_devices=1, algo="ppo",
        metrics_dir=str(metrics), log_every=1)
    steps = [r for r in read_records(str(metrics / "train.jsonl"))
             if r["kind"] == "step"]
    if len(steps) != iters or len(history) != iters:
        raise AssertionError(f"expected {iters} iteration records, got "
                             f"{len(steps)} (history {len(history)})")
    for r in steps:
        loss = r["metrics"]["loss"]
        log(f"[train] iter {r['step']}: loss {loss!r}, return "
            f"{r['metrics']['return_mean']!r}, step "
            f"{r['spans']['step']!r} s")
        if not np.isfinite(loss):
            raise AssertionError(f"iteration {r['step']} loss {loss}")
    if not _finite(params):
        raise AssertionError("trained params hold non-finite values")
    delta = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params0), strict=True))
    if not delta > 0.0:
        raise AssertionError("params did not change over training")
    log(f"[train] params changed: max |delta| {delta!r}")
    log(f"[train] peak_bytes_in_use {peak_bytes(jax.devices()[0])!r}")
    steady = [r["spans"]["step"] for r in steps[1:]]
    log(f"[train] set-up: first iteration (compile + run) "
        f"{steps[0]['spans']['step']!r} s")
    if steady:
        log(f"[train] information: steady iteration "
            f"{sum(steady) / len(steady)!r} s over {len(steady)} "
            f"iteration(s) of {n_envs} envs x {rollout_len} steps")


# ---- phase 3 ----------------------------------------------------------------

def phase_serve(out: Path, n_envs: int, episodes: int):
    """dqn catch conv -> checkpoint -> serve_policy at w8 with parity."""
    ckpt = out / "dqn_catch_conv"
    rl_cli.main(["--algo", "dqn", "--env", "catch", "--net", "conv",
                 "--frame-stack", "4", "--iters", "4",
                 "--n-envs", str(n_envs), "--rollout-len", "8",
                 "--learn-start", "64", "--replay-capacity", "8192",
                 "--save-every", "3", "--ckpt-dir", str(ckpt)])
    stats = serve_policy(str(ckpt), precision=SERVE_PRECISION,
                         do_check_parity=True, episodes=episodes,
                         n_slots=32, max_bucket=max(SERVING_BUCKETS))
    if stats.episodes < episodes:
        raise AssertionError(f"served {stats.episodes} of {episodes} "
                             "episodes")
    s = stats.server
    log(f"[serve] information: {s['actions_per_s']!r} actions/s, p50 "
        f"{s['p50_ms']!r} ms, p99 {s['p99_ms']!r} ms")


# ---- phase 4 ----------------------------------------------------------------

def _i8(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _same(name, got, want, exact=False, **tol):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{name}: {got.shape}/{got.dtype} vs oracle "
                             f"{want.shape}/{want.dtype}")
    if exact:
        np.testing.assert_array_equal(got, want, err_msg=name)
    else:
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
    log(f"[kernel] {name}: matches its oracle"
        + (" exactly" if exact else f" ({tol})"))


def phase_kernels(batch: int, interpret: bool):
    """Each Pallas kernel at the E2HRL shapes, against its ``ref``."""
    from repro.configs.e2hrl import CONFIG, CONFIG_LSTM
    from repro.core.policy import cordic_iterations
    from repro.kernels.qconv import ref as qconv_ref
    from repro.kernels.qlstm import ref as qlstm_ref
    from repro.kernels.qmac import ref as qmac_ref
    from repro.kernels.vact import ref as vact_ref

    ks = iter(jax.random.split(jax.random.PRNGKey(7), 64))

    # the three Q-Conv layers of the stem at the fleet batch
    h = CONFIG.obs_shape[0]
    c = CONFIG.obs_shape[-1]
    k = CONFIG.conv_kernel
    for n in CONFIG.conv_channels:
        qx = _i8(next(ks), (batch, h, h, c))
        sx = jax.random.uniform(next(ks), (batch, h, h, 1), minval=1e-3,
                                maxval=1e-2)
        qw = _i8(next(ks), (k, k, c, n))
        sw = jax.random.uniform(next(ks), (n,), minval=1e-3, maxval=1e-2)
        b = jax.random.normal(next(ks), (n,)) * 0.1
        args = (qx, sx, qw, sw, b)
        got = jax.jit(lambda *a: qconv_ops.qconv2d_i8(
            *a, stride=2, fuse_relu=True, kernel=True,
            interpret=interpret))(*args)
        want = qconv_ref.qconv2d_i8(*args, stride=2, fuse_relu=True)
        _same(f"qconv2d_i8 [{batch},{h},{h},{c}]->{n}", got, want,
              rtol=1e-6, atol=1e-6)
        h, c = (h + 1) // 2, n

    # the Q-FC after the stem, at the fleet batch
    flat = h * h * c
    qx = _i8(next(ks), (batch, flat))
    qw = _i8(next(ks), (flat, CONFIG.embed_dim))
    _same(f"qmac_i8 [{batch},{flat}]x[{flat},{CONFIG.embed_dim}]",
          qmac_ops.qmac_i8(qx, qw, interpret=interpret),
          qmac_ref.qmac_i8(qx, qw), exact=True)
    sx = jax.random.uniform(next(ks), (batch, 1), minval=1e-3, maxval=0.1)
    sw = jax.random.uniform(next(ks), (1, CONFIG.embed_dim), minval=1e-3,
                            maxval=0.1)
    _same(f"qmac_i8_deq [{batch},{flat}]x[{flat},{CONFIG.embed_dim}]",
          qmac_ops.qmac_i8_deq(qx, sx, qw, sw, interpret=interpret),
          qmac_ref.qmac_i8_deq(qx, sx, qw, sw), rtol=1e-6)
    # the serving bucket ladder: small-M blocks
    qb = _i8(next(ks), (max(SERVING_BUCKETS), flat))
    for m in SERVING_BUCKETS:
        _same(f"qmac_i8 [{m},{flat}]x[{flat},{CONFIG.embed_dim}]",
              qmac_ops.qmac_i8(qb[:m], qw, interpret=interpret),
              qmac_ref.qmac_i8(qb[:m], qw), exact=True)

    # V-ACT at the embedding width and the action head
    n_it = cordic_iterations(get_policy(TRAIN_POLICY))
    x = jax.random.normal(next(ks), (batch, CONFIG.embed_dim)) * 4.0
    for kind in ("relu", "sigmoid", "tanh"):
        _same(f"vact {kind} [{batch},{CONFIG.embed_dim}]",
              vact_ops.vact(x, kind, n_it, interpret=interpret),
              vact_ref.vact(x, kind, n_it), atol=1e-6, rtol=1e-5)
    logits = jax.random.normal(next(ks), (batch, CONFIG.n_actions)) * 5.0
    _same(f"vact softmax [{batch},{CONFIG.n_actions}]",
          vact_ops.vact(logits, "softmax", n_it, interpret=interpret),
          vact_ref.vact(logits, "softmax", n_it), atol=1e-6, rtol=1e-5)
    qa = _i8(next(ks), (batch, CONFIG.embed_dim))
    got = vact_ops.vact_q8(qa, 0.05, "tanh", n_it, interpret=interpret)
    want = vact_ref.vact_q8(qa, jnp.float32(0.05), "tanh", n_it)
    lsb = int(np.max(np.abs(np.asarray(got, np.int32)
                            - np.asarray(want, np.int32))))
    if got.dtype != jnp.int8 or lsb > 1:
        raise AssertionError(f"vact_q8: {got.dtype}, {lsb} LSB off")
    log(f"[kernel] vact_q8 tanh [{batch},{CONFIG.embed_dim}]: within "
        f"{lsb} LSB of its oracle")

    # the fused Q-LSTM cell at the E2HRL-LSTM widths
    din, hid = CONFIG_LSTM.embed_dim, CONFIG_LSTM.subgoal_hidden
    args = (_i8(next(ks), (batch, din)), jnp.float32(0.02),
            _i8(next(ks), (batch, hid)), jnp.float32(0.015),
            _i8(next(ks), (din, 4 * hid)),
            jax.random.uniform(next(ks), (1, 4 * hid), minval=1e-3,
                               maxval=5e-3),
            _i8(next(ks), (hid, 4 * hid)),
            jax.random.uniform(next(ks), (1, 4 * hid), minval=1e-3,
                               maxval=5e-3),
            jax.random.normal(next(ks), (4 * hid,)) * 0.1,
            jax.random.normal(next(ks), (batch, hid)) * 0.5)
    h_k, c_k = qlstm_ops.qlstm_cell(*args, n_iters=13, interpret=interpret)
    h_r, c_r = qlstm_ref.qlstm_cell(*args, n_iters=13)
    _same(f"qlstm_cell c [{batch},{din}]->{hid}", c_k, c_r, atol=1e-5,
          rtol=1e-4)
    _same(f"qlstm_cell h [{batch},{din}]->{hid}", h_k, h_r, atol=1e-5,
          rtol=1e-4)


# ---- --chips 4 ----------------------------------------------------------------

def _span(name: str, tree, n: int) -> None:
    for leaf in jax.tree.leaves(tree):
        got = len(leaf.sharding.device_set)
        if got != n:
            raise AssertionError(f"{name}: a {leaf.shape} leaf spans "
                                 f"{got} device(s), not {n}")
    log(f"[chips] {name} spans {n} devices")


def phase_sharded_fleet(n_chips: int, n_envs: int, rollout_len: int):
    """Sharded E2HRL actor fleet vs per-device rollouts, bit-exact."""
    from repro.launch.mesh import make_host_mesh
    from repro.rl import init_envs
    from repro.rl.actor_learner import (collect, collect_sharded,
                                        pack_weights)
    from repro.rl.dists import distribution_for
    from repro.rl.envs import make

    mesh = make_host_mesh(n_chips)
    env = make(E2HRL_ENV)
    params, apply_fn = rl_cli.make_agent("hrl", env,
                                         jax.random.PRNGKey(0),
                                         TRAIN_POLICY)
    packed = pack_weights(params, 8)
    pol = get_policy(TRAIN_POLICY)
    dist = distribution_for(env.action_space)
    est, obs = init_envs(env, jax.random.PRNGKey(1), n_envs, mesh=mesh)
    key = jax.random.PRNGKey(2)
    t0 = time.perf_counter()
    res = jax.jit(lambda p, k, e, o: collect_sharded(
        p, env, apply_fn, pol, k, e, o, rollout_len, mesh, dist))(
            packed, key, est, obs)
    jax.block_until_ready(res)
    log(f"[chips] set-up: sharded fleet compile + run "
        f"{time.perf_counter() - t0!r} s")
    _span("sharded trajectories", res.traj, n_chips)
    one = jax.jit(lambda p, k, e, o: collect(
        p, env, apply_fn, pol, k, e, o, rollout_len, dist))
    per = n_envs // n_chips
    for d in range(n_chips):
        sl = slice(d * per, (d + 1) * per)
        ref = one(packed, jax.random.fold_in(key, d),
                  jax.tree.map(lambda x: np.asarray(x)[sl], est),
                  np.asarray(obs)[sl])
        for a, b in zip(jax.tree.leaves(res.traj),
                        jax.tree.leaves(ref.traj), strict=True):
            np.testing.assert_array_equal(np.asarray(a)[:, sl],
                                          np.asarray(b))
        np.testing.assert_array_equal(np.asarray(res.last_value)[sl],
                                      np.asarray(ref.last_value))
        for a, b in zip(jax.tree.leaves(res.final_env),
                        jax.tree.leaves(ref.final_env), strict=True):
            np.testing.assert_array_equal(np.asarray(a)[sl],
                                          np.asarray(b))
    log(f"[chips] sharded fleet == {n_chips} per-device rollouts, "
        f"bit for bit ({n_envs} envs x {rollout_len} steps)")


def phase_sharded_value(n_chips: int, n_envs: int):
    """qrdqn + PER over a 4-device host mesh, double-buffered sync."""
    state: dict = {}
    _, history = rl_cli.value_train(
        "qrdqn", "cartpole", iters=3, n_envs=n_envs, rollout_len=8,
        replay="per", replay_capacity=64 * n_envs, learn_start=256,
        mesh_kind="host", mesh_devices=n_chips, sync="doublebuf",
        state_out=state, log_every=1)
    if len(history) != 3 or not all(np.isfinite(history)):
        raise AssertionError(f"qrdqn history {history}")
    _span("replay shards", state["replay"], n_chips)
    _span("fleet env state", state["env_state"], n_chips)


# ---- main ---------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded fleets over 4 chips")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = check_device(args.chips)
    if OUT.exists():
        shutil.rmtree(OUT)
    OUT.mkdir(parents=True)
    if args.chips == 4:
        phase_sharded_fleet(args.chips, n_envs=512, rollout_len=128)
        phase_sharded_value(args.chips, n_envs=512)
    else:
        phase_train(OUT, n_envs=512, rollout_len=128, iters=3)
        phase_serve(OUT, n_envs=64, episodes=64)
        phase_kernels(batch=512, interpret=False)
        log(f"[backend] training {TRAIN_POLICY} and serving "
            f"{SERVE_PRECISION} ran the "
            f"{get_policy(TRAIN_POLICY).backend!r} backend (int8 XLA "
            "dots); the Pallas kernels ran only in phase 4")
        log(f"[memory] memory_stats {dev.memory_stats()!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
