"""Plain reference of the E2HRL agent's on-policy iteration on KeyDoor.

Written from the configuration in ``e2hrl_fc.json`` with nothing but
``jax.numpy``: the KeyDoor gridworld, the agent (three stride-2 convs,
a 32-d embedding, the FC sub-goal module, action and value heads), the
int8 actor forward on the Q-MAC grid (per-pixel / per-row activation
scales, per-out-channel weight scales, per-tensor requantization after
each activation), GAE with time-limit bootstrapping, the PPO / A2C
losses and AdamW with global-norm clipping.  Float32 math runs at
``highest`` matmul precision: above the configuration's stated learner
precision (the platform's default, one bfloat16 pass on the TPU), so a
program that computes more exactly reads closer.

``steps`` follows the program's first iterations from the same seed:
it draws its random numbers from the same keys in the same order
(``fold_in(key, g)`` per iteration, one ``categorical`` draw per
rollout step, one permutation per epoch), so the two follow the same
trajectories wherever their logits agree.

Variants replace the reference in the program's place for the control
and the planted faults: ``control`` is the nearest precision below the
configuration's, the actors at int4 weights and the learner's matmul
operands in float8 (e4m3, one scale per tensor); ``unchanged`` returns
the state it was given; ``half_batch`` takes each loss over half of its
minibatch; ``altered`` shifts every action where the actor samples it.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
VARIANTS = ("reference", "control", "unchanged", "half_batch", "altered")
BLOCK = 8192       # rows per block of the bootstrap value forward


def load() -> dict:
    return json.loads((HERE / "e2hrl_fc.json").read_text())


# ---- weights ------------------------------------------------------------

def init_params(key, cfg: dict):
    """He-normal convs, LeCun-normal dense layers, zero biases."""
    h, w, c = cfg["obs_shape"]
    k = cfg["conv_kernel"]
    keys = iter(jax.random.split(key, 8))
    convs = []
    for c_out in cfg["conv_channels"]:
        std = math.sqrt(2.0 / (k * k * c))
        convs.append({"w": std * jax.random.normal(next(keys),
                                                   (k, k, c, c_out)),
                      "b": jnp.zeros((c_out,))})
        c = c_out
        h, w = (h + 1) // 2, (w + 1) // 2

    def dense(d_in, d_out):
        return {"w": math.sqrt(1.0 / d_in)
                * jax.random.normal(next(keys), (d_in, d_out)),
                "b": jnp.zeros((d_out,))}

    e, g = cfg["embed_dim"], cfg["subgoal_dim"]
    return {"stem": {"convs": convs, "fc": dense(h * w * c, e)},
            "subgoal": {"fc1": dense(e, cfg["subgoal_hidden"]),
                        "fc2": dense(cfg["subgoal_hidden"], g)},
            "action": {"fc": dense(e + g, cfg["n_actions"])},
            "value": dense(e + g, 1)}


# ---- KeyDoor --------------------------------------------------------------

GRID, CELL, MAX_STEPS = 8, 4, 64
MOVES = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1]], jnp.int32)


def _layout(key):
    key, sub = jax.random.split(key)
    cells = jax.random.choice(sub, GRID * GRID, (3,), replace=False)
    pos = jnp.stack([cells // GRID, cells % GRID], -1).astype(jnp.int32)
    return {"agent": pos[0], "key_pos": pos[1], "door": pos[2],
            "has_key": jnp.zeros((), bool), "t": jnp.zeros((), jnp.int32),
            "key": key}


def _image(s):
    img = jnp.zeros((GRID, GRID, 3))
    img = img.at[s["agent"][0], s["agent"][1], 0].set(1.0)
    img = img.at[s["key_pos"][0], s["key_pos"][1], 1].set(
        jnp.where(s["has_key"], 0.0, 1.0))
    img = img.at[s["door"][0], s["door"][1], 2].set(1.0)
    return jnp.repeat(jnp.repeat(img, CELL, 0), CELL, 1)


def env_reset(key):
    s = _layout(key)
    return s, _image(s)


def env_step(s, action):
    agent = jnp.clip(s["agent"] + MOVES[action], 0, GRID - 1)
    at_key = jnp.all(agent == s["key_pos"])
    picked = at_key & ~s["has_key"]
    has_key = s["has_key"] | at_key
    opened = jnp.all(agent == s["door"]) & has_key
    t = s["t"] + 1
    reward = -0.01 + 0.5 * picked + 1.0 * opened
    truncated = (t >= MAX_STEPS) & ~opened
    nxt = dict(s, agent=agent, has_key=has_key, t=t)
    out = jax.tree.map(lambda a, b: jnp.where(opened | truncated, a, b),
                       _layout(s["key"]), nxt)
    return out, _image(out), reward, opened, truncated, _image(nxt)


def env_init(seed: int, n_envs: int):
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n_envs)
    return jax.vmap(env_reset)(keys)


# ---- the agent --------------------------------------------------------------

def fake_quant(x, bits, over=None):
    """Symmetric abs-max grid with one scale per slice reduced ``over``
    those axes (None: one scale for the whole tensor)."""
    qmax = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=over, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def per_row(x, bits):
    """One scale per pixel or row: over the last axis."""
    return fake_quant(x, bits, -1)


def per_out_channel(w, bits):
    """One scale per output channel: over every axis but the last."""
    return fake_quant(w, bits, tuple(range(w.ndim - 1)))


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def actor_apply(p, obs, cfg, w_bits):
    """The int8 (or int4-weight) actor: every matmul operand on its
    grid, each activation output requantized per tensor."""
    a = cfg["actor_a_bits"]

    def dense(layer, x):
        return (per_row(x, a) @ per_out_channel(layer["w"], w_bits)
                + layer["b"])

    x = obs
    for layer in p["stem"]["convs"]:
        y = _conv(per_row(x, a), per_out_channel(layer["w"], w_bits),
                  cfg["conv_stride"]) + layer["b"]
        x = fake_quant(jax.nn.relu(y), a)
    x = x.reshape(x.shape[0], -1)
    e = fake_quant(jax.nn.relu(dense(p["stem"]["fc"], x)), a)
    h = fake_quant(jax.nn.relu(dense(p["subgoal"]["fc1"], e)), a)
    g = fake_quant(jnp.tanh(dense(p["subgoal"]["fc2"], h)), a)
    f = jnp.concatenate([e, g], -1)
    return dense(p["action"]["fc"], f), dense(p["value"], f)[..., 0]


def exact(x):
    return x


def fp8(x):
    """x on the float8 e4m3 grid, one scale per tensor (its abs-max to
    448); the gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def learner_apply(p, obs, cfg, operand=exact):
    """The float32 learner; ``operand`` rounds every matmul operand."""
    def dense(layer, x):
        return operand(x) @ operand(layer["w"]) + layer["b"]

    x = obs
    for layer in p["stem"]["convs"]:
        x = jax.nn.relu(_conv(operand(x), operand(layer["w"]),
                              cfg["conv_stride"]) + layer["b"])
    x = x.reshape(x.shape[0], -1)
    e = jax.nn.relu(dense(p["stem"]["fc"], x))
    h = jax.nn.relu(dense(p["subgoal"]["fc1"], e))
    g = jnp.tanh(dense(p["subgoal"]["fc2"], h))
    f = jnp.concatenate([e, g], -1)
    return dense(p["action"]["fc"], f), dense(p["value"], f)[..., 0]


def pack(params, bits):
    """The weight sync: every matmul weight to its per-out-channel grid."""
    return {k: pack(v, bits) if isinstance(v, dict) else
            [pack(x, bits) for x in v] if isinstance(v, list) else
            per_out_channel(v, bits) if k == "w" else v
            for k, v in params.items()}


# ---- one iteration ------------------------------------------------------------

def _gae(rew, val, term, trunc, last_value, boot, gamma, lam):
    nxt = jnp.concatenate([val[1:], last_value[None]], 0)
    nxt = jnp.where(trunc, boot, nxt)
    cont = 1.0 - term.astype(jnp.float32)
    keep = 1.0 - (term | trunc).astype(jnp.float32)

    def back(carry, xs):
        r, v, nv, c, k = xs
        adv = r + gamma * nv * c - v + gamma * lam * k * carry
        return adv, adv

    _, adv = jax.lax.scan(back, jnp.zeros_like(last_value),
                          (rew, val, nxt, cont, keep), reverse=True)
    return adv, adv + val


def _log_softmax_at(logits, actions):
    logp = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]


def _rows(obs):
    """Images [B, H, W, C] of 0/1 pixels -> flat byte rows [B, H*W*C]."""
    return obs.reshape(obs.shape[0], -1).astype(jnp.uint8)


def _images(rows, cfg):
    """Flat byte rows [N, H*W*C] -> float32 images [N, H, W, C]."""
    return rows.astype(jnp.float32).reshape(
        (rows.shape[0],) + tuple(cfg["obs_shape"]))


def _loss(params, batch, cfg, algo, operand, half):
    if half:
        batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    logits, values = learner_apply(params, _images(batch["obs"], cfg), cfg,
                                   operand)
    logp = _log_softmax_at(logits, batch["actions"])
    adv = batch["advantages"]
    hp = cfg["ppo"]
    if algo == "ppo":
        ratio = jnp.exp(logp - batch["log_probs"])
        clipped = jnp.clip(ratio, 1 - hp["clip_eps"], 1 + hp["clip_eps"])
        pg = jnp.mean(-jnp.minimum(ratio * adv, clipped * adv))
    else:
        pg = -jnp.mean(logp * adv)
    v_loss = 0.5 * jnp.mean(jnp.square(values - batch["returns"]))
    lsm = jax.nn.log_softmax(logits)
    entropy = jnp.mean(-jnp.sum(jnp.exp(lsm) * lsm, -1))
    return pg + hp["vf_coef"] * v_loss - hp["ent_coef"] * entropy


def _adam(params, opt, grads, oc):
    grads = jax.tree.map(lambda g: jnp.where(jnp.isfinite(g), g, 0.0),
                         grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, oc["max_grad_norm"]
                                  / jnp.maximum(norm, 1e-12)), grads)
    count = opt["count"] + 1
    b1, b2 = oc["b1"], oc["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)

    def upd(p, m, v):
        step = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count))
                                          + oc["eps"])
        return p - oc["lr"] * (step + oc["weight_decay"] * p)

    return (jax.tree.map(upd, params, mu, nu),
            {"mu": mu, "nu": nu, "count": count})


def make_iteration(cfg: dict, job: dict, variant: str = "reference"):
    """The jitted reference iteration ``(params, opt, est, obs, key) ->
    (params, opt, est, obs, loss)`` for one ``job`` (the traffic mix)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    control = variant == "control"
    operand = fp8 if control else exact
    w_bits = 4 if control else cfg["comm_bits"]
    T, B = job["rollout_len"], job["n_envs"]
    epochs, n_mb, algo = job["epochs"], job["minibatches"], job["algo"]
    hp, oc = cfg["ppo"], cfg["optimizer"]

    def iteration(params, opt, est, obs, key):
        k_collect, k_learn = jax.random.split(key)
        actor = pack(params, w_bits)

        def one(carry, k):
            s, o = carry
            logits, value = actor_apply(actor, o, cfg, w_bits)
            action = jax.random.categorical(k, logits)
            logp = _log_softmax_at(logits, action)
            if variant == "altered":
                action = (action + 1) % cfg["n_actions"]
            s, nxt, r, d, tr, final = jax.vmap(env_step)(s, action)
            # KeyDoor's frames are 0/1 images: bytes hold them exactly
            return (s, nxt), (_rows(o), action, logp, value, r, d, tr,
                              _rows(final))

        keys = jax.random.split(jax.random.fold_in(k_collect, 0), T)
        (est2, obs2), (O, A, LP, V, R, D, TR, FO) = jax.lax.scan(
            one, (est, obs), keys)
        last_value = actor_apply(actor, obs2, cfg, w_bits)[1]
        # the time-limit bootstrap, one block of successors at a time
        blocks = FO.reshape((-1, min(B, BLOCK)) + FO.shape[2:])
        boot = jax.lax.map(
            lambda rows: learner_apply(params, _images(rows, cfg), cfg,
                                       operand)[1], blocks).reshape(T, B)
        adv, ret = _gae(R, V, D, TR, last_value, boot, hp["gamma"],
                        hp["lam"])
        mu = adv.mean()
        adv = (adv - mu) / (jnp.sqrt(jnp.mean(jnp.square(adv - mu)))
                            + 1e-8)
        batch = {"obs": O.reshape(T * B, -1),
                 "actions": A.reshape(-1), "log_probs": LP.reshape(-1),
                 "advantages": adv.reshape(-1), "returns": ret.reshape(-1)}
        n = T * B
        mb = n // n_mb

        def update(carry, i):
            p, o_state, perm, _ = carry
            idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
            mbatch = {k: v[idx] for k, v in batch.items()}
            loss, grads = jax.value_and_grad(_loss)(
                p, mbatch, cfg, algo, operand, variant == "half_batch")
            p, o_state = _adam(p, o_state, grads, oc)
            return (p, o_state, perm, loss), None

        def epoch(carry, _):
            p, o_state, k, loss = carry
            k, sub = jax.random.split(k)
            perm = jax.random.permutation(sub, n)
            (p, o_state, _, loss), _ = jax.lax.scan(
                update, (p, o_state, perm, loss), jnp.arange(n_mb))
            return (p, o_state, k, loss), None

        (p, o_state, _, loss), _ = jax.lax.scan(
            epoch, (params, opt, k_learn, jnp.zeros(())), None,
            length=epochs)
        if variant == "unchanged":
            p, o_state = params, opt
        return p, o_state, est2, obs2, loss

    return jax.jit(iteration)


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.int32)}


def steps(params0, seed: int, cfg: dict, job: dict, n_steps: int,
          variant: str = "reference") -> dict:
    """Follow the program's first ``n_steps`` iterations.  Returns the
    loss of each, the optimizer's moments and the envs' observations
    after the first, and the parameters after the last, all on the
    host."""
    with jax.default_matmul_precision("highest"):
        it = make_iteration(cfg, job, variant)
        params = jax.tree.map(jnp.asarray, params0)
        opt = adam_init(params)
        est, obs = env_init(seed, job["n_envs"])
        key = jax.random.PRNGKey(seed)
        losses, first = [], None
        for g in range(n_steps):
            params, opt, est, obs, loss = it(params, opt, est, obs,
                                             jax.random.fold_in(key, g))
            losses.append(float(loss))
            if g == 0:
                first = jax.device_get({"mu1": opt["mu"], "nu1": opt["nu"],
                                        "obs1": obs})
        return {"losses": losses, **first, "params": jax.device_get(params)}


def leaf_gaps(got, want, gate) -> list:
    """Each leaf's gap between two norms: |‖got‖ - ‖want‖| over the
    larger of ‖want‖ and the median leaf's ‖want‖.  Leaves whose
    ``gate`` norm is under a thousandth of the median leaf's are left
    out (their gradient is nought to rounding)."""
    g_l = [float(np.linalg.norm(x)) for x in jax.tree.leaves(got)]
    w_l = [float(np.linalg.norm(x)) for x in jax.tree.leaves(want)]
    t_l = [float(np.linalg.norm(x)) for x in jax.tree.leaves(gate)]
    med_w, med_t = float(np.median(w_l)), float(np.median(t_l))
    gaps = [abs(g - w) / max(w, med_w, 1e-30)
            for g, w, t in zip(g_l, w_l, t_l, strict=True)
            if t >= 1e-3 * med_t]
    return gaps


def _worst(values) -> float:
    """The largest value; a non-finite one reads as infinite."""
    values = list(values)
    if not all(np.isfinite(values)):
        return math.inf
    return max(values, default=0.0)


def compare(prog: dict, ref: dict, params0) -> dict:
    """The numbers ``correct`` is decided on."""
    delta = lambda r: jax.tree.map(lambda a, b: np.asarray(a) - b,  # noqa: E731
                                   r["params"], params0)
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"],
                                       strict=True)]
    b = ref["obs1"].shape[0]
    moved = np.any(np.asarray(prog["obs1"]).reshape(b, -1)
                   != ref["obs1"].reshape(b, -1), axis=1)
    moment = leaf_gaps(prog["mu1"], ref["mu1"], ref["mu1"])
    return {"diverged1": float(moved.mean()),
            "loss1_gap": _worst(gaps[:1]), "loss_gap": _worst(gaps),
            "moment_gap": _worst(moment),
            "moment_median_gap": float(np.median(moment)),
            "nu_gap": _worst(leaf_gaps(prog["nu1"], ref["nu1"], ref["mu1"])),
            "delta_gap": _worst(leaf_gaps(delta(prog), delta(ref),
                                          ref["mu1"]))}
