"""Operations the E2HRL agent's on-policy iteration needs, from shapes.

One multiply-accumulate is two operations.  A convolution counts the
taps of its SAME windows that fall on the input; a tap on the padding
multiplies by zero and is not needed.
The int8 actors run ``rollout_len + 1`` forwards per env (one per step
and the bootstrap value); the float32 learner runs one value forward
over every transition's successor and, per epoch, a forward and a
backward over every transition.  The backward needs the weight
gradient of every layer and the input gradient of every layer but the
first.
"""
from __future__ import annotations


def taps_on_input(size: int, k: int, s: int) -> tuple:
    """(output size, taps that fall on the input summed over the
    outputs) along one dim of a SAME convolution."""
    out = -(-size // s)
    lo = max((out - 1) * s + k - size, 0) // 2
    taps = sum(1 for i in range(out) for t in range(k)
               if 0 <= i * s - lo + t < size)
    return out, taps


def layer_macs(cfg: dict):
    """[(layer, multiply-accumulates per sample)] in forward order."""
    h, w, c = cfg["obs_shape"]
    k, s = cfg["conv_kernel"], cfg["conv_stride"]
    out = []
    for i, c_out in enumerate(cfg["conv_channels"]):
        h, th = taps_on_input(h, k, s)
        w, tw = taps_on_input(w, k, s)
        out.append((f"conv{i}", th * tw * c * c_out))
        c = c_out
    e, g = cfg["embed_dim"], cfg["subgoal_dim"]
    out += [("fc", h * w * c * e),
            ("subgoal_fc1", e * cfg["subgoal_hidden"]),
            ("subgoal_fc2", cfg["subgoal_hidden"] * g),
            ("action", (e + g) * cfg["n_actions"]),
            ("value", e + g)]
    return out


def forward_macs(cfg: dict) -> int:
    return sum(m for _, m in layer_macs(cfg))


def per_iteration(cfg: dict, job: dict) -> dict:
    fwd = forward_macs(cfg)
    first = layer_macs(cfg)[0][1]
    bwd = fwd + (fwd - first)
    n = job["n_envs"] * job["rollout_len"]
    return {"int8_ops": 2 * fwd * job["n_envs"] * (job["rollout_len"] + 1),
            "fp_flops": 2 * (fwd * n + job["epochs"] * n * (fwd + bwd))}
