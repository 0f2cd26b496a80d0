"""Mode 2 — abstract-evaluation audit of every accepted training combo.

No training FLOPs run: each (env x net x algo x precision) combination
``rl_train`` accepts is swept through ``jax.make_jaxpr`` /
``jax.eval_shape`` / ``jit.lower`` on the *real* step functions
(:mod:`repro.rl.train_steps` — the exact programs training runs) and
audited for:

* **QF901** — no 64-bit dtype anywhere in the traced step, and the
  threaded state comes back with exactly the avals it went in with
  (shape, dtype, weak_type): an aval drift means silent upcasts or a
  retrace every iteration.
* **QF902** — every packed ``QTensor``'s scale sits on its consumer's
  per-out-channel grid: 2-D ``[in, out]`` weights -> ``(1, out)``,
  stacked 3-D ``[L, in, out]`` -> ``(L, 1, out)``, conv HWIO 4-D ->
  ``(1, 1, 1, c_out)``.  Any *other* rank is itself a finding — a new
  layer family must extend the table (and ``quantize_params``)
  deliberately, not inherit a wrong branch (the PR 6 conv bug).
* **QF903** — the serving bucket ladder compiles exactly one program
  per bucket: ``len(_jit_cache) == len(buckets)`` and every cached
  function's jit cache holds exactly 1 entry after a sweep of request
  sizes (a second entry = a silent retrace, the latency cliff the
  pad-to-bucket design exists to prevent).
* **QF904** — donation survives lowering: the step's StableHLO carries
  ``tf.aliasing_output`` input-output aliases (a donate_argnums that
  silently failed to stick would double peak memory).

The bucket audit (QF903) runs a few tiny real forwards (warmup
compiles); everything else is abstract.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.analysis.rules import Finding

CHECKS: Dict[str, str] = {
    "QF901": "64-bit dtype in traced step, or threaded-state aval "
             "drift (shape/dtype/weak_type) across one iteration",
    "QF902": "QTensor scale off the consumer's per-out-channel grid",
    "QF903": "serving bucket ladder compiled more (or fewer) than one "
             "program per bucket",
    "QF904": "donate_argnums did not survive lowering "
             "(no input-output aliases in the StableHLO)",
}

PRECISION_AXIS = ("fp32", "fxp8")
_BAD_DTYPES = ("float64", "int64", "uint64", "complex128")


@dataclasses.dataclass
class TraceResult:
    findings: List[Finding]
    combos_checked: List[str]


# ---------------------------------------------------------------------------
# combo enumeration — by construction the same acceptance logic the
# CLI runs: the real constructors either build the combo or raise
# ---------------------------------------------------------------------------


def accepted_combos() -> List[Tuple[str, str, str, str]]:
    """Every (env, net, algo, precision) that ``rl_train``'s dispatch
    accepts, decided by calling the real env/agent constructors."""
    from repro.rl.envs import make, registered
    from repro.rl.inference import (NETS, ON_POLICY_ALGOS, VALUE_ALGOS,
                                    build_env, make_value_agent)
    from repro.rl.trainer import make_agent

    combos = []
    key = jax.random.PRNGKey(0)
    for env_name in sorted(registered()):
        for net in NETS:
            for algo in ON_POLICY_ALGOS + VALUE_ALGOS:
                try:
                    if algo in ON_POLICY_ALGOS:
                        env = (build_env(env_name, net)
                               if net == "conv" else make(env_name))
                        make_agent("mlp", env, key, None, net)
                    else:
                        env = build_env(env_name, net)
                        make_value_agent(algo, env.spec, net=net)
                except ValueError:
                    continue
                for precision in PRECISION_AXIS:
                    combos.append((env_name, net, algo, precision))
    return combos


def _combo_tag(env_name, net, algo, precision) -> str:
    return f"trace:{env_name}/{net}/{algo}/{precision}"


# ---------------------------------------------------------------------------
# QF901 helpers — jaxpr dtype walk + aval parity
# ---------------------------------------------------------------------------


def _iter_subjaxprs(params):
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jex_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jex_core.Jaxpr):
                    yield x


def find_wide_dtypes(closed: "jex_core.ClosedJaxpr") -> List[str]:
    """All distinct 64-bit dtypes appearing on any var in the jaxpr."""
    seen = set()
    stack = [closed.jaxpr]
    visited = set()
    while stack:
        jxp = stack.pop()
        if id(jxp) in visited:
            continue
        visited.add(id(jxp))
        for v in list(jxp.invars) + list(jxp.outvars) + \
                list(jxp.constvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                if str(aval.dtype) in _BAD_DTYPES:
                    seen.add(str(aval.dtype))
        for eqn in jxp.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "dtype"):
                    if str(aval.dtype) in _BAD_DTYPES:
                        seen.add(str(aval.dtype))
            stack.extend(_iter_subjaxprs(eqn.params))
    return sorted(seen)


def _aval_sig(x):
    return (tuple(x.shape), str(x.dtype),
            bool(getattr(x, "weak_type", False)))


def state_parity_mismatches(in_tree, out_tree, label: str) -> List[str]:
    """Leaves whose (shape, dtype, weak_type) changed across the step."""
    ins, in_def = jax.tree.flatten(in_tree)
    outs, out_def = jax.tree.flatten(out_tree)
    if in_def != out_def:
        return [f"{label}: pytree structure changed "
                f"({in_def} -> {out_def})"]
    bad = []
    paths = jax.tree_util.tree_flatten_with_path(in_tree)[0]
    for (path, i), o in zip(paths, outs, strict=True):
        si, so = _aval_sig(i), _aval_sig(o)
        if si != so:
            bad.append(f"{label}{jax.tree_util.keystr(path)}: "
                       f"{si} -> {so}")
    return bad


# ---------------------------------------------------------------------------
# QF902 — quantization grid audit
# ---------------------------------------------------------------------------


def expected_scale_shape(qvalue_shape: Tuple[int, ...]
                         ) -> Optional[Tuple[int, ...]]:
    """The per-out-channel grid the blessed consumers broadcast
    against; None = rank not in the convention table."""
    nd = len(qvalue_shape)
    if nd == 2:                       # [in, out] linear
        return (1, qvalue_shape[1])
    if nd == 3:                       # [L, in, out] stacked layers
        return (qvalue_shape[0], 1, qvalue_shape[2])
    if nd == 4:                       # [H, W, I, O] conv HWIO
        return (1, 1, 1, qvalue_shape[3])
    return None


def check_packed_tree(packed, bits: int, tag: str) -> List[Finding]:
    """Walk an (abstract or concrete) packed tree and check every
    QTensor against the grid table."""
    from repro.core.fxp import QTensor

    findings: List[Finding] = []

    def visit(node, path):
        if isinstance(node, QTensor):
            qshape = tuple(node.qvalue.shape)
            want = expected_scale_shape(qshape)
            got = tuple(node.scale.shape)
            if want is None:
                findings.append(Finding(
                    tag, 0, "QF902",
                    f"{path}: rank-{len(qshape)} QTensor {qshape} has "
                    "no entry in the per-out-channel grid table — "
                    "extend expected_scale_shape AND quantize_params "
                    "for the new layer family"))
            elif got != want:
                findings.append(Finding(
                    tag, 0, "QF902",
                    f"{path}: scale grid {got} != consumer grid "
                    f"{want} for weight {qshape} (w{bits})"))
            if node.bits != bits:
                findings.append(Finding(
                    tag, 0, "QF902",
                    f"{path}: packed bits {node.bits} != policy "
                    f"w_bits {bits}"))
            return
        if isinstance(node, dict):
            for k, v in node.items():
                visit(v, f"{path}/{k}")
        elif isinstance(node, (tuple, list)):
            for i, v in enumerate(node):
                visit(v, f"{path}[{i}]")

    visit(packed, "params")
    return findings


def audit_qtensor_grids(params, bits: int, tag: str) -> List[Finding]:
    """eval_shape ``quantize_params`` over ``params`` and check every
    produced QTensor against the grid table — abstract, no FLOPs."""
    from repro.core.policy import QuantPolicy
    from repro.core.quantizer import quantize_params

    policy = QuantPolicy(name=f"w{bits}", w_bits=bits,
                         per_channel=True)
    packed = jax.eval_shape(lambda p: quantize_params(p, policy),
                            params)
    return check_packed_tree(packed, bits, tag)


# ---------------------------------------------------------------------------
# per-combo step construction
# ---------------------------------------------------------------------------

_N_ENVS = 4
_ROLLOUT = 2
_CAPACITY = 512


def _build_value_step(env_name, net, algo, precision):
    from repro.core.policy import get_policy
    from repro.optim import AdamWConfig, adamw_init, constant
    from repro.rl.actor_learner import pack_weights
    from repro.rl.inference import build_env, make_value_agent
    from repro.rl.replay import make_replay
    from repro.rl.rollout import init_envs
    from repro.rl.train_steps import make_value_iteration

    env = build_env(env_name, net)
    spec = env.spec
    key = jax.random.PRNGKey(0)
    a_policy = get_policy("fxp8") if precision == "fxp8" else None
    agent = make_value_agent(algo, spec, key, net=net)
    params = agent.params
    target = jax.tree.map(jnp.copy, params)
    if algo == "ddpg":
        opt = {"actor": adamw_init(params["actor"]),
               "critic": adamw_init(params["critic"])}
        rb = make_replay("uniform", _CAPACITY, spec.obs_shape,
                         spec.action_space.shape, jnp.float32)
    else:
        opt = adamw_init(params)
        rb = make_replay("uniform", _CAPACITY, spec.obs_shape)
    buf = rb.init()
    est, obs = init_envs(env, jax.random.PRNGKey(1), _N_ENVS)
    iteration = make_value_iteration(
        env, agent, rb, a_policy, constant(1e-3),
        AdamWConfig(weight_decay=0.0, max_grad_norm=10.0), algo=algo,
        rollout_len=_ROLLOUT, updates_per_iter=1, per_beta0=0.4,
        beta_iters=1)
    comm = 8 if a_policy else 32
    packed = pack_weights(agent.behaviour_subtree(params), comm)
    args = (params, target, opt, buf, packed, est, obs,
            jax.random.PRNGKey(2), jnp.asarray(0))
    threaded = {"params": params, "target": target, "opt": opt,
                "buf": buf, "est": est, "obs": obs}
    out_slots = ("params", "target", "opt", "buf", "est", "obs")
    return iteration, args, threaded, out_slots, params


def _build_onpolicy_step(env_name, net, algo, precision):
    from repro.core.policy import get_policy
    from repro.launch.mesh import make_host_mesh
    from repro.rl.trainer import make_agent
    from repro.optim import AdamWConfig, adamw_init, constant
    from repro.rl import PPOConfig
    from repro.rl.actor_learner import pack_weights
    from repro.rl.dists import distribution_for
    from repro.rl.inference import build_env
    from repro.rl.envs import make
    from repro.rl.ppo import a2c_loss, ppo_loss
    from repro.rl.rollout import init_envs
    from repro.rl.train_steps import make_onpolicy_iteration

    env = build_env(env_name, net) if net == "conv" else make(env_name)
    key = jax.random.PRNGKey(0)
    pol_name = "fxp8" if precision == "fxp8" else None
    params, apply_fn = make_agent("mlp", env, key, pol_name, net)
    a_policy = get_policy(pol_name) if pol_name else None
    mesh = make_host_mesh(1)
    dist = distribution_for(env.action_space)
    pcfg = (PPOConfig() if algo == "ppo"
            else PPOConfig(epochs=1, minibatches=1))
    # 8 steps x 4 envs = 32 samples: divisible by the default 4
    # minibatches
    rollout = 8
    iteration = make_onpolicy_iteration(
        env, apply_fn, a_policy, mesh, dist, pcfg,
        ppo_loss if algo == "ppo" else a2c_loss, constant(3e-3),
        AdamWConfig(weight_decay=0.0, max_grad_norm=0.5),
        rollout_len=rollout, n_envs=_N_ENVS, n_slots=1)
    opt = adamw_init(params)
    est, obs = init_envs(env, jax.random.PRNGKey(1), _N_ENVS,
                         mesh=mesh)
    packed = pack_weights(params, 8 if a_policy else 32)
    args = (params, opt, est, obs, packed, jax.random.PRNGKey(2),
            None, jnp.ones((1,), bool))
    threaded = {"params": params, "opt": opt, "est": est, "obs": obs}
    out_slots = ("params", "opt", "est", "obs")
    return iteration, args, threaded, out_slots, params


def _build_sharded_value_step(env_name, net, algo, precision,
                              replay_kind="uniform"):
    from repro.core.policy import get_policy
    from repro.launch.mesh import make_host_mesh
    from repro.optim import AdamWConfig, adamw_init, constant
    from repro.rl.actor_learner import pack_weights
    from repro.rl.inference import build_env, make_value_agent
    from repro.rl.replay import make_sharded_replay
    from repro.rl.rollout import init_envs
    from repro.rl.train_steps import make_sharded_value_iteration

    env = build_env(env_name, net)
    spec = env.spec
    key = jax.random.PRNGKey(0)
    a_policy = get_policy("fxp8") if precision == "fxp8" else None
    agent = make_value_agent(algo, spec, key, net=net)
    params = agent.params
    target = jax.tree.map(jnp.copy, params)
    mesh = make_host_mesh(1)
    if algo == "ddpg":
        opt = {"actor": adamw_init(params["actor"]),
               "critic": adamw_init(params["critic"])}
        srb = make_sharded_replay(replay_kind, 1, _CAPACITY,
                                  spec.obs_shape,
                                  spec.action_space.shape, jnp.float32)
    else:
        opt = adamw_init(params)
        srb = make_sharded_replay(replay_kind, 1, _CAPACITY,
                                  spec.obs_shape)
    buf = srb.init()
    est, obs = init_envs(env, jax.random.PRNGKey(1), _N_ENVS,
                         mesh=mesh)
    iteration = make_sharded_value_iteration(
        env, agent, srb, a_policy, constant(1e-3),
        AdamWConfig(weight_decay=0.0, max_grad_norm=10.0), mesh,
        algo=algo, rollout_len=_ROLLOUT, updates_per_iter=1,
        per_beta0=0.4, beta_iters=1)
    comm = 8 if a_policy else 32
    packed = pack_weights(agent.behaviour_subtree(params), comm)
    args = (params, target, opt, buf, packed, est, obs,
            jax.random.PRNGKey(2), jnp.asarray(0),
            jnp.ones((1,), bool))
    threaded = {"params": params, "target": target, "opt": opt,
                "buf": buf, "est": est, "obs": obs}
    out_slots = ("params", "target", "opt", "buf", "est", "obs")
    return iteration, args, threaded, out_slots, params


# the sharded value path (mesh-mapped collection + per-device replay
# shards + psum'd learner) must satisfy the same invariants as the
# single-device programs — QF904 especially: the double-buffered
# overlap doubles peak memory if donation silently fails to stick
SHARDED_VALUE_COMBOS = (
    ("cartpole", "mlp", "dqn", "fp32", "uniform"),
    ("cartpole", "mlp", "dqn", "fxp8", "per"),
    ("cartpole", "mlp", "qrdqn", "fxp8", "uniform"),
    ("pendulum", "mlp", "ddpg", "fxp8", "uniform"),
    # pixel stem at fxp8: the integer qconv path (custom-vjp over the
    # taps/Pallas kernel) must keep donation + single-trace discipline
    ("catch", "conv", "qrdqn", "fxp8", "uniform"),
)


# ---------------------------------------------------------------------------
# audits
# ---------------------------------------------------------------------------


def audit_step(env_name, net, algo, precision,
               sharded_replay: Optional[str] = None) -> List[Finding]:
    from repro.rl.inference import ON_POLICY_ALGOS

    tag = _combo_tag(env_name, net, algo, precision)
    if sharded_replay is not None:
        tag += f"/sharded-{sharded_replay}"
        iteration, args, threaded, out_slots, params = \
            _build_sharded_value_step(env_name, net, algo, precision,
                                      sharded_replay)
    else:
        build = (_build_onpolicy_step if algo in ON_POLICY_ALGOS
                 else _build_value_step)
        iteration, args, threaded, out_slots, params = build(
            env_name, net, algo, precision)

    findings: List[Finding] = []

    # QF901a: 64-bit dtypes anywhere in the traced step
    closed = jax.make_jaxpr(iteration)(*args)
    for dt in find_wide_dtypes(closed):
        findings.append(Finding(
            tag, 0, "QF901",
            f"{dt} appears in the traced iteration — 64-bit values "
            "must not enter the quantized training step"))

    # QF901b: threaded-state aval parity across the step
    out = jax.eval_shape(iteration, *args)
    for i, name in enumerate(out_slots):
        for msg in state_parity_mismatches(threaded[name], out[i],
                                           name):
            findings.append(Finding(
                tag, 0, "QF901",
                f"threaded-state aval drift: {msg}"))

    # QF904: donation must survive lowering
    lowered_text = iteration.lower(*args).as_text()
    if "tf.aliasing_output" not in lowered_text:
        findings.append(Finding(
            tag, 0, "QF904",
            "no input-output aliases in the lowered step — "
            "donate_argnums did not stick"))

    # QF902: packed-weight grids, at the serving/actor precisions
    findings.extend(audit_qtensor_grids(params, 8, tag))
    findings.extend(audit_qtensor_grids(params, 4, tag))
    return findings


def audit_buckets(env_name: str = "cartpole", net: str = "mlp",
                  max_bucket: int = 8) -> List[Finding]:
    """QF903 on a real PolicyServer: sweep request sizes across the
    ladder, then require one compiled program per bucket, each traced
    exactly once."""
    from repro.rl.inference import build_env, make_value_agent
    from repro.serve.engine import PolicyServer
    from repro.serve.loader import ServedPolicy

    tag = f"trace:{env_name}/{net}/serve/w8"
    env = build_env(env_name, net)
    agent = make_value_agent("dqn", env.spec,
                             key=jax.random.PRNGKey(0), net=net)
    policy = ServedPolicy.from_agent(agent, env_name, net=net)
    server = PolicyServer(policy, precision="w8",
                          max_bucket=max_bucket)
    server.warmup()
    obs_shape = tuple(policy.env.obs_shape)
    # odd request sizes spanning every bucket + an overflow chunk
    for n in [1, 2, 3, max_bucket, max_bucket + 1]:
        server.act(jnp.zeros((n,) + obs_shape, jnp.float32))
    return check_bucket_ladder(server, tag)


def check_bucket_ladder(server, tag: str) -> List[Finding]:
    findings: List[Finding] = []
    if set(server._jit_cache) != set(server.buckets):
        findings.append(Finding(
            tag, 0, "QF903",
            f"bucket ladder {server.buckets} compiled programs for "
            f"{sorted(server._jit_cache)} — one program per bucket"))
    for b, fn in server._jit_cache.items():
        n_traces = fn._cache_size()
        if n_traces != 1:
            findings.append(Finding(
                tag, 0, "QF903",
                f"bucket {b} retraced: {n_traces} cache entries for "
                "one bucket size — a shape/dtype leak past the "
                "pad-to-bucket boundary"))
    return findings


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run_trace_audit(fast: bool = False,
                    combos: Optional[List[Tuple[str, str, str, str]]]
                    = None) -> TraceResult:
    """Sweep the accepted combos.  ``fast`` keeps one representative
    per (net, algo, precision) family instead of every env — the
    per-family program structure is identical, only shapes differ."""
    all_combos = combos if combos is not None else accepted_combos()
    if fast:
        seen, picked = set(), []
        for c in all_combos:
            k = c[1:]
            if k not in seen:
                seen.add(k)
                picked.append(c)
        all_combos = picked

    findings: List[Finding] = []
    checked: List[str] = []
    for env_name, net, algo, precision in all_combos:
        findings.extend(audit_step(env_name, net, algo, precision))
        checked.append(_combo_tag(env_name, net, algo, precision))

    # the sharded value programs (per-device collect + replay shards +
    # psum learner), donation assertion included
    for env_name, net, algo, precision, rep in SHARDED_VALUE_COMBOS:
        findings.extend(audit_step(env_name, net, algo, precision,
                                   sharded_replay=rep))
        checked.append(_combo_tag(env_name, net, algo, precision)
                       + f"/sharded-{rep}")

    # the serving ladder, on both torso families
    findings.extend(audit_buckets("cartpole", "mlp"))
    checked.append("trace:cartpole/mlp/serve/w8")
    findings.extend(audit_buckets("catch", "conv", max_bucket=4))
    checked.append("trace:catch/conv/serve/w8")
    return TraceResult(findings=findings, combos_checked=checked)
