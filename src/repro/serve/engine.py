"""Batched policy inference: micro-batching engine + episode slots.

The serving analogue of the paper's deployment half: a trained value
policy, weights packed to int8/int4 ``QTensor``s, answering action
requests for thousands of concurrent episodes.  Requests are assembled
into power-of-two *buckets* (pad-to-bucket) so XLA compiles one program
per bucket size instead of one per request count — the same trick the
LM serving path uses for sequence lengths.  The engine records a wall
latency per request (each request in a micro-batch pays that batch's
inference wall) into a fixed-bucket histogram — bounded memory under
production traffic, p50/p99 within one bucket's resolution — plus a
per-bucket-size request counter, and reports actions/s, p50/p99 and
the packed model footprint.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import quantized_nbytes
from repro.obs import SCHEMA, FixedHistogram, JsonlSink, SpanClock
from repro.rl.rollout import init_envs
from repro.serve.loader import PRECISIONS, ServedPolicy


def bucket_sizes(max_bucket: int) -> List[int]:
    """Power-of-two bucket ladder: 1, 2, 4, ..., max_bucket."""
    if max_bucket < 1:
        raise ValueError(f"max_bucket must be >= 1, got {max_bucket}")
    sizes = []
    b = 1
    while b < max_bucket:
        sizes.append(b)
        b *= 2
    sizes.append(max_bucket)
    return sizes


def bucket_for(n: int, sizes: List[int]) -> int:
    """Smallest bucket that fits ``n`` requests (largest bucket caps —
    callers chunk anything bigger)."""
    for b in sizes:
        if n <= b:
            return b
    return sizes[-1]


class PolicyServer:
    """Micro-batched action server over one packed policy.

    ``act(obs)`` answers a [N, ...] observation batch of any N: chunks
    of ``max_bucket`` stream through the largest program, the remainder
    pads up to the smallest fitting bucket.  One jitted program is
    compiled (and cached in ``self._jit_cache``) per bucket size
    actually seen.  ``mode="greedy"`` is the evaluation head —
    bit-identical at w8 to ``value_eval`` under fxp8 — and
    ``mode="sample"`` the stochastic head (Boltzmann / bounded
    Gaussian, scaled by ``temperature``).
    """

    def __init__(self, policy: ServedPolicy, precision: str = "w8",
                 mode: str = "greedy", temperature: float = 1.0,
                 max_bucket: int = 256, seed: int = 0):
        if mode not in ("greedy", "sample"):
            raise ValueError(f"unknown serving mode {mode!r} "
                             "(expected 'greedy' or 'sample')")
        self.policy = policy
        self.precision = precision
        self.mode = mode
        self.temperature = float(temperature)
        self.buckets = bucket_sizes(max_bucket)
        self.max_bucket = max_bucket

        packed, apply_policy = policy.pack(precision)
        # the full-tree shape greedy/sampled expect (ddpg re-wraps the
        # bare actor subtree)
        self.served_params = policy.agent.from_behaviour(packed)
        self.apply_policy = apply_policy
        self._key = jax.random.PRNGKey(seed)
        self._jit_cache: Dict[int, object] = {}
        # bounded telemetry state: O(buckets) forever, never a list
        # that grows with traffic
        self._latency = FixedHistogram()
        self._bucket_requests: Dict[int, int] = {}
        self._requests = 0
        self._infer_s = 0.0

    # -- compiled programs -------------------------------------------------

    def _fn_for(self, bucket: int):
        fn = self._jit_cache.get(bucket)
        if fn is not None:
            return fn
        agent, pol = self.policy.agent, self.apply_policy
        if self.mode == "greedy":
            def run(params, obs, key):
                del key
                return agent.greedy(params, obs, pol)
        else:
            t = self.temperature

            def run(params, obs, key):
                return agent.sampled(params, obs, key, temperature=t,
                                     policy=pol)
        fn = jax.jit(run)
        self._jit_cache[bucket] = fn
        return fn

    def warmup(self, n_slots: Optional[int] = None):
        """Pre-compile the programs a ``n_slots``-wide slot bank will
        hit (all buckets when ``None``), so compile time never lands in
        a request latency."""
        if n_slots is None:
            need = list(self.buckets)
        else:
            need = []
            n = n_slots
            while n > 0:
                b = bucket_for(min(n, self.max_bucket), self.buckets)
                if b not in need:
                    need.append(b)
                n -= min(n, self.max_bucket)
        obs_shape = self.policy.env.obs_shape
        for b in need:
            obs = jnp.zeros((b,) + tuple(obs_shape), jnp.float32)
            jax.block_until_ready(
                self._fn_for(b)(self.served_params, obs, self._key))

    # -- serving -----------------------------------------------------------

    def act(self, obs) -> jax.Array:
        """Actions for an [N, ...] observation batch, micro-batched."""
        obs = jnp.asarray(obs)
        n = obs.shape[0]
        outs = []
        start = 0
        while start < n:
            chunk = min(n - start, self.max_bucket)
            bucket = bucket_for(chunk, self.buckets)
            block = obs[start:start + chunk]
            if bucket != chunk:
                pad = [(0, bucket - chunk)] + [(0, 0)] * (obs.ndim - 1)
                block = jnp.pad(block, pad)
            self._key, sub = jax.random.split(self._key)
            fn = self._fn_for(bucket)
            t0 = time.perf_counter()
            acts = jax.block_until_ready(
                fn(self.served_params, block, sub))
            dt = time.perf_counter() - t0
            self._latency.observe(dt, n=chunk)
            self._bucket_requests[bucket] = (
                self._bucket_requests.get(bucket, 0) + chunk)
            self._requests += chunk
            self._infer_s += dt
            outs.append(acts[:chunk])
            start += chunk
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    # -- accounting --------------------------------------------------------

    def model_bytes(self):
        """(stored bytes, fp32 bytes) of the served behaviour subtree."""
        return quantized_nbytes(
            self.policy.agent.behaviour_subtree(self.served_params))

    def stats(self) -> Dict[str, float]:
        stored, fp32 = self.model_bytes()
        out = {
            "requests": float(self._requests),
            "infer_s": self._infer_s,
            "actions_per_s": (self._requests / self._infer_s
                              if self._infer_s > 0 else 0.0),
            "p50_ms": self._latency.percentile(50) * 1e3,
            "p99_ms": self._latency.percentile(99) * 1e3,
            "model_bytes": float(stored),
            "model_fp32_bytes": float(fp32),
            "compression": stored / fp32 if fp32 else 1.0,
            "jit_programs": float(len(self._jit_cache)),
        }
        return out

    def bucket_requests(self) -> Dict[int, int]:
        """Requests answered per padded micro-batch bucket size."""
        return dict(self._bucket_requests)

    def latency_hist(self) -> Dict:
        """The latency histogram's ``{edges, counts}`` (seconds)."""
        return self._latency.to_dict()

    def reset_stats(self):
        self._latency.reset()
        self._bucket_requests = {}
        self._requests = 0
        self._infer_s = 0.0


@dataclasses.dataclass
class EpisodeStats:
    """What one :func:`serve_episodes` run produced."""

    episodes: int
    env_steps: int
    mean_return: float
    wall_s: float
    server: Dict[str, float]


def serve_episodes(server: PolicyServer, episodes: int,
                   n_slots: int = 64, seed: int = 0,
                   max_env_steps: Optional[int] = None,
                   telemetry: Optional[JsonlSink] = None,
                   flush_every: int = 0) -> EpisodeStats:
    """Run ``n_slots`` concurrent episode slots until ``episodes``
    episodes complete, every action answered through the server's
    micro-batching path.  Slots auto-reset (the envs reset internally
    on done/truncation), so a bank of 64 slots serves thousands of
    episodes back-to-back — the production-traffic shape.

    With ``telemetry`` (a :class:`~repro.obs.sink.JsonlSink`) the loop
    writes ``serve`` records: one per ``flush_every`` loop steps (0:
    one record for the whole run), each carrying the window's request
    count, latency histogram delta, per-bucket request counts and
    ``infer``/``env`` phase spans.
    """
    if n_slots < 1:
        raise ValueError(f"n_slots must be >= 1, got {n_slots}")
    env = server.policy.env
    spec = env.spec
    cap = (max_env_steps if max_env_steps is not None
           else spec.max_steps * (episodes + 2 * n_slots))
    est, obs = init_envs(env, jax.random.PRNGKey(seed), n_slots)
    step_fn = jax.jit(jax.vmap(env.step))
    server.warmup(n_slots)
    # one throwaway step to compile step_fn outside the timed region
    # (the result is discarded; the act() bookkeeping is reset below)
    jax.block_until_ready(step_fn(est, server.act(obs)))
    server.reset_stats()

    clock = SpanClock("serve")
    prev_r = 0
    prev_inf = 0.0
    prev_counts = np.array(server._latency.counts)
    prev_buckets: Dict[int, int] = {}
    prev_steps = prev_eps = 0

    def flush_window(env_steps: int, done_episodes: int):
        nonlocal prev_r, prev_inf, prev_counts, prev_buckets
        nonlocal prev_steps, prev_eps
        r1 = server._requests
        if telemetry is None or r1 == prev_r:
            return
        counts = np.array(server._latency.counts)
        buckets = server.bucket_requests()
        telemetry.write({
            "schema": SCHEMA, "kind": "serve", "t_wall": time.time(),
            "window": [prev_r, r1],
            "metrics": {"requests": r1 - prev_r,
                        "infer_s": server._infer_s - prev_inf,
                        "env_steps": env_steps - prev_steps,
                        "episodes": done_episodes - prev_eps},
            "hists": {"latency_s": {
                "edges": [float(e) for e in server._latency.edges],
                "counts": [int(c) for c in counts - prev_counts]}},
            "buckets": {str(b): n - prev_buckets.get(b, 0)
                        for b, n in buckets.items()
                        if n - prev_buckets.get(b, 0)},
            "spans": clock.drain(),
        })
        prev_r, prev_inf, prev_counts = r1, server._infer_s, counts
        prev_buckets = buckets
        prev_steps, prev_eps = env_steps, done_episodes

    done_episodes = 0
    env_steps = 0
    loop_steps = 0
    acc = np.zeros(n_slots, np.float64)       # running per-slot return
    returns: List[float] = []
    t0 = time.perf_counter()
    while done_episodes < episodes and env_steps < cap:
        with clock("infer"):
            acts = server.act(obs)
        with clock("env"):
            est, obs, r, d, tr, _ = step_fn(est, acts)
            d, tr = np.asarray(d), np.asarray(tr)
        env_steps += n_slots
        loop_steps += 1
        fin = d | tr
        acc += np.asarray(r, np.float64)
        if fin.any():
            returns.extend(acc[fin].tolist())
            done_episodes += int(fin.sum())
            acc[fin] = 0.0
        if flush_every and loop_steps % flush_every == 0:
            flush_window(env_steps, done_episodes)
    wall = time.perf_counter() - t0
    flush_window(env_steps, done_episodes)
    mean_ret = float(np.mean(returns)) if returns else float("nan")
    return EpisodeStats(episodes=done_episodes, env_steps=env_steps,
                        mean_return=mean_ret, wall_s=wall,
                        server=server.stats())


def check_parity(policy: ServedPolicy, precision: str = "w8",
                 n_obs: int = 128, seed: int = 0) -> int:
    """Mismatch count between the served greedy head (packed QTensor
    weights) and the evaluation greedy head (fp32 weights under the
    same quant policy's fake-quant) on a rollout of real observations.

    Zero at w8 by construction — both paths round on the same fxp8
    grid (``quantize_params`` vs ``fake_quant``) and rescale in the
    same order — which is the deployment guarantee: shipping the
    packed policy cannot change a single evaluated action.
    """
    if precision not in PRECISIONS or precision == "fp32":
        raise ValueError("parity is defined for the packed precisions "
                         f"('w8', 'w4'), got {precision!r}")
    env, agent = policy.env, policy.agent
    n_slots = min(n_obs, 32)
    est, obs = init_envs(env, jax.random.PRNGKey(seed), n_slots)
    step_fn = jax.jit(jax.vmap(env.step))
    packed, pol = policy.pack(precision)
    served = agent.from_behaviour(packed)

    fn = jax.jit(lambda p, o: agent.greedy(p, o, pol))
    mismatches = 0
    seen = 0
    while seen < n_obs:
        a_eval = fn(policy.params, obs)
        a_serve = fn(served, obs)
        diff = a_eval != a_serve
        if diff.ndim > 1:
            diff = jnp.any(diff, axis=tuple(range(1, diff.ndim)))
        mismatches += int(jnp.sum(diff))
        seen += n_slots
        est, obs, *_ = step_fn(est, a_eval)
    return mismatches
