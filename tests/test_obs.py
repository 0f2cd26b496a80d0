"""Observability subsystem tests: jit-safe metric buffers, fixed-bucket
histograms, the obs/v1 JSONL schema, and the load-bearing contract that
instrumented training is bitwise identical to uninstrumented training
(docs/observability.md)."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import (LATENCY_EDGES_S, FixedHistogram, JsonlSink,
                       MetricSpec, SpanClock, counter_add, flush,
                       gauge_max, gauge_set, hist_observe, log_edges,
                       read_records, render, summarize, summarize_file,
                       validate_record)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def _step_records(path):
    return [r for r in read_records(path) if r["kind"] == "step"]


def _assert_contiguous(windows, lo, hi):
    assert windows[0][0] == lo and windows[-1][1] == hi
    for (a, b), (c, d) in zip(windows, windows[1:]):
        assert b == c, f"gap between windows {[a, b]} and {[c, d]}"


# ---------------------------------------------------------------------------
# MetricBuffer: jit-safe ops, 32-bit dtypes, flush semantics
# ---------------------------------------------------------------------------


def test_metric_buffer_ops_under_jit_and_flush_resets():
    spec = MetricSpec(counters=("steps",), gauges=("ret", "peak"),
                      hists=(("lat", (0.1, 1.0, 10.0)),))

    @jax.jit
    def update(buf, x):
        buf = counter_add(buf, "steps", 4)
        buf = gauge_set(buf, "ret", x)
        buf = gauge_max(buf, "peak", x)
        buf = hist_observe(spec, buf, "lat",
                           jnp.array([0.05, 0.5, 5.0, 50.0]))
        return buf

    buf = spec.init()
    buf = update(buf, jnp.float32(2.5))
    buf = update(buf, jnp.float32(1.0))
    # everything 32-bit by construction (trace-audit QF901 applies to
    # instrumented programs too)
    for leaf in jax.tree.leaves(buf):
        assert leaf.dtype in (jnp.int32, jnp.float32)

    metrics, hists, fresh = flush(spec, buf)
    assert metrics["steps"] == 8
    assert metrics["ret"] == 1.0          # last write wins
    assert metrics["peak"] == 2.5         # running max
    assert hists["lat"]["counts"] == [2, 2, 2, 2]
    assert hists["lat"]["edges"] == [0.1, 1.0, 10.0]
    # the returned buffer is a fresh zero tree, safe to keep donating
    assert all(not leaf.any() for leaf in jax.tree.leaves(fresh))
    m2, _, _ = flush(spec, fresh)
    assert m2["steps"] == 0 and m2["peak"] == 0.0


def test_metric_spec_rejects_bad_shapes():
    with pytest.raises(ValueError, match="duplicate"):
        MetricSpec(counters=("x",), gauges=("x",))
    with pytest.raises(ValueError, match="sorted"):
        MetricSpec(hists=(("h", (2.0, 1.0)),))
    with pytest.raises(ValueError, match="edge"):
        MetricSpec(hists=(("h", ()),))


# ---------------------------------------------------------------------------
# FixedHistogram: percentiles within bucket resolution, bounded state
# ---------------------------------------------------------------------------


def test_histogram_percentiles_track_numpy_within_resolution():
    rng = np.random.RandomState(0)
    samples = np.exp(rng.normal(-7.0, 1.0, size=2000))  # ~1ms-ish
    h = FixedHistogram()
    for s in samples:
        h.observe(float(s))
    for q in (10, 50, 90, 99):
        exact = float(np.percentile(samples, q))
        approx = h.percentile(q)
        # log-spaced edges at 16/decade: ~15.5% relative resolution
        assert exact / 1.2 <= approx <= exact * 1.2, (q, exact, approx)
    assert h.count == len(samples)
    assert np.isclose(h.mean(), samples.mean(), rtol=1e-6)


def test_histogram_state_is_bounded_and_ends_clamp():
    h = FixedHistogram(log_edges(1e-3, 1e0, per_decade=4))
    n_buckets = len(h.counts)
    for v in (1e-9, 5e-2, 1e6):           # below, inside, above range
        for _ in range(100):
            h.observe(v)
    assert len(h.counts) == n_buckets     # memory never grows
    assert h.counts[0] == 100 and h.counts[-1] == 100
    # open-end percentiles clamp to the observed extremes
    assert h.percentile(0) == pytest.approx(1e-9)
    assert h.percentile(100) == pytest.approx(1e6)
    d = h.to_dict()
    assert len(d["counts"]) == len(d["edges"]) + 1
    h.reset()
    assert h.count == 0 and not any(h.counts)


# ---------------------------------------------------------------------------
# JSONL sink: schema validation, round-trip, append mode
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_and_append(tmp_path):
    p = str(tmp_path / "m" / "train.jsonl")   # parent dir auto-created
    with JsonlSink(p, run={"algo": "dqn", "env": "cartpole"}) as sink:
        sink.write({"schema": "obs/v1", "kind": "step", "t_wall": 1.0,
                    "step": 1, "window": [0, 2],
                    "metrics": {"env_steps": 64, "return_mean": 9.5},
                    "spans": {"step": 0.25},
                    "hists": {"h": {"edges": [1.0], "counts": [0, 3]}}})
    # append mode: reopening continues the same file
    with JsonlSink(p) as sink:
        sink.write({"schema": "obs/v1", "kind": "profile",
                    "t_wall": 2.0, "dir": "/tmp/prof",
                    "window": [0, 2]})
    recs = read_records(p)
    assert [r["kind"] for r in recs] == ["meta", "step", "profile"]
    assert recs[0]["run"]["algo"] == "dqn"
    assert recs[1]["metrics"]["env_steps"] == 64


@pytest.mark.parametrize("rec, err", [
    ({"schema": "obs/v2", "kind": "step", "t_wall": 0.0}, "schema"),
    ({"schema": "obs/v1", "kind": "stepz", "t_wall": 0.0}, "kind"),
    ({"schema": "obs/v1", "kind": "meta", "t_wall": 0.0}, "run"),
    ({"schema": "obs/v1", "kind": "step", "t_wall": 0.0, "step": 1,
      "window": [3, 1], "metrics": {}, "spans": {}}, "window"),
    ({"schema": "obs/v1", "kind": "step", "t_wall": 0.0, "step": 1,
      "window": [0, 1], "metrics": {"x": True}, "spans": {}}, "number"),
    ({"schema": "obs/v1", "kind": "serve", "t_wall": 0.0,
      "window": [0, 1], "metrics": {}, "buckets": {},
      "hists": {"h": {"edges": [1.0], "counts": [1]}}}, "counts"),
    ({"schema": "obs/v1", "kind": "serve", "t_wall": 0.0,
      "window": [0, 1], "metrics": {},
      "hists": {"h": {"edges": [1.0], "counts": [0, -1]}},
      "buckets": {}}, "negative"),
    ({"schema": "obs/v1", "kind": "serve", "t_wall": 0.0,
      "window": [0, 1], "metrics": {}, "hists": {},
      "buckets": {"big": 3}}, "digit"),
])
def test_validate_record_rejects_malformed(rec, err):
    with pytest.raises(ValueError, match=err):
        validate_record(rec)


def test_sink_refuses_to_write_invalid_records(tmp_path):
    sink = JsonlSink(str(tmp_path / "x.jsonl"))
    with pytest.raises(ValueError):
        sink.write({"schema": "obs/v1", "kind": "nope", "t_wall": 0.0})
    sink.close()
    assert read_records(sink.path) == []


def test_span_clock_accumulates_and_drains():
    clock = SpanClock()
    with clock("step"):
        pass
    with clock("step"):
        pass
    with clock("sync"):
        pass
    spans = clock.drain()
    assert set(spans) == {"step", "sync"}
    assert spans["step"] >= 0.0
    assert clock.drain() == {}            # drained


# ---------------------------------------------------------------------------
# the load-bearing contract: metrics do not perturb training
# ---------------------------------------------------------------------------


def test_value_train_bitwise_parity_and_jsonl_content(tmp_path):
    """dqn with --metrics-dir is bitwise identical to without, and the
    JSONL step windows tile [0, iters) with exact env-step counts."""
    from repro.rl.trainer import value_train

    kw = dict(iters=6, n_envs=8, rollout_len=4, verbose=False,
              replay_capacity=512, seed=5, learn_start=32,
              log_every=2, updates_per_iter=1)
    p0, h0 = value_train("dqn", "cartpole", **kw)
    m = str(tmp_path / "metrics")
    p1, h1 = value_train("dqn", "cartpole", metrics_dir=m, **kw)
    assert h0 == h1
    assert _tree_equal(p0, p1)

    path = os.path.join(m, "train.jsonl")
    recs = read_records(path)
    assert recs[0]["kind"] == "meta"
    assert recs[0]["run"]["algo"] == "dqn"
    steps = _step_records(path)
    _assert_contiguous([r["window"] for r in steps], 0, kw["iters"])
    total = sum(r["metrics"]["env_steps"] for r in steps)
    assert total == kw["iters"] * kw["n_envs"] * kw["rollout_len"]
    last = steps[-1]["metrics"]
    for key in ("return_mean", "epsilon", "replay_size",
                "steps_per_s"):
        assert key in last
    assert last["replay_size"] > 0
    assert all("step" in r["spans"] for r in steps)


def test_onpolicy_train_bitwise_parity(tmp_path):
    from repro.rl.trainer import rl_train

    kw = dict(iters=4, n_envs=8, rollout_len=8, verbose=False,
              seed=2, log_every=2, algo="ppo")
    p0, h0 = rl_train("cartpole", **kw)
    m = str(tmp_path / "metrics")
    p1, h1 = rl_train("cartpole", metrics_dir=m, **kw)
    assert h0 == h1
    assert _tree_equal(p0, p1)

    steps = _step_records(os.path.join(m, "train.jsonl"))
    _assert_contiguous([r["window"] for r in steps], 0, kw["iters"])
    total = sum(r["metrics"]["env_steps"] for r in steps)
    assert total == kw["iters"] * kw["n_envs"] * kw["rollout_len"]
    assert "alive_frac" in steps[-1]["metrics"]
    assert "sync_payload_bytes" in steps[-1]["metrics"]
    assert all(np.isfinite(r["metrics"]["loss"]) for r in steps)


def test_sharded_value_train_bitwise_parity(tmp_path):
    from repro.rl.trainer import value_train

    kw = dict(iters=6, n_envs=8, rollout_len=4, verbose=False,
              replay_capacity=512, seed=9, learn_start=32,
              log_every=2, mesh_kind="host", mesh_devices=1,
              sync="lockstep")
    p0, h0 = value_train("dqn", "cartpole", **kw)
    m = str(tmp_path / "metrics")
    p1, h1 = value_train("dqn", "cartpole", metrics_dir=m, **kw)
    assert h0 == h1
    assert _tree_equal(p0, p1)
    steps = _step_records(os.path.join(m, "train.jsonl"))
    last = steps[-1]["metrics"]
    assert "alive_frac" in last and "staleness_max" in last


def test_resume_continues_metric_windows(tmp_path):
    """A checkpoint-resumed run appends to the same JSONL file and its
    first window starts exactly at the resume step — windows stay
    contiguous across the preemption."""
    from repro.rl.trainer import value_train

    d = str(tmp_path / "ck")
    m = str(tmp_path / "metrics")
    kw = dict(iters=6, n_envs=8, rollout_len=4, verbose=False,
              replay_capacity=512, seed=11, learn_start=32,
              log_every=2, mesh_kind="host", mesh_devices=1,
              sync="lockstep", save_every=2, updates_per_iter=1)
    value_train("dqn", "cartpole", ckpt_dir=d, metrics_dir=m, **kw)
    path = os.path.join(m, "train.jsonl")
    n_first = len(read_records(path))
    # drop the last checkpoint to simulate preemption after it=4,
    # rerun the same command line: resumes at it=3
    for sfx in (".npz", ".npz.json"):
        os.unlink(os.path.join(d, f"step_4{sfx}"))
    value_train("dqn", "cartpole", ckpt_dir=d, metrics_dir=m, **kw)
    recs = read_records(path)
    resumed = recs[n_first:]
    assert resumed[0]["kind"] == "meta"   # second run header
    windows = [r["window"] for r in resumed if r["kind"] == "step"]
    _assert_contiguous(windows, 3, kw["iters"])


# ---------------------------------------------------------------------------
# serving: bounded latency state, bucket counters, telemetry windows
# ---------------------------------------------------------------------------


def _mlp_server(max_bucket=8):
    from repro.rl.inference import build_env, make_value_agent
    from repro.serve import PolicyServer, ServedPolicy

    env = build_env("cartpole", "mlp")
    agent = make_value_agent("dqn", env.spec,
                             key=jax.random.PRNGKey(0), net="mlp")
    policy = ServedPolicy.from_agent(agent, "cartpole")
    return PolicyServer(policy, precision="w8", max_bucket=max_bucket)


def test_server_latency_state_is_bounded():
    server = _mlp_server()
    n_buckets = len(server.latency_hist()["counts"])
    for _ in range(40):
        server.act(jnp.zeros((8, 4)))
    # the unbounded per-request list is gone; state stays O(buckets)
    assert not hasattr(server, "_latencies_s")
    assert len(server.latency_hist()["counts"]) == n_buckets
    assert n_buckets == len(LATENCY_EDGES_S) + 1
    s = server.stats()
    assert s["requests"] == 40 * 8
    assert s["p99_ms"] >= s["p50_ms"] > 0
    assert sum(server.latency_hist()["counts"]) == s["requests"]
    assert sum(server.bucket_requests().values()) == s["requests"]
    server.reset_stats()
    assert not any(server.latency_hist()["counts"])
    assert server.bucket_requests() == {}


def test_serve_episodes_telemetry_matches_stats(tmp_path):
    from repro.serve import serve_episodes

    server = _mlp_server()
    path = str(tmp_path / "serve.jsonl")
    sink = JsonlSink(path, run={"algo": "dqn", "env": "cartpole"})
    st = serve_episodes(server, episodes=6, n_slots=8, seed=0,
                        telemetry=sink, flush_every=3)
    sink.close()

    s = st.server
    serves = [r for r in read_records(path) if r["kind"] == "serve"]
    assert len(serves) >= 2               # flushed mid-run and at end
    # request-count windows tile [0, total requests)
    _assert_contiguous([r["window"] for r in serves],
                       0, s["requests"])
    assert sum(r["metrics"]["requests"] for r in serves) \
        == s["requests"]
    assert sum(r["metrics"]["env_steps"] for r in serves) \
        == st.env_steps
    # per-window bucket deltas sum to the engine's counters
    buckets = {}
    for r in serves:
        for b, n in r["buckets"].items():
            buckets[int(b)] = buckets.get(int(b), 0) + n
    assert buckets == server.bucket_requests()
    # folding the per-window hist deltas reproduces the engine's
    # percentiles within bucket resolution
    rows = summarize_file(path)
    fields = next(f for t, _, f in rows if t == "obs/serve")
    assert fields["requests"] == s["requests"]
    for q, key in ((50, "p50_ms"), (99, "p99_ms")):
        assert fields[key] == pytest.approx(s[key], rel=0.35)


# ---------------------------------------------------------------------------
# summary rendering + CLI
# ---------------------------------------------------------------------------


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "obs_summary", os.path.join(ROOT, "tools", "obs_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_train_file(path):
    with JsonlSink(path, run={"algo": "dqn", "env": "cartpole"}) as s:
        s.write({"schema": "obs/v1", "kind": "step", "t_wall": 1.0,
                 "step": 1, "window": [0, 2],
                 "metrics": {"env_steps": 64, "episodes": 3,
                             "return_mean": 12.5},
                 "spans": {"step": 0.5, "sync": 0.1}})
        s.write({"schema": "obs/v1", "kind": "step", "t_wall": 2.0,
                 "step": 3, "window": [2, 4],
                 "metrics": {"env_steps": 64, "episodes": 2,
                             "return_mean": 20.0},
                 "spans": {"step": 0.3, "checkpoint": 0.1}})


def test_summarize_folds_step_records(tmp_path):
    p = str(tmp_path / "train.jsonl")
    _write_train_file(p)
    out = render(summarize(read_records(p)))
    assert "[obs/train] dqn/cartpole:" in out
    assert "iters=4" in out and "env_steps=128" in out
    assert "episodes=5" in out and "final_return=20.0" in out
    assert "steps_per_s=128.0" in out     # 128 steps / 1.0s spans
    assert "[obs/spans] dqn/cartpole:" in out
    assert "step=0.8" in out and "sync=0.1" in out


def test_obs_summary_cli_renders_and_validates(tmp_path, capsys):
    cli = _load_cli()
    p = str(tmp_path / "train.jsonl")
    _write_train_file(p)

    assert cli.main([p]) == 0
    out = capsys.readouterr().out
    assert "[obs/train] dqn/cartpole:" in out

    assert cli.main([p, "--validate"]) == 0
    assert "3 valid records" in capsys.readouterr().out

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"schema": "obs/v1", "kind": "nope",
                            "t_wall": 0.0}) + "\n")
    assert cli.main([bad, "--validate"]) == 1
    assert "INVALID" in capsys.readouterr().err
