"""The op counts of ``work/<config>.py`` against XLA's own count of the
float32 forward pass: XLA also counts the elementwise operations, so
its count may lie above the algorithm's by that much and no more."""
import functools

import jax
import jax.numpy as jnp
import pytest

import harness

CONFIGS = sorted(c["name"] for c in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["configs"])
ELEMENTWISE_SHARE = 0.02   # bias adds, ReLUs, tanh


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_count_matches_xla(name):
    """Each reference's ``learner_apply`` is the forward that its work
    file's ``forward_macs`` counts."""
    ref = harness.import_file(harness.BENCH / "configs" / f"{name}.py")
    work = harness.import_file(harness.BENCH / "work" / f"{name}.py")
    cfg = ref.load()
    params = ref.init_params(jax.random.PRNGKey(0), cfg)
    batch = 16
    x = jnp.zeros((batch,) + tuple(cfg["obs_shape"]))
    fwd = functools.partial(ref.learner_apply, cfg=cfg)
    ca = jax.jit(fwd).lower(params, x).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ours = 2 * work.forward_macs(cfg) * batch
    assert ours <= ca["flops"] <= ours * (1 + ELEMENTWISE_SHARE)


@pytest.mark.parametrize("traffic,T,epochs", [("ppo_2048x128", 128, 4)])
def test_e2hrl_iteration_counts(traffic, T, epochs):
    work = harness.import_file(harness.BENCH / "work" / "e2hrl_fc.py")
    cfg = harness.load_json(harness.BENCH / "configs" / "e2hrl_fc.json")
    job = harness.load_json(harness.BENCH / "traffic" / f"{traffic}.json")
    fwd = work.forward_macs(cfg)
    assert fwd == 518648
    it = work.per_iteration(cfg, job)
    n = 2048 * T
    assert it["int8_ops"] == 2 * fwd * 2048 * (T + 1)
    first = work.layer_macs(cfg)[0][1]
    assert it["fp_flops"] == 2 * (fwd * n + epochs * n * (3 * fwd - first))


def test_taps_on_input():
    work = harness.import_file(harness.BENCH / "work" / "e2hrl_fc.py")
    # 32 -> 16 at stride 2, pads (0, 1): the last window loses one tap
    assert work.taps_on_input(32, 3, 2) == (16, 47)
    assert work.taps_on_input(5, 3, 1) == (5, 13)
