"""A run whose timed path is broken underneath comes out not correct.

Each cell whose driver offers the control and the planted faults
(``SIDES``) is run at a small size on the CPU past the harness's look
for a chip, once for each fault that its limits file lists under
``caught``: a step that leaves its state unchanged, a loss taken over
half of each minibatch, an action altered where the actor samples it;
its control (the reference in the nearest precision below the
configuration's, put in the program's place) comes out not correct
where ``caught`` lists it.  Where it does not, the control and the
half-batch loss are checked to read wider than the program on the
second moment, which has no limit.
"""
import dataclasses

import jax.numpy as jnp
import pytest

import harness
import tiny

TRAIN = tiny.offering("SIDES")
FAULTS = ("unchanged", "half_batch", "altered")


def caught(name):
    """The sides that the cell's limits catch, from its limits file."""
    return harness.load_cell(name).limits["caught"]


def unchanged(monkeypatch):
    import repro.rl.train_steps as ts
    monkeypatch.setattr(ts, "adamw_update",
                        lambda g, state, params, *a, **k: (params, state, {}))


def half_batch(trainer):
    loss = trainer.loss_fn

    def half(params, apply_fn, batch, cfg, *rest):
        n = batch["obs"].shape[0] // 2
        return loss(params, apply_fn, {k: v[:n] for k, v in batch.items()},
                    cfg, *rest)

    trainer.loss_fn = half


@dataclasses.dataclass(frozen=True)
class Shifted:
    """The program's action distribution with every sample moved on."""

    inner: object
    n: int

    def sample(self, key, dparams):
        return (self.inner.sample(key, dparams) + 1) % self.n

    def log_prob(self, dparams, action):
        return self.inner.log_prob(dparams, action)

    def entropy(self, dparams):
        return self.inner.entropy(dparams)


def altered(trainer):
    trainer.dist = Shifted(trainer.dist, trainer.env.spec.n_actions)


@pytest.mark.parametrize("name,fault", [(w, f) for w in TRAIN
                                        for f in FAULTS if f in caught(w)])
def test_training_fault_is_not_correct(name, fault, monkeypatch):
    cell = tiny.cell(name)
    patch = None
    if fault == "unchanged":
        unchanged(monkeypatch)
    else:
        patch = {"half_batch": half_batch, "altered": altered}[fault]
    _, line = tiny.run(cell, patch=patch)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", TRAIN)
def test_control_is_not_correct(name):
    """The control comes out not correct where the cell's numbers catch
    it; where none does yet, its second moment reads wider than the
    program's, and so does the half-batch loss's."""
    cell = tiny.cell(name)
    drv = tiny.driver(cell)
    seed = 2**31 + 5
    control = drv.reading(cell, seed, "control", tiny.devices())
    ok, checks = harness.verdict(control, cell.limits["limits"])
    if "control" in caught(name):
        assert not ok, checks
        return
    sound = drv.reading(cell, seed, "program", tiny.devices())
    half = drv.reading(cell, seed, "half_batch", tiny.devices())
    assert control["nu_gap"] > sound["nu_gap"]
    assert half["nu_gap"] > sound["nu_gap"]


@pytest.mark.parametrize("name", TRAIN)
def test_actor_path_faults_part_the_envs(name):
    """The control's int4 actors and the altered actions make envs part
    from the reference in the first iteration; the program's do not."""
    cell = tiny.cell(name)
    drv = tiny.driver(cell)
    seed = 2**31 + 5
    sound = drv.reading(cell, seed, "program", tiny.devices())
    assert sound["diverged1"] == 0.0
    for side in ("control", "altered"):
        assert drv.reading(cell, seed, side, tiny.devices())["diverged1"] > 0


def test_shifted_samples_stay_in_range():
    from repro.rl.dists import Categorical
    d = Shifted(Categorical(), 4)
    import jax
    a = d.sample(jax.random.PRNGKey(0), jnp.zeros((64, 4)))
    assert int(a.min()) >= 0 and int(a.max()) < 4

