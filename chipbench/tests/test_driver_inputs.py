"""A driver builds the program that the configuration names, with the
loss and optimizer settings that the configuration and the traffic
state, and refuses a configuration that names no agent; the CPU tests
take each driver's size from the driver itself, so a driver that gives
none fails its own cells and no other."""
import functools
import json
import shutil

import jax
import pytest

import harness
import tiny

SEED = 2**31 + 7


def one_per_driver(names):
    """One cell for each driver: what these tests check is the
    driver's, not the cell's."""
    first = {}
    for w in names:
        first.setdefault(harness.load_cell(w).driver, w)
    return sorted(first.values())


BUILT = one_per_driver(tiny.offering("make_trainer"))


@pytest.mark.parametrize("name", BUILT)
def test_trainer_takes_the_stated_agent_and_settings(name):
    """A copy of the configuration that names the conv actor-critic,
    with its own discount, Adam decay and clipping, and a traffic of 3
    epochs x 8 minibatches, reaches the trainer as stated; the reference
    agent's shapes then refuse it."""
    from repro.rl.nets import conv_ac_apply
    c = tiny.cell(name)
    c.config.update(agent="mlp", net="conv",
                    ppo=dict(c.config["ppo"], gamma=0.999),
                    optimizer=dict(c.config["optimizer"], b2=0.999,
                                   max_grad_norm=1.0))
    c.traffic.update(epochs=3, minibatches=8)
    drv = tiny.driver(c)
    trainer = drv.make_trainer(c, SEED, 1)
    assert trainer.apply_fn is conv_ac_apply
    assert trainer.pcfg.gamma == 0.999
    assert (trainer.pcfg.epochs, trainer.pcfg.minibatches) == (3, 8)
    assert (trainer.ocfg.b2, trainer.ocfg.max_grad_norm) == (0.999, 1.0)
    for k, v in c.config["ppo"].items():
        assert getattr(trainer.pcfg, k) == v
    params0 = c.reference().init_params(jax.random.PRNGKey(SEED), c.config)
    with pytest.raises(ValueError, match="shapes"):
        drv._check_states(trainer, c, params0)


@pytest.mark.parametrize("name", BUILT)
def test_stated_epochs_reach_the_iteration(name):
    """2 epochs x 2 minibatches from the traffic: the optimizer has
    stepped 4 times after one iteration of the window's loop."""
    c = tiny.cell(name)
    c.traffic.update(epochs=2, minibatches=2)
    drv = tiny.driver(c)
    trainer, state, iteration, _ = drv.build(c, SEED, 1)
    loop = drv.Loop(trainer, state, iteration, harness.Spans())
    loop()
    assert int(loop.state.opt["count"]) == 4


@pytest.mark.parametrize("name", BUILT)
def test_configuration_without_agent_is_refused(name):
    c = tiny.cell(name)
    del c.config["agent"]
    with pytest.raises(ValueError, match="'agent'"):
        tiny.driver(c).make_trainer(c, SEED, 1)


def test_driver_without_tiny_fails_only_its_cell(tmp_path, monkeypatch):
    """A copy of the benchmark with one more cell, whose driver gives no
    CPU size: finding the cells that offer fault sides still works, the
    other cells still size, and only that cell's sizing fails."""
    bench = tmp_path / harness.BENCH.name
    shutil.copytree(harness.BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", ".out", "__pycache__"))
    (bench / "drivers" / "sizeless.py").write_text(
        'SIDES = ("program",)\n')
    (bench / "traffic" / "sizeless_mix.json").write_text(
        json.dumps({"driver": "sizeless"}))
    (bench / "limits" / "sizeless_cell.json").write_text(
        json.dumps({"limits": {}, "caught": []}))
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    real = [w["name"] for w in spec["workloads"]]
    spec["workloads"].append({"name": "sizeless_cell",
                              "config": spec["configs"][0]["name"],
                              "traffic": "sizeless_mix", "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "load_cell",
                        functools.partial(harness.load_cell, root=tmp_path))

    assert "sizeless_cell" in tiny.offering("SIDES")
    assert [tiny.cell(w).name for w in real] == real
    with pytest.raises(LookupError, match="TINY"):
        tiny.cell("sizeless_cell")
