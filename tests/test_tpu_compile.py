"""Every Pallas kernel compiles for a TPU v5e at the E2HRL shapes.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so tiling and VMEM refusals that the
interpret-mode parity suites cannot see fail here, with no chip.  Each
case lowers one kernel with ``interpret=False`` for one v5e core and
checks that the Mosaic kernel (``tpu_custom_call``) is in the compiled
program.  Shapes are the paper's E2HRL agent (``configs/e2hrl.py``) at
a 512-env fleet, plus the small-M rows of the serving bucket ladder
and a few dims that are not multiples of the native tiles.

The topology is described inside a fixture (never at import): one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.e2hrl import CONFIG, CONFIG_LSTM
from repro.kernels.qconv import ops as qconv_ops
from repro.kernels.qlstm import ops as qlstm_ops
from repro.kernels.qmac import ops as qmac_ops
from repro.kernels.vact import ops as vact_ops
from repro.models import hrl
from repro.nn.module import unbox
from repro.optim import AdamWConfig, adamw_init, adamw_update, constant
from repro.rl.ppo import PPOConfig, minibatch_epochs

FLEET = 512


def _conv_layers():
    """(H, C_in, C_out) of the stem's three stride-2 Q-Conv layers."""
    h, c = CONFIG.obs_shape[0], CONFIG.obs_shape[-1]
    out = []
    for n in CONFIG.conv_channels:
        out.append((h, c, n))
        h, c = (h + 1) // 2, n
    return out, h * h * c


CONV_LAYERS, FLAT = _conv_layers()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,c,n", CONV_LAYERS,
                         ids=[f"{h}x{h}x{c}->{n}" for h, c, n in CONV_LAYERS])
def test_qconv_layer_compiles(one_chip, h, c, n):
    k = CONFIG.conv_kernel
    _compile_for_chip(
        lambda *a: qconv_ops.qconv2d_i8(*a, stride=2, fuse_relu=True,
                                        kernel=True, interpret=False),
        one_chip,
        ((FLEET, h, h, c), jnp.int8), ((FLEET, h, h, 1), jnp.float32),
        ((k, k, c, n), jnp.int8), ((n,), jnp.float32),
        ((n,), jnp.float32))


# the Q-FC at the fleet batch and at the ends of the serving bucket
# ladder, then dims off the native tiles that were refused before
# fit_block took such dims whole instead of as partial-lane blocks
QMAC_SHAPES = [(FLEET, FLAT, CONFIG.embed_dim), (1, FLAT, CONFIG.embed_dim),
               (32, FLAT, CONFIG.embed_dim), (64, 100, 72), (33, 17, 9)]


@pytest.mark.parametrize("m,k,n", QMAC_SHAPES,
                         ids=[f"{m}x{k}x{n}" for m, k, n in QMAC_SHAPES])
def test_qmac_i8_compiles(one_chip, m, k, n):
    _compile_for_chip(lambda x, w: qmac_ops.qmac_i8(x, w, interpret=False),
                      one_chip, ((m, k), jnp.int8), ((k, n), jnp.int8))


def test_qmac_i8_deq_compiles(one_chip):
    n = CONFIG.embed_dim
    _compile_for_chip(
        lambda *a: qmac_ops.qmac_i8_deq(*a, interpret=False), one_chip,
        ((FLEET, FLAT), jnp.int8), ((FLEET, 1), jnp.float32),
        ((FLAT, n), jnp.int8), ((1, n), jnp.float32))


@pytest.mark.parametrize("kind,shape", [("tanh", (FLEET, CONFIG.embed_dim)),
                                        ("softmax", (FLEET, CONFIG.n_actions)),
                                        ("tanh", (7, 33))])
def test_vact_compiles(one_chip, kind, shape):
    _compile_for_chip(lambda x: vact_ops.vact(x, kind, 6, interpret=False),
                      one_chip, (shape, jnp.float32))


def test_vact_q8_compiles(one_chip):
    _compile_for_chip(
        lambda q, s: vact_ops.vact_q8(q, s, "tanh", 6, interpret=False),
        one_chip, ((FLEET, CONFIG.embed_dim), jnp.int8),
        ((), jnp.float32))


def test_qlstm_cell_compiles(one_chip):
    din, h = CONFIG_LSTM.embed_dim, CONFIG_LSTM.subgoal_hidden
    _compile_for_chip(
        lambda *a: qlstm_ops.qlstm_cell(*a, n_iters=13, interpret=False),
        one_chip,
        ((FLEET, din), jnp.int8), ((), jnp.float32),
        ((FLEET, h), jnp.int8), ((), jnp.float32),
        ((din, 4 * h), jnp.int8), ((1, 4 * h), jnp.float32),
        ((h, 4 * h), jnp.int8), ((1, 4 * h), jnp.float32),
        ((4 * h,), jnp.float32), ((FLEET, h), jnp.float32))


_DEF = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\{([\d,]*)")
_GATHER = re.compile(r"gather\(%([^,\s)]+).*start_index_map=\{(\d+)\}"
                     r".*op_name=\"([^\"]*)\"")


def _shuffle_gathers(text):
    """(operand dims, operand minor-to-major, sample axis) of every
    gather under the named scope ``shuffle`` in compiled HLO text."""
    defs = {m.group(1): (m.group(2), m.group(3))
            for m in map(_DEF.match, text.splitlines()) if m}
    out = []
    for line in text.splitlines():
        m = _GATHER.search(line) if " gather(" in line else None
        if m and "shuffle" in m.group(3).split("/"):
            dims, layout = defs[m.group(1)]
            out.append((tuple(int(d) for d in dims.split(",")),
                        tuple(int(d) for d in layout.split(",")),
                        int(m.group(2))))
    return out


def test_minibatch_gather_reads_sample_major_rows(one_chip):
    """The learner's minibatch gather reads whole samples, sample axis
    major, at the E2HRL observation shape.  The batch comes in as the
    rollout hands it over, one flat row per sample reshaped to images,
    so layout assignment is free to lay it out: gathered as images, the
    first conv's batch-minor input layout is pushed back onto the whole
    buffer and each gather reads its rows along the lanes."""
    n, cfg = 8192, PPOConfig(epochs=1, minibatches=4)
    params = unbox(hrl.init(jax.random.PRNGKey(0), CONFIG))
    sched, ocfg = constant(1e-3), AdamWConfig(max_grad_norm=0.5)

    def apply_fn(p, obs):
        logits, value, _ = hrl.apply(p, obs, CONFIG)
        return logits, value

    def opt_step(p, s, g):
        p, s, _ = adamw_update(g, s, p, sched, ocfg)
        return p, s

    def learner(key, params, opt, rows, actions, log_probs, advantages,
                returns):
        batch = {"obs": rows.reshape((n,) + CONFIG.obs_shape),
                 "actions": actions, "log_probs": log_probs,
                 "advantages": advantages, "returns": returns}
        return minibatch_epochs(key, params, opt, batch, apply_fn, cfg,
                                opt_step)

    place = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    args = (place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
            jax.tree.map(place, params),
            jax.tree.map(place, jax.eval_shape(adamw_init, params)),
            place(jax.ShapeDtypeStruct((n, math.prod(CONFIG.obs_shape)),
                                       jnp.float32)),
            place(jax.ShapeDtypeStruct((n,), jnp.int32)),
            *[place(jax.ShapeDtypeStruct((n,), jnp.float32))] * 3)
    text = jax.jit(learner).lower(*args).compile().as_text()
    gathers = _shuffle_gathers(text)
    wide = [g for g in gathers if len(g[0]) > 1]
    assert len(gathers) == 5 * cfg.minibatches and wide, gathers
    lane = [g for g in wide if g[1][0] == g[2]]
    assert not lane, f"gathers along the sample-minor axis: {lane}"
