"""The trace read by the program's own names: the ``tf_op`` decoder,
scope matching, device time per scope on a trace recorded on a TPU
v5e, idle gaps put down to the program's spans, the trace's start on
the host clock, and the readers that return None where the program
has nothing to read.

``data/scoped_trace.xplane.pb`` was recorded on one TPU v5e by this
file's ``record()`` (``PYTHONPATH=chipbench:src python3
chipbench/tests/test_scopes.py <out_dir>``): a jitted function with
two named scopes, ``alpha`` and ``beta``, called three times, each
call inside the program's ``train.dispatch`` span.
"""
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import harness
import scopes

DATA = Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny_trace.xplane.pb"
SCOPED = DATA / "scoped_trace.xplane.pb"


def test_decoder_reads_tf_op_of_recorded_fusion():
    ops = scopes.tf_ops(TINY.read_bytes())
    fusion = [n for n in ops if n.startswith("%fusion = ")]
    assert len(fusion) == 1
    assert ops[fusion[0]] == "jit(<lambda>)/dot_general"


def test_merged_op_counts_under_its_first_path():
    """XLA joins the paths of ops it merged with ``;``; the op counts
    once, under the first."""
    tf_id, name = 26, b"%reshape.1 = f32[8] reshape(f32[2,4] %p)"
    path = (b"jit(it)/learner/advantage/reshape:reshape;"
            b"jit(it)/rollout/relayout/reshape:reshape")

    def varint(v):
        out = b""
        while v >= 0x80:
            out, v = out + bytes([v & 0x7F | 0x80]), v >> 7
        return out + bytes([v])

    def field(n, payload):
        return varint(n << 3 | 2) + varint(len(payload)) + payload

    stat = bytes([1 << 3, tf_id]) + field(5, path)
    event_md = field(2, name) + field(5, stat)
    stat_md = bytes([1 << 3, tf_id]) + field(2, b"tf_op")
    plane = (field(2, b"/device:TPU:0")
             + field(4, bytes([1 << 3, 1]) + field(2, event_md))
             + field(5, bytes([1 << 3, tf_id]) + field(2, stat_md)))
    ops = scopes.tf_ops(field(1, plane))
    assert ops == {name.decode(): "jit(it)/learner/advantage/reshape"}


@pytest.mark.parametrize("path,scope,want", [
    ("jit(f)/rollout/while/body/closed_call/actor/dot_general",
     "rollout/actor", True),
    ("jit(f)/rollout/while/body/closed_call/actor/dot_general",
     "rollout", True),
    ("jit(f)/rollout/while/body/closed_call/actor/dot_general",
     "actor/rollout", False),
    ("jit(f)/rollouts/actor/dot_general", "rollout", False),
    ("jit(f)/learner/grad/transpose(jvp())/mul", "learner/grad", True),
    ("jit(f)/learner/grad/mul", "rollout", False),
])
def test_scopes_match_path_components_in_order(path, scope, want):
    assert scopes.in_scope(path, scope) is want


@pytest.mark.skipif(not SCOPED.is_file(), reason="no recorded TPU trace")
def test_scope_times_sum_to_module_time():
    tr = scopes.Trace.load(SCOPED)
    mods = harness.trace_reducer().reduce(tr.pd, spans=())["modules"]
    (mod,) = [m for k, m in mods.items() if k.startswith("jit_scoped")]
    assert mod["count"] == 3
    alpha, beta = tr.scope_s("alpha"), tr.scope_s("beta")
    assert alpha > 0 and beta > 0
    assert alpha + beta == pytest.approx(mod["time_s"], rel=0.01)
    assert tr.scope_s("gamma") is None
    dispatch = [s for s in tr.host_spans() if s[0] == "train.dispatch"]
    assert len(dispatch) == 3


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_idle_gaps_fall_to_innermost_program_span():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("train.sync", 0, 100), _ev("train.pack", 10, 40),
        _ev("train.step", 100, 200), _ev("train.dispatch", 100, 50),
        _ev("step", 0, 300)])])
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.1", 60, 30), _ev("fusion.2", 160, 120)])])
    tr = scopes.Trace(NS(planes=[host, device]), {})
    gaps = tr.idle_gaps()
    # [0, 60) mid 30: pack inside sync; [90, 160) mid 125: dispatch
    # inside step; [280, 300): step alone
    assert [g[0] for g in gaps] == ["train.dispatch", "train.pack",
                                    "train.step"]
    assert [g[1] for g in gaps] == pytest.approx([70e-9, 60e-9, 20e-9])


def test_trace_start_is_on_the_host_clock(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    t0 = time.time()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    tr = scopes.Trace.load(scopes.newest(tmp_path))
    assert abs(tr.start_ns / 1e9 - t0) < 0.010


def _reader(name):
    return harness.import_file(harness.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", ["train.rollout_device_ms",
                                  "train.learner_device_ms",
                                  "train.pack_ms"])
def test_readers_return_none_without_the_programs_names(name,
                                                         monkeypatch):
    # the recorded tiny trace has neither the scopes nor the spans
    tr = scopes.Trace.load(TINY)
    monkeypatch.setattr(scopes, "current", lambda: tr)
    ctx = {"kind": "train", "trace": {"modules": {
        "jit_iteration": {"time_s": 1.0, "count": 2.0}}}}
    assert _reader(name).read(ctx) is None


def test_compile_seconds_count_only_before_the_trace(monkeypatch):
    from repro.obs import compiles

    log = compiles.CompileLog()
    for ev, a, b in [(compiles.TRACE, 1.0, 2.0),
                     (compiles.BACKEND, 1.5, 4.0),
                     (compiles.BACKEND, 6.0, 7.0),
                     (compiles.BACKEND, 9.0, 12.0)]:
        log(ev, a, b, fun_name="f")
    monkeypatch.setattr(compiles, "LOG", log)
    monkeypatch.setattr(scopes, "current",
                        lambda: NS(start_ns=int(10e9)))
    # [1, 4] and [6, 7] end before the trace; [9, 12] does not
    assert _reader("setup.compile_s").read({}) == pytest.approx(4.0)


def record(out: Path) -> Path:
    """Record ``data/scoped_trace.xplane.pb`` (on the chip)."""
    import jax
    import jax.numpy as jnp

    from repro.obs import SpanClock

    def scoped(x):
        with jax.named_scope("alpha"):
            for _ in range(4):
                x = jnp.tanh(x @ x.T)
        with jax.named_scope("beta"):
            for _ in range(2):
                x = jnp.sin(x @ x)
        return x

    f = jax.jit(scoped)
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048))
    f(x).block_until_ready()
    clock = SpanClock("train")
    jax.profiler.start_trace(str(out))
    for g in range(3):
        with clock("dispatch", g=g):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    return scopes.newest(out)


if __name__ == "__main__":
    print(record(Path(sys.argv[1])))
