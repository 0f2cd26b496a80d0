"""Self-tests for the repro.analysis static checker.

Every lint rule gets at least one positive (fires on a fixture
violation) and one negative (stays quiet on the compliant twin in the
same file); the trace checks get unit-level positives via poisoned
inputs plus a fast end-to-end sweep marked slow.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.allowlist import (AllowEntry, AllowlistError,
                                      DEFAULT_PATH, apply_allowlist,
                                      load_allowlist)
from repro.analysis.cli import main as cli_main
from repro.analysis.lint import LintConfig, run_lint
from repro.analysis.rules import Finding
from repro.analysis import trace_audit as ta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = "tests/analysis_fixtures"


def fixture_config(**over):
    cfg = LintConfig(
        qf101_scope=(FIXDIR + "/",),
        qf101_blessed=(FIXDIR + "/fx_blessed.py",),
        qf501_scope=(FIXDIR + "/fx_qf501.py",),
        library=(FIXDIR + "/",),
    )
    return dataclasses.replace(cfg, **over) if over else cfg


def lint_fixtures(*names, **over):
    paths = [os.path.join(ROOT, FIXDIR, n) for n in names]
    return run_lint(ROOT, paths=paths, config=fixture_config(**over))


def lines_of(findings, rule):
    return sorted(f.line for f in findings if f.rule == rule)


def fixture_line(name, needle):
    with open(os.path.join(ROOT, FIXDIR, name), encoding="utf-8") as fh:
        for i, line in enumerate(fh, 1):
            if needle in line:
                return i
    raise AssertionError(f"{needle!r} not in {name}")


# ---------------------------------------------------------------------------
# Mode 1 — one positive and one negative per rule
# ---------------------------------------------------------------------------


def test_qf101_raw_matmul_fires_and_blessed_is_exempt():
    findings = lint_fixtures("fx_qf101.py", "fx_blessed.py")
    assert {f.rule for f in findings} == {"QF101"}
    # both the jnp.dot call and the @ operator
    want = {fixture_line("fx_qf101.py", "jnp.dot"),
            fixture_line("fx_qf101.py", "x @ w")}
    assert set(lines_of(findings, "QF101")) == want
    # negative: the blessed module uses jnp.dot freely
    assert not [f for f in findings if "fx_blessed" in f.path]
    # negative: elementwise ops in scope are fine
    good = fixture_line("fx_qf101.py", "jnp.add")
    assert good not in lines_of(findings, "QF101")


def test_qf201_tracer_branching_fires_with_reachability():
    findings = lint_fixtures("fx_qf201.py")
    assert {f.rule for f in findings} == {"QF201"}
    got = lines_of(findings, "QF201")
    assert fixture_line("fx_qf201.py", "x.sum() > 0") in got
    assert fixture_line("fx_qf201.py", "len(y)") in got
    # reachable only through jax.lax.scan(scan_body, ...)
    assert fixture_line("fx_qf201.py", "carry.sum() > 0") in got
    # negatives: static shapes, None guards, unreachable helpers
    for needle in ("x.shape[0] > n", "mask is None", "y.mean() > 0"):
        assert fixture_line("fx_qf201.py", needle) not in got


def test_qf301_nondeterminism_fires_only_when_reachable():
    findings = lint_fixtures("fx_qf301.py")
    assert {f.rule for f in findings} == {"QF301"}
    got = lines_of(findings, "QF301")
    for needle in ("np.random.rand", "time.time()", "random.random"):
        assert fixture_line("fx_qf301.py", needle) in got
    # negatives: jax.random is the sanctioned path; host helpers that
    # tracing never reaches may read the clock
    assert fixture_line("fx_qf301.py", "jax.random.normal") not in got
    host = fixture_line("fx_qf301.py", "# negative: not jit-reachable")
    assert host not in got


def test_qf401_missing_donation_fires_on_decorator_and_call_site():
    findings = lint_fixtures("fx_qf401.py")
    assert {f.rule for f in findings} == {"QF401"}
    qns = {f.qualname for f in findings}
    assert "bad_step" in qns            # @jax.jit decorator site
    assert "_local_update" in qns       # jax.jit(fn) call site
    # negative: the donated twin threads the same state
    assert "good_step" not in qns


def test_qf501_untagged_wrapper_fires_outside_wrap():
    findings = lint_fixtures("fx_qf501.py")
    assert {f.rule for f in findings} == {"QF501"}
    got = lines_of(findings, "QF501")
    assert got == [fixture_line("fx_qf501.py", "# QF501 positive")]


def test_qf601_bare_print_fires_in_library_code():
    findings = lint_fixtures("fx_qf601.py")
    assert {f.rule for f in findings} == {"QF601"}
    got = lines_of(findings, "QF601")
    assert fixture_line("fx_qf601.py", "QF601 module positive") in got
    assert fixture_line("fx_qf601.py", "QF601 positive") in got
    assert fixture_line("fx_qf601.py", "QF601 method positive") in got
    # negatives: Console / stream APIs are the sanctioned outputs
    for needle in ("console.info", "stream.write"):
        assert fixture_line("fx_qf601.py", needle) not in got
    # method findings carry the class-qualified name for allowlisting
    assert "Reporter.dump" in {f.qualname for f in findings}


def test_qf601_exempt_paths_are_skipped():
    findings = lint_fixtures(
        "fx_qf601.py",
        qf601_exempt=(FIXDIR + "/fx_qf601.py",))
    assert not findings


def test_rules_filter_restricts_the_run():
    findings = lint_fixtures("fx_qf101.py", "fx_qf301.py",
                             rules=("QF301",))
    assert findings and {f.rule for f in findings} == {"QF301"}


# ---------------------------------------------------------------------------
# allowlist semantics
# ---------------------------------------------------------------------------


def _finding(rule="QF201", path="src/repro/x.py", line=3,
             message="msg about foo", qualname="foo"):
    return Finding(path, line, rule, message, qualname)


def test_allowlist_suppresses_matching_and_reports_stale():
    fd = _finding()
    live = AllowEntry(rule="QF201", path="src/repro/x.py",
                      match="foo", reason="audited")
    stale = AllowEntry(rule="QF101", path="src/repro/y.py",
                       match="", reason="obsolete")
    kept, stale_out, suppressed = apply_allowlist([fd], [live, stale])
    assert kept == [] and suppressed == [fd] and stale_out == [stale]


def test_allowlist_mismatch_keeps_the_finding():
    fd = _finding()
    miss = AllowEntry(rule="QF201", path="src/repro/x.py",
                      match="unrelated", reason="r")
    kept, stale_out, suppressed = apply_allowlist([fd], [miss])
    assert kept == [fd] and suppressed == [] and stale_out == [miss]


def test_committed_allowlist_parses_with_reasons():
    entries = load_allowlist(DEFAULT_PATH)
    assert entries and all(e.reason for e in entries)


def test_allowlist_rejects_entries_without_reason(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[allow]]\nrule = "QF201"\n'
                 'path = "src/repro/x.py"\n')
    with pytest.raises(AllowlistError):
        load_allowlist(str(p))


# ---------------------------------------------------------------------------
# the real tree is clean (modulo the committed allowlist)
# ---------------------------------------------------------------------------


def test_real_tree_lint_is_clean_and_allowlist_not_stale():
    findings = run_lint(ROOT)
    kept, stale, _ = apply_allowlist(findings,
                                     load_allowlist(DEFAULT_PATH))
    assert kept == [], "\n".join(f.render() for f in kept)
    assert stale == [], f"stale allowlist entries: {stale}"


def test_cli_lint_exits_clean_on_the_tree(capsys):
    assert cli_main(["lint", "--root", ROOT]) == 0
    capsys.readouterr()


def test_cli_rejects_unknown_rule_ids(capsys):
    assert cli_main(["lint", "--rules", "QF999"]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# Mode 2 — trace-audit unit checks
# ---------------------------------------------------------------------------


def test_expected_scale_shape_table():
    assert ta.expected_scale_shape((32, 64)) == (1, 64)
    assert ta.expected_scale_shape((3, 32, 64)) == (3, 1, 64)
    assert ta.expected_scale_shape((3, 3, 8, 16)) == (1, 1, 1, 16)
    assert ta.expected_scale_shape((7,)) is None


def test_qf902_wrong_grid_qtensor_fires():
    from repro.core.fxp import QTensor
    # per-tensor scale where the consumer broadcasts per-out-channel
    wrong = QTensor(jax.ShapeDtypeStruct((4, 8), jnp.int8),
                    jax.ShapeDtypeStruct((1, 1), jnp.float32), 8)
    found = ta.check_packed_tree({"w": wrong}, 8, "trace:test")
    assert [f.rule for f in found] == ["QF902"]
    assert "(1, 8)" in found[0].message
    # rank outside the convention table is itself a finding
    odd = QTensor(jax.ShapeDtypeStruct((5,), jnp.int8),
                  jax.ShapeDtypeStruct((1,), jnp.float32), 8)
    found = ta.check_packed_tree({"w": odd}, 8, "trace:test")
    assert found and "grid table" in found[0].message


def test_qf902_real_quantize_params_is_on_grid():
    import numpy as np
    params = {"dense": {"w": jnp.asarray(
        np.linspace(-1, 1, 32 * 8, dtype="float32").reshape(32, 8)),
        "b": jnp.zeros((8,), jnp.float32)}}
    assert ta.audit_qtensor_grids(params, 8, "trace:test") == []
    assert ta.audit_qtensor_grids(params, 4, "trace:test") == []


def test_qf901_wide_dtype_walk():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones(3))
    assert ta.find_wide_dtypes(closed) == ["float64"]
    clean = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(jnp.ones(3))
    assert ta.find_wide_dtypes(clean) == []


def test_qf901_state_parity_catches_dtype_drift():
    good = ta.state_parity_mismatches(
        {"a": jnp.zeros(3)}, {"a": jnp.zeros(3)}, "est")
    assert good == []
    drift = ta.state_parity_mismatches(
        {"a": jnp.zeros(3)}, {"a": jnp.zeros(3, jnp.float16)}, "est")
    assert len(drift) == 1 and "float16" in drift[0]
    reshaped = ta.state_parity_mismatches(
        {"a": jnp.zeros(3)}, {"a": jnp.zeros((3, 1))}, "obs")
    assert len(reshaped) == 1


def test_qf904_donation_survives_lowering_text():
    x = jnp.zeros(8)
    donated = jax.jit(lambda buf: buf + 1, donate_argnums=(0,))
    assert "tf.aliasing_output" in donated.lower(x).as_text()
    plain = jax.jit(lambda buf: buf + 1)
    assert "tf.aliasing_output" not in plain.lower(x).as_text()


def test_accepted_combos_mirror_rl_train_dispatch():
    combos = ta.accepted_combos()
    assert len(combos) == 54
    assert ("pendulum", "mlp", "ddpg", "fp32") in combos
    assert ("cartpole", "mlp", "dqn", "fxp8") in combos
    assert ("catch", "conv", "qrdqn", "fp32") in combos
    # ddpg needs a bounded Box: no discrete env ever qualifies
    assert not any(c[2] == "ddpg" and c[0] != "pendulum"
                   for c in combos)
    # conv needs image obs: no 1-D env reaches the conv stem
    assert not any(c[1] == "conv" and c[0] not in ("catch", "keydoor")
                   for c in combos)


# ---------------------------------------------------------------------------
# Mode 2 — live serving-ladder audit (compiles small programs)
# ---------------------------------------------------------------------------


def _tiny_server(max_bucket=4):
    from repro.rl.inference import build_env, make_value_agent
    from repro.serve.engine import PolicyServer
    from repro.serve.loader import ServedPolicy

    env = build_env("cartpole", "mlp")
    agent = make_value_agent("dqn", env.spec,
                             key=jax.random.PRNGKey(0), net="mlp")
    policy = ServedPolicy.from_agent(agent, "cartpole", net="mlp")
    return PolicyServer(policy, precision="w8", max_bucket=max_bucket)


def test_qf903_bucket_ladder_clean_then_retrace_detected():
    server = _tiny_server()
    server.warmup()
    obs_shape = tuple(server.policy.env.obs_shape)
    for n in (1, 3, 5):
        server.act(jnp.zeros((n,) + obs_shape, jnp.float32))
    assert ta.check_bucket_ladder(server, "trace:test") == []

    # poison: a second program sneaks into one bucket's jit cache via a
    # dtype change past the pad-to-bucket boundary
    bucket = server.buckets[0]
    fn = server._jit_cache[bucket]
    fn(server.served_params,
       jnp.zeros((bucket,) + obs_shape, jnp.float16), server._key)
    found = ta.check_bucket_ladder(server, "trace:test")
    assert [f.rule for f in found] == ["QF903"]
    assert "retraced" in found[0].message

    # poison: a bucket with no compiled program at all
    del server._jit_cache[server.buckets[-1]]
    found = ta.check_bucket_ladder(server, "trace:test")
    assert any("one program per bucket" in f.message for f in found)


@pytest.mark.slow
def test_trace_audit_fast_sweep_is_clean():
    res = ta.run_trace_audit(fast=True)
    assert res.findings == [], "\n".join(
        f.render() for f in res.findings)
    # one representative per (net, algo, precision) family + serving
    assert len(res.combos_checked) >= 18
