"""Static analyzer tests: the analyzer is itself mutation-tested.

* zero findings on main — Pass 2 at default scope (modulo the allowlisted
  WallClock adapter) and Pass 1 over the kv_shards=1 inventory;
* every rule fires on its seeded violation in
  ``repro.analysis.fixtures`` and names the offending op/line;
* the jaxpr walker's byte accounting matches XLA's own
  ``compiled.cost_analysis()['bytes accessed']`` on graphs where both are
  exact (hypothesis property over single-primitive graphs);
* allowlist parsing/matching and the CLI's red/green exit.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import fixtures, lint, rules
from repro.analysis.findings import (Finding, apply_allowlist,
                                     parse_allowlist)
from repro.analysis.hlo import entry_result_shapes, nonaliased_output_bytes
from repro.analysis.jaxpr import byte_traffic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = ("src/repro/analysis/fixtures.py",)


# ---------------------------------------------------------------------------
# zero findings on main
# ---------------------------------------------------------------------------

def test_lint_zero_active_findings_on_main():
    """Pass 2 at default scope: the only findings are the allowlisted
    WallClock lines in serving/clock.py."""
    from repro.analysis.check import DEFAULT_ALLOWLIST
    from repro.analysis.findings import load_allowlist
    active, waived = apply_allowlist(lint.run_all(),
                                     load_allowlist(DEFAULT_ALLOWLIST))
    assert active == [], [f"{f.rule} {f.target}: {f.message}"
                          for f in active]
    assert all(f.rule == "AST103" and "clock.py" in f.target
               for f in waived)


@pytest.mark.slow
def test_pass1_zero_findings_on_main_kv1():
    """The full compiled-artifact audit over the kv_shards=1 inventory:
    donation, vocab escape, host budget, collectives, churn, registration
    — all green on main."""
    from repro.analysis.check import run_pass1
    findings = run_pass1([1])
    assert findings == [], [f"{f.rule} {f.target}: {f.message}"
                            for f in findings]


def test_registration_audit_green_on_main():
    from repro.analysis.inventory import audit_registration
    assert audit_registration() == []


# ---------------------------------------------------------------------------
# mutation fixtures: every rule fires and names the offending op/line
# ---------------------------------------------------------------------------

def test_ast101_raise_before_mutate_fires():
    fs = lint.check_raise_before_mutate(scope=FIX)
    assert [f.rule for f in fs] == ["AST101"]
    assert "BadAllocator.allocate" in fs[0].message
    assert fs[0].target.endswith(":23")          # the seeded raise line


def test_ast102_reserve_before_commit_fires():
    fs = lint.check_reserve_before_commit(scope=FIX)
    assert [f.rule for f in fs] == ["AST102"]
    assert "commit" in fs[0].message and "_reserve_step" in fs[0].message


def test_ast103_wallclock_fires():
    fs = lint.check_wallclock(scope=FIX)
    assert {f.rule for f in fs} == {"AST103"}
    msgs = " ".join(f.message for f in fs)
    assert "time.perf_counter" in msgs and "time.time" in msgs


def test_ast104_tracer_guard_fires():
    fs = lint.check_tracer_guards(scope=FIX)
    assert [f.rule for f in fs] == ["AST104"]
    assert "NULL_TRACER" in fs[0].message


def test_ast105_host_commit_purity_fires():
    fs = lint.check_host_commit_purity(scope=FIX)
    assert any(f.rule == "AST105" and f.target.endswith(":58")
               for f in fs)                      # the seeded jnp import


def test_hlo001_donation_fires_on_undonated_jit():
    fn, args = fixtures.undonated_pool_step()
    txt = fn.lower(*args).compile().as_text()
    fs = rules.check_pool_donation(txt, target="fixture")
    assert [f.rule for f in fs] == ["HLO001"]
    assert "input_output_alias" in fs[0].message


def test_hlo002_vocab_escape_fires():
    fn, args = fixtures.vocab_escaping_step()
    txt = fn.lower(*args).compile().as_text()
    closed = jax.make_jaxpr(fn)(*args)
    fs = rules.check_vocab_escape(txt, closed,
                                  vocab_size=fixtures.FIXTURE_VOCAB,
                                  target="fixture")
    assert {f.rule for f in fs} == {"HLO002"}
    # both surfaces report, naming the escaping shape
    msgs = " ".join(f.message for f in fs)
    assert "jaxpr output" in msgs and "HLO entry output" in msgs
    assert "307" in msgs


def test_hlo003_host_budget_fires():
    fn, args = fixtures.vocab_escaping_step()
    txt = fn.lower(*args).compile().as_text()
    budget = 8 * fixtures.FIXTURE_B * fixtures.FIXTURE_C
    fs = rules.check_host_budget(txt, budget_bytes=budget,
                                 target="fixture")
    assert [f.rule for f in fs] == ["HLO003"]
    assert str(budget) in fs[0].message          # names the budget…
    assert "9824" in fs[0].message               # …and the actual bytes


def test_hlo004_collective_audit_fires():
    fn, args, expected = fixtures.missing_collective_step()
    txt = fn.lower(*args).compile().as_text()
    fs = rules.check_collectives(txt, expected=expected, target="fixture")
    assert [f.rule for f in fs] == ["HLO004"]
    assert "all-reduce" in fs[0].message
    # the reverse direction: an undeclared collective is also a finding
    fs2 = rules.check_collectives(txt, expected={}, target="fixture")
    assert fs2 == []                             # no collectives, none declared


def test_hlo005_recompile_churn_fires():
    fn, makers = fixtures.unbucketed_grid_step()
    fs = rules.check_recompile_churn(fn, makers, declared_buckets=3,
                                     target="fixture")
    assert [f.rule for f in fs] == ["HLO005"]
    assert "4 distinct executables" in fs[0].message
    # bucketed to powers of two the same grid stays within budget
    fn2 = jax.jit(lambda x: x + 1.0)

    def bucket(n):
        b = 1
        while b < n:
            b <<= 1
        return b

    makers2 = [(lambda b=b: ((jnp.zeros((bucket(b), 4)),), {}))
               for b in (1, 2, 3, 4)]
    assert rules.check_recompile_churn(fn2, makers2, declared_buckets=3,
                                       target="fixture") == []


def test_hlo006_registration_fires_when_unregistered(monkeypatch):
    from repro.analysis import inventory
    monkeypatch.setattr(inventory, "KNOWN_JIT_SITES", frozenset())
    fs = inventory.audit_registration()
    assert fs and all(f.rule == "HLO006" for f in fs)
    assert any("model.decode_step_paged" in f.message
               and "backends.py" in f.target for f in fs)


# ---------------------------------------------------------------------------
# jaxpr byte accounting vs XLA cost analysis (hypothesis property)
# ---------------------------------------------------------------------------

def _cost_bytes(fn, *args) -> float:
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(ca["bytes accessed"])


def test_jaxpr_byte_accounting_simple_cases():
    """Deterministic spot checks (run even without hypothesis): on
    single-primitive graphs the walker equals XLA exactly."""
    x = jnp.zeros((8, 16), jnp.float32)
    y = jnp.ones((8, 16), jnp.float32)
    fn = lambda a, b: a + b                      # noqa: E731
    assert byte_traffic(jax.make_jaxpr(fn)(x, y)) == _cost_bytes(fn, x, y)
    a = jnp.zeros((4, 8), jnp.float32)
    b = jnp.zeros((8, 16), jnp.float32)
    dot = lambda p, q: p @ q                     # noqa: E731
    assert byte_traffic(jax.make_jaxpr(dot)(a, b)) == _cost_bytes(dot, a, b)


def test_jaxpr_byte_accounting_matches_cost_analysis_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    dims = st.integers(min_value=1, max_value=8)

    @settings(max_examples=25, deadline=None)
    @given(m=dims, k=dims, n=dims,
           op=st.sampled_from(["add", "mul", "sub", "max", "dot"]))
    def prop(m, k, n, op):
        if op == "dot":
            args = (jnp.zeros((m, k), jnp.float32),
                    jnp.zeros((k, n), jnp.float32))
            fn = lambda a, b: a @ b              # noqa: E731
        else:
            f = {"add": jnp.add, "mul": jnp.multiply,
                 "sub": jnp.subtract, "max": jnp.maximum}[op]
            args = (jnp.zeros((m, k), jnp.float32),
                    jnp.ones((m, k), jnp.float32))
            fn = lambda a, b: f(a, b)            # noqa: E731
        assert byte_traffic(jax.make_jaxpr(fn)(*args)) == \
            _cost_bytes(fn, *args)

    prop()


# ---------------------------------------------------------------------------
# HLO text helpers
# ---------------------------------------------------------------------------

def test_entry_result_shapes_parses_header():
    txt = ("HloModule jit_f\n\n"
           "ENTRY %main.7 (p0: f32[2,4], p1: s32[8]) -> "
           "(f32[2,4]{1,0}, s32[8]{0}) {\n"
           "  ROOT %t = tuple()\n}\n")
    assert entry_result_shapes(txt) == [("f32", (2, 4), 32),
                                        ("s32", (8,), 32)]
    acct = nonaliased_output_bytes(txt)
    assert acct["total"] == 64 and acct["fresh"] == 64


# ---------------------------------------------------------------------------
# allowlist + CLI
# ---------------------------------------------------------------------------

def test_allowlist_parse_and_match():
    entries = parse_allowlist(
        "# comment\n"
        "AST103:src/repro/serving/clock.py:*  # wall-clock adapter\n")
    assert len(entries) == 1
    hit = Finding("AST103", "src/repro/serving/clock.py:28", "m")
    miss = Finding("AST103", "src/repro/serving/engine.py:10", "m")
    active, waived = apply_allowlist([hit, miss], entries)
    assert waived == [hit] and active == [miss]
    with pytest.raises(ValueError, match="reason"):
        parse_allowlist("AST103:foo.py:*\n")     # waiver without a reason
    with pytest.raises(ValueError, match="RULE:target"):
        parse_allowlist("not-a-rule  # why\n")


def test_cli_lint_pass_green(tmp_path):
    """`python -m repro.analysis.check --only lint --json …` exits 0 on
    main and writes the structured findings artifact."""
    out_json = tmp_path / "findings.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.check", "--only", "lint",
         "--no-devices", "--json", str(out_json)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(out_json.read_text())
    assert rec["active"] == []
    assert {f["rule"] for f in rec["waived"]} == {"AST103"}
