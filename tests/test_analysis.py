"""defer_tpu.analysis: static rules against the fixture corpus, the
strict pass over the shipped tree (tier-1 enforcement), and the
runtime trace sanitizer — including the paged server's post-warmup
trace stability."""

import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import pytest

from defer_tpu.analysis import (
    RetraceError,
    analyze_paths,
    trace_sanitizer as sanitize,
)
from defer_tpu.analysis.budget import BudgetError
from defer_tpu.analysis.runner import main, record_findings
from defer_tpu.obs.metrics import MetricsRegistry

HERE = pathlib.Path(__file__).resolve().parent
FIXTURES = HERE / "analysis_fixtures"
REPO = HERE.parent

# (rule, fixture stem, expected positive-finding count) — keep in sync
# with tests/analysis_fixtures/ (see its README).
CASES = [
    ("host-sync-in-hot-loop", "host_sync", 2),
    ("host-sync-in-hot-loop", "window_scan", 2),
    ("host-sync-in-hot-loop", "spec_accept", 2),
    ("host-sync-in-hot-loop", "spec_window", 2),
    ("host-sync-in-hot-loop", "shard_map", 2),
    ("host-sync-in-hot-loop", "kv_spill", 2),
    ("host-sync-in-hot-loop", "constrain", 2),
    ("host-sync-in-hot-loop", "mixed_tick", 2),
    ("fresh-closure-jit", "fresh_closure", 2),
    ("prng-key-reuse", "prng_reuse", 1),
    ("lock-discipline", "lock_discipline", 2),
    ("lock-discipline", "advert_lock", 2),
    ("lock-discipline", "lock_helper", 1),
    ("obs-name-drift", "obs_drift", 3),
    ("cross-domain-write", "domain_race", 2),
    ("host-sync-in-hot-loop", "pp_handoff", 1),
    ("shard-spec", "shard_spec", 3),
    ("shard-spec", "psum_mirror", 1),
]


def _run(path, rule):
    return analyze_paths([str(path)], rules=[rule])


# -- static rules over the fixture corpus ------------------------------


@pytest.mark.parametrize("rule,stem,n", CASES)
def test_rule_catches_positive_fixture(rule, stem, n):
    rep = _run(FIXTURES / f"{stem}_pos.py", rule)
    assert len(rep.findings) == n, [f.format() for f in rep.findings]
    assert all(f.rule == rule for f in rep.findings)


@pytest.mark.parametrize("rule,stem,n", CASES)
def test_rule_passes_negative_fixture(rule, stem, n):
    rep = _run(FIXTURES / f"{stem}_neg.py", rule)
    assert rep.findings == [], [f.format() for f in rep.findings]


def test_shipped_tree_is_strict_clean():
    """The tier-1 gate: every rule over defer_tpu/ is clean or carries
    a justified ignore. A failure here means a new hazard landed
    without a reason next to it."""
    rep = analyze_paths([str(REPO / "defer_tpu")], strict=True)
    assert rep.findings == [], "\n".join(f.format() for f in rep.findings)
    # The 20 deliberate sites (hard_sync itself, the serving syncs,
    # per-stage construction jits, framing locks) stay suppressed.
    assert len(rep.suppressed) >= 15


def test_seeded_violation_is_caught(tmp_path):
    """Acceptance check: a .item() seeded into a _tick is flagged."""
    bad = tmp_path / "seeded.py"
    bad.write_text(
        textwrap.dedent(
            """
            class PagedDecodeServer:
                def _tick(self):
                    tok = self.nxt.item()
                    return tok
            """
        )
    )
    rep = analyze_paths([str(bad)])
    assert [f.rule for f in rep.findings] == ["host-sync-in-hot-loop"]


# -- ignore mechanics --------------------------------------------------


def _ticky(marker):
    return textwrap.dedent(
        f"""
        import numpy as np


        class S:
            def _tick(self):
                {marker}
                h = np.asarray(self.nxt)
                return h
        """
    )


def test_ignore_with_reason_suppresses(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text(
        _ticky("# analysis: ignore[host-sync-in-hot-loop] one batched "
               "transfer per tick by design")
    )
    rep = analyze_paths([str(p)], strict=True)
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_strict_flags_reasonless_ignore(tmp_path):
    p = tmp_path / "bare.py"
    p.write_text(_ticky("# analysis: ignore[host-sync-in-hot-loop]"))
    lax = analyze_paths([str(p)])
    assert lax.findings == []  # non-strict: suppression holds
    strict = analyze_paths([str(p)], strict=True)
    assert [f.rule for f in strict.findings] == ["ignore-without-reason"]


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rules"):
        analyze_paths([str(FIXTURES)], rules=["no-such-rule"])


def test_parse_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    rep = analyze_paths([str(p)])
    assert [f.rule for f in rep.findings] == ["parse-error"]


# -- CLI and obs wiring ------------------------------------------------


def test_cli_exit_codes_and_json(capsys):
    pos = str(FIXTURES / "prng_reuse_pos.py")
    assert main([pos, "--rules", "prng-key-reuse", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["counts"] == {"prng-key-reuse": 1}
    neg = str(FIXTURES / "prng_reuse_neg.py")
    assert main([neg, "--rules", "prng-key-reuse"]) == 0
    assert main(["--list-rules"]) == 0
    assert main([pos, "--rules", "bogus"]) == 2
    with pytest.raises(SystemExit):  # the gate reads no bench artifact
        main([pos, "--bench", "x.json"])


def test_findings_metric_recorded():
    rep = analyze_paths(
        [str(FIXTURES / "obs_drift_pos.py")], rules=["obs-name-drift"]
    )
    reg = MetricsRegistry()
    record_findings(rep, registry=reg)
    assert reg.value(
        "defer_analysis_findings_total", rule="obs-name-drift"
    ) == 3
    # Clean rules are published as explicit zeros, not absent.
    assert reg.value(
        "defer_analysis_findings_total", rule="prng-key-reuse"
    ) == 0


# -- perf-contract budget gate -----------------------------------------

BUDGET = FIXTURES / "budget"


def test_budget_static_and_bench_pass():
    """Healthy tree: every contract's counter is registered and fed."""
    rep = analyze_paths(
        [str(BUDGET / "hot.py")],
        budget=str(BUDGET / "budgets.toml"),
    )
    assert rep.findings == [], [f.format() for f in rep.findings]
    assert set(rep.budget) == {"path", "contracts"}
    statuses = {
        c["contract"]: c["status"] for c in rep.budget["contracts"]
    }
    assert statuses == {
        "dispatches_per_token_w8": "pass",
        "kv_rows_per_shard_tp2": "pass",
        "window_drain_b_k": "pass",
    }


def test_budget_static_violation_needs_no_bench():
    """cold.py registers the metrics but its _tick feeds none of them:
    every contract fails."""
    rep = analyze_paths(
        [str(BUDGET / "cold.py")],
        budget=str(BUDGET / "budgets.toml"),
    )
    assert [f.rule for f in rep.findings] == ["perf-contract"] * 3
    assert all("nothing reachable" in f.message for f in rep.findings)
    assert {c["status"] for c in rep.budget["contracts"]} == {"fail"}


@pytest.mark.parametrize(
    "stale",
    [
        'bench_metric = "windows.8.dispatches_per_token"',
        'bench_section = "decode_window"',
        "max = 0.25",
        "min = 1.0",
    ],
)
def test_budget_rejects_measured_keys(tmp_path, capsys, stale):
    """The gate is static. A contracts file that still carries a key
    of the measured half it once had fails loudly, and is not
    half-read as if its bound were enforced."""
    bad = tmp_path / "budgets.toml"
    bad.write_text(f"{(BUDGET / 'budgets.toml').read_text()}{stale}\n")
    key = stale.split()[0]
    with pytest.raises(BudgetError, match=f"`{key}`.*measured half"):
        analyze_paths([str(BUDGET / "hot.py")], budget=str(bad))
    assert main([str(BUDGET / "hot.py"), "--budget", str(bad)]) == 2
    assert "measured half is gone" in capsys.readouterr().err


def test_budget_malformed_toml_rejected(tmp_path, capsys):
    bad = tmp_path / "budgets.toml"
    bad.write_text('[contract.x]\ncounter = 5\nfunctions = ["_tick"]\n')
    with pytest.raises(BudgetError, match="counter"):
        analyze_paths([str(BUDGET / "hot.py")], budget=str(bad))
    assert main([str(BUDGET / "hot.py"), "--budget", str(bad)]) == 2
    assert "counter" in capsys.readouterr().err


def test_repo_budget_gate_and_suppression_ledger(capsys):
    """The shipped gate: --strict --budget over defer_tpu/ stays green
    (every contract's counter is fed from its hot functions), and the
    JSON payload carries the per-rule suppression ledger."""
    rc = main([
        str(REPO / "defer_tpu"), "--strict", "--json",
        "--budget", str(REPO / "budgets.toml"),
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["findings"] == []
    ledger = out["suppressed_by_rule"]
    assert ledger.get("host-sync-in-hot-loop", 0) >= 15
    assert sum(ledger.values()) == out["suppressed"]
    verdicts = {
        c["contract"]: c["status"] for c in out["budget"]["contracts"]
    }
    assert verdicts == dict.fromkeys(
        (
            "dispatches_per_token_w8",
            "kv_rows_per_shard_tp2",
            "mixed",
            "pp",
            "window_drain_b_k",
        ),
        "pass",
    )


# -- trace sanitizer ---------------------------------------------------


def test_sanitizer_detects_retrace():
    f = jax.jit(lambda x: x + 1)
    f(jnp.zeros((2,)))  # warmup
    with pytest.raises(RetraceError, match="1 retrace"):
        with sanitize(f):
            f(jnp.zeros((3,)))  # new shape -> new trace


def test_sanitizer_clean_block_and_report():
    f = jax.jit(lambda x: x * 2)
    f(jnp.zeros((2,)))
    with sanitize(f) as rep:
        for _ in range(3):
            f(jnp.ones((2,)))
    assert rep.retraces == 0
    assert len(rep.watched) == 1


def test_sanitizer_allow_budget():
    f = jax.jit(lambda x: x - 1)
    f(jnp.zeros((2,)))
    with sanitize(f, allow=1):
        f(jnp.zeros((3,)))  # exactly one retrace, inside budget


def test_sanitizer_refuses_empty_watch():
    with pytest.raises(ValueError, match="no jitted callables"):
        with sanitize(object()):
            pass


def test_sanitizer_does_not_mask_block_errors():
    f = jax.jit(lambda x: x + 1)
    f(jnp.zeros((2,)))
    with pytest.raises(RuntimeError, match="boom"):
        with sanitize(f):
            f(jnp.zeros((3,)))  # retraces, but the block's own error wins
            raise RuntimeError("boom")


def test_conftest_fixture_wraps_sanitizer(trace_sanitizer):
    f = jax.jit(lambda x: x + 3)
    f(jnp.zeros((2,)))
    with trace_sanitizer(f) as rep:
        f(jnp.zeros((2,)))
    assert rep.retraces == 0


def test_jit_cached_is_trace_stable():
    """utils/memo.jit_cached: same static key -> the same jitted
    callable, so re-building the closure per call costs no retrace —
    the migration target for fresh-closure-jit findings."""
    from defer_tpu.utils.memo import jit_cached

    def make(scale):
        def f(x):
            return x * scale

        return f

    a = jit_cached(make(2.0), ("test_analysis", "stable"))
    b = jit_cached(make(2.0), ("test_analysis", "stable"))
    assert a is b
    a(jnp.zeros((2,)))
    with sanitize(a) as rep:
        b(jnp.zeros((2,)))
    assert rep.retraces == 0
    # Distinct jit options are distinct cache entries.
    c = jit_cached(make(2.0), ("test_analysis", "stable"), static_argnums=())
    assert c is not a


def test_paged_tick_trace_stable_after_warmup():
    """The enforcement form of the paged server's design contract: a
    warmed `_tick` loop lowers nothing new — 3 post-warmup ticks, zero
    retraces across every jitted callable the server holds."""
    from defer_tpu.models.gpt import tiny_gpt
    from defer_tpu.runtime.paged import PagedDecodeServer

    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    srv = PagedDecodeServer(
        dec, params, num_blocks=12, block_size=4, max_batch=2
    )
    srv.submit(jnp.asarray([[3, 9, 27]], jnp.int32), 10)
    srv.submit(jnp.asarray([[5, 1]], jnp.int32), 9)
    srv._admit()
    for _ in range(2):  # warmup: first tick compiles the step
        srv._tick()
    with sanitize(srv, dec) as rep:
        for _ in range(3):
            srv._tick()
    assert rep.retraces == 0
    assert rep.watched  # the step/insert callables were actually seen
