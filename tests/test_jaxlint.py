"""Tier-1 gate for the jaxlint suite (lightgbm_tpu/analysis/,
tools/jaxlint.py, jaxlint_baseline.json).

Positive direction: the repo must be CLEAN against its committed
baseline — no new Tier A findings, no stale pinned debt, every Tier B
compile-artifact budget honored (the same comparison ``tools/jaxlint.py
--check`` runs).

Negative direction (the guards must actually guard): a deliberately
injected JL001 host sync in ops/histogram.py and a while-body
copy-budget regression — the default subtraction path's REAL measured
body fed to the mega-kernel's zero-copy budget — must both fail the
comparison, plus per-rule detection tests for JL002/JL003/JL004/JL005
and the suppression pragma.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, REPO)

from lightgbm_tpu.analysis import astlint, baseline, conlint  # noqa: E402

BASELINE = baseline.load(os.path.join(REPO, "jaxlint_baseline.json"))


# ---------------------------------------------------------------------------
# Tier A vs the committed ratchet
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tier_a_counts():
    return astlint.finding_counts(astlint.lint_tree(REPO))


def test_baseline_is_committed():
    assert BASELINE.get("tier_a") is not None
    assert BASELINE.get("tier_b"), \
        "jaxlint_baseline.json must pin the tier B budgets"
    assert BASELINE.get("tier_c") is not None, \
        "jaxlint_baseline.json must carry the tier_c table"


def test_tier_c_clean_against_baseline():
    """The tier-C concurrency gate (full rule/fixture coverage lives
    in tests/test_conlint.py; this is the suite-level clean check)."""
    measured = conlint.finding_counts(conlint.lint_tree(REPO))
    problems = baseline.compare_tier_c(measured, BASELINE)
    assert not problems, "\n".join(p.render() for p in problems)


def test_tier_a_clean_against_baseline(tier_a_counts):
    problems = baseline.compare_tier_a(tier_a_counts, BASELINE)
    assert not problems, "\n".join(p.render() for p in problems)


def test_fixed_hot_path_syncs_stay_fixed(tier_a_counts):
    """The three JL001s fixed in this PR (balanced-bagging int(),
    NDCG/MAP per-bucket float() loops) must not come back — and must
    NOT be pinned in the baseline either."""
    for key in ("JL001:lightgbm_tpu/models/boosting.py:GBDT._bagging_mask",
                "JL001:lightgbm_tpu/models/metric.py:NDCGMetric.eval",
                "JL001:lightgbm_tpu/models/metric.py:MapMetric.eval"):
        assert tier_a_counts.get(key, 0) == 0, key
        assert BASELINE["tier_a"].get(key, 0) == 0, key


# ---------------------------------------------------------------------------
# Negative: injected JL001 in ops/histogram.py fails the check
# ---------------------------------------------------------------------------
def test_injected_host_sync_in_histogram_is_caught():
    path = os.path.join(REPO, "lightgbm_tpu", "ops", "histogram.py")
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    bad = src + ("\n\ndef _injected(grad):\n"
                 "    return float(jnp.sum(grad))\n")
    findings = astlint.lint_source(bad, "lightgbm_tpu/ops/histogram.py")
    jl001 = [f for f in findings
             if f.rule == "JL001" and f.func == "_injected"]
    assert jl001, "the injected host sync must be flagged"
    counts = astlint.finding_counts(findings)
    problems = baseline.compare_tier_a(counts, BASELINE)
    assert any(p.kind == "new" and "histogram" in p.key
               for p in problems), problems


def test_clean_histogram_has_no_findings():
    path = os.path.join(REPO, "lightgbm_tpu", "ops", "histogram.py")
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    assert astlint.lint_source(src, "lightgbm_tpu/ops/histogram.py") == []


# ---------------------------------------------------------------------------
# Per-rule detection (source snippets under hot-path virtual names)
# ---------------------------------------------------------------------------
def _rules(src, path="lightgbm_tpu/ops/x.py"):
    return sorted({f.rule for f in astlint.lint_source(src, path)})


def test_jl001_item_and_asarray():
    assert _rules("def f(a):\n    return a.item()\n") == ["JL001"]
    assert _rules(
        "import numpy as np, jax.numpy as jnp\n"
        "def f(a):\n    return np.asarray(jnp.exp(a))\n") == ["JL001"]
    assert _rules(
        "import jax\n"
        "def f(xs):\n"
        "    out = []\n"
        "    for x in xs:\n"
        "        out.append(jax.device_get(x))\n"
        "    return out\n") == ["JL001"]


def test_jl001_ignores_host_numpy():
    assert _rules(
        "import numpy as np\n"
        "def f(a):\n    return float(np.sum(a))\n") == []


def test_jl001_scoped_to_hot_modules():
    src = "import jax.numpy as jnp\ndef f(a):\n    return float(jnp.sum(a))\n"
    assert _rules(src, "lightgbm_tpu/models/serving.py") == ["JL001"]
    assert _rules(src, "lightgbm_tpu/utils/log.py") == []


def test_jl002_jit_in_loop_and_immediate():
    assert _rules(
        "import jax\n"
        "def f(xs):\n"
        "    for x in xs:\n"
        "        g = jax.jit(lambda v: v + 1)\n") == ["JL002"]
    assert _rules(
        "import jax\n"
        "def f(x):\n    return jax.jit(lambda v: v + 1)(x)\n") == ["JL002"]


def test_jl002_unhashable_static_arg():
    src = ("import jax\n"
           "import functools\n"
           "@functools.partial(jax.jit, static_argnames=('cfg',))\n"
           "def k(x, cfg=None):\n    return x\n"
           "def f(x):\n    return k(x, cfg=[1, 2])\n")
    assert _rules(src) == ["JL002"]


def test_jl003_f64_outside_x64_scope():
    src = ("import numpy as np, jax.numpy as jnp\n"
           "def f(a):\n    return jnp.asarray(a, dtype=np.float64)\n")
    assert _rules(src) == ["JL003"]
    scoped = ("import jax, numpy as np, jax.numpy as jnp\n"
              "def f(a):\n"
              "    with jax.enable_x64(True):\n"
              "        return jnp.asarray(a, dtype=np.float64)\n")
    assert _rules(scoped) == []


def test_jl004_python_sized_carry():
    src = ("import jax\n"
           "def f(n, x):\n"
           "    return jax.lax.fori_loop(0, 8, lambda i, c: c,\n"
           "                             tuple(x for _ in range(n)))\n")
    assert _rules(src) == ["JL004"]
    ok = ("import jax, jax.numpy as jnp\n"
          "def f(x):\n"
          "    return jax.lax.fori_loop(0, 8, lambda i, c: c, (x, x))\n")
    assert _rules(ok) == []


def test_jl005_collective_under_rank_branch():
    src = ("from . import network\n"
           "def f(v):\n"
           "    if network.rank() == 0:\n"
           "        return network.global_sum(v)\n"
           "    return v\n")
    assert _rules(src, "lightgbm_tpu/parallel/x.py") == ["JL005"]
    # the ELSE arm of a rank conditional is entered by exactly the
    # complementary ranks — just as divergent
    in_else = ("from . import network\n"
               "def f(v, is_master):\n"
               "    if is_master:\n"
               "        return v\n"
               "    else:\n"
               "        return network.global_sum(v)\n")
    assert _rules(in_else, "lightgbm_tpu/parallel/x.py") == ["JL005"]
    # uniform conditions (process_count/num_machines) are not divergent
    ok = ("from . import network\n"
          "def f(v):\n"
          "    if network.num_machines() > 1:\n"
          "        return network.global_sum(v)\n"
          "    return v\n")
    assert _rules(ok, "lightgbm_tpu/parallel/x.py") == []


def test_pragma_suppresses():
    src = ("import jax.numpy as jnp\n"
           "def f(a):\n"
           "    return float(jnp.sum(a))  # jaxlint: ok=JL001 one "
           "sync to report the value\n")
    assert _rules(src) == []
    other = ("import jax.numpy as jnp\n"
             "def f(a):\n"
             "    return float(jnp.sum(a))  # jaxlint: ok=JL003\n")
    assert _rules(other) == ["JL001"], "pragma is rule-specific"


# ---------------------------------------------------------------------------
# Tier B budgets (compiles the entry points once, module scope)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tier_b_measured():
    from lightgbm_tpu.analysis import artifacts
    return artifacts.collect_tier_b()


def test_tier_b_budgets_hold(tier_b_measured):
    problems = baseline.compare_tier_b(tier_b_measured, BASELINE)
    assert not problems, "\n".join(p.render() for p in problems)


def test_tier_b_detector_sees_the_subtraction_copies(tier_b_measured):
    """The default path's two contextual hist-state copies must be
    visible to the detector, or the mega zero-copy budget proves
    nothing (mirrors test_hlo_guard.py)."""
    assert tier_b_measured["while_body.default"]["hist_state_copies"] == 2


def test_copy_budget_regression_is_caught(tier_b_measured):
    """Negative: feed the DEFAULT body's real measured counts to the
    MEGA body's zero-copy budget — the comparison must fail, proving a
    reintroduced hist-state carry would be caught."""
    regressed = {"while_body.mega": {
        "hist_state_copies":
            tier_b_measured["while_body.default"]["hist_state_copies"],
        "hist_state_shape_lines": 1,
        "copies": tier_b_measured["while_body.mega"]["copies"],
    }}
    problems = baseline.compare_tier_b(regressed, BASELINE)
    keys = {p.key for p in problems if p.kind == "budget"}
    assert "while_body.mega.hist_state_copies" in keys, problems
    assert "while_body.mega.hist_state_shape_lines" in keys, problems


def test_serving_budget_regression_is_caught():
    """Negative: a retrace per call must breach the serving budget."""
    regressed = {"serving.compiles": {"max_traces_per_bucket": 4,
                                      "buckets_with_retrace": 3}}
    problems = baseline.compare_tier_b(regressed, BASELINE)
    assert any(p.key == "serving.compiles.max_traces_per_bucket"
               and p.kind == "budget" for p in problems), problems


def test_stale_baseline_entry_fails_the_ratchet(tier_a_counts):
    """Fixing a pinned violation must force shrinking the baseline."""
    inflated = dict(BASELINE["tier_a"])
    inflated["JL001:lightgbm_tpu/ops/ghost.py:gone"] = 3
    problems = baseline.compare_tier_a(
        tier_a_counts, {"tier_a": inflated})
    assert any(p.kind == "stale" and "ghost" in p.key for p in problems)


# ---------------------------------------------------------------------------
# CLI contract: --check exit codes and --json line format
# ---------------------------------------------------------------------------
def test_cli_check_and_json():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "jaxlint.py"),
         "--check", "--tier", "a", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    for ln in lines:
        rec = json.loads(ln)        # one machine-readable line each
        assert rec.get("tier") in ("A", "B") or "problem" in rec


def test_cli_check_fails_against_empty_baseline(tmp_path):
    """--check must exit non-zero when findings exceed the baseline
    (here: an empty one)."""
    bl = tmp_path / "empty_baseline.json"
    bl.write_text('{"version": 1, "tier_a": {}, "tier_b": {}}\n')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "jaxlint.py"),
         "--check", "--tier", "a", "--baseline", str(bl)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
