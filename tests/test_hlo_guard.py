"""Tier-1 guard on the compiled tree while-body's HLO op counts
(tools/hlo_report.py).

The per-split fixed cost is op-count bound (PERF.md round 2: ~1.5 us
dispatch overhead per op x 327 body ops WAS the 0.45 ms/split), so a
bookkeeping-op regression is a perf regression — and under a
timing's noise floor it would land silently.  This test fails
tier-1 instead.

Two guards:
  * ceilings on the default path's body counts (generous headroom over
    the measured values — a tripwire for gross regressions, not a
    byte-exact pin);
  * the mega-kernel split body must carry ZERO histogram-state copies
    (the round-4 "two contextual f32[L+1, G, B, 2] copies per split"
    are structurally gone — there is no histogram state in its carry).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))

from hlo_report import body_counts, compile_tree_build, report  # noqa: E402


@pytest.fixture(scope="module")
def reports():
    base = report({})
    mega = report({"tpu_megakernel": "xla"})
    return base, mega


def test_baseline_body_ceilings(reports):
    base, _ = reports
    # measured on the installed toolchain (jaxlib 0.9.0 XLA:CPU): 240
    # ops / 152 fusions / 22 copies with the default (leaf-size-
    # adaptive) chunk policy — the band variants add zero-trip loop
    # headers and s32[] trip-counter copies only (ops/chunkpolicy.py;
    # the explicitly fixed grid measures 181/122/14).  Re-pinned in
    # PR 21: the earlier 171/77/22 came from the jax 0.4.37 XLA, which
    # packed the same body into about half as many, larger fusions;
    # the copy counts did not move.  Ceilings leave ~30% headroom for
    # legitimate drift.
    assert base["total_ops"] <= 312, base
    assert base["fusions"] <= 198, base
    assert base["copies"] <= 28, base


def test_fixed_grid_body_ceilings():
    """The explicitly fixed-grid body keeps its OWN (tighter) ceilings
    — the adaptive default's headroom above must not hide a
    bookkeeping regression on the base formulation every band variant
    still contains (measured on jaxlib 0.9.0: 181 ops / 122 fusions /
    14 copies; 112/61/14 on the jax 0.4.37 XLA it was first pinned
    on)."""
    fixed = report({"tpu_chunk_policy": "fixed"})
    assert fixed["total_ops"] <= 235, fixed
    assert fixed["fusions"] <= 159, fixed
    assert fixed["copies"] <= 19, fixed
    assert fixed["hist_state_copies"] == 2, fixed["copies_by_shape"]


def test_baseline_has_the_parent_hist_copies(reports):
    """The detector must actually see the smoking gun on the
    subtraction path, or the mega assertion below proves nothing."""
    base, _ = reports
    assert base["hist_state_copies"] == 2, base["copies_by_shape"]


def test_mega_body_drops_hist_state_copies(reports):
    base, mega = reports
    assert mega["mega"] == "xla"
    assert mega["hist_state_copies"] == 0, mega["copies_by_shape"]
    assert mega["hist_state_copies"] < base["hist_state_copies"]


def test_mega_body_has_no_hist_state_buffer():
    """Stronger than no-copies: the (L+1)-slot state SHAPE must not
    appear anywhere in the mega while-body — the buffer does not exist."""
    hlo, learner = compile_tree_build({"tpu_megakernel": "xla"})
    counts = body_counts(hlo)
    L1, G, B = learner.L + 1, learner.G, learner.B
    state_token = f"f32[{L1},{G},{B},2]"
    assert learner.plan.mega == "xla"
    from hlo_report import _computation_blocks
    body_lines = _computation_blocks(hlo)[counts["body"]]
    assert not any(state_token in ln for ln in body_lines), state_token
