"""Compile-artifact inspection: optimized-HLO parsing and op-count
extraction for the tree-build while-body and other entry points.

The per-split fixed cost of the tree loop is OP-COUNT bound, not
any-single-op bound (PERF.md round 2: 327 HLO ops / 32 copies in the
while body at ~1.5 us dispatch overhead each IS the 0.45 ms/split), so
bookkeeping-op regressions are perf regressions that a timing's noise
floor would otherwise hide.  This module compiles designated entry
points on the CURRENT backend, extracts computations from the optimized
HLO text, and counts instructions, fusions and copies — including
copies grouped by shape, which is how the round-4 "two contextual
f32[256,28,255,2] parent-hist copies per split" smoking gun was pinned.

Consumers: ``tools/hlo_report.py`` (CLI), ``tests/test_hlo_guard.py``
(tier-1 ceilings) and :mod:`lightgbm_tpu.analysis.artifacts` (the
jaxlint Tier B budget checks keyed to ``jaxlint_baseline.json``).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

__all__ = [
    "body_counts", "compile_tree_build", "entry_name", "report",
]


def _computation_blocks(hlo_text: str) -> Dict[str, List[str]]:
    """Split optimized HLO text into {computation_name: instruction
    lines} (top-level `name (...) -> ... {` blocks)."""
    blocks: Dict[str, List[str]] = {}
    cur = None
    head = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{")
    for line in hlo_text.splitlines():
        if cur is None:
            m = head.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = m.group(1)
                blocks[cur] = []
        elif line.strip() == "}":
            cur = None
        else:
            s = line.strip()
            if s and not s.startswith("//"):
                blocks[cur].append(s)
    return blocks


def entry_name(hlo_text: str) -> Optional[str]:
    """Name of the ENTRY computation, or None."""
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)\s*\(", hlo_text, re.MULTILINE)
    return m.group(1) if m else None


def _while_bodies(hlo_text: str) -> List[str]:
    """Names of every while-loop body computation, outermost first by
    instruction count (the tree loop is the largest)."""
    names = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    blocks = _computation_blocks(hlo_text)
    found = [n for n in names if n in blocks]
    return sorted(found, key=lambda n: -len(blocks[n]))


_OP_RE = re.compile(r"=\s*(?:[\w\[\],:{}\s/#*()$-]*?\s)?([a-z][\w-]*)\(")
_SHAPE_RE = re.compile(r"=\s*([a-z0-9]+\[[^\]]*\])(?:\{[^}]*\})?\s")


def body_counts(hlo_text: str, body_name: str = None) -> Dict[str, Any]:
    """Instruction/fusion/copy counts of one while-body computation
    (default: the largest, i.e. the tree loop)."""
    blocks = _computation_blocks(hlo_text)
    if body_name is None:
        bodies = _while_bodies(hlo_text)
        if not bodies:
            raise ValueError("no while body found in HLO text")
        body_name = bodies[0]
    lines = blocks[body_name]
    ops: Dict[str, int] = {}
    copies_by_shape: Dict[str, int] = {}
    for ln in lines:
        m = _OP_RE.search(ln)
        if not m:
            continue
        op = m.group(1)
        ops[op] = ops.get(op, 0) + 1
        if op == "copy":
            sm = _SHAPE_RE.search(ln)
            shape = sm.group(1) if sm else "?"
            copies_by_shape[shape] = copies_by_shape.get(shape, 0) + 1
    return {
        "body": body_name,
        "total_ops": sum(ops.values()),
        "fusions": ops.get("fusion", 0),
        "copies": ops.get("copy", 0),
        "whiles": ops.get("while", 0),
        "ops": dict(sorted(ops.items())),
        "copies_by_shape": dict(sorted(copies_by_shape.items(),
                                       key=lambda kv: -kv[1])),
    }


def compile_tree_build(params: Dict[str, Any] = None, n: int = 2048,
                       f: int = 10):
    """Compile one tree build on synthetic binned data and return the
    optimized HLO text (mirrors __graft_entry__.entry's flagship
    compute)."""
    import jax.numpy as jnp
    import numpy as np

    from ..config import Config
    from ..dataset import BinnedDataset
    from ..models.learner import SerialTreeLearner

    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] * 2.0 + X[:, 1] - X[:, 2]
         + 0.5 * rng.normal(size=n) > 0).astype(np.float64)
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "min_data_in_leaf": 5, "verbosity": -1,
                  **(params or {})})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    learner = SerialTreeLearner(ds, cfg)
    grad = jnp.asarray((0.5 - y).astype(np.float32))
    hess = jnp.full((len(y),), 0.25, dtype=jnp.float32)
    fmask = jnp.ones((learner.F,), dtype=bool)
    import jax
    lowered = jax.jit(learner._build_impl).lower(
        learner._part0, grad, hess, jnp.int32(len(y)), fmask)
    return lowered.compile().as_text(), learner


def report(params: Dict[str, Any] = None) -> Dict[str, Any]:
    hlo, learner = compile_tree_build(params)
    out = body_counts(hlo)
    out["params"] = dict(params or {})
    out["mega"] = learner.plan.mega
    out["frontier_k"] = learner.plan.frontier_k
    # the hist-state buffer shape (the subtraction path's per-split
    # dynamic-slice target) — its copies are the round-4 smoking gun.
    # The frontier-batched body sizes the state by its speculative slack
    # (L + K slots) instead of L + 1.
    slots = learner.L + learner.plan.frontier_k
    G, B = learner.G, learner.B
    state_shapes = [f"f32[{slots},{G},{B},2]",
                    f"f32[{slots},8,{learner._flat_geom[2]}]"
                    if learner._flat_geom else None]
    out["hist_state_copies"] = sum(
        cnt for shape, cnt in out["copies_by_shape"].items()
        if shape in [s for s in state_shapes if s])
    # whether the state SHAPE appears at all in the body (the mega
    # kernel's invariant is stronger than zero copies: no buffer)
    body_lines = _computation_blocks(hlo)[out["body"]]
    tokens = [s for s in state_shapes if s]
    out["hist_state_shape_lines"] = sum(
        1 for ln in body_lines if any(t in ln for t in tokens))
    return out
