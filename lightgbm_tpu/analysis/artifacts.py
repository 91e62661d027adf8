"""Tier B of jaxlint: compile-artifact budget checks.

Tier A reads source; this tier lowers the designated entry points to
optimized HLO / live trace counters and asserts STRUCTURAL invariants
as machine-checked budgets, so the regressions that only a profiler
would otherwise catch fail tier-1 instead:

* ``while_body.default`` / ``while_body.mega`` — op/fusion/copy counts
  of the compiled tree-build while body (generalizing
  tools/hlo_report.py): the default subtraction path carries exactly
  its two known contextual hist-state copies, the mega-kernel body
  carries zero and the (L+1)-slot state buffer must not exist at all.
* ``serving.compiles`` — N same-bucket serving calls (raw / leaf /
  contrib) cost exactly one XLA trace per (kind, bucket); a second
  trace is a retrace regression.
* ``serving.transfers`` — the compiled raw-serving program contains no
  host callbacks and stays under a copy/transfer op budget in its
  entry computation.
* ``predict.layered`` — the layered dense predictor
  (ops/forest_tensor.py) lowers with ZERO while loops (fixed trip
  count, unrolled at trace time), no host callbacks and the pinned
  transfer budget: the dataflow shape cannot silently regress to
  data-dependent traversal.
* ``train.donation`` — the fused train step is jitted with donated
  score/payload buffers (losing donation doubles the resident score
  footprint and adds a copy per iteration).
* ``shap.kernel`` — the device TreeSHAP program keeps its unrolled
  D/q-loop structure (at most the single tree scan ``while``), runs
  f64 under the scoped x64 context, and contains no host callbacks.
* ``linear.gain`` — constant-gain tree builds lower op-for-op
  identically with the piece-wise-linear (leafwise_gain) machinery in
  the codebase: ``linear_tree=True`` in refit mode may not change the
  fused while-body by a single op, and the leafwise body itself keeps
  a pinned op count.
* ``continual.tick`` — steady-state continual-runtime ticks add zero
  serving retraces (the in-place refit rides the leaf-refresh fast
  path) and a hot swap compiles each (kind, bucket) at most once,
  during the candidate warm-up, never on the serving path.
* ``telemetry.off`` — the obs layer stages ZERO device ops: the fused
  train step's lowered while-body is op-for-op identical with
  telemetry off and at full trace mode (spans/counters/compile
  detection are host-side bookkeeping by construction); with the
  session off, iterations record nothing (no span but the set-up
  stages, no counter, no event) and the set-up record still names the
  step's build and the ``train.setup`` stage.
* ``health.off`` — same zero-HLO invariant for the model/data-health
  layer (flight recorder, skew digests): the lowered while-body is
  op-for-op identical with health off and at trace mode.
* ``perfwatch.off`` — same zero-HLO invariant for the perf-trajectory
  layer (obs/regress.py): lowering inside an active perfwatch
  recording (injectable clock + BENCH_history append) changes nothing.

Every metric is a ceiling checked against ``jaxlint_baseline.json``
(see :mod:`lightgbm_tpu.analysis.baseline`).  All checks run on the
current backend — CPU in tier-1 — exactly like tests/test_hlo_guard.py.
"""

from __future__ import annotations

import contextlib
import functools
import re
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["collect_tier_b", "CHECKS"]


# ---------------------------------------------------------------------------
# while-body checks (tree build)
# ---------------------------------------------------------------------------
def check_while_body_default() -> Dict[str, int]:
    from .hlo import report
    r = report({})
    return {
        "total_ops": r["total_ops"],
        "fusions": r["fusions"],
        "copies": r["copies"],
        "hist_state_copies": r["hist_state_copies"],
    }


def check_while_body_mega() -> Dict[str, int]:
    from .hlo import report
    r = report({"tpu_megakernel": "xla"})
    return {
        "hist_state_copies": r["hist_state_copies"],
        "hist_state_shape_lines": r["hist_state_shape_lines"],
        "copies": r["copies"],
    }


def check_chunk_adaptive() -> Dict[str, int]:
    """Leaf-size-adaptive chunk-policy budget (ops/chunkpolicy.py).

    The adaptive body must dispatch its per-leaf band variants via
    zero-trip loops, never conditionals: the hist-state copies stay at
    the fixed body's exact count and the total-copy delta versus an
    explicitly fixed lowering stays pinned (lax.switch plumbing would
    add one copy PER ROW BUFFER per split — the round-1 conditional
    pathology, measured again while building this policy).  The traced
    variant registry additionally pins the compiled-variant count per
    pass to the static menu — the training-side analog of the serving
    engine's per-(kind, bucket) compile keys."""
    from ..ops import chunkpolicy
    from .hlo import report
    chunkpolicy.reset_variant_log()
    ra = report({"tpu_chunk_policy": "adaptive"})
    per_pass: Dict[str, set] = {}
    for (pass_name, width) in chunkpolicy.variant_log():
        per_pass.setdefault(pass_name, set()).add(width)
    menu_max = 4
    over = sum(1 for ws in per_pass.values() if len(ws) > menu_max)
    rf = report({"tpu_chunk_policy": "fixed"})
    return {
        "hist_state_copies": ra["hist_state_copies"],
        "hist_state_copies_delta": abs(ra["hist_state_copies"]
                                       - rf["hist_state_copies"]),
        "copies_delta_vs_fixed": max(ra["copies"] - rf["copies"], 0),
        "passes_over_menu": over,
        "variants_missing": 0 if per_pass else 1,
    }


_FRONTIER_K = 4


def check_while_body_frontier() -> Dict[str, int]:
    """Frontier-batched (tpu_frontier_k=4) tree-build while body: the
    per-SPLIT bookkeeping op budget (outer-body ops amortize over up to
    K splits per step) and the structural invariant that the K-row
    parent-hist gather + 2K-row child scatter carry ZERO contextual
    hist-state copies (the subtraction path's two copies per split are
    the round-4 fixed-cost smoking gun; the K=1 budget pins them at
    exactly 2, this budget pins their absence under batching)."""
    from .hlo import report
    r = report({"tpu_frontier_k": _FRONTIER_K})
    return {
        "ops_per_split": -(-r["total_ops"] // _FRONTIER_K),
        "copies": r["copies"],
        "hist_state_copies": r["hist_state_copies"],
    }


# ---------------------------------------------------------------------------
# serving-engine checks
# ---------------------------------------------------------------------------
_TINY = {}


def _tiny_serving_booster():
    """One small trained booster shared by the serving checks (module
    cache: artifact collection may run several checks per process)."""
    if "bst" in _TINY:
        return _TINY["bst"], _TINY["X"]
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.normal(size=(4500, 6))
    y = X[:, 0] * 2.0 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=len(X))
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "num_leaves": 15, "min_data_in_leaf": 10,
                     "metric": ""},
                    lgb.Dataset(X[:, :], label=y), num_boost_round=5)
    bst._gbdt._flush_pending()
    _TINY["bst"] = bst
    _TINY["X"] = X
    return bst, X


def check_serving_compiles() -> Dict[str, int]:
    """max traces per (kind, bucket) across repeated same-bucket calls
    — the compile-count guard as a budget."""
    bst, X = _tiny_serving_booster()
    eng = bst._gbdt.serving
    eng.trace_counts.clear()
    eng.call_counts.clear()
    bst.predict(X, raw_score=True)            # >= COLD_MIN_ROWS: warms
    for n in (700, 700, 600, 900):            # all pad to bucket 1024
        bst.predict(X[:n], raw_score=True)
        bst.predict(X[:n], pred_leaf=True)
        bst.predict(X[:n], pred_contrib=True)
    max_traces = max(eng.trace_counts.values(), default=0)
    # every (kind, bucket) seen must have exactly one trace
    multi = sum(1 for v in eng.trace_counts.values() if v > 1)
    return {"max_traces_per_bucket": max_traces,
            "buckets_with_retrace": multi}


def _serving_raw_lowered_text() -> str:
    import jax.numpy as jnp
    bst, X = _tiny_serving_booster()
    eng = bst._gbdt.serving
    pack = eng._pack("insession", eng._insession_pack)
    assert pack is not None, "tiny booster must be device-eligible"
    binned = eng._bin(X[:128], pack["has_cat"])
    pk = pack["per_k"][0]
    mask = eng._tree_mask(pack["T_k"], 0, pack["T_k"])
    fn = eng._fn("raw")
    lowered = fn.lower(pk["nodes"], pk["deltas"], mask,
                       jnp.asarray(binned))
    return lowered.compile().as_text()


def check_serving_transfers() -> Dict[str, int]:
    from .hlo import body_counts, entry_name
    txt = _serving_raw_lowered_text()
    entry = entry_name(txt)
    counts = body_counts(txt, body_name=entry) if entry else {
        "copies": 0, "total_ops": 0}
    callbacks = len(re.findall(r"callback", txt))
    transfers = len(re.findall(
        r"\b(?:copy-start|copy-done|send|recv|infeed|outfeed)\(", txt))
    return {"entry_copies": counts["copies"],
            "transfer_ops": transfers,
            "host_callbacks": callbacks}


def check_predict_layered() -> Dict[str, int]:
    """The layered dense predictor (ops/forest_tensor.py) is a
    DATAFLOW program: the lowered raw-serving path must contain ZERO
    while loops (the trip count is a pack-time host constant, unrolled
    at trace time — any ``while`` means the data-dependent traversal
    silently came back), no host callbacks, and the same pinned
    transfer budget as the loop path."""
    import jax.numpy as jnp
    bst, X = _tiny_serving_booster()
    eng = bst._gbdt.serving
    pack = eng._pack("insession", eng._insession_pack)
    assert pack is not None and pack.get("layers_depth") is not None, \
        "tiny booster must be layered-eligible"
    binned = eng._bin(X[:128], pack["has_cat"])
    pk = pack["per_k"][0]
    mask = eng._tree_mask(pack["T_k"], 0, pack["T_k"])
    fn = eng._fn("raw_layered")
    lowered = fn.lower(pk["layers"], pk["deltas"], mask,
                       jnp.asarray(binned),
                       max_depth=pack["layers_depth"])
    txt = lowered.compile().as_text()
    from .hlo import body_counts, entry_name
    entry = entry_name(txt)
    counts = body_counts(txt, body_name=entry) if entry else {
        "copies": 0}
    return {"whiles": len(re.findall(r"\bwhile\(", txt)),
            "host_callbacks": len(re.findall(r"callback", txt)),
            "transfer_ops": len(re.findall(
                r"\b(?:copy-start|copy-done|send|recv|infeed|outfeed)\(",
                txt)),
            "entry_copies": counts["copies"]}


# ---------------------------------------------------------------------------
# donation of the fused train step's score/payload buffers
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def _record_jits(records: List[Tuple[str, Any]]):
    import jax
    orig = jax.jit

    @functools.wraps(orig)
    def spy(fun, *a, **k):
        records.append((getattr(fun, "__qualname__", repr(fun)),
                        k.get("donate_argnums")))
        return orig(fun, *a, **k)

    jax.jit = spy
    try:
        yield
    finally:
        jax.jit = orig


def check_train_donation() -> Dict[str, int]:
    """The fused per-iteration step must be jitted with donated
    buffers; count fused steps constructed WITHOUT donation."""
    import numpy as np

    import lightgbm_tpu as lgb
    records: List[Tuple[str, Any]] = []
    rng = np.random.RandomState(5)
    X = rng.normal(size=(400, 5))
    y = X[:, 0] - X[:, 2] + 0.1 * rng.normal(size=len(X))
    with _record_jits(records):
        lgb.train({"objective": "regression", "verbosity": -1,
                   "num_leaves": 7, "min_data_in_leaf": 5,
                   "metric": ""},
                  lgb.Dataset(X, label=y), num_boost_round=2)
    fused = [(q, d) for q, d in records
             if "_setup_fused" in q and q.endswith(".lgbm_fused_step")]
    undonated = sum(1 for _, d in fused if not d)
    return {"fused_steps_jitted": len(fused),
            "fused_steps_without_donation": undonated,
            "fused_step_missing": 0 if fused else 1}


def check_train_residency() -> Dict[str, int]:
    """Single-copy binned residency invariants: the fused trainer must
    ADOPT the ingest/learner master buffer (alias, not copy), update it
    in place every iteration, retire every other reference, and the
    ledger must attribute the surviving carrier.  Budgets pin:

      * ``binned_residents`` — live binned-footprint device buffers
        among {physical carrier, learner ``_part0``, ingest buffer}
        after two fused iterations (must be exactly 1);
      * ``adopt_not_aliased`` — the init forwarded a COPY instead of
        aliasing the donated master buffer;
      * ``step_not_inplace`` — the donated step returned the bins in a
        different buffer (XLA refused the aliasing);
      * ``master_not_retired`` — learner/ingest still hold a reference
        the donation is about to invalidate;
      * ``carrier_unattributed`` — the ledger's ``train.state`` owner
        does not account the carrier's bytes."""
    import numpy as np

    import lightgbm_tpu as lgb
    from ..obs import memory as obs_memory
    rng = np.random.RandomState(6)
    X = rng.normal(size=(600, 6))
    y = X[:, 0] - X[:, 3] + 0.1 * rng.normal(size=len(X))
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster({"objective": "regression", "verbosity": -1,
                       "num_leaves": 7, "min_data_in_leaf": 5,
                       "metric": ""}, ds)
    g = bst._gbdt
    lr = g.learner
    p0 = lr._part0
    ptr0 = p0.unsafe_buffer_pointer() if p0 is not None else None
    bst.update()
    if g._phys is None:
        return {"fused_phys_missing": 1, "binned_residents": 0,
                "adopt_not_aliased": 0, "step_not_inplace": 0,
                "master_not_retired": 0, "carrier_unattributed": 0}
    pb = g._phys[0]
    adopt_not_aliased = 0 if (ptr0 is not None
                              and pb.unsafe_buffer_pointer() == ptr0) else 1
    ptr1 = pb.unsafe_buffer_pointer()
    bst.update()
    pb2 = g._phys[0]
    step_not_inplace = 0 if pb2.unsafe_buffer_pointer() == ptr1 else 1
    ing = getattr(lr, "_ingest", None)
    master_not_retired = 0 if (
        lr._part0 is None
        and (ing is None or getattr(ing, "buffer", None) is None)) else 1
    residents = 1                       # the carrier itself
    for cand in (getattr(ing, "buffer", None),
                 getattr(lr, "_part0", None)):
        if cand is not None and not cand.is_deleted():
            residents += 1
    st = obs_memory.snapshot()["owners"].get("train.state", {})
    carrier_unattributed = (
        0 if st.get("device_unique_bytes", 0) >= int(pb2.nbytes) else 1)
    return {"fused_phys_missing": 0, "binned_residents": residents,
            "adopt_not_aliased": adopt_not_aliased,
            "step_not_inplace": step_not_inplace,
            "master_not_retired": master_not_retired,
            "carrier_unattributed": carrier_unattributed}


# ---------------------------------------------------------------------------
# device TreeSHAP program structure
# ---------------------------------------------------------------------------
def check_shap_kernel() -> Dict[str, int]:
    import jax
    import jax.numpy as jnp

    from .hlo import body_counts, entry_name
    from ..ops.shap import tree_shap_stacked
    bst, X = _tiny_serving_booster()
    eng = bst._gbdt.serving
    eng._pack("insession", eng._insession_pack)
    pack = eng._pack("contrib", eng._contrib_pack)
    assert pack is not None, "tiny booster must be SHAP-eligible"
    grp = pack["per_k"][0]["groups"][0]
    binned = eng._bin(X[:128], pack["has_cat"])
    ncols = pack["num_cols"]
    with jax.enable_x64(True):
        mask = jnp.asarray((grp["iters"] >= 0).astype("float32"))
        fn = jax.jit(functools.partial(tree_shap_stacked,
                                       num_columns=ncols))
        lowered = fn.lower(jnp.asarray(binned), grp["nodes"],
                           grp["paths"], mask, jnp.asarray(grp["tq"]),
                           jnp.asarray(grp["om"]))
        txt = lowered.compile().as_text()
    entry = entry_name(txt)
    counts = body_counts(txt, body_name=entry) if entry else {}
    whiles = len(re.findall(r"\bwhile\(", txt))
    callbacks = len(re.findall(r"callback", txt))
    f64_absent = 0 if "f64[" in txt else 1
    return {"whiles": whiles, "host_callbacks": callbacks,
            "f64_absent": f64_absent,
            "entry_copies": counts.get("copies", 0)}


# ---------------------------------------------------------------------------
# telemetry zero-HLO invariant
# ---------------------------------------------------------------------------
def check_telemetry_off() -> Dict[str, int]:
    """The obs layer must never stage device ops: the fused train
    step's lowered while-body is OP-FOR-OP identical whether the
    telemetry session is off or at full trace mode.  (The off-mode
    lowering equals the pre-obs program by the same argument — spans
    and the compile detector are host-side bookkeeping — and the
    separate ``while_body.default`` budget pins the absolute counts.)
    Off records nothing per iteration and keeps the set-up record: two
    ``update()`` calls add no span but the set-up stages, no counter and
    no event (``off_iteration_records``), and the record holds the step's
    build and the ``train.setup`` stage (``off_setup_unrecorded``).  Every
    metric is an invariant budgeted at 0."""
    import jax.numpy as jnp
    import numpy as np

    import lightgbm_tpu as lgb
    from ..obs import telemetry as obs
    from .hlo import body_counts

    def lower_step(updates=0):
        rng = np.random.RandomState(11)
        X = rng.normal(size=(512, 6))
        y = X[:, 0] - 0.5 * X[:, 2] + 0.1 * rng.normal(size=len(X))
        bst = lgb.Booster(params={"objective": "regression",
                                  "verbosity": -1, "num_leaves": 15,
                                  "min_data_in_leaf": 5, "metric": ""},
                          train_set=lgb.Dataset(X, label=y))
        g = bst._gbdt
        assert g._fused_phys is not None, \
            "telemetry.off budget needs the fused physical step"
        pb, ghi = g._init_phys(g.learner._part0, g.scores,
                                 *g._phys_payloads)
        fmask = jnp.ones((g.learner.F,), dtype=bool)
        feat_used = jnp.zeros((g.learner.F,), dtype=bool)
        lowered = g._fused_phys.lower(pb, ghi, fmask, jnp.int32(1),
                                      feat_used)
        text = lowered.compile().as_text()
        for _ in range(updates):
            bst.update()
        bst._gbdt._flush_pending()
        return text

    def records(rep):
        stages = {st["name"] for st in rep["stages"]}
        return ({n: h["count"] for n, h in rep["spans"].items()
                 if n not in stages},
                dict(rep["counters"]), rep["events_recorded"])

    sess = obs.get()
    prev = sess.mode
    try:
        sess.set_mode("off")
        before = sess.report()
        off = body_counts(lower_step(updates=2))
        after = sess.report()
        sess.set_mode("trace")
        on = body_counts(lower_step())
    finally:
        sess.set_mode(prev)

    def grew(group, name):
        return (after[group].get(name, {}).get("count", 0)
                > before[group].get(name, {}).get("count", 0))

    iteration_records = int(records(before) != records(after))
    setup_unrecorded = int(not (grew("programs", "lgbm_fused_step")
                                and grew("spans", "train.setup")))
    keys = set(off["ops"]) | set(on["ops"])
    hist_delta = sum(abs(off["ops"].get(k, 0) - on["ops"].get(k, 0))
                     for k in keys)
    return {"body_op_histogram_delta": hist_delta,
            "total_ops_delta": abs(off["total_ops"] - on["total_ops"]),
            "copies_delta": abs(off["copies"] - on["copies"]),
            "off_iteration_records": iteration_records,
            "off_setup_unrecorded": setup_unrecorded}


# ---------------------------------------------------------------------------
# health zero-HLO invariant
# ---------------------------------------------------------------------------
def check_health_off() -> Dict[str, int]:
    """The health layer must never stage device ops in the training
    loop: the fused train step's lowered while-body is OP-FOR-OP
    identical with health off and at full trace mode (the flight
    recorder consumes host records the trainer already materializes;
    device digest reductions only run in explicit snapshot calls).
    Mirrors ``telemetry.off``; every delta metric is an invariant
    budgeted at 0."""
    import jax.numpy as jnp
    import numpy as np

    import lightgbm_tpu as lgb
    from ..obs import health as obs_health
    from ..obs import telemetry as obs_tel
    from .hlo import body_counts

    def lower_step(mode):
        rng = np.random.RandomState(13)
        X = rng.normal(size=(512, 6))
        y = X[:, 0] - 0.5 * X[:, 2] + 0.1 * rng.normal(size=len(X))
        bst = lgb.Booster(params={"objective": "regression",
                                  "verbosity": -1, "num_leaves": 15,
                                  "min_data_in_leaf": 5, "metric": "",
                                  "health": mode},
                          train_set=lgb.Dataset(X, label=y))
        g = bst._gbdt
        assert g._fused_phys is not None, \
            "health.off budget needs the fused physical step"
        pb, ghi = g._init_phys(g.learner._part0, g.scores,
                                 *g._phys_payloads)
        fmask = jnp.ones((g.learner.F,), dtype=bool)
        feat_used = jnp.zeros((g.learner.F,), dtype=bool)
        lowered = g._fused_phys.lower(pb, ghi, fmask, jnp.int32(1),
                                      feat_used)
        return lowered.compile().as_text()

    sess = obs_health.get()
    tel = obs_tel.get()
    prev, tel_prev = sess.mode, tel.mode
    try:
        sess.set_mode("off")
        off = body_counts(lower_step("off"))
        sess.set_mode("trace")
        on = body_counts(lower_step("trace"))
    finally:
        sess.set_mode(prev)
        tel.set_mode(tel_prev)       # health trace upgrades telemetry
    keys = set(off["ops"]) | set(on["ops"])
    hist_delta = sum(abs(off["ops"].get(k, 0) - on["ops"].get(k, 0))
                     for k in keys)
    return {"body_op_histogram_delta": hist_delta,
            "total_ops_delta": abs(off["total_ops"] - on["total_ops"]),
            "copies_delta": abs(off["copies"] - on["copies"])}


# ---------------------------------------------------------------------------
# perfwatch zero-HLO invariant
# ---------------------------------------------------------------------------
def check_perfwatch_off() -> Dict[str, int]:
    """The perf-trajectory layer (obs/regress.py) must never stage
    device ops or syncs: the fused train step's lowered while-body is
    OP-FOR-OP identical whether or not a perfwatch recording (clock +
    BENCH_history append) is in flight around the lowering.  Same
    contract as ``telemetry.off``: spans are host clock reads, the
    store is a host JSONL append — every delta metric is an invariant
    budgeted at 0."""
    import os
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    import lightgbm_tpu as lgb
    from ..obs import regress
    from .hlo import body_counts

    def lower_step():
        rng = np.random.RandomState(17)
        X = rng.normal(size=(512, 6))
        y = X[:, 0] - 0.5 * X[:, 2] + 0.1 * rng.normal(size=len(X))
        bst = lgb.Booster(params={"objective": "regression",
                                  "verbosity": -1, "num_leaves": 15,
                                  "min_data_in_leaf": 5, "metric": ""},
                          train_set=lgb.Dataset(X, label=y))
        g = bst._gbdt
        assert g._fused_phys is not None, \
            "perfwatch.off budget needs the fused physical step"
        pb, ghi = g._init_phys(g.learner._part0, g.scores,
                                 *g._phys_payloads)
        fmask = jnp.ones((g.learner.F,), dtype=bool)
        feat_used = jnp.zeros((g.learner.F,), dtype=bool)
        lowered = g._fused_phys.lower(pb, ghi, fmask, jnp.int32(1),
                                      feat_used)
        return lowered.compile().as_text()

    off = body_counts(lower_step())
    with tempfile.TemporaryDirectory() as td:
        with regress.recording("jaxlint.perfwatch",
                               path=os.path.join(td, "h.jsonl"),
                               config={}):
            on = body_counts(lower_step())
    keys = set(off["ops"]) | set(on["ops"])
    hist_delta = sum(abs(off["ops"].get(k, 0) - on["ops"].get(k, 0))
                     for k in keys)
    return {"body_op_histogram_delta": hist_delta,
            "total_ops_delta": abs(off["total_ops"] - on["total_ops"]),
            "copies_delta": abs(off["copies"] - on["copies"])}


# ---------------------------------------------------------------------------
# piece-wise-linear gain: constant-mode lowering invariant
# ---------------------------------------------------------------------------
def check_linear_gain() -> Dict[str, int]:
    """The leafwise-gain machinery (models/learner.py NLF_LINEAR rows,
    ops/split.py:find_best_split_linear) must be invisible to constant
    trees: the tree-build while body lowers OP-FOR-OP identically
    between a plain config and ``linear_tree=True`` in the default
    (refit) mode — the refit happens post-hoc on the host, so the
    device program may not change by a single op.  The ``_nlf`` gate
    is a Python-level branch; if it ever leaks into the trace (e.g. an
    unconditional 28-row leafmat), these deltas light up.
    ``leafwise_total_ops`` additionally pins that the leafwise body
    keeps compiling, as a drifting count with headroom.  (The fused
    single-program step is off under linear_tree, so the lowering
    vehicle is the tree-build body itself, same as
    ``while_body.default``.)"""
    from .hlo import report

    plain = report({})
    refit = report({"linear_tree": True})
    leafwise = report(
        {"linear_tree": True, "linear_tree_mode": "leafwise_gain"})
    keys = set(plain["ops"]) | set(refit["ops"])
    hist_delta = sum(abs(plain["ops"].get(k, 0) - refit["ops"].get(k, 0))
                     for k in keys)
    shape_keys = set(plain["copies_by_shape"]) | \
        set(refit["copies_by_shape"])
    shape_delta = sum(abs(plain["copies_by_shape"].get(k, 0)
                          - refit["copies_by_shape"].get(k, 0))
                      for k in shape_keys)
    return {"body_op_histogram_delta": hist_delta,
            "total_ops_delta": abs(plain["total_ops"]
                                   - refit["total_ops"]),
            "copies_delta": abs(plain["copies"] - refit["copies"]),
            "copy_shape_histogram_delta": shape_delta,
            "leafwise_total_ops": leafwise["total_ops"]}


# ---------------------------------------------------------------------------
# continual-runtime tick/swap budgets
# ---------------------------------------------------------------------------
def check_continual_tick() -> Dict[str, int]:
    """Tick-loop artifact budget for the continual runtime: steady-state
    ticks (prequential eval + in-place leaf refit) must add ZERO serving
    retraces — the refit rides the engine's leaf-refresh fast path, so
    only the small delta matrices re-transfer — and a hot swap must cost
    at most ONE compile per (kind, bucket), paid while warming the
    candidate off the serving path."""
    from ..continual.drift import _DRILL_PARAMS, DriftStream
    from ..continual.runtime import ContinualBooster

    p = dict(_DRILL_PARAMS)
    p.update({"num_iterations": 5, "num_leaves": 7})
    stream = DriftStream(num_features=5, rows=128, seed=9)
    X0, y0 = DriftStream(num_features=5, rows=512, seed=10).batch(0)
    cb = ContinualBooster(p, X0, y0)
    # settle: the first tick pays the per-kind compiles once
    cb.tick(*stream.batch(0))
    snap = cb.serving_engine.trace_snapshot()
    for t in range(1, 4):
        cb.tick(*stream.batch(t))
    tick_retraces = sum(
        cb.serving_engine.new_traces_since(snap).values())

    # a forced swap: candidate warm-up may trace each (kind, bucket)
    # once, never twice
    import lightgbm_tpu as lgb
    Xc, yc = DriftStream(num_features=5, rows=512, seed=12).batch(0)
    cand = lgb.train({"objective": "regression", "verbosity": -1,
                      "num_leaves": 7, "metric": ""},
                     lgb.Dataset(Xc, label=yc), num_boost_round=5)
    r = cb.force_swap(cand, gate=stream.batch(4))
    over = sum(1 for v in r.swap_new_traces.values() if v > 1)
    return {"tick_retraces": tick_retraces,
            "swap_retraces_over_one": over,
            "swap_missing_warm": 0 if r.swap_new_traces else 1}


CHECKS = {
    "while_body.default": check_while_body_default,
    "while_body.mega": check_while_body_mega,
    "frontier.body": check_while_body_frontier,
    "chunk.adaptive": check_chunk_adaptive,
    "serving.compiles": check_serving_compiles,
    "serving.transfers": check_serving_transfers,
    "predict.layered": check_predict_layered,
    "train.donation": check_train_donation,
    "train.residency": check_train_residency,
    "shap.kernel": check_shap_kernel,
    "continual.tick": check_continual_tick,
    "linear.gain": check_linear_gain,
    "telemetry.off": check_telemetry_off,
    "health.off": check_health_off,
    "perfwatch.off": check_perfwatch_off,
}


def collect_tier_b(only: Optional[List[str]] = None
                   ) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for name, fn in CHECKS.items():
        if only and name not in only:
            continue
        out[name] = fn()
    return out
