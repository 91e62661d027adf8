"""Device-resident serving engine: packed forests, bucketed batches,
and a compiled-predictor cache.

The training path dispatches one fused program per iteration; before
this module the PREDICT path re-stacked tree arrays per
(start_iteration, end_iteration) range and re-traced its jitted
traversal for every distinct batch size — serving-shaped traffic
(many small, oddly-sized batches) paid a host re-stack plus an XLA
compile on almost every call.  The engine removes both costs:

* **Packed forests** — per model version, the whole forest's node
  arrays (and, lazily, TreeSHAP path matrices) are stacked ONCE on the
  host and shipped in one transfer.  ``start_iteration``/
  ``num_iteration`` slicing is a (T,) 0/1 tree mask argument, never a
  re-stack or a re-trace.
* **Bucketed batches** — rows are padded to power-of-two buckets
  (``MIN_BUCKET``..``MAX_BUCKET``; larger batches stream in
  ``MAX_BUCKET`` chunks), so the jit cache is keyed by (pred kind,
  bucket, forest signature) and N same-bucket calls cost exactly one
  trace.  Compare the reference's OpenMP batch predictor
  (predictor.hpp:30) and the batched-traversal design point of the
  GPU-GBDT literature (Mitchell & Frank, arXiv:1806.11248).
* **Compiled-predictor cache** — packs are keyed on the model mutation
  counter (``gbdt._model_version``); ``update``/``rollback``/model
  load bump the counter, so a stale pack can never serve a mutated
  model.  ``invalidate()`` additionally drops the device arrays
  eagerly.  Trace/call counters are exported for the compile-count
  guard tests and ``tools/profile_predict.py``.

Prediction kinds served: ``raw_score`` (in-session bin-space and
loaded threshold-index forests — including piece-wise LINEAR forests,
whose per-leaf models ride (T, L, J) coefficient planes applied by one
FMA over the caller's raw rows after the ordinary traversal; see
``_insession_pack``), ``pred_leaf``, ``pred_contrib`` (ops/shap.py
vectorized TreeSHAP, f64 under an x64 context), and
``pred_early_stop`` (block-masked device accumulation).  Anything the
device cannot serve exactly (EFB-bundled categoricals without an OOV
sentinel, loaded models for SHAP, loaded or SHAP'd/early-stopped
linear models) falls back to the host paths, which remain the oracles.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import health as obs_health
from ..obs import memory as obs_memory
from ..obs import telemetry as obs
from ..ops import forest_tensor
from ..ops.predict import predict_leaf_binned, predict_leaf_thridx
from ..ops.shap import leggauss_01, tree_shap_stacked
from ..utils import log
from ..utils.log import LightGBMError
from .shap import _expected_value, tree_path_arrays
from .tree import K_CATEGORICAL_MASK

K_EPSILON = 1e-15


def _pack_memory_arrays(eng):
    """Telemetry memory provider: every pack payload (full forests and
    range sub-packs) this engine keeps resident."""
    out = [payload for _, payload in eng._packs.values()]
    out.extend(eng._range_packs.values())
    return out


def bucket_rows(n: int, min_bucket: int = 128,
                max_bucket: int = 1 << 16) -> int:
    """Smallest power-of-two bucket >= n (clamped to the bucket range)."""
    b = min_bucket
    while b < n and b < max_bucket:
        b <<= 1
    return b


class ServingEngine:
    MIN_BUCKET = 128
    MAX_BUCKET = 1 << 16
    # TreeSHAP streams ~L doubles per (row, element); chunks above ~8k
    # rows push the (leaves, rows) working set out of L2/L3 and the
    # unroll-fused kernel becomes DRAM-bound (measured ~2x on the CPU
    # host).  Traversal kinds keep the big bucket.
    CONTRIB_MAX_BUCKET = 1 << 13
    # a COLD pack stack costs a host gather + device round trip that only
    # pays for itself on big batches; once warm, any size is served
    COLD_MIN_ROWS = 4096
    # bounded LRU of per-range sub-packs: a start/num_iteration slice
    # traverses ONLY its trees instead of the whole forest under a mask
    # (the PERF.md round-7 trade-off), at one extra trace per distinct
    # slice LENGTH (jit keys on the stacked shapes) and ~4 live slices
    RANGE_CACHE = 4

    def __init__(self, gbdt):
        self.gbdt = gbdt
        self.trace_counts: Dict[Any, int] = {}   # (kind, bucket) -> traces
        self.call_counts: Dict[Any, int] = {}    # (kind, bucket) -> calls
        self._packs: Dict[str, Any] = {}         # name -> (key, payload)
        self._range_packs: "OrderedDict[Any, Any]" = OrderedDict()
        self._fns: Dict[str, Any] = {}           # kind -> jitted callable
        # pack names to re-warm LAZILY on the first predict after a
        # pickle/deepcopy restore: the restored copy bypasses the
        # COLD_MIN_ROWS gate for these names (the original was serving
        # them, so the copy is serving-shaped traffic too) instead of
        # silently answering small batches from the host paths
        self._rewarm: set = set()
        # training<->serving skew monitor (obs/health.py): None = not
        # built yet, False = this model can't host one (no reference
        # profile / no mappers)
        self._skew = None
        # telemetry HBM attribution: whatever packs this engine holds
        obs_memory.register("serving.packs", self, _pack_memory_arrays)

    # jitted callables and device packs are neither picklable nor worth
    # copying (sklearn deepcopy / dask shipping): a copy re-packs and
    # re-traces ONCE on its first predict (see _rewarm above).  The
    # GBDT itself holds jitted closures too, so a STANDALONE engine
    # pickle (a registry snapshot, a worker shipping one engine) snaps
    # the forest to its model string — the same model-text state
    # Booster uses — and the restored copy rebuilds a loaded-model
    # GBDT whose first predict re-packs + traces once per
    # (kind, bucket), exactly like a pickled Booster's engine.
    def __getstate__(self):
        from ..basic import Booster
        g = self.gbdt
        g._flush_pending()
        # a boolean, not the name list: the restored forest is a
        # LOADED model serving from a different pack family, so only
        # was-warm-at-all survives (same contract as Booster's
        # _serving_was_warm flag)
        return {"model_str":
                Booster._shell_for_gbdt(g).model_to_string(),
                "warm": bool(self._packs or self._rewarm)}

    def __setstate__(self, state):
        from ..basic import Booster
        self.__init__(Booster(model_str=state["model_str"])._gbdt)
        if state.get("warm"):
            # the restored forest is a LOADED model (threshold-index
            # space, no training mappers), so warmth must cover the
            # pack family it will actually serve from — the same
            # translation Booster.__setstate__ applies
            self.mark_rewarm()

    def mark_rewarm(self, names=("insession", "contrib", "loaded")) -> None:
        """Treat ``names`` as warm for cold-row gating until their packs
        are actually rebuilt (Booster.__setstate__ calls this when the
        pickled booster's engine was warm)."""
        self._rewarm |= set(names)

    # -- cache plumbing -------------------------------------------------
    def _sig(self):
        """Forest signature: any mutation (update/rollback/load) bumps
        ``_model_version``, so packs keyed on it can never serve stale
        trees."""
        return (len(self.gbdt.models), self.gbdt._model_version)

    def invalidate(self) -> None:
        """Drop every pack (device arrays included).  Correctness never
        depends on this — pack keys embed the model version — but
        mutation paths call it so dead forests free their HBM."""
        self._packs.clear()
        self._range_packs.clear()

    def _pack(self, name: str, build):
        key = self._sig()
        hit = self._packs.get(name)
        if hit is not None and hit[0] == key:
            return hit[1]
        payload = build()
        if payload is not None:
            self._packs[name] = (key, payload)
        # settle the re-warm debt either way: one failed build means
        # this model can't serve the pack (e.g. a restored categorical
        # model), and re-attempting the O(trees) eligibility scan on
        # every small-batch predict would be worse than the cold gate
        self._rewarm.discard(name)
        return payload

    def _warm(self, name: str) -> bool:
        if name in self._rewarm:
            return True
        hit = self._packs.get(name)
        return hit is not None and hit[0] == self._sig()

    def _count_trace(self, kind: str, bucket: int) -> None:
        k = (kind, bucket)
        self.trace_counts[k] = self.trace_counts.get(k, 0) + 1
        # runtime retrace detector (obs/): the same per-(kind, bucket)
        # compile counts the tests pin, now visible while serving —
        # attributed to whichever span (tick, swap, predict) traced it
        obs.compile_event(f"serving.{kind}@{bucket}")

    def _count_call(self, kind: str, bucket: int) -> None:
        k = (kind, bucket)
        self.call_counts[k] = self.call_counts.get(k, 0) + 1

    def stats(self) -> Dict[str, Any]:
        return {"traces": dict(self.trace_counts),
                "calls": dict(self.call_counts),
                "packs": sorted(self._packs)}

    def trace_snapshot(self) -> Dict[Any, int]:
        """Copy of the (kind, bucket) -> trace-count map, for callers
        (the continual runtime's drift drill, the jaxlint tier-B tick
        budget) that assert how many NEW compiles an operation cost."""
        return dict(self.trace_counts)

    def new_traces_since(self, snapshot: Dict[Any, int]) -> Dict[Any, int]:
        """Traces added since ``snapshot`` (positive deltas only)."""
        out = {}
        for k, v in self.trace_counts.items():
            d = v - snapshot.get(k, 0)
            if d > 0:
                out[k] = d
        return out

    def refit_leaf_values(self, new_values) -> None:
        """Leaf-only mutation fast path.  ``GBDT.apply_refit_leaf_values``
        commits through here AFTER bumping the model version: a refit
        changes every tree's leaf values but NO structure, so the warm
        in-session raw pack keeps its stacked node arrays and only the
        small per-class delta matrices re-transfer — a refit tick in
        the continual runtime costs one (T_k, L) device put instead of
        a full forest re-pack, and zero re-traces (shapes unchanged).
        The refreshed packs are re-keyed to the CURRENT signature, so
        the mutation counter still gates staleness exactly as for a
        full re-pack.  The same refresh applies to the loaded
        (threshold-index) pack — its per-tree leaf-value matrix is the
        only thing a refit changes.  Everything else (contrib path
        matrices carry leaf values; range sub-packs hold stale slices)
        drops and rebuilds lazily."""
        self._range_packs.clear()
        self._packs.pop("contrib", None)
        g = self.gbdt
        # the pack must be EXACTLY one version behind (the caller just
        # bumped it): a length-only check would resurrect a pack some
        # earlier mutation left version-stale under a fresh signature
        prev_sig = (len(g.models), g._model_version - 1)

        def stack(vals, W):
            mat = np.zeros((len(vals), W), np.float32)
            for i, v in enumerate(vals):
                n = min(len(v), W)
                mat[i, :n] = np.asarray(v)[:n]
            return jnp.asarray(mat)

        for name in ("insession", "loaded"):
            hit = self._packs.get(name)
            if hit is None:
                continue
            key, pack = hit
            if key != prev_sig or len(new_values) != len(g.models):
                # stale or structurally changed: no fast path
                self._packs.pop(name, None)
                continue
            if name == "insession" and pack.get("is_linear"):
                # a refit rewrites linear leaves as constants (the host
                # trees drop their models) — the coefficient planes are
                # wholesale stale, so rebuild lazily instead of
                # refreshing deltas nothing reads
                self._packs.pop(name, None)
                continue
            # refresh OUT OF PLACE and install with one reference
            # assignment: a concurrent predict grabs the pack once per
            # call, so it sees all-old or all-new leaf values — never
            # class 0 post-refit paired with class 1 pre-refit
            K = pack["K"]
            fresh = dict(pack)
            fresh["per_k"] = list(pack["per_k"])
            for k in range(K):
                vals = new_values[k::K]
                if name == "insession":
                    pk = dict(pack["per_k"][k])
                    # keep the pack's leaf dtype (a bf16 quantized
                    # plane refreshed as f32 would change shapes/
                    # dtypes and re-trace)
                    pk["deltas"] = stack(
                        vals, int(pk["deltas"].shape[1])).astype(
                            pk["deltas"].dtype)
                    fresh["per_k"][k] = pk
                else:
                    node, lv = pack["per_k"][k]
                    fresh["per_k"][k] = (node, stack(vals,
                                                     int(lv.shape[1])))
            self._packs[name] = (self._sig(), fresh)

    # -- kernel selection (predict_kernel = auto | layered | loop) ------
    def _kernel_for(self, pack) -> str:
        """Which traversal kernel serves this pack: the layered dense
        path (ops/forest_tensor.py — fixed trip count, quantized
        planes) or the stacked while-loop oracle (ops/predict.py).
        ``auto`` prefers layered whenever the pack could build planes
        (it falls back for over-deep or overflowing forests); ``loop``
        forces the oracle; ``layered`` forces the dense path and warns
        once when the pack cannot take it."""
        choice = str(getattr(self.gbdt.config, "predict_kernel",
                             "auto") or "auto")
        if choice not in ("auto", "layered", "loop"):
            raise LightGBMError(
                f"predict_kernel={choice!r} must be one of "
                "auto | layered | loop")
        if choice == "loop":
            return "loop"
        if pack.get("layers_depth") is not None:
            return "layered"
        if choice == "layered" and not getattr(self, "_warned_layered",
                                               False):
            self._warned_layered = True
            log.warning(
                "predict_kernel=layered: this forest cannot take the "
                "layered path (depth > %d or bin values overflow the "
                "quantized planes); serving from the loop oracle",
                forest_tensor.MAX_UNROLL_DEPTH)
        return "loop"

    # -- jitted predictors (one per kind; jit caches per shape) ---------
    def _fn(self, kind: str):
        if kind in self._fns:
            return self._fns[kind]
        eng = self
        static = ()

        if kind == "raw":
            def f(nodes, deltas, mask, binned):
                eng._count_trace("raw", binned.shape[0])
                leaves = jax.vmap(
                    lambda nd: predict_leaf_binned(binned, nd))(nodes)
                vals = jax.vmap(jnp.take)(deltas, leaves)      # (T, n)
                return jnp.sum(vals * mask[:, None], axis=0)
        elif kind == "raw_layered":
            # same (kind, bucket) trace label as the loop path: the
            # compile-count pins are kernel-agnostic
            def f(layers, deltas, mask, binned, max_depth):
                eng._count_trace("raw", binned.shape[0])
                leaves = forest_tensor.predict_leaf_layered(
                    binned, layers, max_depth)
                return forest_tensor.raw_from_leaves(deltas, leaves,
                                                     mask)
            static = ("max_depth",)
        elif kind == "raw_linear":
            # piece-wise linear forests: same traversal, then the
            # coefficient-plane FMA over the caller's raw rows.  Trace
            # label stays "raw" — the per-(kind, bucket) compile-count
            # pins are representation-agnostic, like the layered path.
            def f(nodes, linear, mask, binned, raw_aug):
                eng._count_trace("raw", binned.shape[0])
                leaves = jax.vmap(
                    lambda nd: predict_leaf_binned(binned, nd))(nodes)
                return forest_tensor.linear_from_leaves(
                    raw_aug, leaves, linear["const"], linear["coeff"],
                    linear["fid"], linear["fallback"], mask)
        elif kind == "raw_linear_layered":
            def f(layers, linear, mask, binned, raw_aug, max_depth):
                eng._count_trace("raw", binned.shape[0])
                leaves = forest_tensor.predict_leaf_layered(
                    binned, layers, max_depth)
                return forest_tensor.linear_from_leaves(
                    raw_aug, leaves, linear["const"], linear["coeff"],
                    linear["fid"], linear["fallback"], mask)
            static = ("max_depth",)
        elif kind == "leaf":
            def f(nodes, binned):
                eng._count_trace("leaf", binned.shape[0])
                return jax.vmap(
                    lambda nd: predict_leaf_binned(binned, nd))(nodes)
        elif kind == "leaf_layered":
            def f(layers, binned, max_depth):
                eng._count_trace("leaf", binned.shape[0])
                return forest_tensor.predict_leaf_layered(
                    binned, layers, max_depth)
            static = ("max_depth",)
        elif kind.startswith("contrib"):
            def f(nodes, paths, mask, tq, om, col_iota, binned,
                  _kind=kind):
                eng._count_trace(_kind, binned.shape[0])
                return tree_shap_stacked(binned, nodes, paths, mask,
                                         tq, om, col_iota.shape[0])
        elif kind == "raw_loaded":
            def f(node, lv, mask, packed_vals):
                eng._count_trace("raw_loaded", packed_vals.shape[1])
                leaves = jax.vmap(
                    lambda nd: predict_leaf_thridx(packed_vals, nd))(node)
                vals = jax.vmap(jnp.take)(lv, leaves)
                return jnp.sum(vals * mask[:, None], axis=0)
        elif kind == "leaf_loaded":
            def f(node, packed_vals):
                eng._count_trace("leaf_loaded", packed_vals.shape[1])
                return jax.vmap(
                    lambda nd: predict_leaf_thridx(packed_vals, nd))(node)
        else:
            raise ValueError(kind)
        self._fns[kind] = jax.jit(f, static_argnames=static) \
            if static else jax.jit(f)
        return self._fns[kind]

    def _run_raw(self, sub, mask, b, raw=None) -> np.ndarray:
        """One bucketed raw-score dispatch per class forest, through
        whichever kernel ``predict_kernel`` selects (``sub`` is a full
        pack or a per-range sub-pack; both carry ``layers_depth``).
        ``raw`` is the (bucket, F+1) sentinel-augmented raw chunk that
        linear packs apply their coefficient planes to."""
        bd = jnp.asarray(b)
        layered = self._kernel_for(sub) == "layered"
        if sub.get("is_linear"):
            rd = jnp.asarray(raw)
            if layered:
                fn = self._fn("raw_linear_layered")
                d = sub["layers_depth"]
                return np.stack(
                    [np.asarray(fn(pk["layers"], pk["linear"], mask,
                                   bd, rd, max_depth=d))
                     for pk in sub["per_k"]], axis=1)
            fn = self._fn("raw_linear")
            return np.stack(
                [np.asarray(fn(pk["nodes"], pk["linear"], mask, bd, rd))
                 for pk in sub["per_k"]], axis=1)
        if layered:
            fn = self._fn("raw_layered")
            d = sub["layers_depth"]
            return np.stack(
                [np.asarray(fn(pk["layers"], pk["deltas"], mask, bd,
                               max_depth=d))
                 for pk in sub["per_k"]], axis=1)
        fn = self._fn("raw")
        return np.stack(
            [np.asarray(fn(pk["nodes"], pk["deltas"], mask, bd))
             for pk in sub["per_k"]], axis=1)

    # -- bucketed execution over row chunks -----------------------------
    def _chunks(self, n: int, max_bucket: Optional[int] = None):
        """(start, stop, bucket) spans covering [0, n)."""
        mb = max_bucket or self.MAX_BUCKET
        out = []
        pos = 0
        while pos < n:
            take = min(n - pos, mb)
            out.append((pos, pos + take, bucket_rows(
                take, self.MIN_BUCKET, mb)))
            pos += take
        return out

    def _skew_monitor(self):
        """The skew monitor for this model, built lazily the first time
        health is enabled AND the model carries a reference profile +
        training mappers; False caches "can't" so the eligibility scan
        never repeats on the hot path."""
        if self._skew is None:
            g = self.gbdt
            prof = getattr(g, "health_profile", None)
            ds = g.train_data
            if (prof is None or ds is None
                    or getattr(ds, "groups", None) is None):
                self._skew = False
            else:
                self._skew = obs_health.SkewMonitor.from_dataset(
                    prof, ds, g.config)
        return self._skew or None

    def _run_bucketed(self, kind: str, rows: np.ndarray, run, out_cols,
                      dtype=np.float64, max_bucket: Optional[int] = None,
                      observe: bool = True, aux: Optional[np.ndarray] = None):
        """Pad ``rows`` (n, G) to buckets and collect ``run(padded)``
        slices into an (n, out_cols) host array.  ``aux`` is an optional
        second row-aligned matrix (the raw rows a linear pack's FMA
        reads) chunked and zero-padded in lockstep; when given, ``run``
        is called as ``run(chunk, aux_chunk)``."""
        n = rows.shape[0]
        # training<->serving skew digests: for bin-space kinds the rows
        # ARE the packed bin matrix, already host-resident — one
        # vectorized bincount per chunk folds them into the rolling
        # per-bucket digest (obs/health.py).  health=off costs one
        # attribute load + compare.  ``observe=False`` opts a caller
        # out (the early-stop loop re-runs the same rows per block with
        # PARTIAL sums — double-counted digests and part-sum margins
        # would poison the distributions).
        mon = None
        if observe and obs_health.enabled() \
                and kind in ("raw", "leaf", "contrib"):
            mon = self._skew_monitor()
        out = np.zeros((n, out_cols), dtype=dtype)
        for start, stop, bucket in self._chunks(n, max_bucket):
            chunk = rows[start:stop]
            if mon is not None:
                mon.observe_binned(chunk, bucket=bucket)
            if bucket > chunk.shape[0]:
                pad = np.zeros((bucket - chunk.shape[0],) + chunk.shape[1:],
                               dtype=chunk.dtype)
                chunk = np.concatenate([chunk, pad], axis=0)
            args = (chunk,)
            if aux is not None:
                a = aux[start:stop]
                if bucket > a.shape[0]:
                    # zero padding (never NaN): padded rows pass the
                    # FMA's NaN test cheaply and are sliced away below
                    a = np.concatenate(
                        [a, np.zeros((bucket - a.shape[0],)
                                     + a.shape[1:], dtype=a.dtype)],
                        axis=0)
                args = (chunk, a)
            self._count_call(kind, bucket)
            # per-(kind, bucket) latency histogram: run() materializes
            # its result to the host, so the span measures the real
            # round trip — no extra sync is added (off mode skips even
            # the name formatting)
            with (obs.span(f"serve.{kind}@{bucket}")
                  if obs.enabled() else obs.NULL):
                out[start:stop] = run(*args)[:stop - start]
        if mon is not None and kind == "raw":
            mon.observe_margins(out)
        return out

    # ------------------------------------------------------------------
    # In-session forests (bin-space traversal over the training mappers)
    # ------------------------------------------------------------------
    def _insession_eligible(self) -> bool:
        # linear-leaf forests are served too: traversal is unchanged and
        # the per-leaf models ride coefficient planes applied by one FMA
        # over the caller's raw rows (see _insession_pack), so the old
        # linear_tree exclusion is gone.  SHAP and early-stop for linear
        # models still answer from the host paths (their guards below).
        g = self.gbdt
        return not (g.train_data is None
                    or getattr(g.train_data, "bin_mappers", None) is None
                    or not g.models
                    or any(d is None for d in g.device_trees))

    def _insession_pack(self):
        """Stack the WHOLE forest's node arrays per class: one host
        gather, one device transfer, any (start, end) range afterwards
        is a mask."""
        g = self.gbdt
        if not self._insession_eligible():
            return None
        K = g.num_tree_per_iteration
        has_cat = any(d.get("has_cat_split", "is_cat" in d["nodes"])
                      for d in g.device_trees)
        if has_cat and not g._cat_sentinel_ok():
            return None
        # stack the per-tree node arrays on the HOST with ONE device_get
        # (per-tree jnp.stack dispatches hundreds of tiny device ops)
        host = jax.device_get([(d["nodes"], d["leaf_value"])
                               for d in g.device_trees])
        bf16 = bool(getattr(g.config, "predict_bf16_leaves", False))
        # predict_kernel=loop forces the oracle: skip building (and
        # uploading) layered planes the selected kernel can never read
        # — they cost ~45% extra resident pack bytes per model.  A
        # later knob flip to layered/auto takes effect at the next
        # pack build (invalidate/update), matching how the pack
        # already binds other config at build time.
        want_layers = str(getattr(g.config, "predict_kernel", "auto")
                          or "auto") != "loop"
        # piece-wise linear forests (linear_tree, both refit and
        # leafwise_gain): the device traversal is identical, the leaf
        # VALUES become per-leaf FMAs over the caller's raw rows.  The
        # coefficient planes come from the HOST trees (leaf_const /
        # leaf_coeff / leaf_features — host and device leaf ids match,
        # the same contract refit_leaf_values relies on): const (T, L),
        # coeff/fid (T, L, J) with unused slots pointing fid at the
        # appended all-zero sentinel column of the raw matrix, and
        # fallback (T, L) = leaf_value for NaN rows.  ONE global J
        # across classes keeps uniform shapes (one trace per bucket).
        is_linear = any(t.is_linear for t in g.models)
        J = 1
        if is_linear:
            J = max([1] + [len(f) for t in g.models
                           for f in (t.leaf_features or [])])
        fid_sentinel = g.max_feature_idx + 1
        per_k = []
        depth = 0
        for k in range(K):
            hk = host[k::K]
            host_stacked = {name: np.stack([h[0][name] for h in hk])
                            for name in hk[0][0]}
            nodes = jax.tree.map(jnp.asarray, dict(host_stacked))
            deltas_np = np.stack([h[1] for h in hk])
            deltas = jnp.asarray(deltas_np)
            if bf16:
                # quantized leaf plane: half the gather traffic;
                # accumulation stays f32 (ops/forest_tensor.py
                # raw_from_leaves) so only the leaf representation
                # loses precision.  Opt-in — the f32 default keeps
                # bit-parity with the loop oracle.
                deltas = deltas.astype(jnp.bfloat16)
            layers = (forest_tensor.pack_layered(host_stacked)
                      if want_layers else None)
            if layers is not None:
                depth = max(depth, layers.pop("max_depth"))
            linear = None
            if is_linear:
                trees = g.models[k::K]
                W = deltas_np.shape[1]
                const = np.zeros((len(trees), W), np.float32)
                coeffp = np.zeros((len(trees), W, J), np.float32)
                fidp = np.full((len(trees), W, J), fid_sentinel,
                               np.int32)
                fall = np.zeros((len(trees), W), np.float32)
                for i, t in enumerate(trees):
                    lv = np.asarray(t.leaf_value, np.float64)
                    m = min(len(lv), W)
                    fall[i, :m] = lv[:m]
                    if not t.is_linear:
                        const[i, :m] = lv[:m]
                        continue
                    lc = np.asarray(t.leaf_const, np.float64)
                    const[i, :min(len(lc), W)] = lc[:W]
                    for lf in range(min(len(t.leaf_features), W)):
                        fs = t.leaf_features[lf]
                        if fs:
                            d = len(fs)
                            coeffp[i, lf, :d] = t.leaf_coeff[lf]
                            fidp[i, lf, :d] = fs
                linear = {"const": jnp.asarray(const),
                          "coeff": jnp.asarray(coeffp),
                          "fid": jnp.asarray(fidp),
                          "fallback": jnp.asarray(fall)}
            per_k.append({"nodes": nodes, "deltas": deltas,
                          "layers": layers, "linear": linear})
        layered_ok = all(pk["layers"] is not None for pk in per_k)
        return {"per_k": per_k, "has_cat": has_cat, "K": K,
                "T_k": len(g.models) // K,
                "is_linear": is_linear,
                "num_raw_cols": fid_sentinel + 1,
                # ONE forest-wide unroll depth (max over classes):
                # per-class depths would compile one program per
                # distinct depth and break the pinned one-trace-per-
                # (kind, bucket) counts; extra levels are settled-row
                # no-ops
                "layers_depth": depth if layered_ok else None}

    def _bin(self, data: np.ndarray, has_cat: bool):
        try:
            return self.gbdt.train_data.bin_matrix(
                np.asarray(data), cat_oov_sentinel=has_cat)
        except Exception:
            return None

    def _tree_mask(self, T_k: int, start: int, end: int) -> jnp.ndarray:
        m = np.zeros(T_k, dtype=np.float32)
        m[start:end] = 1.0
        return jnp.asarray(m)

    # -- per-range sub-packs --------------------------------------------
    def _range_sub(self, name: str, pack, start: int, end: int, slice_k):
        """A sub-pack holding ONLY trees [start, end) of ``pack`` so a
        ``start/num_iteration`` slice traverses its own trees instead of
        the whole forest under a mask (a 100-of-1000-trees slice used to
        pay the full 1000-tree traversal — the PERF.md round-7 known
        trade-off).  Sub-packs live in a bounded LRU (``RANGE_CACHE``
        entries, stale model versions age out); the device slices cost
        one gather each and one extra trace per distinct slice LENGTH
        (the jit cache keys on the stacked tree-array shapes, so two
        different same-length ranges share a trace)."""
        T_k = pack["T_k"]
        start, end = max(start, 0), min(end, T_k)
        if start == 0 and end == T_k:
            return pack
        key = (name, self._sig(), start, end)
        hit = self._range_packs.get(key)
        if hit is None:
            hit = dict(pack)
            hit["per_k"] = [slice_k(pk, start, end)
                            for pk in pack["per_k"]]
            hit["T_k"] = end - start
            self._range_packs[key] = hit
            while len(self._range_packs) > self.RANGE_CACHE:
                self._range_packs.popitem(last=False)
        else:
            self._range_packs.move_to_end(key)
        return hit

    @staticmethod
    def _slice_insession(pk, start: int, end: int):
        return {"nodes": jax.tree.map(lambda a: a[start:end],
                                      pk["nodes"]),
                "deltas": pk["deltas"][start:end],
                "layers": (forest_tensor.slice_layered(
                    pk["layers"], start, end)
                    if pk.get("layers") is not None else None),
                "linear": ({n: a[start:end]
                            for n, a in pk["linear"].items()}
                           if pk.get("linear") is not None else None)}

    @staticmethod
    def _slice_loaded(pk, start: int, end: int):
        node, lv = pk
        return (jax.tree.map(lambda a: a[start:end], node),
                lv[start:end])

    def _ready_insession(self, data, start_iteration: int, end_iter: int,
                         min_rows: int, warm_name: str = "insession"):
        """Shared in-session prologue: range guard, eligibility,
        cold-row gating, pack fetch, row binning.  Returns
        (n, pack, binned) or None.

        Note a deliberate scope decision (vs the pre-engine code):
        eligibility is whole-model, so continued-training boosters
        whose loaded head has no device arrays always use the host
        paths.  Sliced ranges are served from per-range sub-packs (see
        ``_range_sub``) so traversal cost scales with the slice; only
        early-stop keeps full-forest masks (its per-block ranges would
        churn the bounded cache)."""
        if end_iter <= start_iteration or not self._insession_eligible():
            return None
        n = np.asarray(data).shape[0]
        if n < min_rows and not self._warm(warm_name):
            return None
        pack = self._pack("insession", self._insession_pack)
        if pack is None:
            return None
        binned = self._bin(data, pack["has_cat"])
        if binned is None:
            return None
        return n, pack, binned

    def raw_insession(self, data: np.ndarray, start_iteration: int,
                      end_iter: int) -> Optional[np.ndarray]:
        """(n, K) raw-score sums over iterations [start, end), or None
        when the device can't serve this model."""
        g = self.gbdt
        ready = self._ready_insession(data, start_iteration, end_iter,
                                      self.COLD_MIN_ROWS)
        if ready is None:
            return None
        n, pack, binned = ready
        K = pack["K"]
        sub = self._range_sub("insession", pack, start_iteration,
                              end_iter, self._slice_insession)
        mask = self._tree_mask(sub["T_k"], 0, sub["T_k"])
        aux = None
        if pack.get("is_linear"):
            # sentinel-augmented raw rows for the coefficient-plane FMA
            # (ops/predict.py linear_leaf_values): unused fid slots
            # gather the appended zero column
            F = pack["num_raw_cols"] - 1
            raw = np.asarray(data, dtype=np.float32)
            aux = np.concatenate(
                [raw[:, :F], np.zeros((n, 1), np.float32)], axis=1)

        def run(b, r=None):
            # one device put per chunk; the K class forests share it
            return self._run_raw(sub, mask, b, raw=r)

        out = self._run_bucketed("raw", binned, run, K, aux=aux)
        # boost-from-average is folded into the first HOST tree only;
        # the device deltas exclude it — EXCEPT linear packs, whose
        # planes come from the host trees and so already carry it
        if not pack.get("is_linear"):
            for k in range(K):
                if (start_iteration == 0
                        and abs(g.init_scores[k]) > K_EPSILON):
                    out[:, k] += g.init_scores[k]
        return out

    def leaves_insession(self, data: np.ndarray, start_iteration: int,
                         end_iter: int) -> Optional[np.ndarray]:
        """(n, num_sliced_trees) leaf indices, model order, or None."""
        ready = self._ready_insession(data, start_iteration, end_iter,
                                      self.COLD_MIN_ROWS)
        if ready is None:
            return None
        n, pack, binned = ready
        K = pack["K"]
        sub = self._range_sub("insession", pack, start_iteration,
                              end_iter, self._slice_insession)
        lo = start_iteration if sub is pack else 0
        layered = self._kernel_for(sub) == "layered"
        fn = self._fn("leaf_layered" if layered else "leaf")
        width = (end_iter - start_iteration) * K

        def run(b):
            bd = jnp.asarray(b)
            cols = np.zeros((b.shape[0], width), dtype=np.int32)
            for k, pk in enumerate(sub["per_k"]):
                allk = np.asarray(
                    fn(pk["layers"], bd, max_depth=sub["layers_depth"])
                    if layered else fn(pk["nodes"], bd)
                ).T                                   # (bucket, T_sub)
                cols[:, k::K] = allk[:, lo:lo + width // K]
            return cols

        return self._run_bucketed("leaf", binned, run, width,
                                  dtype=np.int32)

    # -- device TreeSHAP ------------------------------------------------
    def _contrib_pack(self):
        g = self.gbdt
        if any(t.is_linear for t in g.models):
            # TreeSHAP over linear leaves needs the reference's
            # path-dependent linear redistribution — the host oracle
            # keeps serving those models
            return None
        base = self._pack("insession", self._insession_pack)
        if base is None:
            return None
        K = base["K"]
        num_cols = g.max_feature_idx + 2
        per_k = []
        for k in range(K):
            trees = g.models[k::K]
            mats = [tree_path_arrays(t) for t in trees]
            L = max(m["zf"].shape[0] for m in mats)
            # group trees by PADDED unique-path depth (next even value):
            # one worst-case tree must not inflate every tree's padded D
            # and quadrature count — with a 100-tree forest where late
            # trees split on noise features, global-max padding measured
            # ~8x slower than depth-grouped stacks
            groups: Dict[int, List[int]] = {}
            for i, m in enumerate(mats):
                dg = max(2, (m["zf"].shape[1] + 1) // 2 * 2)
                groups.setdefault(dg, []).append(i)
            built = []
            for dg in sorted(groups):
                idxs = groups[dg]
                M = max(mats[i]["node"].shape[2] for i in idxs)
                T = len(idxs)
                zf = np.ones((T, L, dg))
                feat = np.zeros((T, L, dg), np.int32)
                nodec = np.zeros((T, L, dg, M), np.int32)
                dirc = np.full((T, L, dg, M), 2, np.int8)
                lv = np.zeros((T, L))
                for j, i in enumerate(idxs):
                    m = mats[i]
                    l, d = m["zf"].shape
                    mm = m["node"].shape[2]
                    zf[j, :l, :d] = m["zf"]
                    feat[j, :l, :d] = m["feat"]
                    nodec[j, :l, :d, :mm] = m["node"]
                    dirc[j, :l, :d, :mm] = m["dir"]
                    lv[j, :l] = m["leaf_value"]
                tq, om = leggauss_01(dg)
                # node arrays are all-integer, so the raw pack's device
                # stacks serve SHAP unchanged; only the f64 path
                # matrices need an x64-context conversion
                with jax.enable_x64(True):
                    paths = {"zf": jnp.asarray(zf),
                             "feat": jnp.asarray(feat),
                             "node": jnp.asarray(nodec),
                             "dir": jnp.asarray(dirc),
                             "leaf_value": jnp.asarray(lv)}
                    nodes = jax.tree.map(
                        lambda a, sel=np.asarray(idxs): jnp.asarray(
                            np.asarray(a)[sel]),
                        base["per_k"][k]["nodes"])
                built.append({"dg": dg, "iters": np.asarray(idxs),
                              "paths": paths, "nodes": nodes,
                              "tq": tq, "om": om})
            # row-independent bias terms (host oracle: expected value per
            # multi-leaf tree, leaf_value for stumps)
            expected = np.asarray(
                [(float(t.leaf_value[0]) if len(t.leaf_value) else 0.0)
                 if t.num_leaves <= 1 else _expected_value(t)
                 for t in trees])
            per_k.append({"groups": built, "expected": expected})
        return {"per_k": per_k, "K": K, "T_k": len(g.models) // K,
                "num_cols": num_cols, "has_cat": base["has_cat"]}

    def contrib(self, data: np.ndarray, start_iteration: int,
                end_iter: int) -> Optional[np.ndarray]:
        """(n, K, num_features + 1) SHAP contributions with the
        expected-value bias in the last column, or None (host oracle
        serves loaded/linear/ineligible models)."""
        ready = self._ready_insession(data, start_iteration, end_iter,
                                      self.MIN_BUCKET, warm_name="contrib")
        if ready is None:
            return None
        n, _, binned = ready
        pack = self._pack("contrib", self._contrib_pack)
        if pack is None:
            return None
        K, num_cols = pack["K"], pack["num_cols"]
        col_iota = np.zeros(num_cols, np.int32)
        with jax.enable_x64(True):

            def run(b):
                bd = jnp.asarray(b)      # one device put per chunk
                blocks = []
                for pk in pack["per_k"]:
                    acc = None
                    for grp in pk["groups"]:
                        m = ((grp["iters"] >= start_iteration)
                             & (grp["iters"] < end_iter)).astype(
                                 np.float32)
                        fn = self._fn("contrib_d%d" % grp["dg"])
                        r = fn(grp["nodes"], grp["paths"],
                               jnp.asarray(m), grp["tq"], grp["om"],
                               col_iota, bd)
                        acc = r if acc is None else acc + r
                    blocks.append(np.asarray(acc))
                return np.concatenate(blocks, axis=1)  # (bucket, K*cols)

            flat = self._run_bucketed(
                "contrib", binned, run, K * num_cols,
                max_bucket=self.CONTRIB_MAX_BUCKET)
        out = flat.reshape(n, K, num_cols)
        for k, pk in enumerate(pack["per_k"]):
            out[:, k, -1] += float(
                pk["expected"][start_iteration:end_iter].sum())
        return out

    # -- device early stopping ------------------------------------------
    def raw_early_stop(self, data: np.ndarray, start_iteration: int,
                       end_iter: int, freq: int,
                       margin: float) -> Optional[np.ndarray]:
        """Block-masked device accumulation replicating the host
        early-stop loop (reference: prediction_early_stop.cpp): margins
        are re-evaluated every ``freq`` iterations and settled rows stop
        traversing — on device, by shrinking the active-row bucket."""
        g = self.gbdt
        if freq <= 0:
            return None
        ready = self._ready_insession(data, start_iteration, end_iter,
                                      self.COLD_MIN_ROWS)
        if ready is None:
            return None
        n, pack, binned = ready
        if pack.get("is_linear"):
            # the block loop re-dispatches shrinking row subsets with
            # full-forest masks; threading aligned raw-row subsets
            # through it buys nothing (early stop is a margin check,
            # not a hot serving path) — host loop serves linear models
            return None
        K = pack["K"]
        out = np.zeros((n, K), dtype=np.float64)
        # boost-from-average is folded into the first HOST tree, so the
        # host loop's margins include it from iteration 0 — seed it
        # BEFORE the blocks or rows settle at different margins
        if start_iteration == 0:
            for k in range(K):
                if abs(g.init_scores[k]) > K_EPSILON:
                    out[:, k] += g.init_scores[k]
        active = np.arange(n)
        for block in range(start_iteration, end_iter, freq):
            if block > start_iteration:
                if K == 1:
                    m = np.abs(out[active, 0])
                else:
                    part = np.partition(out[active], K - 2, axis=1)
                    m = part[:, K - 1] - part[:, K - 2]
                active = active[m < margin]
                if not len(active):
                    break
            mask = self._tree_mask(pack["T_k"], block,
                                   min(block + freq, end_iter))
            sub = binned[active]

            def run(b, mask=mask):
                return self._run_raw(pack, mask, b)

            out[active] += self._run_bucketed("raw", sub, run, K,
                                              observe=False)
        return out

    # ------------------------------------------------------------------
    # Loaded forests (real thresholds -> exact threshold-index space)
    # ------------------------------------------------------------------
    def _loaded_pack(self):
        """Pack a LOADED model (no bin mappers): per-feature threshold
        tables + per-tree node arrays in threshold-index space (see
        ops/predict.py predict_leaf_thridx)."""
        g = self.gbdt
        if not g.models:
            return None
        trees = g.models
        # loaded linear models stay host-served: in-session linear packs
        # get their raw-row alignment from the training mappers, which a
        # loaded model doesn't carry (threshold-index space only)
        if any(t.is_linear or
               (len(t.decision_type) and
                (np.asarray(t.decision_type) & K_CATEGORICAL_MASK).any())
               for t in trees):
            return None
        K = g.num_tree_per_iteration
        feat_thr: Dict[int, set] = {}
        for t in trees:
            for f, thr in zip(np.asarray(t.split_feature),
                              np.asarray(t.threshold)):
                feat_thr.setdefault(int(f), set()).add(float(thr))
        feats = sorted(feat_thr)
        enum = {f: i for i, f in enumerate(feats)}
        thr_list = [np.asarray(sorted(feat_thr[f]), np.float64)
                    for f in feats]
        b0 = np.asarray([int(np.searchsorted(tl, 0.0, side="left"))
                         for tl in thr_list], np.int32)
        nmax = max(max((len(t.split_feature) for t in trees),
                       default=1), 1)
        per_k = []
        for k in range(K):
            ts = trees[k::K]
            T = len(ts)
            arrs = {name: np.zeros((T, nmax), np.int32)
                    for name in ("col", "kidx", "default_left",
                                 "mtype", "left", "right")}
            arrs["left"][:] = -1
            arrs["right"][:] = -1
            nn = np.zeros((T,), np.int32)
            lv = np.zeros((T, nmax + 1), np.float32)
            for ti, t in enumerate(ts):
                m = len(t.split_feature)
                nn[ti] = m
                lv[ti, :len(t.leaf_value)] = t.leaf_value
                if m == 0:
                    if len(t.leaf_value):
                        lv[ti, 0] = t.leaf_value[0]
                    continue
                dt = np.asarray(t.decision_type).astype(np.int32)
                arrs["col"][ti, :m] = [enum[int(f)]
                                       for f in t.split_feature]
                arrs["kidx"][ti, :m] = [
                    int(np.searchsorted(thr_list[enum[int(f)]],
                                        float(v), side="left"))
                    for f, v in zip(t.split_feature, t.threshold)]
                arrs["default_left"][ti, :m] = (dt >> 1) & 1
                arrs["mtype"][ti, :m] = (dt >> 2) & 3
                arrs["left"][ti, :m] = t.left_child
                arrs["right"][ti, :m] = t.right_child
            node = {n_: jnp.asarray(a) for n_, a in arrs.items()}
            node["num_nodes"] = jnp.asarray(nn)
            node["b0"] = jnp.broadcast_to(jnp.asarray(b0),
                                          (T, len(feats)))
            per_k.append((node, jnp.asarray(lv)))
        return {"feats": feats, "thr_list": thr_list, "per_k": per_k,
                "K": K, "T_k": len(trees) // K}

    def _pack_thridx_rows(self, data: np.ndarray, pack) -> np.ndarray:
        """(n, Fu) packed threshold-index rows: b*4 + nan*2 + zeroish."""
        data = np.asarray(data, dtype=np.float64)
        feats, thr_list = pack["feats"], pack["thr_list"]
        packed = np.zeros((data.shape[0], max(len(feats), 1)), np.int32)
        for i, f in enumerate(feats):
            v = data[:, f]
            nan = np.isnan(v)
            fv = np.where(nan, 0.0, v)
            b = np.searchsorted(thr_list[i], v, side="left")
            packed[:, i] = (b.astype(np.int64) * 4 + nan * 2 +
                            (np.abs(fv) <= 1e-35)).astype(np.int32)
        return packed

    def raw_loaded(self, data: np.ndarray, start_iteration: int,
                   end_iter: int) -> Optional[np.ndarray]:
        if end_iter <= start_iteration:
            return None
        n = np.asarray(data).shape[0]
        if n < self.COLD_MIN_ROWS and not self._warm("loaded"):
            return None
        pack = self._pack("loaded", self._loaded_pack)
        if pack is None:
            return None
        K = pack["K"]
        sub = self._range_sub("loaded", pack, start_iteration, end_iter,
                              self._slice_loaded)
        mask = self._tree_mask(sub["T_k"], 0, sub["T_k"])
        rows = self._pack_thridx_rows(data, pack)
        fn = self._fn("raw_loaded")

        def run(b):
            pv = jnp.asarray(b).T        # one device put per chunk
            return np.stack([np.asarray(fn(node, lv, mask, pv))
                             for node, lv in sub["per_k"]], axis=1)

        return self._run_bucketed("raw_loaded", rows, run, K)

    def leaves_loaded(self, data: np.ndarray, start_iteration: int,
                      end_iter: int) -> Optional[np.ndarray]:
        n = np.asarray(data).shape[0]
        if end_iter <= start_iteration:
            return None
        if n < self.COLD_MIN_ROWS and not self._warm("loaded"):
            return None
        pack = self._pack("loaded", self._loaded_pack)
        if pack is None:
            return None
        K = pack["K"]
        sub = self._range_sub("loaded", pack, start_iteration, end_iter,
                              self._slice_loaded)
        lo = start_iteration if sub is pack else 0
        rows = self._pack_thridx_rows(data, pack)
        fn = self._fn("leaf_loaded")
        width = (end_iter - start_iteration) * K

        def run(b):
            pv = jnp.asarray(b).T
            cols = np.zeros((b.shape[0], width), dtype=np.int32)
            for k, (node, _) in enumerate(sub["per_k"]):
                allk = np.asarray(fn(node, pv)).T     # (bucket, T_sub)
                cols[:, k::K] = allk[:, lo:lo + width // K]
            return cols

        return self._run_bucketed("leaf_loaded", rows, run, width,
                                  dtype=np.int32)
