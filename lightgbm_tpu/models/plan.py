"""Which split-step program a tree learner runs, decided in one place.

``resolve`` maps a ``PlanFacts`` record (plain Python values: the backend's
name, the dataset's shape, which tree features are on, and the ``tpu_*``
option values) to a ``SplitPlan``: one field per decision, plus ``why``,
which holds for every path NOT taken the conditions that excluded it.
Nothing here touches an array, a ``Dataset``, a ``Config`` or JAX, so the
plan of any shape can be read without building a learner:

    >>> from lightgbm_tpu.config import Config
    >>> from lightgbm_tpu.models import plan
    >>> options = {k: getattr(Config({}), k) for k in plan.OPTION_FIELDS}
    >>> p = plan.resolve(plan.PlanFacts(
    ...     backend="tpu", rows=42_000_000, F=28, G=28, B=255,
    ...     num_leaves=255, **options))
    >>> p.mega, p.frontier_k, p.why["mega"]

``SerialTreeLearner.__init__`` gathers the facts, calls ``resolve`` once,
keeps the result as ``learner.plan`` and builds only the arrays that plan
needs.  A kernel named in the plan that cannot compile raises from the
first build: it is never swapped for another.  So the plan names no
kernel that cannot be built at the shape: every Pallas kernel has a pure
``vmem_bytes`` beside it (ops/*_pallas.py), and a kernel whose VMEM at
``(G, B, row_chunk)`` is over ``ops.VMEM_LIMIT_BYTES`` is excluded here,
with the bytes in ``why``.  The partition and histogram kernels tile over
the width, so theirs do not grow with it (``pass_rows``).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, List, Tuple

from ..ops import (VMEM_LIMIT_BYTES, chunkpolicy, hist_state_pallas,
                   histogram_pallas, partition_pallas, split_megakernel_pallas,
                   split_pallas)

# the f32 count cumsum of the op-packed fast search is exact below this
FAST_SEARCH_MAX_ROWS = 1 << 24

# What ``tpu_frontier_k=auto`` says in ``why``.  auto is the one-leaf body
# (K=1) on every backend and shape: on the v5e the batched body (K=4) was
# ahead of it at no shape measured: 42M x 28 at 255 and 63 bins, 10.5M x
# 28 under the mega plan, 16.8M x 128, 1M x 256 to 512, 600k x 2000 (PERF.md
# section 6, PRs 27, 35 and 36: 0.5306 / 0.5105 and 0.4342 / 0.4134 s an
# iteration in the two one-chip HIGGS cells, 1.4966 / 1.4883 under the mega
# plan); its undo snapshot insured an undo that ran in 0 of 15 trees, and
# its step compiles in 32 s at the cells' shape where K=1's takes 21.
AUTO_FRONTIER_K = ("tpu_frontier_k=auto: the one-leaf body was no slower than "
                   "the batched one at any shape measured on the v5e "
                   "(PERF_LEDGER.jsonl, PR 36)")

# For an explicit tpu_frontier_k > 1 alone.  The batched body reads its K
# parents by one gather over the (L + K, G, B, 2) histogram state and
# writes their children by one scatter; the K=1 body slices one slot and
# updates two in place.  On the v5e with this gate lifted (PR 35, PERF.md
# section 6; s/iter K=4 / K=1 at 255 bins and leaves, one run each): 16.8M
# x 128 features (a state of 68 MB, the general search) 0.6242 / 0.6144;
# under 2^24 rows, 1M x 256 (135 MB) 0.1039 / 0.0964, x 384 (203 MB)
# 0.1499 / 0.1340, x 512 (271 MB) 0.2463 / 0.1803, 600k x 2000 (1.06 GB)
# 1.035 / 0.459.  From 500 features XLA:TPU lays the whole state out anew
# for the gather at every step (compiled for the v5e: none at 300 and
# 384), which an explicit request is refused rather than handed.
FRONTIER_STATE_MAX_BYTES = 64 << 20


@dataclass(frozen=True, kw_only=True)
class PlanFacts:
    """Everything the choice of a split-step program may depend on."""

    backend: str                  # jax.default_backend(), passed in
    interpret: bool = False       # tpu_kernel_interpret
    rows: int                     # rows this learner holds (local shard)
    # rows of every shard together, where SUMMED counts must stay exact
    # integers in f32 (None: one shard, the same as ``rows``)
    global_rows: int | None = None
    F: int                        # used features
    G: int                        # feature groups
    B: int                        # bins of the widest group
    num_leaves: int
    host_bin_dtype: str = "uint8"
    has_bins: bool = True         # a binned matrix or a device ingest
    plain_view: bool = True       # one unbundled group per feature, in order
    has_categorical: bool = False
    use_mc: bool = False          # monotone constraints on a used feature
    has_cegb: bool = False
    cegb_lazy: bool = False
    path_smooth: float = 0.0
    forced: bool = False          # forced splits loaded
    extra_trees: bool = False
    has_bynode: bool = False      # 0 < feature_fraction_bynode < 1
    feature_contri: bool = False
    interaction_constraints: bool = False
    l1: float = 0.0
    max_delta_step: float = 0.0
    linear_gain_requested: bool = False   # linear_tree_mode=leafwise_gain
    quantized: bool = False       # use_quantized_grad: integer carriers
    parallel_mode: str = "serial"
    axis_name: bool = False       # runs inside shard_map
    num_shards: int = 1
    # the option values, as config.py holds them (its defaults are the
    # only defaults: none here)
    tpu_partition_kernel: str
    tpu_megakernel: str
    tpu_frontier_k: str
    tpu_hist_state: str
    tpu_chunk_policy: str
    tpu_row_chunk: str
    tpu_pack_rowid: bool
    tpu_data_hist_sync: str


OPTION_FIELDS = tuple(f.name for f in fields(PlanFacts)
                      if f.name.startswith("tpu_"))


@dataclass(frozen=True, kw_only=True)
class SplitPlan:
    partition: str          # "pallas" | "xla"
    hist: str               # leaf histograms: "pallas" (one kernel) | "xla"
    fast_search: bool       # op-packed XLA search (else the general one)
    search: str             # "pallas" (pair kernel) | "xla"
    mega: str               # "pallas" | "xla" | "off"
    frontier_k: int
    hist_state: str         # "flat" (Pallas RMW) | "xla" ((L+1,G,B,2))
    row_chunk: int          # rows per partition/histogram chunk
    # u8 bin sublanes one pass of the partition kernel moves; the learner
    # pads its bin rows to a multiple of it (0 off the kernel)
    pass_rows: int
    chunk_adaptive: bool
    pack_rowid: bool
    scatter_groups: bool    # ReduceScatter histogram ownership
    linear_gain: bool
    # decision -> "<value taken> (<what excluded the other path>)"
    why: Dict[str, str]
    # explicit requests that could not be honoured, as warnings to log
    unmet: Tuple[str, ...] = ()

    def kernel_plan(self) -> Dict[str, Any]:
        """The printable record ``GBDT.kernel_plan`` extends."""
        return {"partition": self.partition, "hist": self.hist,
                "search": self.search,
                "hist_state": self.hist_state, "mega": self.mega,
                "frontier_k": self.frontier_k}


def _given(*pairs) -> List[str]:
    return [text for cond, text in pairs if cond]


def _said(value, reasons: List[str]) -> str:
    return f"{value} ({'; '.join(reasons)})"


def _refused(request: str, reasons: List[str], instead: str) -> str:
    return f"{request} cannot be honoured ({'; '.join(reasons)}); {instead}"


def _over_vmem(kernel: str, need: int) -> List[str]:
    """The reason that excludes ``kernel`` where it would hold ``need``
    bytes of VMEM, or none."""
    return _given((need > VMEM_LIMIT_BYTES,
                   f"{kernel} would hold {need:,} B of VMEM at this shape, "
                   f"over the {VMEM_LIMIT_BYTES:,} a kernel may take"))


def resolve(f: PlanFacts) -> SplitPlan:
    why: Dict[str, str] = {}
    unmet: List[str] = []
    serial = f.parallel_mode == "serial"
    parallel = _given(
        (not serial or f.axis_name, f"tree_learner={f.parallel_mode}"))
    global_rows = f.rows if f.global_rows is None else f.global_rows
    no_features = _given((f.F == 0, "no usable features"))
    u8_bins = _given(
        (not f.has_bins, "no binned matrix or device ingest"),
        (f.host_bin_dtype != "uint8",
         f"{f.host_bin_dtype} bins: the kernels' tiles are uint8"))

    # the base chunk: what the kernels' VMEM is reckoned at (whether the
    # XLA paths band it by leaf size is decided below)
    row_chunk, _ = chunkpolicy.resolve(
        f.tpu_chunk_policy, f.tpu_row_chunk, f.rows, f.num_leaves,
        eligible=False)

    # ---- Pallas partition kernel (ops/partition_pallas.py): it moves
    # the bins ``pass_rows`` sublanes a pass, as many as fit its VMEM ----
    pass_rows = partition_pallas.pass_rows_for(
        f.G, row_chunk, VMEM_LIMIT_BYTES)
    block = _given(
        (f.tpu_partition_kernel != "pallas",
         f"tpu_partition_kernel={f.tpu_partition_kernel}"),
        (f.backend != "tpu" and not f.interpret,
         f"backend {f.backend} without tpu_kernel_interpret"),
        (f.has_categorical,
         "categorical features: the kernel decides numerical splits only"),
        (f.cegb_lazy, "cegb_penalty_feature_lazy: the kernel does not "
                      "carry its per-row bitset"),
        (f.parallel_mode in ("feature", "voting"),
         f"tree_learner={f.parallel_mode}: "
         + ("every chip holds every row, the search is what is sharded"
            if f.parallel_mode == "feature" else
            "leaf histograms stay device-local for the vote")
         + "; the kernels run per shard under tree_learner=data only"),
    ) + no_features + u8_bins + _over_vmem(
        "lgbm_partition", partition_pallas.vmem_bytes(
            pass_rows or 32, row_chunk,
            passes=-(-max(f.G, 1) // (pass_rows or 32))))
    pallas_part = not block
    if not pallas_part:
        pass_rows = 0
    if block:
        why["partition"] = _said("xla", block)
    partition_xla = _given((not pallas_part, "partition=xla"))

    # ---- Pallas leaf histogram (ops/histogram_pallas.py): reads the
    # sublane-padded buffers the partition kernel's DMA tiling asks for,
    # and splits f32 weights into bf16 limbs ----
    block = _given(
        (not pallas_part, "partition=xla: the row buffers are not "
                          "sublane-padded for window DMAs"),
        (f.quantized, "use_quantized_grad: the integer carriers are "
                      "exact in one bf16 pass of the XLA loop"),
    ) + _over_vmem("lgbm_histogram", histogram_pallas.vmem_bytes(
        row_chunk, min(f.B, 256), f.G))
    hist = "xla" if block else "pallas"
    if block:
        why["hist"] = _said("xla", block)

    # ---- op-packed fast search ----
    not_fast = _given(
        (f.has_categorical, "categorical features"),
        (f.use_mc, "monotone constraints"),
        (f.has_cegb, "CEGB penalties"),
        (f.path_smooth > 0.0, "path_smooth > 0"),
        (global_rows >= FAST_SEARCH_MAX_ROWS,
         f"rows {global_rows:,} >= 2^24: the f32 count cumsum of the "
         "fast search is exact only below it"))
    fast = not not_fast
    if not_fast:
        why["fast_search"] = _said("off", not_fast)

    # ---- leafwise-linear gain (ops/split.py:find_best_split_linear):
    # the fast-search envelope less the refinements whose bodies
    # re-derive candidate statistics ----
    linear_gain = f.linear_gain_requested
    if linear_gain:
        block = not_fast + _given(
            (f.forced, "forced splits"),
            (bool(parallel), "parallel tree learners"),
            (f.l1 > 0.0, "lambda_l1 > 0"),
            (f.max_delta_step > 0.0, "max_delta_step > 0"),
            (f.feature_contri, "feature_contri")) + no_features
        if block:
            linear_gain = False
            why["linear_gain"] = _said("off", block)
            unmet.append(
                "linear_tree_mode=leafwise_gain is not supported with "
                + ", ".join(block)
                + "; falling back to the post-hoc refit mode")

    # ---- ReduceScatter histogram ownership (data-parallel only) ----
    scatter = False
    if f.parallel_mode == "data":
        block = _given(
            (not f.axis_name, "not inside shard_map"),
            (f.tpu_data_hist_sync != "scatter",
             f"tpu_data_hist_sync={f.tpu_data_hist_sync}"),
        ) + not_fast + _given(
            (not f.plain_view, "bundled feature groups"),
            (f.forced, "forced splits"),
            (f.num_shards <= 1, "one shard"),
            (f.F < f.num_shards,
             f"{f.F} features < {f.num_shards} shards"))
        scatter = not block
        if block:
            why["scatter_groups"] = _said(
                "off", block + ["the histogram sync is the plain psum"])

    # ---- what the pair-search kernel and the mega-kernel share: the
    # plain all-numerical fast path, whose 13-scalar split tile carries
    # neither linear child models nor per-feature refinements ----
    not_plain = not_fast + _given(
        (not f.plain_view, "bundled feature groups: the kernels read one "
                           "group per feature"),
        (f.forced, "forced splits"),
        (linear_gain, "linear_tree_mode=leafwise_gain"),
        (f.extra_trees, "extra_trees"),
        (f.feature_contri, "feature_contri"))

    # ---- Pallas pair search (ops/split_pallas.py): the flat state it
    # feeds on is updated in place from the LOCAL smaller child, so the
    # shards' histograms would never be summed ----
    block = partition_xla + not_plain + _given(
        (bool(parallel), "parallel tree learners: the histogram sync "
                         "lives on the XLA search's path"),
    ) + _over_vmem("lgbm_split_search", split_pallas.vmem_bytes(f.F, f.B))
    search = "xla" if block else "pallas"
    if block:
        why["search"] = _said("xla", block)

    # ---- split mega-kernel (ops/split_megakernel_pallas.py): "xla" is
    # the same math as plain XLA operations, on any backend ----
    mode = str(f.tpu_megakernel or "off").lower()
    block = not_plain + _given(
        (not serial, f"tree_learner={f.parallel_mode}"),
    ) + no_features + _given(
        (f.B > 256, f"{f.B} bins in a group > 256")) + u8_bins
    mega = "off"
    if mode == "xla":
        if not block:
            mega = "xla"
            why["mega"] = "xla (tpu_megakernel=xla)"
    elif mode in ("auto", "pallas"):
        block = partition_xla + block + _over_vmem(
            "lgbm_split_mega", split_megakernel_pallas.vmem_bytes(
                row_chunk, min(f.B, 256), f.G)) + _given(
            (0 < pass_rows < f.G, f"the partition moves {pass_rows} bin "
                                  "rows a pass: lgbm_split_mega moves "
                                  "them all at once"))
        if not block:
            mega = "pallas"
    elif mode == "off":
        block = ["tpu_megakernel=off"]
    else:
        block = [f"unknown tpu_megakernel={f.tpu_megakernel!r}"]
        unmet.append(f"unknown tpu_megakernel={f.tpu_megakernel!r}; "
                     "treating as off")
    if mega == "off":
        why["mega"] = _said("off", block)
        if mode in ("xla", "pallas"):
            unmet.append(_refused(f"tpu_megakernel={mode}", block,
                                  "using the current split path"))

    # ---- frontier-batched growth (K leaves a loop step): auto is the
    # one-leaf body everywhere (AUTO_FRONTIER_K); an explicit K > 1 is
    # honoured unless order-dependent machinery needs the K=1 body ----
    spec = str(f.tpu_frontier_k or "auto").strip().lower()
    if spec == "auto":
        k_req = 1
    else:
        try:
            k_req = int(spec)
        except ValueError:
            raise ValueError("tpu_frontier_k must be 'auto' or a "
                             f"positive integer, got {spec!r}")
        if k_req < 1:
            raise ValueError("tpu_frontier_k must be >= 1")
    refused: List[str] = []
    if k_req > 1:
        state_bytes = (f.num_leaves + 4) * f.G * f.B * 2 * 4
        refused = _given(
            (bool(parallel), "parallel tree learners"),
            (f.forced, "forced splits"),
            (linear_gain, "linear_tree_mode=leafwise_gain"),
            (f.use_mc, "monotone constraints"),
            (f.has_cegb, "CEGB penalties"),
            (f.extra_trees, "extra_trees"),
            (f.has_bynode, "feature_fraction_bynode"),
            (f.interaction_constraints, "interaction constraints"),
            (search == "pallas" and mega == "off",
             "search=pallas with mega=off: the batched body has the pair "
             "search only on the mega path"),
            (mega == "off" and state_bytes > FRONTIER_STATE_MAX_BYTES,
             f"a histogram state of {state_bytes:,} B, over "
             f"{FRONTIER_STATE_MAX_BYTES:,}: the K=1 body was the faster at "
             "every state that large measured, and from 500 features the "
             "batched body's gather copies the whole state every step"),
        ) + no_features
        if refused:
            unmet.append(_refused(f"tpu_frontier_k={k_req}", refused,
                                  "using 1"))
    frontier_k = 1 if refused else max(1, min(k_req, f.num_leaves - 1))
    if frontier_k == 1:
        why["frontier_k"] = _said(1, refused or [
            AUTO_FRONTIER_K if spec == "auto"
            else "tpu_frontier_k=1" if k_req == 1
            else f"num_leaves={f.num_leaves}"])

    # ---- flat histogram state + Pallas RMW (ops/hist_state_pallas.py):
    # the mega path holds no state, the batched body moves its rows
    # itself ----
    block = _given(
        (search != "pallas", "search=xla"),
        (mega != "off", f"mega={mega}: no histogram state"),
        (frontier_k > 1, f"frontier_k={frontier_k}"),
        (f.tpu_hist_state == "xla", "tpu_hist_state=xla"),
    ) + _over_vmem("lgbm_hist_state",
                   hist_state_pallas.vmem_bytes(f.G, f.B))
    hist_state = "xla" if block else "flat"
    if block:
        why["hist_state"] = _said("xla", block)

    # ---- leaf-size-adaptive chunks (ops/chunkpolicy.py): the plain XLA
    # serial paths only, the kernels keep their base grid ----
    block = parallel + _given(
        (pallas_part, "partition=pallas: the kernels keep the base "
                      "grid")) + no_features
    _, policy = chunkpolicy.resolve(
        f.tpu_chunk_policy, f.tpu_row_chunk, f.rows, f.num_leaves,
        eligible=not block)
    chunk_mode = str(f.tpu_chunk_policy or "auto").strip().lower()
    if not policy.adaptive:
        if block and chunk_mode == "adaptive":
            unmet.append(_refused("tpu_chunk_policy=adaptive", block,
                                  "using the fixed grid"))
        why["chunk_adaptive"] = _said("fixed", block or _given(
            (chunk_mode == "fixed", "tpu_chunk_policy=fixed"),
            (len(policy.sizes) < 2,
             f"row_chunk {row_chunk} has no narrower menu width"),
        ) or [f"auto: (num_leaves-1) * {row_chunk} <= rows, the average "
              "leaf fills a chunk"])

    # ---- rowid in the spare packed-bin bytes ----
    pack_rowid = False
    if f.tpu_pack_rowid:
        g32 = -(-f.G // 32) * 32
        block = partition_xla + _given(
            (g32 - f.G < 4,
             f"{f.G} groups leave {g32 - f.G} spare rows of {g32}: "
             "needs 4"),
            (pass_rows < g32, f"{g32} bin rows move {pass_rows} a pass: "
                              "the spare bytes are the last pass's alone"))
        pack_rowid = not block
        if block:
            why["pack_rowid"] = _said("off", block)

    return SplitPlan(
        partition="pallas" if pallas_part else "xla", hist=hist,
        fast_search=fast,
        search=search, mega=mega, frontier_k=frontier_k,
        hist_state=hist_state, row_chunk=row_chunk, pass_rows=pass_rows,
        chunk_adaptive=policy.adaptive, pack_rowid=pack_rowid,
        scatter_groups=scatter, linear_gain=linear_gain, why=why,
        unmet=tuple(unmet))
