"""Device-vectorized dataset construction.

The host construction path (dataset.py) was a per-feature Python loop
four times over: F stable argsorts for bin finding, an O(n) Python
distinct-value scan per feature, a per-feature ``values_to_bins`` call
per chunk, and an O(F_sparse x groups x n) pairwise loop for EFB
conflict counting.  On multi-million-row inputs that rivals the (now
optimized) train loop.  Both GPU GBDT systems this repo tracks land
the same move on the accelerator side: XGBoost's GPU pipeline bins and
compresses on-device (Mitchell & Frank, arXiv:1806.11248) and
ThunderGBM builds feature-value layouts on the accelerator to feed its
kernels without a host detour (Wen et al., arXiv:1706.08359).

This module provides the vectorized replacements, each bit-identical
to the host oracle in ops/binning.py / dataset.py (asserted by
tests/test_construct_device.py):

* ``sorted_sample_columns`` — ONE column-wise sort of the whole
  (sample_cnt, F) matrix replaces F per-feature stable argsorts; the
  per-feature zero/NaN filtering becomes searchsorted index arithmetic
  on the sorted columns.
* ``find_bin_sorted`` — BinMapper construction from a pre-sorted
  column: the O(n) Python distinct-value scan becomes a vectorized
  nextafter merge, and the greedy equal-count bin search jumps
  cut-to-cut with searchsorted (O(max_bin log n)) in the no-big-bin
  case instead of walking every distinct value.  Falls back to the
  ops/binning.py reference loops whenever the fast path's
  preconditions do not hold.
* ``BatchedMapper`` — one batched values->bins mapping over ALL
  features: a padded (F, B_max) bin-bounds matrix drives a vectorized
  branchless binary search plus vectorized NaN / zero-as-missing /
  default-bin / categorical resolution.  The same code path runs on
  host (numpy) or on device (jnp).  The host path additionally keys
  most numerical columns through an exact uniform-grid table (one
  gather + ``span`` compares instead of a log2(B) branchy binary
  search per element) and bins zero-dominated columns through a
  nonzero-only shortcut — both gated so every output stays
  bit-identical to ``np.searchsorted``.
* ``conflict_matrix`` — EFB conflict counting as one nonzero-mask
  matmul (F_sparse, n) @ (n, F_sparse) instead of the host pairwise
  loop; with the reference's max_conflict_rate = 0.0 the pairwise
  counts decide the greedy coloring bit-identically to the
  union-mask loop.
* ``DeviceIngest`` — streams packed row chunks straight into the
  learner's transposed (G, N_pad) device layout with double-buffered
  host->device copies, so the full row-major host binned matrix, its
  transpose and the padded copy never materialize.

``construct_device=auto|on|off`` (config.py) selects the path; ``off``
keeps the original per-feature loops as the oracle.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import log
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, K_SPARSE_THRESHOLD,
                      K_ZERO_THRESHOLD, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinMapper, find_bin_with_predefined_bin,
                      greedy_find_bin)

# ---------------------------------------------------------------------------
# Shared row geometry (must agree with models/learner.py so a dataset-built
# device buffer can be consumed by the learner without reshaping)
# ---------------------------------------------------------------------------


def _pow2ceil(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def row_geometry(tpu_row_chunk: int, n: int) -> Tuple[int, int, int]:
    """(row_chunk, row0, n_pad) for ``n`` data rows — the learner's layout:
    [C front-pad rows][N data rows][>= 2C tail-pad rows] (see
    models/learner.py row-geometry comment for why two tail chunks)."""
    c = min(int(tpu_row_chunk), max(_pow2ceil(n), 256))
    if c & (c - 1):
        c = _pow2ceil(c)
    c = min(c, 1 << 15)
    n_pad = c + ((n + c - 1) // c + 2) * c
    return c, c, n_pad


def resolve_mode(config, is_reference: bool, is_distributed: bool
                 ) -> Tuple[bool, bool, bool]:
    """(vectorized, device_ingest, keep_host_binned) for this dataset.

    * ``off``  — the original per-feature host loops (the oracle).
    * ``auto`` — vectorized host construction everywhere; training
      datasets additionally stream into the device (G, N_pad) buffer
      (the learner consumes it), host binned is still materialized.
    * ``on``   — like auto, but the host binned matrix is NOT
      materialized for training datasets (it can be recovered from the
      device buffer on demand).
    Validation datasets (``is_reference``) and multi-process
    construction never device-ingest: their consumers want row-major
    host bins / rank-local shards.  Under ``auto`` a parallel
    ``tree_learner`` does not either: the mesh trainer cuts each device's
    block from the host matrix (parallel/trainer.py), and the ingest's
    master buffer would put every shard's rows on the first device.
    """
    mode = str(getattr(config, "construct_device", "auto") or "auto").lower()
    if mode not in ("auto", "on", "off"):
        log.warning("construct_device=%s unknown; using 'auto'", mode)
        mode = "auto"
    if mode == "off":
        return False, False, True
    ingest_ok = not is_reference and not is_distributed
    if mode == "on" and not ingest_ok:
        log.warning("construct_device=on ignored for %s construction; "
                    "using the vectorized host path",
                    "aligned (validation)" if is_reference
                    else "multi-process")
    if mode == "on" and ingest_ok:
        return True, True, False
    parallel = str(getattr(config, "tree_learner", "serial")) != "serial"
    return True, ingest_ok and not parallel, True


# ---------------------------------------------------------------------------
# Vectorized bin finding (stage 1: one matrix sort + index arithmetic)
# ---------------------------------------------------------------------------


def sorted_sample_columns(sample: np.ndarray, workers: int = 1
                          ) -> Dict[str, np.ndarray]:
    """ONE column-wise sort of the whole (sample_cnt, F) matrix plus the
    per-feature zero/NaN boundaries, replacing F stable argsorts.

    NaNs sort to the end of each column (numpy guarantee), so the
    per-feature "non-zero + NaN sample" the mappers consume is just two
    index ranges of the sorted column.  ``workers`` > 1 sorts column
    blocks on threads (np.sort releases the GIL; per-column results are
    unaffected by the split).
    """
    sample = np.asarray(sample, dtype=np.float64)
    ncol = sample.shape[1]
    if workers > 1 and ncol > 1:
        from concurrent.futures import ThreadPoolExecutor
        svals = np.empty_like(sample)
        blocks = [slice(b, min(b + (ncol + workers - 1) // workers,
                               ncol))
                  for b in range(0, ncol,
                                 (ncol + workers - 1) // workers)]
        with ThreadPoolExecutor(max_workers=workers) as ex:
            list(ex.map(
                lambda blk: svals.__setitem__(
                    (slice(None), blk), np.sort(sample[:, blk], axis=0)),
                blocks))
    else:
        svals = np.sort(sample, axis=0)              # one sort, all columns
    nan_cnt = np.count_nonzero(np.isnan(sample), axis=0)
    m = sample.shape[0] - nan_cnt                    # non-NaN length per col
    # abs(v) > K_ZERO_THRESHOLD keeps v < -K or v > K; on the sorted
    # column those are [0, lo) and [hi, m)
    lo = np.empty(sample.shape[1], dtype=np.int64)
    hi = np.empty(sample.shape[1], dtype=np.int64)
    for f in range(sample.shape[1]):
        col = svals[: m[f], f]
        lo[f] = np.searchsorted(col, -K_ZERO_THRESHOLD, side="left")
        hi[f] = np.searchsorted(col, K_ZERO_THRESHOLD, side="right")
    return {"sorted": svals, "nan_cnt": nan_cnt, "non_nan": m,
            "lo": lo, "hi": hi}


def _distinct_from_sorted(vals: np.ndarray, zero_cnt: int,
                          counts: Optional[np.ndarray] = None
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Distinct values + counts from an ascending non-zero non-NaN value
    array, with the implied-zero bin spliced in — the vectorized replica
    of the O(n) Python scan in BinMapper.find_bin (ops/binning.py:270).

    Merge rule (reference bin.cpp): adjacent values with
    ``b <= nextafter(a, inf)`` collapse into one distinct value keeping
    the LARGER value; a run's representative is therefore its last
    element.

    ``counts`` (optional) marks ``vals`` as an already-deduplicated
    weighted array — each entry stands for ``counts[i]`` raw
    occurrences (the sketch path, ops/sketch.py).  Identical raw values
    always share one weighted entry, so run boundaries — and therefore
    the merged distincts — match the unweighted scan bit for bit.
    """
    m = len(vals)
    if m == 0:
        return (np.asarray([0.0]), np.asarray([zero_cnt], dtype=np.int64))
    if m == 1:
        d = np.asarray([float(vals[0])])
        c = (np.asarray([1], dtype=np.int64) if counts is None
             else np.asarray([int(counts[0])], dtype=np.int64))
    else:
        merge = vals[1:] <= np.nextafter(vals[:-1], np.inf)
        ends = np.flatnonzero(np.concatenate([~merge, [True]]))
        d = vals[ends]
        starts = np.concatenate([[0], ends[:-1] + 1])
        if counts is None:
            c = (ends - starts + 1).astype(np.int64)
        else:
            csum = np.concatenate([[0], np.cumsum(counts)])
            c = (csum[ends + 1] - csum[starts]).astype(np.int64)
    # zero insertion, replicating find_bin's three sites exactly:
    #  * all-positive sample with zeros present -> leading zero bin
    #  * sign change between adjacent distincts -> zero spliced between
    #    (with zero_cnt, EVEN when zero_cnt == 0, like the reference)
    #  * all-negative sample with zeros present -> trailing zero bin
    if d[0] > 0.0:
        if zero_cnt > 0:
            d = np.concatenate([[0.0], d])
            c = np.concatenate([[zero_cnt], c])
    elif d[-1] < 0.0:
        if zero_cnt > 0:
            d = np.concatenate([d, [0.0]])
            c = np.concatenate([c, [zero_cnt]])
    else:
        pos = int(np.searchsorted(d, 0.0, side="left"))
        if 0 < pos < len(d) and d[pos - 1] < 0.0 and d[pos] > 0.0:
            d = np.concatenate([d[:pos], [0.0], d[pos:]])
            c = np.concatenate([c[:pos], [zero_cnt], c[pos:]])
    return d, c


def _double_equal_ordered(a: float, b: float) -> bool:
    return b <= math.nextafter(a, math.inf)


def _greedy_find_bin_fast(distinct: np.ndarray, counts: np.ndarray,
                          max_bin: int, total_cnt: int,
                          min_data_in_bin: int) -> List[float]:
    """greedy_find_bin (ops/binning.py:42) with the dominant case — more
    distinct values than bins, no 'big' bins — jumped cut-to-cut via
    searchsorted on the count cumsum: O(max_bin log n) instead of an
    O(n) Python walk.  Any other case delegates to the reference loop
    (bit-identity is trivially preserved there)."""
    num_distinct = len(distinct)
    assert max_bin > 0
    if num_distinct <= max_bin:
        # <= max_bin Python iterations: already cheap, reuse the oracle
        return greedy_find_bin(distinct, counts, max_bin, total_cnt,
                               min_data_in_bin)
    if min_data_in_bin > 0:
        max_bin = max(min(max_bin, total_cnt // min_data_in_bin), 1)
    mean_bin_size = total_cnt / max_bin
    # max() compares ONE scalar (np.any(counts >= float) would promote
    # the whole int64 array to f64 first)
    if len(counts) and int(counts.max()) >= mean_bin_size:
        # 'big' distinct values re-plan the running mean mid-walk in a
        # data-dependent way — take the reference loop
        return greedy_find_bin(distinct, counts, max_bin, total_cnt,
                               min_data_in_bin)
    # No big bins: every close happens at the first index i (searched,
    # not walked) where the count accumulated since the last cut
    # reaches the CURRENT mean; after each close the mean is re-derived
    # from the remaining samples and bins, exactly like the loop.
    # f64 cumsum: the cut search needle (base + mean_bin_size) is a
    # float, and searchsorted over an int64 array with a float needle
    # silently promotes THE WHOLE ARRAY to f64 on every call.  Counts
    # are exact in f64 (<= 2^53), so the comparisons are identical.
    cum = np.cumsum(counts, dtype=np.float64)
    upper_bounds: List[float] = []
    lower_bounds: List[float] = [float(distinct[0])]
    bin_cnt = 0
    rest_bin_cnt = max_bin
    base = 0                         # samples consumed before current bin
    start = 0                        # first distinct index of current bin
    while bin_cnt < max_bin - 1 and start <= num_distinct - 2:
        # first i >= start with cum[i] - base >= mean_bin_size; the loop
        # only closes at i <= num_distinct - 2
        i = int(np.searchsorted(cum[: num_distinct - 1],
                                base + mean_bin_size, side="left"))
        if i >= num_distinct - 1:
            break                    # never reaches the mean: loop ends
        if i < start:
            i = start
        upper_bounds.append(float(distinct[i]))
        lower_bounds.append(float(distinct[i + 1]))
        bin_cnt += 1
        if bin_cnt >= max_bin - 1:
            break
        rest_bin_cnt -= 1
        rest_sample_cnt = total_cnt - int(cum[i])
        mean_bin_size = (rest_sample_cnt / rest_bin_cnt
                         if rest_bin_cnt > 0 else math.inf)
        base = int(cum[i])
        start = i + 1
    bin_cnt += 1
    bin_upper: List[float] = []
    for i in range(bin_cnt - 1):
        val = math.nextafter((upper_bounds[i] + lower_bounds[i + 1]) / 2.0,
                             math.inf)
        if not bin_upper or not _double_equal_ordered(bin_upper[-1], val):
            bin_upper.append(val)
    bin_upper.append(math.inf)
    return bin_upper


def _find_bin_with_zero_as_one_bin_fast(distinct: np.ndarray,
                                        counts: np.ndarray, max_bin: int,
                                        total_sample_cnt: int,
                                        min_data_in_bin: int) -> List[float]:
    """find_bin_with_zero_as_one_bin (ops/binning.py:174) with the
    left/zero/right partition computed by searchsorted on the (sorted)
    distinct array instead of Python scans."""
    n = len(distinct)
    left_cnt = int(np.searchsorted(distinct, -K_ZERO_THRESHOLD,
                                   side="right"))
    right_start = int(np.searchsorted(distinct, K_ZERO_THRESHOLD,
                                      side="right"))
    left_cnt_data = int(counts[:left_cnt].sum())
    right_cnt_data = int(counts[right_start:].sum())
    # the reference counts zeros from the distinct list; replicate that
    # (the two agree except for NaN counts, which never reach here)
    cnt_zero = int(counts[left_cnt:right_start].sum())

    bin_upper: List[float] = []
    if left_cnt > 0 and max_bin > 1:
        left_max_bin = int(left_cnt_data
                           / max(total_sample_cnt - cnt_zero, 1)
                           * (max_bin - 1))
        left_max_bin = max(1, left_max_bin)
        bin_upper = _greedy_find_bin_fast(
            distinct[:left_cnt], counts[:left_cnt], left_max_bin,
            left_cnt_data, min_data_in_bin)
        if bin_upper:
            bin_upper[-1] = -K_ZERO_THRESHOLD
    rs = right_start if right_start < n else -1
    right_max_bin = max_bin - 1 - len(bin_upper)
    if rs >= 0 and right_max_bin > 0:
        right_bounds = _greedy_find_bin_fast(
            distinct[rs:], counts[rs:], right_max_bin, right_cnt_data,
            min_data_in_bin)
        bin_upper.append(K_ZERO_THRESHOLD)
        bin_upper.extend(right_bounds)
    else:
        bin_upper.append(math.inf)
    assert len(bin_upper) <= max_bin
    return bin_upper


def find_bin_sorted(sorted_nonzero: np.ndarray, na_cnt: int,
                    total_sample_cnt: int, max_bin: int,
                    min_data_in_bin: int = 3, min_split_data: int = 0,
                    pre_filter: bool = False, bin_type: int = BIN_NUMERICAL,
                    use_missing: bool = True, zero_as_missing: bool = False,
                    forced_upper_bounds: Optional[List[float]] = None
                    ) -> BinMapper:
    """BinMapper.find_bin (ops/binning.py:241) from an ALREADY-SORTED
    non-zero non-NaN value array — the per-feature stage of the batched
    construction.  Distinct extraction, bin counting and the greedy
    search are vectorized; every branch mirrors the oracle exactly."""
    vals = np.asarray(sorted_nonzero, dtype=np.float64)
    non_na_cnt = len(vals)
    zero_cnt = int(total_sample_cnt - non_na_cnt - na_cnt)
    distinct, counts = _distinct_from_sorted(vals, zero_cnt)
    if non_na_cnt == 0 and zero_cnt == 0:
        # find_bin still emits the zero distinct with its (zero) count
        distinct, counts = np.asarray([0.0]), np.asarray([0],
                                                         dtype=np.int64)
    return mapper_from_distinct(
        distinct, counts, na_cnt, total_sample_cnt, max_bin,
        min_data_in_bin=min_data_in_bin, min_split_data=min_split_data,
        pre_filter=pre_filter, bin_type=bin_type, use_missing=use_missing,
        zero_as_missing=zero_as_missing,
        forced_upper_bounds=forced_upper_bounds)


def mapper_from_distinct(distinct: np.ndarray, counts: np.ndarray,
                         na_cnt: int, total_sample_cnt: int, max_bin: int,
                         min_data_in_bin: int = 3, min_split_data: int = 0,
                         pre_filter: bool = False,
                         bin_type: int = BIN_NUMERICAL,
                         use_missing: bool = True,
                         zero_as_missing: bool = False,
                         forced_upper_bounds: Optional[List[float]] = None
                         ) -> BinMapper:
    """The shared distinct+counts -> BinMapper tail of the bin finder:
    bounds search, per-bin counting, the categorical most-frequent-first
    walk, pre-filtering and the default/most-freq-bin epilogue.  Both
    the exact path (``find_bin_sorted``, distincts from a full column
    sort) and the out-of-core sketch path (ops/sketch.py, distincts
    from merged cell maxes) end here, which is what makes the two
    bit-comparable."""
    bm = BinMapper()
    if not use_missing:
        bm.missing_type = MISSING_NONE
    elif zero_as_missing:
        bm.missing_type = MISSING_ZERO
    else:
        bm.missing_type = MISSING_NAN if na_cnt > 0 else MISSING_NONE
    bm.bin_type = bin_type
    bm.default_bin = 0
    distinct = np.asarray(distinct, dtype=np.float64)
    counts = np.asarray(counts, dtype=np.int64)
    bm.min_val = float(distinct[0]) if len(distinct) else 0.0
    bm.max_val = float(distinct[-1]) if len(distinct) else 0.0
    num_distinct = len(distinct)

    if bin_type == BIN_NUMERICAL:
        def bounds(mb, total):
            if forced_upper_bounds:
                return find_bin_with_predefined_bin(
                    list(distinct), list(counts), mb, total,
                    min_data_in_bin, forced_upper_bounds)
            return _find_bin_with_zero_as_one_bin_fast(
                distinct, counts, mb, total, min_data_in_bin)

        if bm.missing_type == MISSING_ZERO:
            bm.bin_upper_bound = bounds(max_bin, total_sample_cnt)
            if len(bm.bin_upper_bound) == 2:
                bm.missing_type = MISSING_NONE
        elif bm.missing_type == MISSING_NONE:
            bm.bin_upper_bound = bounds(max_bin, total_sample_cnt)
        else:
            bm.bin_upper_bound = bounds(max_bin - 1,
                                        total_sample_cnt - na_cnt)
            bm.bin_upper_bound.append(math.nan)
        bm.num_bin = len(bm.bin_upper_bound)
        # vectorized cnt_in_bin: first bin whose upper >= value, capped
        # at num_bin-1 — identical to the oracle's walking i_bin
        search = np.asarray(bm.bin_upper_bound[: bm.num_bin - 1],
                            dtype=np.float64)
        idx = np.searchsorted(search, distinct, side="left")
        cnt_in_bin = np.bincount(idx, weights=counts,
                                 minlength=bm.num_bin).astype(np.int64)
        if bm.missing_type == MISSING_NAN:
            cnt_in_bin[bm.num_bin - 1] = na_cnt
        assert bm.num_bin <= max_bin
        cnt_in_bin = list(cnt_in_bin)
    else:
        # categorical: truncate toward zero like int(); negatives fold
        # into the NaN bin with the reference's per-value warning
        ivs = distinct.astype(np.int64)
        neg = ivs < 0
        if bool(neg.any()):
            na_cnt += int(counts[neg].sum())
            for _ in range(int(neg.sum())):
                log.warning("Met negative value in categorical features, "
                            "will convert it to NaN")
        ivs, counts_i = ivs[~neg], counts[~neg].astype(np.int64)
        if len(ivs):
            # ascending distinct floats can collapse after truncation
            ends = np.flatnonzero(np.concatenate(
                [ivs[1:] != ivs[:-1], [True]]))
            starts = np.concatenate([[0], ends[:-1] + 1])
            csum = np.concatenate([[0], np.cumsum(counts_i)])
            distinct_int = ivs[ends]
            counts_int = (csum[ends + 1] - csum[starts]).astype(np.int64)
        else:
            distinct_int = np.asarray([], dtype=np.int64)
            counts_int = np.asarray([], dtype=np.int64)
        rest_cnt = total_sample_cnt - na_cnt
        bm.num_bin = 1
        cnt_in_bin = [0]
        if rest_cnt > 0 and len(distinct_int):
            order2 = np.argsort(-counts_int, kind="stable")
            counts_l = counts_int[order2]
            distinct_l = distinct_int[order2]
            cut_cnt = int(round((total_sample_cnt - na_cnt) * 0.99))
            distinct_cnt = len(distinct_l) + (1 if na_cnt > 0 else 0)
            eff_max_bin = min(distinct_cnt, max_bin)
            bm.bin_2_categorical = [-1]
            bm.categorical_2_bin = {-1: 0}
            used_cnt = 0
            cur = 0
            while cur < len(distinct_l) and (used_cnt < cut_cnt or
                                             bm.num_bin < eff_max_bin):
                if counts_l[cur] < min_data_in_bin and cur > 1:
                    break
                bm.bin_2_categorical.append(int(distinct_l[cur]))
                bm.categorical_2_bin[int(distinct_l[cur])] = bm.num_bin
                used_cnt += int(counts_l[cur])
                cnt_in_bin.append(int(counts_l[cur]))
                bm.num_bin += 1
                cur += 1
            if cur == len(distinct_l) and na_cnt == 0:
                bm.missing_type = MISSING_NONE
            else:
                bm.missing_type = MISSING_NAN
            cnt_in_bin[0] = int(total_sample_cnt - used_cnt)

    bm.is_trivial = bm.num_bin <= 1
    if not bm.is_trivial and pre_filter and min_split_data > 0:
        if bm._need_filter(cnt_in_bin, total_sample_cnt, min_split_data):
            bm.is_trivial = True
    if not bm.is_trivial:
        bm.default_bin = bm.value_to_bin(0.0)
        bm.most_freq_bin = int(np.argmax(cnt_in_bin))
        max_sparse_rate = cnt_in_bin[bm.most_freq_bin] / total_sample_cnt
        if (bm.most_freq_bin != bm.default_bin
                and max_sparse_rate < K_SPARSE_THRESHOLD):
            bm.most_freq_bin = bm.default_bin
        bm.sparse_rate = cnt_in_bin[bm.most_freq_bin] / total_sample_cnt
    else:
        bm.sparse_rate = 1.0
    return bm


# ---------------------------------------------------------------------------
# Batched values -> bins mapping (host numpy or device jnp, one code path)
# ---------------------------------------------------------------------------

_CAT_PAD = np.int64(2 ** 62)       # > any real category key
_GRID_NCELL = 8192                 # grid cells per feature (32KB table)
_GRID_MAXSPAN = 4                  # fall back to searchsorted past this


def _searchsorted_rows(bounds, vals, xp):
    """Per-row ``searchsorted(bounds[f], vals[:, f], side='left')`` as a
    branchless batched binary search: ``bounds`` (F, B) row-sorted,
    ``vals`` (n, F); returns (n, F) int32.  Identical semantics in
    numpy and jnp."""
    if xp is np:
        # host: F C-speed searchsorted calls beat the branchless form,
        # whose ~log2(B) iterations each stream several (n, F) f64
        # temporaries through memory.  side='left' == count of bounds
        # strictly below the value == the branchless result.
        out = np.empty(vals.shape, dtype=np.int32)
        for f in range(bounds.shape[0]):
            out[:, f] = np.searchsorted(bounds[f], vals[:, f],
                                        side="left")
        return out
    f_idx = xp.arange(bounds.shape[0])[None, :]
    b = bounds.shape[1]
    lo = xp.zeros(vals.shape, dtype=xp.int32)
    hi = xp.full(vals.shape, b, dtype=xp.int32)
    for _ in range(max(b - 1, 0).bit_length() + 1):
        active = lo < hi               # converged lanes must not move
        mid = (lo + hi) >> 1
        # mid == b only once lo == hi == b (converged); clamp the gather
        below = (bounds[f_idx, xp.minimum(mid, b - 1)] < vals) & active
        lo = xp.where(below, mid + 1, lo)
        hi = xp.where(active & ~below, mid, hi)
    return lo


class BatchedMapper:
    """Padded per-feature tables driving ONE vectorized mapping over all
    used features — the batched replacement for the per-feature
    ``BinMapper.values_to_bins`` loop.  ``map_chunk`` reproduces the
    per-feature results bit-identically (tests/test_construct_device.py)
    and runs through numpy on host or jnp on device."""

    def __init__(self, bin_mappers: Sequence[BinMapper],
                 used_features: Sequence[int]):
        self.used_features = list(used_features)
        F = len(self.used_features)
        self.num_cols = F
        mappers = [bin_mappers[f] for f in self.used_features]
        self.is_cat = np.asarray(
            [bm.bin_type == BIN_CATEGORICAL for bm in mappers], dtype=bool)
        self.missing_type = np.asarray(
            [bm.missing_type for bm in mappers], dtype=np.int32)
        self.num_bin = np.asarray([bm.num_bin for bm in mappers],
                                  dtype=np.int32)
        self.default_bin = np.asarray([bm.default_bin for bm in mappers],
                                      dtype=np.int32)
        # bin of a literal 0.0 value (the NaN target for MISSING_NONE)
        self.zero_bin = np.asarray(
            [0 if self.is_cat[i] else bm.value_to_bin(0.0)
             for i, bm in enumerate(mappers)], dtype=np.int32)
        # numerical search bounds: drop the NaN sentinel, pad with +inf —
        # searchsorted over the padded row equals searchsorted over the
        # oracle's bounds[:n_search-1] for every input (inf catches the
        # overflow at the same index)
        b_max = 1
        for bm in mappers:
            if bm.bin_type == BIN_NUMERICAL:
                n_search = len(bm.bin_upper_bound)
                if bm.missing_type == MISSING_NAN:
                    n_search -= 1
                b_max = max(b_max, max(n_search - 1, 0))
        self.bounds = np.full((F, b_max), np.inf, dtype=np.float64)
        for i, bm in enumerate(mappers):
            if bm.bin_type != BIN_NUMERICAL:
                continue
            n_search = len(bm.bin_upper_bound)
            if bm.missing_type == MISSING_NAN:
                n_search -= 1
            k = max(n_search - 1, 0)
            if k:
                self.bounds[i, :k] = bm.bin_upper_bound[:k]
        # true (unpadded) bound count per feature: the host path
        # searches bounds[f, :blen[f]] — identical results (inf pad
        # entries never compare below a finite value) with log2(blen)
        # probes instead of log2(b_max) for few-bin features
        self._blen = np.asarray(
            [int(np.sum(np.isfinite(self.bounds[i])))
             for i in range(F)], dtype=np.int64)
        # uniform-grid accelerator for the host per-column search: a
        # NCELL-cell grid over [b0, b_last] where cell(v) is monotone
        # in v, so with lo_tab[c] = #bounds in cells < c the exact
        # searchsorted('left') result is lo_tab[cell(v)] plus at most
        # `span` (= max bounds per cell) one-gather correction steps —
        # bounds in earlier cells are always < v, later cells never,
        # the own cell resolves by direct compares.  Features whose
        # bounds cluster past MAXSPAN per cell keep np.searchsorted.
        self._grid: list = [None] * F
        for i in range(F):
            if self.is_cat[i]:
                continue
            blen = int(self._blen[i])
            if blen < 2:
                continue
            b = self.bounds[i, :blen]
            g0, top = b[0], b[-1]
            if not (np.isfinite(g0) and np.isfinite(top)) or top <= g0:
                continue
            inv_w = _GRID_NCELL / (top - g0)
            if not np.isfinite(inv_w):
                continue
            cellb = np.clip((b - g0) * inv_w,
                            0, _GRID_NCELL - 1).astype(np.int32)
            counts = np.bincount(cellb, minlength=_GRID_NCELL)
            span = int(counts.max())
            if span > _GRID_MAXSPAN:
                continue
            lo_tab = np.zeros(_GRID_NCELL, np.int32)
            np.cumsum(counts[:-1], out=lo_tab[1:])
            self._grid[i] = (g0, inv_w, lo_tab,
                             np.append(b, np.inf), span)
        # zero-domination hint from the construction sample: the
        # count_nonzero probe feeding the sparse shortcut below only
        # runs where the sample says zeros might dominate — the gate
        # picks between two exact paths, so a stale hint costs speed,
        # never correctness
        self._try_sparse = np.asarray(
            [(not self.is_cat[i]) and bm.sparse_rate >= 0.4
             and bm.most_freq_bin == self.zero_bin[i]
             for i, bm in enumerate(mappers)], dtype=bool)
        # bins fit a byte when every feature's bin count does: the
        # feature-major host path then emits uint8 rows (4x less
        # write traffic); consumers upcast where they do arithmetic
        self._out_dtype = (np.uint8 if (self.num_bin.size == 0
                                        or int(self.num_bin.max()) <= 255)
                           else np.int32)
        # categorical tables: sorted keys padded with a huge sentinel
        self.has_cat = bool(self.is_cat.any())
        if self.has_cat:
            c_max = max((len(bm.categorical_2_bin) for bm in mappers
                         if bm.bin_type == BIN_CATEGORICAL), default=0)
            c_max = max(c_max, 1)
            self.cat_keys = np.full((F, c_max), _CAT_PAD, dtype=np.int64)
            self.cat_bins = np.zeros((F, c_max), dtype=np.int32)
            for i, bm in enumerate(mappers):
                if bm.bin_type != BIN_CATEGORICAL or not bm.categorical_2_bin:
                    continue
                keys = np.asarray(list(bm.categorical_2_bin.keys()),
                                  dtype=np.int64)
                vals = np.asarray(list(bm.categorical_2_bin.values()),
                                  dtype=np.int32)
                srt = np.argsort(keys)
                self.cat_keys[i, : len(keys)] = keys[srt]
                self.cat_bins[i, : len(keys)] = vals[srt]
        # column index sets for the host fast path (_map_chunk_np):
        # only columns whose missing type can actually fire pay a fixup
        nc = ~self.is_cat
        self._idx_nan = np.flatnonzero(
            (self.missing_type == MISSING_NAN) & nc)
        self._idx_zero = np.flatnonzero(
            (self.missing_type == MISSING_ZERO) & nc)
        self._idx_none = np.flatnonzero(
            (self.missing_type == MISSING_NONE) & nc)
        self._idx_cat = np.flatnonzero(self.is_cat)
        # raw searchsorted result of a literal 0.0 per feature (before
        # any missing fixup) — the shared answer for every exact zero
        # in the sparse-column shortcut below
        self._zero_ss = np.sum(self.bounds < 0.0, axis=1).astype(np.int32)

    def map_chunk_T(self, chunk: np.ndarray,
                    oov_sentinel: bool = False) -> np.ndarray:
        """Host fast path, feature-major: (n, F_used) raw values ->
        (F_used, n) int32 bins, C-order (each feature's bins form one
        contiguous row — writing bins column-wise into a row-major
        (n, F) matrix touches a full cache line per element).

        Per-column C-speed searchsorted with column-gated
        NaN/zero/default fixups — bit-identical to the batched
        where-chain in ``map_chunk``: a where over an all-false mask is
        the identity, so skipping it for columns where the condition
        cannot fire changes nothing."""
        # one feature-major copy up front: every per-column pass below
        # (count_nonzero, searchsorted, fixups) then reads a contiguous
        # ~0.5MB row instead of striding across the whole row-major
        # chunk — measured ~15% off the chunk map even net of the
        # transpose cost (blocked so each transpose tile stays
        # cache-resident)
        src = np.asarray(chunk, dtype=np.float64)
        n = src.shape[0]
        vals = np.empty((self.num_cols, n), dtype=np.float64)
        for s in range(0, n, 4096):
            e = min(s + 4096, n)
            vals[:, s:e] = src[s:e].T
        out = np.empty((self.num_cols, n), dtype=self._out_dtype)
        nan_mask = np.isnan(vals)
        col_nan = nan_mask.any(axis=1)
        # scratch shared by every grid-search column in this chunk
        f8 = np.empty(n)
        i4 = np.empty(n, dtype=np.int32)
        g8 = np.empty(n)
        bl = np.empty(n, dtype=bool)
        for f in range(self.num_cols):
            if self.is_cat[f]:
                continue
            col = vals[f]
            if col_nan[f]:
                col = np.where(nan_mask[f], 0.0, col)
            bounds = self.bounds[f, : self._blen[f]]
            nz_cnt = (int(np.count_nonzero(col))
                      if self._try_sparse[f] else n)
            if nz_cnt * 2 < n:
                # zero-dominated column: binary-search only the
                # non-zeros; every exact 0.0 (incl. -0.0 and the
                # scrubbed NaNs above) shares the precomputed result,
                # so this is bit-identical at a fraction of the
                # searchsorted work
                idx = np.flatnonzero(col)
                row = out[f]
                row.fill(self._zero_ss[f])
                row[idx] = np.searchsorted(bounds, col[idx],
                                           side="left")
            elif self._grid[f] is not None:
                g0, inv_w, lo_tab, bpad, span = self._grid[f]
                np.subtract(col, g0, out=f8)
                np.multiply(f8, inv_w, out=f8)
                np.clip(f8, 0, _GRID_NCELL - 1, out=f8)
                np.copyto(i4, f8, casting="unsafe")
                res = lo_tab[i4]
                for _ in range(span):
                    np.take(bpad, res, out=g8)
                    np.greater(col, g8, out=bl)
                    np.add(res, bl, out=res, casting="unsafe")
                out[f] = res
            else:
                out[f] = np.searchsorted(bounds, col, side="left")
        for f in self._idx_nan:
            if col_nan[f]:
                out[f][nan_mask[f]] = self.num_bin[f] - 1
        for f in self._idx_zero:
            col = vals[f]
            if col_nan[f]:
                col = np.where(nan_mask[f], 0.0, col)
            # NaN -> 0.0 above, so |col| <= K covers the chain's
            # (zeroish | nan_mask) exactly
            z = (col >= -K_ZERO_THRESHOLD) & (col <= K_ZERO_THRESHOLD)
            out[f][z] = self.default_bin[f]
        for f in self._idx_none:
            if col_nan[f]:
                out[f][nan_mask[f]] = self.zero_bin[f]
        for f in self._idx_cat:
            iv = np.where(nan_mask[f], -1.0,
                          vals[f]).astype(np.int64)
            keys = self.cat_keys[f]
            pos = np.minimum(np.searchsorted(keys, iv, side="left"),
                             keys.shape[0] - 1)
            hit = keys[pos] == iv
            miss = np.int32(self.num_bin[f]) if oov_sentinel \
                else np.int32(0)
            out[f] = np.where(hit, self.cat_bins[f][pos], miss)
        return out

    def map_chunk(self, chunk, xp=np, oov_sentinel: bool = False):
        """(n, F_used) raw values -> (n, F_used) int32 bins.  ``chunk``
        columns follow ``used_features`` order.  ``xp`` is numpy or
        jax.numpy; categorical resolution always runs through the same
        vectorized search (int64 keys) on host tables."""
        if xp is np:
            # transposed VIEW of the feature-major result: mat[:, i] is
            # the contiguous row map_chunk_T wrote, so per-feature
            # consumers pay no copy
            return self.map_chunk_T(chunk, oov_sentinel).T
        vals = xp.asarray(chunk)
        nan_mask = xp.isnan(vals)
        safe = xp.where(nan_mask, 0.0, vals)
        out = _searchsorted_rows(xp.asarray(self.bounds), safe, xp)
        mt = xp.asarray(self.missing_type)[None, :]
        nbin = xp.asarray(self.num_bin)[None, :]
        dbin = xp.asarray(self.default_bin)[None, :]
        zbin = xp.asarray(self.zero_bin)[None, :]
        out = xp.where((mt == MISSING_NAN) & nan_mask, nbin - 1, out)
        zeroish = (safe >= -K_ZERO_THRESHOLD) & (safe <= K_ZERO_THRESHOLD)
        out = xp.where((mt == MISSING_ZERO) & (zeroish | nan_mask),
                       dbin, out)
        out = xp.where((mt == MISSING_NONE) & nan_mask, zbin, out)
        if self.has_cat:
            # categorical columns: exact-match batched search on host
            # tables (int64 keys; NaN maps to key -1 = bin 0 like the
            # oracle).  Rare columns, always numpy.
            v_np = np.asarray(vals) if xp is not np else vals
            iv = np.where(np.asarray(nan_mask) if xp is not np
                          else nan_mask, -1.0, v_np).astype(np.int64)
            pos = _searchsorted_rows(self.cat_keys, iv, np)
            pos = np.minimum(pos, self.cat_keys.shape[1] - 1)
            f_idx = np.arange(self.num_cols)[None, :]
            hit = self.cat_keys[f_idx, pos] == iv
            miss = np.int32(self.num_bin) if oov_sentinel else 0
            cat_out = np.where(hit, self.cat_bins[f_idx, pos],
                               miss * np.ones((1, self.num_cols),
                                              np.int32))
            is_cat = xp.asarray(self.is_cat)[None, :]
            out = xp.where(is_cat, xp.asarray(cat_out), out)
        return out.astype(xp.int32)


# ---------------------------------------------------------------------------
# EFB conflict counting as one nonzero-mask matmul
# ---------------------------------------------------------------------------


def conflict_matrix(masks: np.ndarray, use_device: bool = False
                    ) -> np.ndarray:
    """(F_sparse, F_sparse) pairwise conflict counts from the 0/1
    non-default-row mask matrix (F_sparse, n_sample): ONE matmul
    M @ M.T replaces the host's per-(feature, bundle) mask-AND loop.
    Diagonal = per-feature non-default counts."""
    m = np.ascontiguousarray(masks, dtype=np.float32)
    if use_device:
        import jax
        import jax.numpy as jnp
        c = jax.device_get(jnp.matmul(jnp.asarray(m), jnp.asarray(m).T))
        return np.asarray(np.rint(c), dtype=np.int64)
    c = m @ m.T
    # f32 dot of 0/1 vectors is exact below 2^24 samples (n <= 50000)
    return np.asarray(np.rint(c), dtype=np.int64)


# ---------------------------------------------------------------------------
# Direct-to-device (G, N_pad) ingest
# ---------------------------------------------------------------------------


class DeviceIngest:
    """Streams packed (rows, G) host chunks into the learner's
    transposed (G, N_pad) device buffer with double-buffered
    host->device copies: the device_put of chunk k+1 is issued before
    chunk k's update is awaited (JAX async dispatch overlaps the
    transfer with the in-place dynamic_update_slice), and neither the
    full host binned matrix, its transpose, nor the padded copy ever
    materialize on the host."""

    def __init__(self, num_groups: int, num_data: int, dtype,
                 tpu_row_chunk: int):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        self.G = max(int(num_groups), 1)
        self.N = int(num_data)
        self.dtype = np.dtype(dtype)
        self.row_chunk, self.row0, self.n_pad = row_geometry(
            tpu_row_chunk, self.N)
        self.buffer = jnp.zeros((self.G, self.n_pad), self.dtype)
        # in-place chunk write: donation keeps ONE device buffer alive
        self._upd = jax.jit(
            lambda buf, chunk, off: jax.lax.dynamic_update_slice(
                buf, chunk, (0, off)),
            donate_argnums=(0,))
        self._row = 0
        self._pending = None           # (device chunk, offset) in flight
        # single-copy residency handoff: the fused trainer may ADOPT the
        # buffer outright (donating it through its per-iteration step) and
        # leave a recovery callback that reconstructs the original-order
        # layout from its live permuted carrier
        self._recover = None

    def _flush(self):
        if self._pending is not None:
            dev, off = self._pending
            self.buffer = self._upd(self.buffer, dev,
                                    self._jnp.int32(off))
            self._pending = None

    def push(self, packed_rows: np.ndarray) -> None:
        """Append a (rows, G) packed host chunk (row-major, any chunking
        the producer likes)."""
        self.push_t(packed_rows.T)

    def push_t(self, packed_cols: np.ndarray) -> None:
        """Append a (G, rows) packed host chunk — the buffer's native
        orientation, so a feature-major producer pays no transpose."""
        n = packed_cols.shape[1]
        if n == 0:
            return
        if self._row + n > self.N:
            raise ValueError("device ingest overflow: %d rows into %d"
                             % (self._row + n, self.N))
        host_t = np.ascontiguousarray(packed_cols.astype(
            self.dtype, copy=False))
        if host_t.shape[0] < self.G:      # zero usable features edge
            host_t = np.zeros((self.G, n), self.dtype)
        dev = self._jax.device_put(host_t)    # async; overlaps prior upd
        off = self.row0 + self._row
        self._row += n
        self._flush()
        self._pending = (dev, off)

    def finish(self):
        """Seal the buffer; returns the (G, N_pad) device array."""
        if self._row != self.N:
            raise ValueError("device ingest underflow: %d of %d rows"
                             % (self._row, self.N))
        self._flush()
        return self.buffer

    # -- learner handoff -------------------------------------------------
    def matches(self, row_chunk: int, n_pad: int, dtype) -> bool:
        return (self.row_chunk == row_chunk and self.n_pad == n_pad
                and self.dtype == np.dtype(dtype))

    def release_buffer(self, recover) -> None:
        """Hand the buffer to the fused trainer (single-copy residency:
        the trainer's physical carrier becomes the ONLY binned resident
        and is donated in place across iterations).  ``recover()`` must
        return a fresh (G, n_pad) original-order device buffer rebuilt
        from the carrier — it is called lazily by ``host_binned`` /
        ``part0`` when a later consumer (pickle, save_binary, a second
        booster) needs the pristine layout back."""
        self.buffer = None
        self._recover = recover

    def live_buffer(self):
        """The (G, n_pad) device buffer, reconstructing it from the
        adopting trainer's carrier when the buffer was released.  May
        transiently hold 2x the binned footprint (carrier + rebuilt
        buffer) until the caller drops one of them."""
        buf = self.buffer
        if buf is not None and not buf.is_deleted():
            return buf
        if self._recover is None:
            raise ValueError(
                "device ingest buffer was consumed by training and no "
                "recovery callback is installed")
        return self._recover()

    def part0(self, pb_rows: int):
        """The learner-shaped buffer: padded with zero rows on device
        when the Pallas partition wants sublane-aligned extra rows."""
        if self.buffer is None or self.buffer.is_deleted():
            # a previous booster adopted the buffer: restore the pristine
            # layout so this learner starts from the same state
            self.buffer = self.live_buffer()
            self._recover = None
        if pb_rows <= self.buffer.shape[0]:
            return self.buffer
        return self._jnp.pad(self.buffer,
                             ((0, pb_rows - self.buffer.shape[0]), (0, 0)))

    def host_binned(self, block_rows: int = 262144) -> np.ndarray:
        """Materialize the row-major host binned matrix back from the
        device buffer (fallback for consumers that need host bins after
        a host-binned-free construction).

        Streams in bounded row blocks: the peak HOST-side delta beyond
        the (N, G) result is one (G, block) transfer staging buffer plus
        its transpose — not a second full-matrix copy (the full-transfer
        path doubled the host footprint exactly where pickling /
        save_binary are already memory-tight)."""
        import jax
        buf = self.live_buffer()
        # a carrier-recovered buffer may carry extra sublane-pad rows
        # beyond G (learner _pb_rows > G): slice them off
        out = np.empty((self.N, self.G), dtype=self.dtype)
        for lo in range(0, self.N, block_rows):
            hi = min(lo + block_rows, self.N)
            sl = buf[:self.G, self.row0 + lo: self.row0 + hi]
            # deliberate per-block transfer: batching is the hazard
            # here — one get of the whole buffer is exactly the
            # 2x-host-peak this path exists to avoid
            out[lo:hi] = np.asarray(
                jax.device_get(sl)).T    # jaxlint: ok=JL001
        return out
