"""Binned dataset resident in TPU HBM.

TPU-native re-design of the reference Dataset / DatasetLoader / Metadata
(src/io/dataset.cpp, src/io/dataset_loader.cpp, include/LightGBM/dataset.h):
host-side NumPy builds the per-feature BinMappers from sampled values
(reference: DatasetLoader::ConstructFromSampleData, dataset_loader.cpp:593),
then the full data matrix is binned into a packed integer tensor that is
uploaded once to device HBM.  Histogram construction consumes this tensor via
MXU one-hot matmuls instead of the reference's per-thread scatter loops.

Feature grouping (EFB, reference dataset.cpp:60-244 FindGroups /
FastFeatureBundling) bundles mutually-exclusive sparse features into shared
columns with bin offsets.
"""

from __future__ import annotations

import itertools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .config import Config
from .obs import memory as obs_memory
from .obs import telemetry as obs
from .ops.binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN,
                          MISSING_NONE, MISSING_ZERO, BinMapper)
from .utils import log


def _dataset_memory_arrays(ds):
    """Telemetry memory provider: the packed binned matrix (host) and
    the direct-to-device ingest buffers, when present."""
    out = [ds.binned, getattr(ds, "raw_data", None)]
    di = getattr(ds, "device_ingest", None)
    if di is not None:
        out.extend(v for v in vars(di).values()
                   if getattr(v, "nbytes", None) is not None)
    # a donated/adopted buffer (single-copy residency) stays reachable
    # as a deleted jax Array: it holds no memory, so skip it
    def _alive(a):
        deleted = getattr(a, "is_deleted", None)
        return a is not None and not (deleted is not None and deleted())
    return [a for a in out if _alive(a)]


def _fill_rows_t(dst: np.ndarray, start: int, packed_cols: np.ndarray
                 ) -> None:
    """``dst[start:start+rows] = packed_cols.T`` in cache-sized blocks:
    the naive full transpose-assign streams the whole strided source
    per destination row; 8k-row blocks keep the working set (~G x 8k)
    L2-resident."""
    rows = packed_cols.shape[1]
    blk = 8192
    for s in range(0, rows, blk):
        e = min(s + blk, rows)
        dst[start + s:start + e] = packed_cols[:, s:e].T


def _construct_workers(config) -> int:
    """Host threads for the vectorized construction path: the explicit
    ``num_threads`` param when set, else one per core.  The parallel
    sections are GIL-releasing numpy (searchsorted, copies, sorts), so
    plain threads scale them without changing any result — work is
    split per-feature / per-chunk and merged in deterministic order."""
    nt = int(getattr(config, "num_threads", 0) or 0)
    return nt if nt > 0 else max(1, os.cpu_count() or 1)


class _TextFileSequenceImpl:
    """File-backed text/CSV row reader for streaming construction (the
    concrete body of :class:`lightgbm_tpu.TextFileSequence`, which mixes
    this with the :class:`~lightgbm_tpu.basic.Sequence` protocol — the
    split avoids a dataset<->basic import cycle).

    Indexes line byte-offsets in ONE pass at open (12 bytes of index per
    row), then serves ``__getitem__`` slices by seek+read of exactly the
    requested rows — the raw matrix never materializes in host memory,
    so the PR-17 two-pass sketch construction streams straight off disk
    (first slice of the ROADMAP "Arrow/text readers" remainder).

    Fields parse as float64 via Python ``float`` (empty / NA-ish fields
    -> NaN), so a file round-tripped through ``repr`` is bit-identical
    to the in-memory matrix it came from — the chunk-boundary parity
    test relies on that.
    """

    _NA = frozenset(("", "na", "nan", "n/a", "null", "none", "?"))

    def __init__(self, path: str, delimiter: str = ",",
                 header: Any = "auto", batch_size: int = 4096,
                 usecols: Optional[List[int]] = None):
        self.path = str(path)
        self.delimiter = delimiter
        self.batch_size = int(batch_size)
        self.usecols = list(usecols) if usecols is not None else None
        starts: List[int] = []
        lens: List[int] = []
        off = 0
        first_line = None
        with open(self.path, "rb") as f:
            for line in f:
                if line.strip():
                    if first_line is None:
                        first_line = line
                    starts.append(off)
                    lens.append(len(line))
                off += len(line)
        if header == "auto":
            header = (first_line is not None
                      and not self._parses(first_line))
        if header and starts:
            starts, lens = starts[1:], lens[1:]
        self._starts = np.asarray(starts, dtype=np.int64)
        self._lens = np.asarray(lens, dtype=np.int32)
        if len(self._starts):
            self.ncols = len(self._fields(self._read_block(0, 1)[0]))
        else:
            self.ncols = 0

    # -- parsing --------------------------------------------------------
    def _fields(self, line: bytes) -> List[str]:
        txt = line.decode("utf-8").strip()
        parts = (txt.split(self.delimiter) if self.delimiter != " "
                 else txt.split())
        if self.usecols is not None:
            parts = [parts[c] for c in self.usecols]
        return parts

    def _parses(self, line: bytes) -> bool:
        try:
            self._row(line)
            return True
        except (ValueError, IndexError):
            return False

    def _row(self, line: bytes) -> List[float]:
        return [float("nan") if p.strip().lower() in self._NA else float(p)
                for p in self._fields(line)]

    def _read_block(self, lo: int, hi: int) -> List[bytes]:
        with open(self.path, "rb") as f:
            f.seek(int(self._starts[lo]))
            raw = f.read(int(self._starts[hi - 1] + self._lens[hi - 1]
                             - self._starts[lo]))
        return [ln for ln in raw.split(b"\n") if ln.strip()]

    # -- Sequence protocol ---------------------------------------------
    def __len__(self) -> int:
        return len(self._starts)

    def __getitem__(self, idx):
        n = len(self._starts)
        if isinstance(idx, slice):
            lo, hi, step = idx.indices(n)
            if step != 1:
                raise ValueError("TextFileSequence slices must be "
                                 "contiguous (step 1)")
            if hi <= lo:
                return np.empty((0, self.ncols), dtype=np.float64)
            lines = self._read_block(lo, hi)
            out = np.empty((len(lines), self.ncols), dtype=np.float64)
            for i, ln in enumerate(lines):
                out[i] = self._row(ln)
            return out
        if idx < 0:
            idx += n
        if not 0 <= idx < n:
            raise IndexError(idx)
        return np.asarray(self._row(self._read_block(idx, idx + 1)[0]),
                          dtype=np.float64)

    def read_column(self, col: int) -> np.ndarray:
        """Stream one ORIGINAL-file column (e.g. a label column excluded
        from ``usecols``) in ``batch_size`` row blocks."""
        saved = self.usecols
        self.usecols = [col]
        try:
            out = np.empty((len(self),), dtype=np.float64)
            for lo in range(0, len(self), self.batch_size):
                hi = min(lo + self.batch_size, len(self))
                out[lo:hi] = self[lo:hi][:, 0]
            return out
        finally:
            self.usecols = saved


class Metadata:
    """Per-row side data: label / weight / query groups / init_score.

    reference: include/LightGBM/dataset.h:47-398 (Metadata).
    """

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # int32 [nq+1]
        self.init_score: Optional[np.ndarray] = None
        self.positions: Optional[np.ndarray] = None         # int32 ids/row
        self.position_ids: Optional[List[str]] = None       # id -> label

    def set_position(self, position) -> None:
        """Per-row presentation positions for unbiased lambdarank
        (reference: Metadata::SetPosition, metadata.cpp; positions factorize
        to compact ids like the `.position` file loader)."""
        if position is None:
            self.positions = None
            self.position_ids = None
            return
        vals = np.asarray(position).reshape(-1)
        if vals.shape[0] != self.num_data:
            log.fatal("Length of position (%d) != num_data (%d)",
                      vals.shape[0], self.num_data)
        # vectorized first-seen factorization (compact ids in order of
        # first appearance, matching the reference's `.position` loader)
        uniq, first, inv = np.unique(vals, return_index=True,
                                     return_inverse=True)
        order = np.argsort(first, kind="stable")
        remap = np.empty(len(uniq), dtype=np.int32)
        remap[order] = np.arange(len(uniq), dtype=np.int32)
        self.positions = remap[inv.reshape(-1)]
        self.position_ids = [str(uniq[o]) for o in order]

    def set_label(self, label) -> None:
        arr = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            log.fatal("Length of label (%d) != num_data (%d)", len(arr), self.num_data)
        self.label = arr

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        arr = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            log.fatal("Length of weight (%d) != num_data (%d)", len(arr), self.num_data)
        self.weight = arr

    def set_group(self, group) -> None:
        """Accepts per-query sizes (like the reference's query counts)."""
        if group is None:
            self.query_boundaries = None
            return
        arr = np.asarray(group, dtype=np.int64).reshape(-1)
        if arr.sum() != self.num_data:
            log.fatal("Sum of query counts (%d) != num_data (%d)", arr.sum(), self.num_data)
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(arr)]).astype(np.int32)

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        arr = np.asarray(init_score, dtype=np.float64).reshape(-1)
        self.init_score = arr

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class FeatureGroupInfo:
    """One packed bin column, possibly bundling several exclusive features.

    reference: include/LightGBM/feature_group.h:25 (FeatureGroup).  Bundled
    features occupy disjoint bin ranges [bin_offset[i], bin_offset[i+1]) of the
    shared column; bin 0 is the shared "all-default" bin.
    """

    def __init__(self, feature_indices: List[int], num_total_bin: int,
                 bin_offsets: List[int]):
        self.feature_indices = feature_indices
        self.num_total_bin = num_total_bin
        self.bin_offsets = bin_offsets  # per sub-feature start bin


class BinnedDataset:
    """The training matrix in binned form (reference: dataset.h:486 Dataset)."""

    def __init__(self, config: Config):
        self.config = config
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.bin_mappers: List[BinMapper] = []       # per original feature
        self.used_features: List[int] = []           # original idx of non-trivial
        self.groups: List[FeatureGroupInfo] = []
        self.binned: Optional[np.ndarray] = None     # (num_data, num_groups) int
        self.metadata: Optional[Metadata] = None
        self.monotone_constraints: Optional[List[int]] = None
        self.raw_data: Optional[np.ndarray] = None   # retained for linear trees
        self._device_cache: Dict[str, Any] = {}
        # construction path (ops/construct.py, construct_device param):
        # _vec = vectorized bin-finding/binning, _ingest_ok = stream the
        # packed chunks into the learner's (G, N_pad) device layout,
        # _keep_host = materialize the row-major host binned matrix
        self._vec: bool = False
        self._ingest_ok: bool = False
        self._keep_host: bool = True
        self._batched = None                         # cached BatchedMapper
        self.device_ingest = None                    # ops.construct.DeviceIngest
        # data-health reference profile (obs/digest.py), captured lazily
        # at construction when health != off and persisted with models
        self._health_profile = None

    # jitted device buffers and the padded mapper tables are neither
    # picklable nor worth shipping; a host-binned-free dataset
    # materializes its matrix back first so no data is lost
    def __getstate__(self):
        st = dict(self.__dict__)
        if st.get("binned") is None and st.get("device_ingest") is not None:
            st["binned"] = self.device_ingest.host_binned()
        st["device_ingest"] = None
        st["_batched"] = None
        return st

    def batched_mapper(self):
        """The padded-table batched values->bins mapper over all used
        features (built once, reused by binning / bin_matrix)."""
        if self._batched is None:
            from .ops.construct import BatchedMapper
            self._batched = BatchedMapper(self.bin_mappers,
                                          self.used_features)
        return self._batched

    def reference_profile(self):
        """The data-health reference profile of THIS dataset's rows
        (obs/digest.py): per-feature bin occupancy, missing/zero rates
        and categorical cardinalities, computed with one reduction over
        the packed bin matrix — on device (one sync) when only the
        ingest buffer holds the data, on host otherwise.  Cached; None
        when no binned data exists."""
        if self._health_profile is not None:
            return self._health_profile
        from .obs import digest as _digest
        with obs.span("dataset.profile", rows=self.num_data):
            if self.binned is not None:
                counts = _digest.bin_counts_host(self.binned,
                                                 self.max_group_bins)
            elif self.device_ingest is not None:
                di = self.device_ingest
                # live_buffer: recovers the pristine layout if the fused
                # trainer adopted the buffer (single-copy residency);
                # [:G] drops carrier sublane-pad rows
                snap = _digest.snapshot_device(
                    di.live_buffer()[:di.G], self.max_group_bins,
                    transposed=True, pad_cols=di.n_pad - di.N)
                counts = snap["group_counts"]
            else:
                return None
            self._health_profile = _digest.build_reference_profile(
                self, counts)
        return self._health_profile

    def host_binned(self) -> Optional[np.ndarray]:
        """The row-major (num_data, num_groups) host bin matrix,
        materialized from the device ingest buffer when the host copy
        was freed (construct_device=on / free_host_binned)."""
        if self.binned is not None:
            return self.binned
        if self.device_ingest is not None:
            return self.device_ingest.host_binned()
        return None

    # -- construction ---------------------------------------------------
    @staticmethod
    def from_matrix(data: np.ndarray, config: Config,
                    label=None, weight=None, group=None, init_score=None,
                    feature_names: Optional[List[str]] = None,
                    categorical_features: Optional[Sequence[int]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    position=None) -> "BinnedDataset":
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("Data must be 2-dimensional")
        obs.configure_from_config(config)
        with obs.span("dataset.construct", rows=int(data.shape[0]),
                      features=int(data.shape[1])):
            return BinnedDataset._from_matrix_impl(
                data, config, label, weight, group, init_score,
                feature_names, categorical_features, reference, position)

    @staticmethod
    def _from_matrix_impl(data, config, label, weight, group, init_score,
                          feature_names, categorical_features, reference,
                          position) -> "BinnedDataset":
        ds = BinnedDataset(config)
        obs_memory.register("dataset.binned", ds, _dataset_memory_arrays)
        ds._resolve_construct_mode(is_reference=reference is not None)
        ds.num_data, ds.num_total_features = data.shape
        ds.feature_names = feature_names or [
            f"Column_{i}" for i in range(ds.num_total_features)]
        ds.metadata = Metadata(ds.num_data)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_group(group)
        ds.metadata.set_init_score(init_score)
        ds.metadata.set_position(position)

        if reference is not None:
            # validation data: reuse the training mappers & grouping
            # (reference: dataset_loader.cpp LoadFromFileAlignWithOtherDataset:299)
            ds.bin_mappers = reference.bin_mappers
            ds.used_features = reference.used_features
            ds.groups = reference.groups
            ds.feature_names = reference.feature_names
            ds._bin_data(data)
            if config.linear_tree:
                ds.raw_data = np.ascontiguousarray(data, dtype=np.float32)
            return ds

        ds._construct_mappers(data, categorical_features or [])
        ds._build_groups()
        ds._bin_data(data)
        if config.linear_tree:
            ds.raw_data = np.ascontiguousarray(data, dtype=np.float32)
        # data-health reference profile, captured while the binned data
        # is guaranteed fresh (obs/health.py; persisted with the model)
        from .obs import health as obs_health
        obs_health.configure_from_config(config)
        if obs_health.enabled():
            ds.reference_profile()
        return ds

    @staticmethod
    def from_sequences(seqs, config: Config, label=None, weight=None,
                       group=None, init_score=None,
                       feature_names: Optional[List[str]] = None,
                       categorical_features: Optional[Sequence[int]] = None,
                       position=None,
                       reference: Optional["BinnedDataset"] = None
                       ) -> "BinnedDataset":
        """Streaming construction from chunk-accessible sequences
        (reference: the Sequence ABC path, python-package basic.py:896 +
        LGBM_DatasetCreateFromSampledColumn/PushRows in c_api.cpp): bin
        mappers and feature groups are built from a row SAMPLE, then each
        sequence is binned chunk by chunk — the full raw matrix is never
        materialized."""
        if not isinstance(seqs, (list, tuple)):
            seqs = [seqs]
        lens = [len(s) for s in seqs]
        total = int(sum(lens))
        if total == 0:
            log.fatal("Cannot construct a Dataset from empty sequences")
        first_nonempty = next(s for s, ln in zip(seqs, lens) if ln > 0)
        probe = np.asarray(first_nonempty[0:1], dtype=np.float64)
        F = probe.reshape(1, -1).shape[1]
        ds = BinnedDataset(config)
        obs.configure_from_config(config)
        obs_memory.register("dataset.binned", ds, _dataset_memory_arrays)
        ds._resolve_construct_mode(is_reference=reference is not None)
        ds.num_data = total
        ds.num_total_features = F
        ds.feature_names = feature_names or [f"Column_{i}" for i in range(F)]
        ds.metadata = Metadata(total)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weight(weight)
        ds.metadata.set_group(group)
        ds.metadata.set_init_score(init_score)
        ds.metadata.set_position(position)

        mode = None
        if reference is not None:
            # validation data: reuse the training mappers & grouping so bin
            # ids live in the SAME space (reference:
            # LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:299)
            ds.bin_mappers = reference.bin_mappers
            ds.used_features = reference.used_features
            ds.groups = reference.groups
            ds.feature_names = reference.feature_names
        else:
            cfg = config
            from .ops.sketch import resolve_bin_mode
            from .parallel import network as _net
            mode = resolve_bin_mode(cfg, total)
            sample_cnt = min(total, cfg.bin_construct_sample_cnt)
            rng = np.random.RandomState(cfg.data_random_seed)
            idx = np.sort(rng.choice(total, size=sample_cnt, replace=False)) \
                if sample_cnt < total else np.arange(total)
            if mode == "sketch":
                # pass 1 of 2 (out-of-core): fold every chunk into the
                # mergeable per-feature sketches; the SAME rng-chosen
                # row sample the exact path would block-fetch is
                # gathered chunk-by-chunk for the EFB conflict graph,
                # so the bundling decision — and its rng consumption —
                # is identical across modes
                from .ops.sketch import SketchSet
                sset = SketchSet(F, cfg.sketch_k)
                want_sample = bool(cfg.enable_bundle) \
                    and _net.num_machines() <= 1
                # idx is sorted and chunks arrive in row order, so the
                # sample rows land contiguously: fill a preallocated
                # matrix instead of concatenating parts (a parts list
                # would hold 2x the sample at the concat)
                sample = np.empty((len(idx) if want_sample else 0, F),
                                  dtype=np.float64)
                w = 0
                for start, chunk in BinnedDataset._iter_seq_chunks(seqs):
                    sset.update_chunk(chunk)
                    if want_sample:
                        sel = idx[(idx >= start)
                                  & (idx < start + len(chunk))] - start
                        if len(sel):
                            sample[w:w + len(sel)] = chunk[sel]
                            w += len(sel)
                sample = sample[:w]
                ds._construct_mappers_from_sketches(
                    sset, categorical_features or [])
            else:
                # sample rows across all sequences for binning; contiguous
                # index runs are fetched through the slice protocol in
                # blocks so disk-backed sequences see few large reads, not
                # one per row
                sample_rows = []
                offset = 0
                for s, ln in zip(seqs, lens):
                    sel = idx[(idx >= offset) & (idx < offset + ln)] - offset
                    i = 0
                    while i < len(sel):
                        j = i
                        while j + 1 < len(sel) and sel[j + 1] == sel[j] + 1:
                            j += 1
                        block = np.asarray(s[int(sel[i]):int(sel[j]) + 1],
                                           dtype=np.float64)
                        sample_rows.append(block.reshape(-1, F))
                        i = j + 1
                    offset += ln
                sample = np.concatenate(sample_rows, axis=0)
                ds._construct_mappers_from_sample(sample,
                                                  categorical_features or [])
            ds._build_groups()
            # resolve any pending sparse bundling with the SAMPLE columns
            # (skip the binning pass entirely when nothing is pending)
            if getattr(ds, "_pending_sparse", None):
                if ds._vec and ds.used_features:
                    # map the sample in row blocks: the used-features
                    # fancy index copies its input, so a one-shot call
                    # would hold a second full-f64 sample at peak
                    bm = ds.batched_mapper()
                    parts = [bm.map_chunk(sample[b:b + 65536,
                                                 ds.used_features])
                             for b in range(0, len(sample), 65536)]
                    smat = (np.concatenate(parts, axis=0) if parts else
                            np.empty((0, len(ds.used_features)),
                                     dtype=ds._bin_dtype()))
                    del parts
                    sample_cols = {f: np.asarray(smat[:, i]) for i, f
                                   in enumerate(ds.used_features)}
                else:
                    sample_cols = {
                        f: ds.bin_mappers[f].values_to_bins(sample[:, f])
                        for f in ds.used_features}
                ds._finalize_groups(sample_cols)
                del sample_cols
            else:
                ds._finalize_groups({})
            # the raw sample has served binning + bundling; drop it
            # before the pack pass so it doesn't ride the whole stream
            sample = None

        # stream (pass 2 of 2): bin each chunk, pack, and push it into the
        # host matrix and/or the device ingest buffer — chunk boundaries
        # never change the result (the mapping is per-row;
        # tests/test_construct_device straddles sequence boundaries to
        # prove it)
        dtype = ds._bin_dtype()
        ingest = ds._make_ingest(dtype)
        # out-of-core default: when the sketch path streamed the data and
        # the device ingest buffer holds it, the host binned matrix is NOT
        # kept unless free_host_binned was set explicitly — geometry
        # changes at train time re-stream from the retained source instead
        # (restream_ingest)
        free_host = bool(getattr(config, "free_host_binned", False))
        if (mode == "sketch" and ingest is not None
                and "free_host_binned" not in getattr(config, "_raw", {})):
            free_host = True
        keep = ds._keep_host and not (ingest is not None and free_host)
        out = (np.zeros((total, len(ds.groups)), dtype=dtype)
               if keep or ingest is None else None)
        raw = (np.zeros((total, F), dtype=np.float32)
               if config.linear_tree else None)
        ds._stream_map_pack(seqs, dtype, ingest=ingest, out=out, raw=raw)
        ds.binned = out
        if ingest is not None:
            ingest.finish()
            ds.device_ingest = ingest
        ds.raw_data = raw
        if reference is None and ingest is not None and out is None:
            # keep the chunk source: epoch re-streaming (a geometry
            # change at train time rebuilds the ingest buffer from here
            # instead of materializing the full host matrix)
            ds._stream_src = list(seqs)
        if reference is None:
            from .obs import health as obs_health
            obs_health.configure_from_config(config)
            if obs_health.enabled():
                ds.reference_profile()
        return ds

    @staticmethod
    def _iter_seq_chunks(seqs):
        """Yield (global_row_offset, float64 chunk) across sequences,
        honoring EACH sequence's own ``batch_size`` — the one chunk
        iterator every streaming pass shares, so a mixed-batch-size
        sequence list chunks identically in the sketch pass, the
        map-and-pack pass and epoch re-streaming (bit-parity asserted
        by tests/test_sketch.py)."""
        row = 0
        for s in seqs:
            ln = len(s)
            bs = int(getattr(s, "batch_size", 4096) or 4096)
            for startr in range(0, ln, bs):
                chunk = np.asarray(s[startr:startr + bs],
                                   dtype=np.float64)
                if chunk.ndim == 1:
                    chunk = chunk.reshape(1, -1)
                yield row + startr, chunk
            row += ln

    def _stream_map_pack(self, seqs, dtype, ingest=None, out=None,
                         raw=None) -> None:
        """Map-and-pack every sequence chunk into the given sinks (the
        shared body of construction pass 2 and epoch re-streaming)."""
        bmap = self.batched_mapper() if (self._vec and self.used_features) \
            else None
        for start, chunk in self._iter_seq_chunks(seqs):
            if bmap is not None:
                mat = bmap.map_chunk(chunk[:, self.used_features])
                cols = {f: np.asarray(mat[:, i]) for i, f
                        in enumerate(self.used_features)}
            else:
                cols = {f: self.bin_mappers[f].values_to_bins(chunk[:, f])
                        for f in self.used_features}
            packed = self._pack_groups(cols, len(chunk), dtype)
            if out is not None:
                out[start:start + len(chunk)] = packed
            if ingest is not None:
                ingest.push(packed)
            if raw is not None:
                raw[start:start + len(chunk)] = chunk.astype(np.float32)

    def restream_ingest(self, tpu_row_chunk: int):
        """Re-stream the retained chunk source into a FRESH DeviceIngest
        with the requested row geometry — the out-of-core twin of
        ``DeviceIngest.host_binned()`` for the learner's recovery path
        when the construct-time geometry no longer matches: one more
        pass over the source instead of materializing the full host
        binned matrix.  Returns None when there is no retained source
        or the device path is unavailable."""
        seqs = getattr(self, "_stream_src", None)
        if not seqs:
            return None
        dtype = self._bin_dtype()
        try:
            from .ops.construct import DeviceIngest
            ingest = DeviceIngest(len(self.groups), self.num_data, dtype,
                                  int(tpu_row_chunk))
        except Exception as exc:
            log.warning("restream ingest unavailable (%s)",
                        str(exc).split("\n")[0][:120])
            return None
        self._stream_map_pack(seqs, dtype, ingest=ingest)
        ingest.finish()
        self.device_ingest = ingest
        return ingest

    def _resolve_construct_mode(self, is_reference: bool) -> None:
        """Pick the construction path for this dataset from
        ``construct_device`` (see ops/construct.py resolve_mode)."""
        from .parallel import network as _net
        from .ops.construct import resolve_mode
        self._vec, self._ingest_ok, self._keep_host = resolve_mode(
            self.config, is_reference, _net.num_machines() > 1)

    def _make_ingest(self, dtype):
        """A DeviceIngest streaming target for this dataset's geometry,
        or None when the device path is unavailable."""
        if not self._ingest_ok:
            return None
        try:
            from .ops.chunkpolicy import resolve_base
            from .ops.construct import DeviceIngest
            return DeviceIngest(len(self.groups), self.num_data, dtype,
                                resolve_base(self.config.tpu_row_chunk))
        except Exception as exc:
            log.warning("device ingest unavailable (%s); keeping the "
                        "host binned matrix", str(exc).split("\n")[0][:120])
            return None

    def _construct_mappers_from_sample(self, sample: np.ndarray,
                                       categorical_features) -> None:
        """Build per-feature BinMappers from an already-sampled row matrix
        (reference: DatasetLoader::ConstructFromSampleData,
        dataset_loader.cpp:593 — the streaming/in-memory path)."""
        self._construct_mappers(sample, categorical_features,
                                _presampled=True)

    def _mapper_param_table(self):
        """Per-feature bin-finding knobs shared by the exact and sketch
        paths: (max_bin_by_feature list or None, forced bounds dict)."""
        cfg = self.config
        max_bin_by_feature = None
        if cfg.max_bin_by_feature:
            max_bin_by_feature = [int(x) for x in str(cfg.max_bin_by_feature).split(",")]
        # forced bin upper bounds (reference: DatasetLoader reads
        # forcedbins_filename as [{"feature": i, "bin_upper_bound": [...]}]
        # and threads them into BinMapper::FindBin, dataset_loader.cpp)
        forced_bounds: dict = {}
        if getattr(cfg, "forcedbins_filename", ""):
            import json as _json
            try:
                with open(cfg.forcedbins_filename) as fh:
                    for entry in _json.load(fh):
                        forced_bounds[int(entry["feature"])] = [
                            float(v) for v in entry["bin_upper_bound"]]
            except (OSError, ValueError, KeyError, TypeError) as exc:
                log.warning("could not read forcedbins file %s (%s); "
                            "ignoring", cfg.forcedbins_filename, exc)
        return max_bin_by_feature, forced_bounds

    def _finish_mappers(self) -> None:
        """Shared epilogue of every mapper-construction path."""
        self.used_features = [f for f in range(self.num_total_features)
                              if not self.bin_mappers[f].is_trivial]
        if not self.used_features:
            log.warning("There are no meaningful features which satisfy the "
                        "provided configuration. Decreasing Dataset parameters "
                        "min_data_in_bin or min_data_in_leaf and re-constructing "
                        "Dataset might resolve this warning.")

    def _construct_mappers_from_sketches(self, sset,
                                         categorical_features) -> None:
        """BinMappers from accumulated per-feature sketches
        (ops/sketch.py).  Under multi-process construction each rank
        sketched only its ROW shard; the fixed-size sketch states are
        allgathered and canonically merged, so every rank derives
        bit-identical global mappers without any rank ever holding the
        global matrix (the rank-sharded out-of-core path)."""
        cfg = self.config
        from .parallel import network as _net
        self._distributed = _net.num_machines() > 1
        if self._distributed:
            from .parallel.distributed import allgather_feature_sketches
            sset = allgather_feature_sketches(sset)
            # feature widths agree by max, like allgather_bin_mappers
            self.num_total_features = max(self.num_total_features,
                                          len(sset))
        cat_set = set(int(c) for c in categorical_features)
        max_bin_by_feature, forced_bounds = self._mapper_param_table()
        # the sketch pass consumes the FULL stream, so the pre-filter's
        # sample/population ratio is exactly 1
        filter_cnt = int(cfg.min_data_in_leaf)

        def _mb(f):
            if max_bin_by_feature and f < len(max_bin_by_feature):
                return max_bin_by_feature[f]
            return cfg.max_bin

        trivial = BinMapper()
        self.bin_mappers = [
            sset.sketches[f].to_mapper(
                _mb(f), min_data_in_bin=cfg.min_data_in_bin,
                min_split_data=filter_cnt,
                pre_filter=cfg.feature_pre_filter,
                bin_type=(BIN_CATEGORICAL if f in cat_set
                          else BIN_NUMERICAL),
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                forced_upper_bounds=forced_bounds.get(f))
            if f < len(sset) else trivial
            for f in range(self.num_total_features)]
        self._finish_mappers()

    def _construct_mappers(self, data: np.ndarray,
                           categorical_features: Sequence[int],
                           _presampled: bool = False) -> None:
        cfg = self.config
        n = self.num_data
        if not _presampled:
            from .ops.sketch import resolve_bin_mode
            if resolve_bin_mode(cfg, n) == "sketch":
                # sketch-based bin finding over row chunks: no full
                # sample materialization, no full column sort — and the
                # distributed branch inside merges rank ROW shards
                from .ops.sketch import SketchSet
                sset = SketchSet(self.num_total_features, cfg.sketch_k)
                step = self.CONSTRUCT_CHUNK
                for start in range(0, n, step):
                    sset.update_chunk(np.asarray(
                        data[start:min(start + step, n)],
                        dtype=np.float64))
                self._construct_mappers_from_sketches(
                    sset, categorical_features)
                return
        if _presampled:
            sample_cnt = len(data)
            sample_idx = np.arange(sample_cnt)
        else:
            sample_cnt = min(n, cfg.bin_construct_sample_cnt)
            rng = np.random.RandomState(cfg.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(
                    rng.choice(n, size=sample_cnt, replace=False))
            else:
                sample_idx = np.arange(n)
        cat_set = set(int(c) for c in categorical_features)
        max_bin_by_feature, forced_bounds = self._mapper_param_table()
        # feature_pre_filter threshold (reference: dataset_loader.cpp FindBin call)
        filter_cnt = int(cfg.min_data_in_leaf * sample_cnt / max(n, 1))
        # multi-process construction: each rank finds bins only for its
        # FEATURE shard (from its local sample) and the serialized
        # mappers are allgathered so every rank agrees
        # (dataset_loader.cpp:658-740, :1228-1236)
        from .parallel import network as _net
        nmach = _net.num_machines()
        my_rank = _net.rank() if nmach > 1 else 0
        self._distributed = nmach > 1
        my_feats = [f for f in range(self.num_total_features)
                    if not self._distributed or (f % nmach) == my_rank]

        def _mb(f):
            if max_bin_by_feature and f < len(max_bin_by_feature):
                return max_bin_by_feature[f]
            return cfg.max_bin

        self.bin_mappers = [None] * self.num_total_features
        if self._vec and my_feats:
            # vectorized bin finding (ops/construct.py): ONE column-wise
            # sort of the whole (sample_cnt, F) matrix replaces F stable
            # argsorts; the per-feature non-zero/NaN filtering becomes
            # two index ranges of the sorted column
            from .ops.construct import find_bin_sorted, sorted_sample_columns
            rows = (data if len(sample_idx) == len(data)
                    else data[sample_idx])
            sub = np.asarray(
                rows if my_feats == list(range(data.shape[1]))
                else rows[:, my_feats], dtype=np.float64)
            info = sorted_sample_columns(
                sub, workers=_construct_workers(cfg))
            sv = info["sorted"]

            def _find_one(j: int) -> "BinMapper":
                f = my_feats[j]
                lo, hi, m = (info["lo"][j], info["hi"][j],
                             info["non_nan"][j])
                nz_sorted = np.concatenate([sv[:lo, j], sv[hi:m, j]])
                return find_bin_sorted(
                    nz_sorted, na_cnt=int(info["nan_cnt"][j]),
                    total_sample_cnt=sample_cnt, max_bin=_mb(f),
                    min_data_in_bin=cfg.min_data_in_bin,
                    min_split_data=filter_cnt,
                    pre_filter=cfg.feature_pre_filter,
                    bin_type=(BIN_CATEGORICAL if f in cat_set
                              else BIN_NUMERICAL),
                    use_missing=cfg.use_missing,
                    zero_as_missing=cfg.zero_as_missing,
                    forced_upper_bounds=forced_bounds.get(f))

            workers = _construct_workers(cfg)
            if workers > 1 and len(my_feats) > 1:
                # per-feature bin finding is independent; the numpy
                # parts (concatenate, cumsum, searchsorted) release the
                # GIL, and results land by index — deterministic
                from concurrent.futures import ThreadPoolExecutor
                with ThreadPoolExecutor(max_workers=workers) as ex:
                    found = list(ex.map(_find_one,
                                        range(len(my_feats))))
            else:
                found = [_find_one(j) for j in range(len(my_feats))]
            for j, f in enumerate(my_feats):
                self.bin_mappers[f] = found[j]
        else:
            for f in my_feats:
                col = np.asarray(data[sample_idx, f], dtype=np.float64)
                # mirror the reference's sparse sampling: non-zero values
                # + implied zeros
                nonzero = col[(np.abs(col) > 1e-35) | np.isnan(col)]
                bm = BinMapper()
                bm.find_bin(
                    nonzero, total_sample_cnt=len(col), max_bin=_mb(f),
                    min_data_in_bin=cfg.min_data_in_bin,
                    min_split_data=filter_cnt,
                    pre_filter=cfg.feature_pre_filter,
                    bin_type=(BIN_CATEGORICAL if f in cat_set
                              else BIN_NUMERICAL),
                    use_missing=cfg.use_missing,
                    zero_as_missing=cfg.zero_as_missing,
                    forced_upper_bounds=forced_bounds.get(f))
                self.bin_mappers[f] = bm
        if self._distributed:
            from .parallel.distributed import allgather_bin_mappers
            local = {f: bm for f, bm in enumerate(self.bin_mappers)
                     if bm is not None}
            merged, num_total = allgather_bin_mappers(
                local, self.num_total_features)
            # a feature past some rank's local width may be binned by no
            # rank (num_total agrees by max); degrade it to a trivial
            # mapper instead of crashing
            trivial = BinMapper()
            self.bin_mappers = [merged.get(f, trivial)
                                for f in range(num_total)]
            self.num_total_features = num_total
        self._finish_mappers()

    def _build_groups(self) -> None:
        """EFB bundling (reference: dataset.cpp FindGroups:60 / FastFeatureBundling:246).

        Greedy graph-coloring over conflict counts on sampled rows.  Features
        whose non-default rows overlap less than ``max_conflict`` share one
        packed column with per-feature bin offsets.  Dense features (low sparse
        rate) stay in their own group.
        """
        self.groups = []
        if not self.config.enable_bundle or getattr(self, "_distributed",
                                                    False):
            if (getattr(self, "_distributed", False)
                    and self.config.enable_bundle):
                # conflict counts are rank-local samples; divergent
                # bundles would give each process a different physical
                # layout (the reference reaches group agreement through
                # its synced sample — not modeled here yet)
                log.warning("EFB disabled under multi-process construction")
            for f in self.used_features:
                nb = self.bin_mappers[f].num_bin
                self.groups.append(FeatureGroupInfo([f], nb, [0]))
            return
        # Candidate selection here; the conflict graph itself runs later
        # in _bin_data / _finalize_groups over the binned columns.
        sparse, dense = [], []
        for f in self.used_features:
            bm = self.bin_mappers[f]
            # Any feature whose shared "all-default" bin is bin 0 may
            # bundle (the learner's bundled-bin decode — bin b ->
            # offset+b-1, b>=1 — and FixHistogram reconstruction assume
            # it).  The conflict graph decides who actually shares a
            # group, like the reference's FindGroups over ALL features
            # (dataset.cpp:60-244): dense features conflict with
            # everything and come out as singletons on their own.
            if bm.most_freq_bin == 0 and bm.default_bin == 0:
                sparse.append(f)
            else:
                dense.append(f)
        for f in dense:
            self.groups.append(FeatureGroupInfo([f], self.bin_mappers[f].num_bin, [0]))
        # defer true conflict-graph bundling to _bin_data (needs the columns)
        self._pending_sparse = sparse

    def _finalize_groups(self, cols: Dict[int, np.ndarray]) -> None:
        """Resolve pending sparse bundling against binned columns, or fall
        back to singleton groups (shared by the in-memory and streaming
        construction paths)."""
        pending = getattr(self, "_pending_sparse", None)
        if pending:
            self._bundle_sparse(pending, cols)
            self._pending_sparse = None
        elif not self.groups and self.used_features:
            for f in self.used_features:
                self.groups.append(FeatureGroupInfo(
                    [f], self.bin_mappers[f].num_bin, [0]))

    def _bin_data(self, data: np.ndarray) -> None:
        if self._vec:
            self._bin_data_vectorized(data)
            return
        # oracle: bin all used features column-wise first
        cols: Dict[int, np.ndarray] = {}
        for f in self.used_features:
            cols[f] = self.bin_mappers[f].values_to_bins(data[:, f])
        self._finalize_groups(cols)

        self.binned = self._pack_groups(cols, self.num_data,
                                        self._bin_dtype())

    # rows per vectorized binning chunk: big enough to amortize the
    # batched searchsorted, small enough that the packed chunk + its
    # transpose stay cache/transfer friendly
    CONSTRUCT_CHUNK = 1 << 16

    def _bin_data_vectorized(self, data: np.ndarray) -> None:
        """The batched construction path: groups are finalized from a
        <=50k-row binned sample, then row chunks are mapped with ONE
        vectorized searchsorted over all features, packed, and (for
        training datasets) streamed straight into the learner's
        transposed (G, N_pad) device layout — the full host binned
        matrix only materializes when ``_keep_host`` asks for it."""
        n = self.num_data
        uf = self.used_features
        bmap = self.batched_mapper() if uf else None
        pending = getattr(self, "_pending_sparse", None)
        if pending:
            # identical rng consumption to the oracle's _bundle_sparse:
            # one choice() for the conflict sample, then the probe draws
            rng = np.random.RandomState(self.config.data_random_seed)
            sample = (rng.choice(n, size=min(n, 50000), replace=False)
                      if n > 50000 else np.arange(n))
            smat = bmap.map_chunk(np.asarray(data[np.ix_(sample, uf)],
                                             dtype=np.float64))
            nz = {f: np.asarray(smat[:, i]
                                != self.bin_mappers[f].most_freq_bin)
                  for i, f in enumerate(uf) if f in set(pending)}
            self._bundle_greedy(pending, nz, rng)
            self._pending_sparse = None
        else:
            self._finalize_groups({})

        dtype = self._bin_dtype()
        ingest = self._make_ingest(dtype)
        keep = self._keep_host and not (
            ingest is not None
            and bool(getattr(self.config, "free_host_binned", False)))
        out = (np.zeros((n, len(self.groups)), dtype=dtype)
               if keep or ingest is None else None)
        step = self.CONSTRUCT_CHUNK
        # identity feature selection: the chunk is a contiguous row
        # slice, no (rows, F) fancy-index copy needed
        uf_all = uf == list(range(data.shape[1]))

        def _map_pack(start: int) -> np.ndarray:
            """One chunk, feature-major end to end: (F, rows) bins ->
            (G, rows) packed — the ingest buffer's native orientation,
            so no stage writes a strided column."""
            stop = min(start + step, n)
            rows = stop - start
            if uf:
                sl = data[start:stop]
                sub = sl if uf_all else sl[:, uf]
                matT = bmap.map_chunk_T(np.asarray(sub,
                                                   dtype=np.float64))
                cols = {f: matT[i] for i, f in enumerate(uf)}
            else:
                cols = {}
            packed = self._pack_groups_T(cols, rows, dtype)
            if out is not None:
                # disjoint row slices: safe (and faster) to fill from
                # the worker that produced the chunk
                _fill_rows_t(out, start, packed)
            return packed

        starts = [s for s in range(0, max(n, 1), step)
                  if min(s + step, n) > s]
        workers = _construct_workers(self.config)
        if workers > 1 and len(starts) > 1:
            # overlap chunk k+1's map+pack (GIL-releasing numpy:
            # searchsorted, copies) with chunk k's ordered device push —
            # results are consumed in submission order, so the binned
            # matrix and the ingest stream are bit-identical to the
            # sequential loop
            from concurrent.futures import ThreadPoolExecutor
            from collections import deque
            with ThreadPoolExecutor(max_workers=workers) as ex:
                pend: deque = deque()
                it = iter(starts)
                for s in itertools.islice(it, workers + 1):
                    pend.append((s, ex.submit(_map_pack, s)))
                while pend:
                    start, fut = pend.popleft()
                    packed = fut.result()
                    nxt = next(it, None)
                    if nxt is not None:
                        pend.append((nxt, ex.submit(_map_pack, nxt)))
                    if ingest is not None:
                        ingest.push_t(packed)
        else:
            for start in starts:
                packed = _map_pack(start)
                if ingest is not None:
                    ingest.push_t(packed)
        self.binned = out
        if ingest is not None:
            ingest.finish()
            self.device_ingest = ingest

    def _bin_dtype(self):
        max_bin_overall = max((grp.num_total_bin for grp in self.groups),
                              default=2)
        return np.uint8 if max_bin_overall <= 256 else np.uint16

    def bin_matrix(self, data: np.ndarray,
                   cat_oov_sentinel: bool = False) -> np.ndarray:
        """Bin NEW raw rows with this dataset's mappers into the packed
        (n, num_groups) layout — the same transform validation sets get
        (reference: LoadFromFileAlignWithOtherDataset).  For trees trained
        against this dataset, bin-space traversal of the result is EXACT
        (split thresholds are bin uppers).

        cat_oov_sentinel: prediction-path flag — unseen categories map to
        an out-of-range sentinel bin so categorical splits send them to
        the right child like the reference's raw-value predictor (see
        BinMapper.values_to_bins).  Only valid when no categorical
        feature is EFB-bundled (the caller checks)."""
        data = np.asarray(data)
        from .ops.binning import BIN_CATEGORICAL
        if self._vec and self.used_features:
            # one batched mapping over all features (the serving hot
            # path binning); oov_sentinel applies to categorical
            # columns only, like the per-feature oracle below
            mat = self.batched_mapper().map_chunk(
                np.asarray(data[:, self.used_features], dtype=np.float64),
                oov_sentinel=cat_oov_sentinel)
            cols = {f: np.asarray(mat[:, i])
                    for i, f in enumerate(self.used_features)}
        else:
            cols = {f: self.bin_mappers[f].values_to_bins(
                        data[:, f],
                        oov_sentinel=(cat_oov_sentinel and
                                      self.bin_mappers[f].bin_type
                                      == BIN_CATEGORICAL))
                    for f in self.used_features}
        return self._pack_groups(cols, data.shape[0],
                                 self._bin_dtype())

    def _pack_groups(self, cols: Dict[int, np.ndarray], n: int,
                     out_dtype=np.int32) -> np.ndarray:
        """Pack per-feature bin columns into the (n, num_groups) matrix.
        ``out_dtype`` lets callers pack straight into the bin dtype —
        the column assignments C-cast exactly like the ``.astype`` the
        callers used to do, minus one full-matrix pass."""
        out = np.zeros((n, len(self.groups)), dtype=out_dtype)
        for g, grp in enumerate(self.groups):
            if len(grp.feature_indices) == 1:
                out[:, g] = cols[grp.feature_indices[0]]
            else:
                # bundled: shift non-default bins by the feature's offset
                acc = np.zeros(n, dtype=np.int32)
                for sub, f in enumerate(grp.feature_indices):
                    bm = self.bin_mappers[f]
                    # cols may arrive uint8 (map_chunk_T); the offset
                    # arithmetic below needs a wide dtype
                    c = np.asarray(cols[f], dtype=np.int32)
                    offset = grp.bin_offsets[sub]
                    nz = c != bm.most_freq_bin
                    # conflicts resolved last-writer-wins like reference push order
                    shifted = c + offset - (1 if bm.most_freq_bin == 0 else 0)
                    acc = np.where(nz, shifted, acc)
                out[:, g] = acc
        return out

    def _pack_groups_T(self, cols: Dict[int, np.ndarray], n: int,
                       out_dtype=np.int32) -> np.ndarray:
        """Feature-major twin of ``_pack_groups``: (G, n) packed matrix
        from per-feature bin ROWS — every read and write is contiguous,
        and the result is the device ingest buffer's native orientation.
        Same offset/last-writer-wins arithmetic, so ``out.T`` is
        bit-identical to ``_pack_groups``'s output."""
        out = np.zeros((len(self.groups), n), dtype=out_dtype)
        for g, grp in enumerate(self.groups):
            if len(grp.feature_indices) == 1:
                out[g] = cols[grp.feature_indices[0]]
            else:
                acc = np.zeros(n, dtype=np.int32)
                for sub, f in enumerate(grp.feature_indices):
                    bm = self.bin_mappers[f]
                    # cols may arrive uint8 (map_chunk_T); the offset
                    # arithmetic below needs a wide dtype
                    c = np.asarray(cols[f], dtype=np.int32)
                    offset = grp.bin_offsets[sub]
                    nz = c != bm.most_freq_bin
                    shifted = c + offset - (1 if bm.most_freq_bin == 0
                                            else 0)
                    acc = np.where(nz, shifted, acc)
                out[g] = acc
        return out

    def _bundle_sparse(self, sparse: List[int], cols: Dict[int, np.ndarray]) -> None:
        """Greedy conflict-count bundling (reference: dataset.cpp FindGroups).

        ``cols`` may hold fewer rows than the dataset (the streaming path
        passes SAMPLE columns), so row indices are drawn over the columns'
        actual length."""
        n = len(next(iter(cols.values()))) if cols else 0
        # sample rows for conflict counting to bound cost
        rng = np.random.RandomState(self.config.data_random_seed)
        sample = rng.choice(
            n, size=min(n, 50000), replace=False) if n > 50000 else np.arange(n)
        nz_masks = {f: (cols[f][sample] != self.bin_mappers[f].most_freq_bin)
                    for f in sparse}
        self._bundle_greedy(sparse, nz_masks, rng)

    def _bundle_greedy(self, sparse: List[int],
                       nz_masks: Dict[int, np.ndarray], rng) -> None:
        """The greedy coloring over conflict counts.  With the reference
        max_conflict_rate = 0.0 a feature may join a bundle iff it has
        ZERO pairwise overlap with every member, so on the vectorized
        path the per-(feature, bundle) union-mask AND loop collapses to
        lookups in ONE (F_sparse, F_sparse) nonzero-mask matmul
        (ops/construct.py conflict_matrix) — bit-identical bundles,
        asserted by tests/test_construct_device.py."""
        max_conflict = 0  # int(max_conflict_rate * n) with rate = 0.0
        pair = None
        fpos = {f: i for i, f in enumerate(sparse)}
        if self._vec and sparse:
            from .ops.construct import conflict_matrix
            pair = conflict_matrix(np.stack([nz_masks[f] for f in sparse]))
            counts = {f: int(pair[fpos[f], fpos[f]]) for f in sparse}
        else:
            counts = {f: int(nz_masks[f].sum()) for f in sparse}
        bundles: List[List[int]] = []
        bundle_masks: List[Optional[np.ndarray]] = []
        bundle_bins: List[int] = []
        order = sorted(sparse, key=lambda f: -counts[f])
        # reference FindGroups' random-search fallback (dataset.cpp:92):
        # with many groups, each feature probes a random subset instead
        # of every group, bounding the O(F x groups) conflict scan
        max_search = 100
        # a bundle stays within one u8 bin column: groups beyond 256
        # total bins would force the whole matrix to u16 and off the
        # Pallas partition kernel
        max_group_bins = 256
        for f in order:
            nb_add = self.bin_mappers[f].num_bin - 1
            placed = False
            if len(bundles) <= max_search:
                probe = range(len(bundles))
            else:
                probe = rng.choice(len(bundles), size=max_search,
                                   replace=False)
            for bi in probe:
                if bundle_bins[bi] + nb_add > max_group_bins:
                    continue
                if pair is not None:
                    # zero overlap with the union mask == zero pairwise
                    # overlap with every member (counts are non-negative)
                    row = pair[fpos[f]]
                    conflict = int(max((int(row[fpos[g]])
                                        for g in bundles[bi]), default=0))
                else:
                    conflict = int((bundle_masks[bi] & nz_masks[f]).sum())
                if conflict <= max_conflict:
                    bundles[bi].append(f)
                    if pair is None:
                        bundle_masks[bi] |= nz_masks[f]
                    bundle_bins[bi] += nb_add
                    placed = True
                    break
            if not placed:
                bundles.append([f])
                bundle_masks.append(None if pair is not None
                                    else nz_masks[f].copy())
                bundle_bins.append(1 + nb_add)
        for bundle in bundles:
            bundle.sort()
            if len(bundle) == 1:
                f = bundle[0]
                self.groups.append(FeatureGroupInfo(
                    [f], self.bin_mappers[f].num_bin, [0]))
            else:
                # shared column: bin 0 = all-default; feature i occupies
                # [offset_i, offset_i + num_bin_i - 1) (skipping its default bin)
                offsets = []
                cur = 1
                for f in bundle:
                    offsets.append(cur)
                    bm = self.bin_mappers[f]
                    cur += bm.num_bin - (1 if bm.most_freq_bin == 0 else 0)
                self.groups.append(FeatureGroupInfo(bundle, cur, offsets))

    # -- views used by the tree learner ---------------------------------
    def feature_meta_arrays(self) -> Dict[str, np.ndarray]:
        """Per used-feature metadata arrays for the device split finder.

        Features are enumerated in (group, sub-feature) order; ``sub_feature_map``
        translates back to original feature indices.
        """
        feats: List[int] = []
        group_idx: List[int] = []
        bin_start: List[int] = []
        num_bin: List[int] = []
        missing_type: List[int] = []
        default_bin: List[int] = []
        is_cat: List[int] = []
        for g, grp in enumerate(self.groups):
            for sub, f in enumerate(grp.feature_indices):
                bm = self.bin_mappers[f]
                offset = grp.bin_offsets[sub]
                feats.append(f)
                group_idx.append(g)
                if len(grp.feature_indices) == 1:
                    bin_start.append(0)
                    num_bin.append(bm.num_bin)
                    default_bin.append(bm.default_bin)
                else:
                    # bundled feature: bin b (≠ default) lives at offset+b-(mfb==0)
                    shift = offset - (1 if bm.most_freq_bin == 0 else 0)
                    bin_start.append(shift)
                    num_bin.append(bm.num_bin)
                    default_bin.append(bm.default_bin)
                missing_type.append(bm.missing_type)
                is_cat.append(1 if bm.bin_type == BIN_CATEGORICAL else 0)
        return {
            "feature": np.asarray(feats, dtype=np.int32),
            "group": np.asarray(group_idx, dtype=np.int32),
            "bin_start": np.asarray(bin_start, dtype=np.int32),
            "num_bin": np.asarray(num_bin, dtype=np.int32),
            "missing_type": np.asarray(missing_type, dtype=np.int32),
            "default_bin": np.asarray(default_bin, dtype=np.int32),
            "is_categorical": np.asarray(is_cat, dtype=np.int32),
        }

    @property
    def num_groups(self) -> int:
        return len(self.groups)

    @property
    def max_group_bins(self) -> int:
        return max((g.num_total_bin for g in self.groups), default=2)

    def num_used_features(self) -> int:
        return sum(len(g.feature_indices) for g in self.groups)

    # -- binary serialization -------------------------------------------
    # TPU-native replacement for the reference's Dataset binary file
    # (dataset.h:691 SaveBinaryFile / dataset_loader.cpp:417 LoadFromBinFile):
    # one .npz holding the packed bin matrix plus a JSON header with the
    # mappers/groups, so re-binning is skipped entirely on reload.
    BINARY_VERSION = 1

    def save_binary(self, path: str) -> None:
        import json as _json
        header = {
            "version": self.BINARY_VERSION,
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "feature_names": self.feature_names,
            "used_features": self.used_features,
            "bin_mappers": [bm.to_dict() for bm in self.bin_mappers],
            "groups": [{"feature_indices": g.feature_indices,
                        "num_total_bin": g.num_total_bin,
                        "bin_offsets": g.bin_offsets}
                       for g in self.groups],
        }
        host = self.host_binned()
        arrays = {"binned": host if host is not None
                  else np.zeros((self.num_data, 0), np.uint8)}
        md = self.metadata
        if md is not None:
            for name in ("label", "weight", "query_boundaries", "init_score",
                         "positions"):
                v = getattr(md, name)
                if v is not None:
                    arrays[f"meta_{name}"] = np.asarray(v)
            if md.position_ids is not None:
                header["position_ids"] = list(md.position_ids)
        if self.raw_data is not None:
            arrays["raw_data"] = self.raw_data
        with open(path, "wb") as fh:   # keep the exact filename (no .npz)
            np.savez_compressed(fh, header=np.frombuffer(
                _json.dumps(header).encode(), dtype=np.uint8), **arrays)

    @classmethod
    def load_binary(cls, path: str, config: Config) -> "BinnedDataset":
        import json as _json
        with np.load(path) as z:
            header = _json.loads(bytes(z["header"]).decode())
            if header.get("version") != cls.BINARY_VERSION:
                log.fatal("Unsupported binary dataset version: %s",
                          header.get("version"))
            ds = cls(config)
            # re-binning is skipped, but the batched mapper still serves
            # bin_matrix (the serving path) when the config allows it
            ds._resolve_construct_mode(is_reference=False)
            ds._ingest_ok = False
            ds.num_data = int(header["num_data"])
            ds.num_total_features = int(header["num_total_features"])
            ds.feature_names = list(header["feature_names"])
            ds.used_features = [int(f) for f in header["used_features"]]
            ds.bin_mappers = [BinMapper.from_dict(d)
                              for d in header["bin_mappers"]]
            ds.groups = [FeatureGroupInfo(list(g["feature_indices"]),
                                          int(g["num_total_bin"]),
                                          list(g["bin_offsets"]))
                         for g in header["groups"]]
            ds.binned = np.ascontiguousarray(z["binned"])
            ds.metadata = Metadata(ds.num_data)
            for name in ("label", "weight", "query_boundaries", "init_score",
                         "positions"):
                key = f"meta_{name}"
                if key in z:
                    setattr(ds.metadata, name, np.ascontiguousarray(z[key]))
            if "position_ids" in header:
                ds.metadata.position_ids = list(header["position_ids"])
            if "raw_data" in z:
                ds.raw_data = np.ascontiguousarray(z["raw_data"])
            elif config.linear_tree:
                log.fatal(
                    "linear_tree=true requires raw feature values, but the "
                    "binary dataset file was saved without them; re-save it "
                    "with linear_tree=true in the dataset params")
        return ds

    @staticmethod
    def is_binary_file(path: str) -> bool:
        """True when `path` is a saved binary dataset (a .npz zip with our
        header member)."""
        try:
            with open(path, "rb") as fh:
                if fh.read(2) != b"PK":
                    return False
            with np.load(path) as z:
                return "header" in z and "binned" in z
        except Exception:
            return False
