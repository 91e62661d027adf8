"""Leaf-size-adaptive chunk policy for the histogram/partition passes.

The tree learner processes every per-leaf pass (histogram build, leaf
partition, mega-kernel both-children histogram) in fixed-size row
chunks (``tpu_row_chunk``).  The chunk loop's trip count is dynamic —
all-padding chunks are never executed — but the LAST (often only)
chunk still pays the full chunk width regardless of how few live rows
the leaf holds: at ``num_leaves=255`` and the 4096-row default almost
every split processes a full 4096-row chunk for a leaf of a few dozen
rows.  PERF.md round 12 measured this padded-chunk compute at **68%**
of the training iteration on the 2-core CPU host.

This module picks the chunk width *per pass, per leaf* from a bounded
static menu (<= 4 power-of-two sizes, seeded by ``tpu_row_chunk``):

* a leaf whose live rows fit ONE chunk of a smaller menu width runs
  that width's separately-traced pass variant instead of the base
  grid;
* larger leaves stay on the base grid — multi-chunk processing must
  reproduce the fixed grid's chunk boundaries exactly, because the
  partition's right-side row order depends on them.

Band dispatch is **branch-free**: every width's pass is wrapped in a
``fori_loop`` whose trip count is 0 unless that band is selected.
``lax.switch``/``lax.cond`` would force whole-buffer copies of the
multi-MB row buffers per split (measured — the round-1 conditional
pathology); zero-trip loops skip at runtime and their carries alias in
place, which the tree build already relies on everywhere.

Bit-identity contract (``tpu_chunk_policy=adaptive`` trains trees
bit-identical to ``fixed``):

* **Partition** — a single-window compaction at ANY width W >= cnt
  produces byte-identical buffers to the base grid's single chunk:
  the move is an integer sort + gather (exact), lefts pack forward
  and rights land at ``[start+nl, start+cnt)`` in encounter order in
  both forms, and writes are masked to the live rows.
* **Histogram** — a single chunk of width W accumulates the same live
  rows plus exactly-zero masked padding terms.  Adding exact zeros
  never changes an f32 sum, but XLA's dot reduction STRATEGY changes
  with the contraction length: measured on this stack, widths <= 256
  reduce the live prefix identically to the 4096-wide oracle while
  512/1024 diverge from ~266 live rows up.  Histogram bands are
  therefore capped at ``HIST_EXACT_MAX`` (the e2e matrix in
  tests/test_chunkpolicy.py pins the equivalence; quantized integer
  carriers are exact at any width by construction).

``tpu_row_chunk=auto`` is the constant ``DEFAULT_ROW_CHUNK`` and
``tpu_chunk_policy=auto`` the small-leaf rule in ``resolve``: both follow
from the training parameters and the data's shape alone and consult
nothing else.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# Smaller menu widths considered below the base width (descending).
# The menu is the base width plus every entry strictly below it, capped
# at 4 sizes total.
MENU_LADDER = (1024, 256, 64)

# Histogram passes only band down to widths whose dot-reduction order
# is bit-identical to the base contraction (see module docstring);
# partition passes may use every menu width (integer-exact).
HIST_EXACT_MAX = 256

# base width under tpu_row_chunk=auto
# (PERF.md round 3: best end-to-end on v5e at equal slope)
DEFAULT_ROW_CHUNK = 4096

__all__ = [
    "ChunkPolicy", "DEFAULT_ROW_CHUNK", "HIST_EXACT_MAX", "MENU_LADDER",
    "note_variant", "parse_row_chunk", "resolve", "resolve_base",
    "reset_variant_log", "variant_log", "waste_stats",
]


# ---------------------------------------------------------------------------
# traced-variant registry: every time a (pass, width) variant is built
# into a traced program the learner notes it here, so tests and the
# jaxlint tier-B ``chunk.adaptive`` budget can pin the compiled-variant
# count to the menu — the training-side analog of the serving engine's
# per-(kind, bucket) compile-count keys.
# ---------------------------------------------------------------------------
_VARIANT_LOG: Dict[Tuple[str, int], int] = {}


def note_variant(pass_name: str, width: int) -> None:
    key = (str(pass_name), int(width))
    _VARIANT_LOG[key] = _VARIANT_LOG.get(key, 0) + 1


def variant_log() -> Dict[Tuple[str, int], int]:
    return dict(_VARIANT_LOG)


def reset_variant_log() -> None:
    _VARIANT_LOG.clear()


# ---------------------------------------------------------------------------
# policy object
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ChunkPolicy:
    """Static per-learner chunk plan.

    ``sizes`` is the full menu (base first, strictly descending);
    ``hist_sizes`` the subset the histogram passes may band to.  With
    ``adaptive=False`` (or a single-entry menu) every pass runs the
    base grid and the learner's lowering is unchanged.
    """

    base: int
    adaptive: bool = False
    sizes: Tuple[int, ...] = field(default=None)  # type: ignore[assignment]
    hist_sizes: Tuple[int, ...] = field(default=None)  # type: ignore[assignment]

    def __post_init__(self):
        base = int(self.base)
        if base <= 0:
            raise ValueError(f"chunk base must be positive, got {base}")
        sizes = (base,) + tuple(w for w in MENU_LADDER if w < base)
        sizes = sizes[:4]
        hist = (base,) + tuple(w for w in sizes[1:] if w <= HIST_EXACT_MAX)
        object.__setattr__(self, "sizes", sizes)
        object.__setattr__(self, "hist_sizes", hist)

    # -- traced helpers -------------------------------------------------
    def band(self, cnt, sizes: Tuple[int, ...]):
        """Traced band index into ``sizes`` (descending): the smallest
        width covering ``cnt`` in one chunk; 0 (the base grid) when
        none does."""
        import jax.numpy as jnp
        idx = jnp.int32(0)
        for w in sizes[1:]:
            idx = idx + (cnt <= w).astype(jnp.int32)
        return idx

    def small_trips(self, cnt, sizes: Tuple[int, ...]):
        """Per-small-width trip counts (0 or 1): entry i-1 gates the
        ``sizes[i]`` variant.  Empty leaves run nothing."""
        import jax.numpy as jnp
        band = self.band(cnt, sizes)
        live = cnt > 0
        return [((band == i) & live).astype(jnp.int32)
                for i in range(1, len(sizes))]

    def base_cover(self, cnt, sizes: Tuple[int, ...]):
        """Base-grid chunk count covering ``cnt`` — zero when a smaller
        band handles the leaf (the all-padding chunks the fixed grid
        would still execute are skipped outright)."""
        import jax.numpy as jnp
        n = (cnt + self.base - 1) // self.base
        if not self.adaptive or len(sizes) < 2:
            return n
        return jnp.where(self.band(cnt, sizes) == 0, n, 0)

    # -- host-side helpers ----------------------------------------------
    def band_of(self, cnt: int, sizes: Optional[Tuple[int, ...]] = None
                ) -> int:
        sizes = sizes or self.sizes
        if not self.adaptive:
            return 0
        idx = 0
        for i, w in enumerate(sizes[1:], 1):
            if cnt <= w:
                idx = i
        return idx

    def padded_rows(self, cnt: int,
                    sizes: Optional[Tuple[int, ...]] = None) -> int:
        """Rows one pass actually processes for a leaf of ``cnt`` live
        rows under this policy (``sizes`` picks the pass menu: the
        full partition menu by default, ``hist_sizes`` for the
        exactness-capped histogram bands)."""
        if cnt <= 0:
            return 0
        sizes = sizes or self.sizes
        w = sizes[self.band_of(cnt, sizes)]
        return -(-cnt // w) * w


def parse_row_chunk(spec) -> Optional[int]:
    """``tpu_row_chunk`` accepts an integer or ``auto`` (the constant
    ``DEFAULT_ROW_CHUNK``).  Returns None for auto."""
    s = str(spec).strip().lower()
    if s in ("auto", ""):
        return None
    try:
        # int(float(.)) matches the int-param coercion this knob had
        # before it learned "auto" (sklearn grids pass 4096.0)
        v = int(float(s))
    except ValueError:
        raise ValueError(
            f"tpu_row_chunk must be 'auto' or a positive integer, "
            f"got {spec!r}")
    if v <= 0:
        raise ValueError(f"tpu_row_chunk must be positive, got {v}")
    return v


def _pow2ceil(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def resolve_base(row_chunk) -> int:
    """Uncapped base chunk width: the explicit ``tpu_row_chunk`` value,
    or the constant under ``auto``.  Dataset construction and the
    learner both resolve through here so the streamed ingest geometry
    matches the training geometry."""
    spec = parse_row_chunk(row_chunk)
    return DEFAULT_ROW_CHUNK if spec is None else spec


def resolve(chunk_policy, row_chunk, num_data: int, num_leaves: int,
            eligible: bool) -> Tuple[int, "ChunkPolicy"]:
    """(base row chunk, policy) for one learner from the two option
    values ``tpu_chunk_policy`` and ``tpu_row_chunk``.

    The base is derived HERE and nowhere else (a power of two, no wider
    than the rows need and than the partition's packed sort key allows),
    so ``policy.base`` can never drift from the grid the partition loops
    stride.  ``eligible`` gates the adaptive mode: the caller
    (models/plan.py) owns the path checks and the warning for an
    explicit ``adaptive`` it refuses.
    """
    base = _pow2ceil(min(resolve_base(row_chunk),
                         max(_pow2ceil(num_data), 256)))
    # the partition packs (dest << bits) | src into one uint32 sort key
    base = min(base, 1 << 15)
    mode = str(chunk_policy or "auto").strip().lower()
    if mode not in ("auto", "fixed", "adaptive"):
        mode = "auto"      # Config._post_process already warned
    if mode == "fixed" or not eligible:
        return base, ChunkPolicy(base, adaptive=False)
    # auto, the small-leaf regime: adaptive pays when the fixed grid's
    # worst case (one base chunk per split) exceeds the data actually
    # touched per tree level, i.e. when the average leaf is smaller
    # than the chunk
    adaptive = (mode == "adaptive"
                or max(num_leaves - 1, 1) * base > num_data)
    policy = ChunkPolicy(base, adaptive=adaptive)
    if len(policy.sizes) < 2:
        policy = ChunkPolicy(base, adaptive=False)
    return base, policy


# ---------------------------------------------------------------------------
# padding-waste accounting (telemetry: train.chunk.* gauges)
# ---------------------------------------------------------------------------
def waste_stats(leaf_counts, policy: "ChunkPolicy") -> Dict[str, float]:
    """Per-band occupancy + padding-waste ratio of one tree's leaves
    (host ints — called at tree materialization time with values the
    trainer already has; zero device ops).

    ``waste`` is the fraction of processed rows that were padding
    under ``policy``, accounting BOTH pass families — the partition
    (full menu) and the exactness-capped histogram bands
    (``hist_sizes``; leaves in the 256..base gap still pay a full
    base-width histogram chunk and the gauge must not hide it);
    ``fixed_waste`` is the same for the base-only grid, so the pair
    shows what the adaptive bands actually saved.  Per-band occupancy
    is the partition-window view (one leaf = one selected width)."""
    live = 0
    part_padded = 0
    hist_padded = 0
    fixed_padded = 0
    per_band: Dict[int, Dict[str, float]] = {}
    fixed = ChunkPolicy(policy.base, adaptive=False)
    for cnt in leaf_counts:
        cnt = int(cnt)
        if cnt <= 0:
            continue
        live += cnt
        part_padded += policy.padded_rows(cnt)
        hist_padded += policy.padded_rows(cnt, policy.hist_sizes)
        fixed_padded += 2 * fixed.padded_rows(cnt)
        w = policy.sizes[policy.band_of(cnt)]
        b = per_band.setdefault(w, {"leaves": 0, "rows": 0, "padded": 0})
        b["leaves"] += 1
        b["rows"] += cnt
        b["padded"] += policy.padded_rows(cnt)
    padded = part_padded + hist_padded
    out: Dict[str, float] = {
        "live_rows": float(live),
        "padded_rows": float(padded),
        "waste": 1.0 - 2 * live / padded if padded else 0.0,
        "fixed_waste": (1.0 - 2 * live / fixed_padded
                        if fixed_padded else 0.0),
    }
    for w, b in sorted(per_band.items()):
        band = f"band_{1 << int(math.log2(w)):d}" if w else "band_0"
        out[f"{band}.leaves"] = float(b["leaves"])
        out[f"{band}.occupancy"] = (b["rows"] / b["padded"]
                                    if b["padded"] else 0.0)
    return out
