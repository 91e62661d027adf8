"""The two row kernels at widths over one 32-sublane tile of bins
(ops/partition_pallas.py, ops/histogram_pallas.py; PERF.md section 6, PR
35), interpreted on the CPU at toy sizes.

Each width is a case: 28 features are one u8 tile (the benchmark's HIGGS
shape: one pass, one trip), 33 are two tiles with one feature in the
second, 137 are five, 300 are ten.  ``_narrow_passes`` holds the partition
kernel to 64 sublanes a pass, so 137 and 300 features move in 3 and 5
passes, as 2000 do in 11 at the chip's chunk.
"""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops import partition_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import datagen  # noqa: E402
import reference  # noqa: E402

WIDTHS = [28, 33, 137, 300]
ROWS, C = 3000, 256
LIMITS = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads",
    "epsilon-l255-b255.rows600k.json")))["limits"]


@pytest.fixture
def narrow_passes(monkeypatch):
    """At a 256-row chunk every width here fits the VMEM limit in one
    pass; 64 sublanes a pass is what the limit does to 2000 features at
    the chip's 4096."""
    monkeypatch.setattr(
        partition_pallas, "pass_rows_for",
        lambda groups, chunk, limit: min(64, 32 * -(-groups // 32)))


def _booster(G, **extra):
    X, y = datagen.make_table(11, ROWS, G)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1,
              "learning_rate": 0.1, "verbosity": -1, "tpu_row_chunk": C,
              **extra}
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    return lgb.Booster(params, ds), X, y, params


def _stable(pb, pg, start, cnt, left):
    """The contract's layout: lefts in order, rights behind them in order."""
    order = np.concatenate([np.where(left)[0], np.where(~left)[0]]) + start
    pb, pg = pb.copy(), pg.copy()
    pb[:, start:start + cnt] = pb[:, order]
    pg[:, start:start + cnt] = pg[:, order]
    return pb, pg


@pytest.mark.parametrize("G", WIDTHS)
def test_partition_layout_is_the_xla_partitions(G, narrow_passes):
    """``lgbm_partition`` against ``learner.py:_partition_leaf``'s XLA
    form on the same binned table.  The kernel's layout is the stable
    one, bit for bit in the bins and in every live payload row: lefts in
    order, rights behind them in order.  The XLA form has the same left
    count and the same lefts, bit for bit; its rights are the same rows
    (payload row 2 numbers them), each chunk's in order, the chunks packed
    backward from the range's end."""
    kernel = _booster(G, tpu_kernel_interpret=True)[0]._gbdt.learner
    oracle = _booster(G, tpu_partition_kernel="xla")[0]._gbdt.learner
    assert kernel.plan.partition == "pallas" \
        and oracle.plan.partition == "xla"
    assert kernel._pb_rows // kernel.plan.pass_rows == {
        28: 1, 33: 1, 137: 3, 300: 5}[G]
    Np = kernel.N_pad
    assert Np == oracle.N_pad and oracle._part0.shape == (G, Np)
    bins = np.asarray(oracle._part0)
    np.testing.assert_array_equal(np.asarray(kernel._part0)[:G], bins)
    rng = np.random.RandomState(G)
    pg = rng.randn(8, Np).astype(np.float32)
    pg[2] = np.arange(Np)
    live = kernel._ghi_live
    for trial in range(3):
        start = kernel.row0 + int(rng.randint(0, 700))
        cnt = int(rng.randint(1, 2000))
        col = [0, G - 1, int(rng.randint(0, G))][trial]
        nb = int(bins[col].max()) + 1
        thr = int(rng.randint(0, nb))
        scalars = tuple(jnp.int32(v) for v in (0, 0, nb, 0, 0, thr, 0)) + (
            jnp.bool_(False), jnp.zeros((1,), jnp.bool_))
        got, got_nl = jax.jit(kernel._partition_leaf)(
            {"part_bins": kernel._part0, "part_ghi": jnp.asarray(pg),
             "sc_packed": jnp.zeros(
                 (partition_pallas.sc_rows_for(kernel.plan.pass_rows), Np),
                 jnp.int32)}, start, cnt, col, scalars)
        ref, ref_nl = jax.jit(oracle._partition_leaf)(
            {"part_bins": oracle._part0, "part_ghi": jnp.asarray(pg),
             "sc32": jnp.zeros((G + 8, Np), jnp.int32)},
            start, cnt, col, scalars)
        left = bins[col, start:start + cnt] <= thr
        nl = int(left.sum())
        assert int(got_nl) == int(ref_nl) == nl
        want_pb, want_pg = _stable(bins, pg, start, cnt, left)
        got_pb = np.asarray(got["part_bins"])
        got_pg = np.asarray(got["part_ghi"])
        np.testing.assert_array_equal(got_pb[:G], want_pb)
        assert not got_pb[G:].any()
        np.testing.assert_array_equal(got_pg[:live].view(np.int32),
                                      want_pg[:live].view(np.int32))
        ref_pb = np.asarray(ref["part_bins"])
        ref_pg = np.asarray(ref["part_ghi"])
        lefts = slice(0, start + nl)
        np.testing.assert_array_equal(ref_pb[:, lefts], want_pb[:, lefts])
        np.testing.assert_array_equal(ref_pg[:live, lefts],
                                      want_pg[:live, lefts])
        np.testing.assert_array_equal(ref_pb[:, start + cnt:],
                                      want_pb[:, start + cnt:])
        rights = slice(start + nl, start + cnt)
        by_id = np.argsort(ref_pg[2, rights], kind="stable")
        np.testing.assert_array_equal(ref_pb[:, rights][:, by_id],
                                      want_pb[:, rights])
        np.testing.assert_array_equal(ref_pg[:live, rights][:, by_id],
                                      want_pg[:live, rights])


@pytest.mark.parametrize("G", WIDTHS)
def test_leaf_histogram_is_the_xla_loops(G):
    """``lgbm_histogram`` over its feature tiles against the XLA chunk
    loop: every product is exact in both, so they differ by f32 summation
    order alone (tests/test_pallas_interpret.py: 1e-6 of the largest
    bin).  Rows outside the leaf carry weights 1e30 times the leaf's, and
    the bin rows past the last feature are not zero: neither may leak."""
    from lightgbm_tpu.ops.histogram import leaf_hist_slice
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas, tiles
    B, Np, start, cnt = 255, 8 * C, C + 37, 3 * C + 17
    G32 = -(-G // 32) * 32 + 32          # a carrier padded to whole passes
    assert tiles(B, G)[0] == {28: 1, 33: 2, 137: 5, 300: 10}[G]
    rng = np.random.RandomState(G)
    pb = rng.randint(0, 255, (G32, Np)).astype(np.uint8)
    pb[:G] = rng.randint(0, B, (G, Np))
    pg = (rng.randn(8, Np) * 1e30).astype(np.float32)
    pg[:2, start:start + cnt] = rng.randn(2, cnt)
    kw = dict(num_bins=B, row_chunk=C, num_groups=G)
    args = (jnp.asarray(pb), jnp.asarray(pg), jnp.int32(start),
            jnp.int32(cnt))
    got = np.asarray(jax.jit(functools.partial(
        leaf_hist_pallas, interpret=True, **kw))(*args))
    ref = np.asarray(jax.jit(functools.partial(
        leaf_hist_slice, **kw))(*args))
    assert got.shape == ref.shape == (G, B, 2) and np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=1e-6,
                               atol=1e-6 * np.abs(ref).max())


@pytest.mark.parametrize("G", [137, 300])
def test_five_trees_held_to_the_plain_reference(G, narrow_passes):
    """Five trees of a 3000-row binary table through both kernels, held to
    benchmark/reference.py's ``route`` and ``follow`` under the wide
    cell's five limits; the tiling gauges say how the width was cut."""
    obs.get().reset(mode="counters")
    try:
        bst, X, y, params = _booster(G, tpu_kernel_interpret=True)
        gauges = bst.telemetry_report(include_memory=False)["gauges"]
    finally:
        obs.get().reset(mode="off")
    assert gauges["train.hist.feature_tiles"] == -(-G // 32)
    assert gauges["train.partition.payload_tiles"] == {137: 3, 300: 5}[G]
    kp = bst._gbdt.kernel_plan()
    assert (kp["partition"], kp["hist"]) == ("pallas", "pallas")
    for _ in range(5):
        bst.update()
    scores = np.asarray(bst._gbdt.scores, np.float64)
    trees = reference.parse_model(bst.model_to_string())
    numbers, ref_scores, _, _ = reference.follow(
        reference.route(X, trees), y, trees, params, 5)
    gap = np.abs(scores - ref_scores)
    numbers.update(
        trees_missing=abs(5 - len(trees)),
        leaf_count_sum_gap=max(abs(int(t.leaf_count.sum()) - ROWS)
                               for t in trees),
        train_score_gap=float(gap.max()),
        train_score_median_gap=float(np.median(gap)))
    assert all(t.num_leaves == 15 for t in trees)
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit, (name, numbers)
