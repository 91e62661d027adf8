"""tree_learner=data with the Pallas kernels on each shard's own rows.

Four of the suite's eight virtual CPU devices stand for the four chips of
one v5e host; the kernels run through the interpreter (toy sizes: it is
slow).  The plain reference is the benchmark's (benchmark/reference.py),
with the limits of the four-chip cell's workload file.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import memory as obs_memory
from lightgbm_tpu.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import datagen  # noqa: E402
import reference  # noqa: E402

LIMITS = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads",
    "higgs-l255-b255-dp4.rows84m.json")))["limits"]
NDEV = 4
TREES = 2


@pytest.fixture
def four_devices(monkeypatch):
    """The booster builds its mesh from jax.devices(): hand it four."""
    devices = jax.devices()[:NDEV]
    assert len(devices) == NDEV, "conftest must provide 8 virtual devices"
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    return devices


def _params(bins, **extra):
    return dict({"objective": "binary", "num_leaves": 15, "max_bin": bins,
                 "learning_rate": 0.1, "verbosity": -1,
                 "tpu_kernel_interpret": True}, **extra)


def _train(X, y, params, trees=TREES):
    """(booster, the step's scope table, the telemetry counters of the
    training): a compile of the interpreted kernels takes the CPU ten
    seconds, so each shape is trained once for every test that reads it."""
    from lightgbm_tpu import obs
    obs.get().reset(mode="counters")
    try:
        ds = lgb.Dataset(X, label=y)
        ds.construct(params)
        bst = lgb.Booster(params, ds)
        for _ in range(trees):
            bst.update()
        bst._gbdt._flush_pending()
        report = obs.get().report()
    finally:
        obs.get().reset(mode="off")
    return bst, scopes.scope_table()["train.fused_step"], report


_TRAINED = {}


def _trained(bins, rows, learner):
    key = (bins, rows, learner)
    if key not in _TRAINED:
        X, y = datagen.make_table(11, rows, 28)
        # the serial learner is the plain XLA one: what the shards'
        # kernels have to agree with, and three times as fast to compile
        params = _params(bins, tree_learner=learner,
                         tpu_kernel_interpret=learner == "data")
        _TRAINED[key] = (X, y, params) + _train(X, y, params)
    return _TRAINED[key]


SHAPES = [(255, 4000), (255, 4003), (63, 3001)]


@pytest.mark.parametrize("bins,rows", SHAPES)
def test_data_parallel_kernels_against_reference_and_serial(
        four_devices, bins, rows):
    """The trees of four shards, each running lgbm_partition and
    lgbm_histogram on its own rows, held (a) against the plain reference
    by the cell's limits, every row of every shard counted once, and (b)
    against the serial learner's, split for split."""
    X, y, params, bst, _, _ = _trained(bins, rows, "data")
    g = bst._gbdt
    plan = g.kernel_plan()
    assert (plan["partition"], plan["hist"], plan["frontier_k"],
            plan["fused"], plan["tree_learner"]) == (
        "pallas", "pallas", 1, "on", "data"), plan
    sb = g.sharded_builder
    assert sb.ndev == NDEV
    counts = np.asarray(sb.local_counts)
    assert counts.sum() == rows and (rows % NDEV == 0) == (
        len(set(counts.tolist())) == 1), counts
    scores = np.asarray(g.scores, dtype=np.float64)
    trees = reference.parse_model(bst.model_to_string())
    leaf_of = reference.route(X, trees)
    numbers, ref_scores, _, _ = reference.follow(
        leaf_of, y, trees, params, len(trees))
    numbers["leaf_count_sum_gap"] = max(
        abs(int(t.leaf_count.sum()) - rows) for t in trees)
    numbers["trees_missing"] = abs(TREES - len(trees))
    diff = np.abs(scores - ref_scores)
    numbers["train_score_gap"] = float(diff.max())
    numbers["train_score_median_gap"] = float(np.median(diff))
    assert all(numbers[k] <= LIMITS[k] for k in LIMITS), numbers

    serial = _trained(bins, rows, "serial")[3]._gbdt
    assert serial.kernel_plan()["partition"] == "xla"
    for a, b in zip(g.models, serial.models, strict=True):
        n = a.num_leaves - 1
        assert n == b.num_leaves - 1 == 14
        np.testing.assert_array_equal(a.split_feature[:n],
                                      b.split_feature[:n])
        np.testing.assert_array_equal(a.threshold_bin[:n],
                                      b.threshold_bin[:n])
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, atol=2e-5)


def test_the_shards_shares_add_up(four_devices):
    """One leaf whose rows lie over the four shards unevenly (one shard
    holds none of them): the shards' lgbm_histogram outputs summed are the
    one-device kernel's histogram of the whole leaf to f32 rounding, and
    their left counts sum to the one-device partition's."""
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
    from lightgbm_tpu.ops.partition_pallas import (make_scalars,
                                                   partition_leaf_pallas,
                                                   sc_rows_for)
    C, G, B, local = 2048, 28, 255, 3000
    n = NDEV * local
    rng = np.random.RandomState(3)
    bins = rng.randint(0, B, size=(n, G)).astype(np.uint8)
    gh = rng.normal(size=(2, n)).astype(np.float32)
    a, b = 2500, 8100           # the leaf: rows [a, b) of shards 0, 1, 2

    def buffers(lo, hi):
        """[C pad][rows lo..hi][pad] as the learner lays a shard out."""
        n_pad = C + (-(-local // C) + 2) * C if hi - lo <= local \
            else C + (-(-n // C) + 2) * C
        pb = np.zeros((32, n_pad), np.uint8)
        pg = np.zeros((8, n_pad), np.float32)
        pb[:G, C:C + hi - lo] = bins[lo:hi].T
        pg[:2, C:C + hi - lo] = gh[:, lo:hi]
        return pb, pg

    def one(pb, pg, start, cnt):
        hist = leaf_hist_pallas(pb, pg, start, cnt, num_bins=B,
                                row_chunk=C, num_groups=G, interpret=True)
        sp = jnp.zeros((sc_rows_for(32), pb.shape[1]), jnp.int32)
        nl = partition_leaf_pallas(
            pb, pg, sp, make_scalars(start, cnt, 5, 0, 0, B, 0, 0, 100, 0),
            row_chunk=C, ghi_live=3, pack_rowid=False, interpret=True)[3]
        return hist, nl[0, 0]

    pb, pg = buffers(0, n)
    whole_hist, whole_nl = jax.jit(one)(pb, pg, C + a, b - a)
    assert int(whole_nl) == int((bins[a:b, 5] <= 100).sum())

    blocks = [buffers(d * local, (d + 1) * local) for d in range(NDEV)]
    pbs = np.concatenate([blk[0] for blk in blocks], axis=1)
    pgs = np.concatenate([blk[1] for blk in blocks], axis=1)
    lo = [min(max(a - d * local, 0), local) for d in range(NDEV)]
    hi = [min(max(b - d * local, 0), local) for d in range(NDEV)]
    starts = np.asarray([C + s for s in lo], np.int32)
    cnts = np.asarray([h - s for s, h in zip(lo, hi)], np.int32)
    assert cnts.sum() == b - a and cnts[3] == 0 and len(set(cnts)) == 4

    def shard(pb, pg, start, cnt):
        hist, nl = one(pb, pg, start[0], cnt[0])
        with scopes.scope("hist_sync"):
            return jax.lax.psum(hist, "data"), nl[None]

    mesh = Mesh(np.asarray(four_devices), ("data",))
    hist, nls = jax.jit(jax.shard_map(
        shard, mesh=mesh, check_vma=False,
        in_specs=(P(None, "data"), P(None, "data"), P("data"), P("data")),
        out_specs=(P(), P("data"))))(pbs, pgs, starts, cnts)
    assert int(np.asarray(nls).sum()) == int(whole_nl)
    scale = float(np.abs(np.asarray(whole_hist)).max())
    np.testing.assert_allclose(np.asarray(hist), np.asarray(whole_hist),
                               atol=4e-6 * scale)


def _collective(name):
    return name.startswith(("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"))


def _stem(name):
    return name.split(".")[0]


def test_sharded_step_names_its_collectives_and_holds_no_global_rows(
        four_devices):
    """The sharded train.fused_step's scope table gives every collective
    the phase hist_sync and leaves unattributed only what the serial step
    leaves; no device holds an array sized by the rows of all shards, and
    the first device no more than the last."""
    bins, rows = SHAPES[0]
    serial_table = _trained(bins, rows, "serial")[4]
    bst, table = _trained(bins, rows, "data")[3:5]
    g = bst._gbdt
    assert g.learner is g.sharded_builder.learner
    collectives = {n: ph for n, ph in table.items() if _collective(n)}
    assert collectives and set(collectives.values()) == {"hist_sync"}, \
        collectives
    loose = {_stem(n) for n, ph in serial_table.items() if ph is None}
    # the mesh's own bookkeeping (which shard am I) carries no scope;
    # a computation's parameters are not operations
    loose |= {"partition-id", "replica-id", "param"}
    assert {_stem(n) for n, ph in table.items() if ph is None} <= loose

    _ = g.scores     # a read makes the one array of all rows, and only it
    assert g._scores_arr.shape == (rows,)
    bst.update()     # ... and the next step's layout init lets it go
    assert g._scores_arr is None and g._phys is not None
    per_device = {d.id: 0 for d in four_devices}
    owners = obs_memory.snapshot()["owners"]
    assert owners["parallel.binned_sharded"]["device_bytes"] > 0
    for (name, _), (ref, provider) in list(
            obs_memory.LEDGER._providers.items()):
        owner = ref()
        if owner is not g and owner is not g.learner \
                and owner is not g.sharded_builder:
            continue
        for arr in jax.tree_util.tree_leaves(provider(owner)):
            if not isinstance(arr, jax.Array) or arr.is_deleted():
                continue
            # nothing has a dimension of all the rows, and what is as
            # long as one shard's rows is cut over the mesh (a toy
            # shard's pads are longer than its rows, so the whole's
            # length says nothing)
            assert rows not in arr.shape, (name, arr.shape)
            by_rows = max(arr.shape, default=0) >= rows // NDEV
            for shard in arr.addressable_shards:
                assert not by_rows \
                    or shard.data.size * NDEV == arr.size, \
                    (name, arr.shape, shard.data.shape)
                if by_rows:
                    per_device[shard.device.id] += shard.data.nbytes
    ids = [d.id for d in four_devices]
    # (the learner's tables by features and bins sit on the first device:
    # a few KB that no row count moves)
    assert per_device[ids[-1]] > 0
    assert per_device[ids[0]] <= per_device[ids[-1]], per_device
    # the objective's and the metrics' per-row copies went back to the host
    for holder in (g.objective, *g.train_metrics):
        assert not any(isinstance(v, jax.Array) and v.shape[:1] == (rows,)
                       for v in vars(holder).values())


def test_hist_sync_counters(four_devices):
    """train.parallel.hist_sync_bytes counts, from shapes, what one shard
    hands to the histogram sums: (splits + 1) x G x B x 2 x 4 a tree."""
    bins, rows = SHAPES[2]
    bst, _, report = _trained(bins, rows, "data")[3:]
    g = bst._gbdt
    splits = sum(t.num_leaves - 1 for t in g.models[:TREES])
    lr = g.learner
    assert report["counters"]["train.parallel.hist_sync_bytes"] == \
        (splits + TREES) * lr.G * lr.B * 2 * 4
    assert report["gauges"]["train.parallel.shards"] == NDEV
    assert "train.parallel.hist_sync_bytes" not in \
        _trained(bins, rows, "serial")[5]["counters"]
