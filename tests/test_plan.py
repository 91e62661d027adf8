"""Which split-step program a learner runs (lightgbm_tpu/models/plan.py).

One table over ``PlanFacts``: no dataset, no array, no backend.  A row is
(facts that differ from the benchmark cells' shape, the plan fields they
must give, a decision and a phrase its ``why`` must hold, a phrase a
warning must hold or None).  Then three real toy trainings whose
``kernel_plan()`` must equal ``resolve`` on facts written by hand.
"""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.models import plan

OPTIONS = {k: getattr(Config({}), k) for k in plan.OPTION_FIELDS}
# benchmark/configs/higgs-l255-b255.json at rows42m on the chip
CELL = dict(backend="tpu", rows=42_000_000, F=28, G=28, B=255,
            num_leaves=255)
CELL_PLAN = dict(partition="pallas", hist="pallas", fast_search=False,
                 search="xla",
                 mega="off", frontier_k=1, hist_state="xla",
                 row_chunk=4096, pass_rows=32, chunk_adaptive=False,
                 pack_rowid=False, scatter_groups=False, linear_gain=False)
SMOKE = dict(rows=10_500_000)          # chip_smoke.py's shape (PR 21)
TOY_CPU = dict(backend="cpu", rows=2000, num_leaves=31)
DATA4 = dict(TOY_CPU, parallel_mode="data", axis_name=True, num_shards=4)
DP4_CHIP = dict(parallel_mode="data", axis_name=True, num_shards=4)
# benchmark/configs/epsilon-l255-b255.json at rows600k, and the shape whose
# default plan did not compile before PR 35 (ISSUE 35's table)
WIDE = dict(rows=600_000, F=2000, G=2000)
WIDE_PLAN = dict(partition="pallas", hist="pallas", fast_search=True,
                 search="xla", mega="off", frontier_k=1, hist_state="xla",
                 row_chunk=4096, pass_rows=192)
F137 = dict(rows=1_000_000, F=137, G=137)


def facts(**kw):
    return plan.PlanFacts(**{**OPTIONS, **CELL, **kw})


# tpu_frontier_k=auto is the one-leaf body on every shape (PR 36)
AUTO_K = "1 (tpu_frontier_k=auto: the one-leaf body was no slower"
K4 = dict(tpu_frontier_k="4")
K4_REFUSED = "tpu_frontier_k=4 cannot be honoured "

CASES = {
    "cell_b255": ({}, CELL_PLAN, ("frontier_k", AUTO_K), None),
    "cell_b255_mega": ({}, CELL_PLAN,
                       ("mega", "off (rows 42,000,000 >= 2^24: the f32 "
                                "count cumsum of the fast search is exact "
                                "only below it)"), None),
    "cell_b63": (dict(B=63), CELL_PLAN,
                 ("frontier_k", "(PERF_LEDGER.jsonl, PR 36)"), None),
    "cell_b63_chunks": (dict(B=63), CELL_PLAN,
                        ("chunk_adaptive", "partition=pallas"), None),
    # an explicit request still builds the batched body at the cells' shape
    "cell_b255_k4": (K4, dict(CELL_PLAN, frontier_k=4),
                     ("hist_state", "search=xla"), None),
    # PR 21's smoke line
    "smoke_10m5": (SMOKE, dict(fast_search=True, search="pallas",
                               mega="pallas", frontier_k=1,
                               hist_state="xla"),
                   ("hist_state", "mega=pallas"), None),
    "smoke_10m5_k4": (dict(SMOKE, **K4),
                      dict(fast_search=True, search="pallas", mega="pallas",
                           frontier_k=4, hist_state="xla"),
                      ("hist_state", "mega=pallas"), None),
    # PR 21's 0.42 s arm: the plan no default and no cell reaches
    "smoke_10m5_mega_off": (dict(SMOKE, tpu_megakernel="off"),
                            dict(partition="pallas", search="pallas",
                                 mega="off", frontier_k=1,
                                 hist_state="flat"),
                            ("mega", "tpu_megakernel=off"), None),
    "smoke_10m5_mega_off_k4": (dict(SMOKE, tpu_megakernel="off", **K4),
                               dict(search="pallas", mega="off",
                                    frontier_k=1, hist_state="flat"),
                               ("frontier_k", "search=pallas with mega=off"),
                               K4_REFUSED + "(search=pallas with mega=off"),
    # width: a kernel whose VMEM at the shape is over the limit is not
    # named, and the partition moves the bins a few tiles a pass
    "wide_2000": (WIDE, WIDE_PLAN,
                  ("mega", "lgbm_split_mega would hold"), None),
    "wide_2000_search": (WIDE, WIDE_PLAN,
                         ("search", "lgbm_split_search would hold 143,"),
                         None),
    "wide_2000_frontier": (WIDE, WIDE_PLAN, ("frontier_k", AUTO_K), None),
    "wide_2000_frontier_k4": (dict(WIDE, **K4), WIDE_PLAN,
                              ("frontier_k", "a histogram state of 1,056,"),
                              K4_REFUSED + "(a histogram state of 1,056,"),
    "wide_2000_mega_off": (dict(WIDE, tpu_megakernel="off"), WIDE_PLAN,
                           ("mega", "tpu_megakernel=off"), None),
    "wide_137": (F137, dict(partition="pallas", hist="pallas",
                            search="pallas", mega="off", frontier_k=1,
                            hist_state="flat", pass_rows=160),
                 ("mega", "lgbm_split_mega would hold 18,"), None),
    "wide_124": (dict(F137, F=124, G=124),
                 dict(search="pallas", mega="pallas", frontier_k=1,
                      pass_rows=128), ("hist_state", "mega=pallas"), None),
    "wide_pack_rowid": (dict(WIDE, tpu_pack_rowid=True),
                        dict(WIDE_PLAN, pack_rowid=False),
                        ("pack_rowid", "2016 bin rows move 192 a pass"),
                        None),
    "rows_2p24_less_1": (dict(rows=(1 << 24) - 1),
                         dict(fast_search=True, search="pallas",
                              mega="pallas", frontier_k=1),
                         ("hist_state", "mega=pallas"), None),
    "rows_2p24_less_1_k4": (dict(rows=(1 << 24) - 1, **K4),
                            dict(fast_search=True, search="pallas",
                                 mega="pallas", frontier_k=4),
                            ("hist_state", "frontier_k=4"), None),
    "rows_2p24": (dict(rows=1 << 24),
                  dict(fast_search=False, search="xla", mega="off",
                       frontier_k=1),
                  ("fast_search", "rows 16,777,216 >= 2^24"), None),
    "rows_2p24_k4": (dict(rows=1 << 24, **K4),
                     dict(fast_search=False, search="xla", mega="off",
                          frontier_k=4),
                     ("fast_search", "rows 16,777,216 >= 2^24"), None),
    # the leaf-histogram kernel goes where the partition kernel goes, f32
    # gradients only
    "hist_cpu": (TOY_CPU, dict(partition="xla", hist="xla"),
                 ("hist", "partition=xla"), None),
    "hist_categorical": (dict(has_categorical=True),
                         dict(partition="xla", hist="xla"),
                         ("hist", "partition=xla"), None),
    "hist_data_parallel": (DATA4, dict(partition="xla", hist="xla"),
                           ("hist", "partition=xla"), None),
    "hist_quantized": (dict(quantized=True),
                       dict(partition="pallas", hist="xla"),
                       ("hist", "use_quantized_grad"), None),
    "hist_smoke_mega": (SMOKE, dict(mega="pallas", hist="pallas"),
                        ("hist_state", "mega=pallas"), None),
    "hist_interpret": (dict(TOY_CPU, interpret=True),
                       dict(partition="pallas", hist="pallas"),
                       ("frontier_k", AUTO_K), None),
    "cpu": (TOY_CPU, dict(partition="xla", fast_search=True, search="xla",
                          mega="off", frontier_k=1, hist_state="xla",
                          row_chunk=2048, chunk_adaptive=True),
            ("partition", "backend cpu without tpu_kernel_interpret"),
            None),
    "cpu_interpret": (dict(TOY_CPU, interpret=True),
                      dict(partition="pallas", search="pallas",
                           mega="pallas", frontier_k=1, hist_state="xla",
                           chunk_adaptive=False),
                      ("frontier_k", AUTO_K), None),
    "categorical": (dict(SMOKE, has_categorical=True),
                    dict(partition="xla", fast_search=False, search="xla",
                         mega="off", frontier_k=1),
                    ("partition", "categorical features"), None),
    "categorical_k4": (dict(SMOKE, has_categorical=True, **K4),
                       dict(partition="xla", fast_search=False,
                            search="xla", mega="off", frontier_k=4),
                       ("partition", "categorical features"), None),
    "u16_bins": (dict(SMOKE, host_bin_dtype="uint16", B=300),
                 dict(partition="xla", search="xla", mega="off"),
                 ("partition", "uint16 bins"), None),
    "bins_over_256": (dict(TOY_CPU, host_bin_dtype="uint16", B=300,
                           tpu_megakernel="xla"),
                      dict(mega="off"), ("mega", "300 bins in a group"),
                      "tpu_megakernel=xla cannot be honoured"),
    "monotone": (dict(SMOKE, use_mc=True),
                 dict(partition="pallas", fast_search=False, search="xla",
                      mega="off", frontier_k=1),
                 ("fast_search", "monotone constraints"), None),
    "monotone_k4": (dict(SMOKE, use_mc=True, **K4), dict(frontier_k=1),
                    ("frontier_k", "monotone constraints"),
                    K4_REFUSED + "(monotone constraints)"),
    "cegb_lazy": (dict(SMOKE, has_cegb=True, cegb_lazy=True),
                  dict(partition="xla", fast_search=False, frontier_k=1),
                  ("partition", "cegb_penalty_feature_lazy"), None),
    "forced_k4": (dict(SMOKE, forced=True, tpu_frontier_k="4"),
                  dict(partition="pallas", search="xla", mega="off",
                       frontier_k=1),
                  ("frontier_k", "forced splits"),
                  "tpu_frontier_k=4 cannot be honoured (forced splits); "
                  "using 1"),
    "extra_trees": (dict(SMOKE, extra_trees=True),
                    dict(fast_search=True, search="xla", mega="off",
                         frontier_k=1),
                    ("mega", "extra_trees"), None),
    # how tests/test_scopes.py reaches the cells' plan at toy size
    "path_smooth": (dict(rows=1500, num_leaves=15, path_smooth=1.0),
                    dict(CELL_PLAN, row_chunk=2048),
                    ("search", "path_smooth > 0"), None),
    "data_scatter": (DATA4, dict(partition="xla", scatter_groups=True,
                                 frontier_k=1, chunk_adaptive=False),
                     ("partition", "backend cpu"), None),
    # tree_learner=data runs the kernels on each shard's own rows; the
    # count-exactness gate sees the rows of all shards together
    "dp4_10m5_a_shard": (dict(DP4_CHIP, rows=10_500_000,
                              global_rows=42_000_000),
                         dict(partition="pallas", hist="pallas",
                              fast_search=False, search="xla", mega="off",
                              frontier_k=1, scatter_groups=False),
                         ("fast_search", "rows 42,000,000 >= 2^24"), None),
    # benchmark/configs/higgs-l255-b255-dp4.json at rows84m: the cells'
    # plan at K=1, the sync the plain psum
    "dp4_cell": (dict(DP4_CHIP, rows=21_000_000, global_rows=84_000_000),
                 dict(CELL_PLAN, frontier_k=1),
                 ("scatter_groups", "the histogram sync is the plain psum"),
                 None),
    "dp4_cell_auto_why": (dict(DP4_CHIP, rows=21_000_000,
                               global_rows=84_000_000),
                          dict(mega="off", frontier_k=1),
                          ("frontier_k", AUTO_K), None),
    "dp4_cell_k1_why": (dict(DP4_CHIP, rows=21_000_000,
                             global_rows=84_000_000, **K4),
                        dict(mega="off", frontier_k=1),
                        ("frontier_k", "parallel tree learners"),
                        K4_REFUSED + "(parallel tree learners)"),
    "dp4_small": (dict(DP4_CHIP, rows=500_000, global_rows=2_000_000),
                  dict(partition="pallas", hist="pallas", fast_search=True,
                       search="xla", mega="off", frontier_k=1,
                       hist_state="xla", scatter_groups=True),
                  ("search", "parallel tree learners"), None),
    "dp4_interpret": (dict(DATA4, interpret=True),
                      dict(partition="pallas", hist="pallas", search="xla",
                           mega="off", frontier_k=1, hist_state="xla"),
                      ("mega", "tree_learner=data"), None),
    "feature_keeps_xla": (dict(DP4_CHIP, parallel_mode="feature"),
                          dict(partition="xla", hist="xla", search="xla",
                               mega="off", frontier_k=1),
                          ("partition", "tree_learner=feature: every chip "
                                        "holds every row"), None),
    "voting_keeps_xla": (dict(DP4_CHIP, parallel_mode="voting"),
                         dict(partition="xla", hist="xla", search="xla",
                              mega="off", frontier_k=1),
                         ("partition", "tree_learner=voting: leaf "
                                       "histograms stay device-local"),
                         None),
    "data_few_features": (dict(DATA4, F=2, G=2),
                          dict(scatter_groups=False),
                          ("scatter_groups", "2 features < 4 shards"),
                          None),
    "data_psum": (dict(DATA4, tpu_data_hist_sync="psum"),
                  dict(scatter_groups=False),
                  ("scatter_groups", "tpu_data_hist_sync=psum"), None),
    "linear_l1": (dict(TOY_CPU, linear_gain_requested=True, l1=0.5),
                  dict(linear_gain=False),
                  ("linear_gain", "lambda_l1 > 0"),
                  "lambda_l1 > 0; falling back to the post-hoc refit mode"),
    "linear_on_chip": (dict(SMOKE, linear_gain_requested=True),
                       dict(linear_gain=True, partition="pallas",
                            search="xla", mega="off", frontier_k=1),
                       ("search", "linear_tree_mode=leafwise_gain"), None),
    "interaction_k4": (dict(TOY_CPU, interaction_constraints=True,
                            tpu_frontier_k="4"),
                       dict(frontier_k=1),
                       ("frontier_k", "interaction constraints"),
                       "tpu_frontier_k=4 cannot be honoured"),
    "k4_on_cpu": (dict(TOY_CPU, tpu_frontier_k="4"), dict(frontier_k=4),
                  ("hist_state", "search=xla"), None),
    "k_capped": (dict(TOY_CPU, num_leaves=15, tpu_frontier_k="99"),
                 dict(frontier_k=14), ("mega", "partition=xla"), None),
    "mega_pallas_on_cpu": (dict(TOY_CPU, tpu_megakernel="pallas"),
                           dict(mega="off"), ("mega", "partition=xla"),
                           "tpu_megakernel=pallas cannot be honoured "
                           "(partition=xla); using the current split path"),
    "mega_unknown": (dict(TOY_CPU, tpu_megakernel="maybe"),
                     dict(mega="off"), ("mega", "unknown"),
                     "unknown tpu_megakernel='maybe'; treating as off"),
    "adaptive_on_kernel_path": (dict(SMOKE, tpu_chunk_policy="adaptive"),
                                dict(chunk_adaptive=False),
                                ("chunk_adaptive", "partition=pallas"),
                                "tpu_chunk_policy=adaptive cannot be "
                                "honoured"),
    "chunks_fill": (dict(TOY_CPU, rows=200_000), dict(chunk_adaptive=False),
                    ("chunk_adaptive", "auto: (num_leaves-1) * 4096"),
                    None),
    "pack_rowid": (dict(SMOKE, tpu_pack_rowid=True), dict(pack_rowid=True),
                   ("hist_state", "mega=pallas"), None),
    "pack_rowid_no_room": (dict(SMOKE, F=30, G=30, tpu_pack_rowid=True),
                           dict(pack_rowid=False),
                           ("pack_rowid", "30 groups leave 2 spare rows"),
                           None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_plan_table(name):
    changed, expect, (decision, phrase), warning = CASES[name]
    p = plan.resolve(facts(**changed))
    got = {k: getattr(p, k) for k in expect}
    assert got == expect, p
    assert phrase in p.why[decision], p.why
    if warning is None:
        assert p.unmet == ()
    else:
        assert any(warning in w for w in p.unmet), p.unmet
    # a decision that took its kernel path gives no reason
    for key, value in (("partition", "pallas"), ("hist", "pallas"),
                       ("search", "pallas"), ("mega", "pallas"),
                       ("hist_state", "flat")):
        assert (getattr(p, key) == value) == (key not in p.why), (key, p)


@pytest.mark.parametrize("shape", ["wide_2000", "wide_137", "cell_b255"])
def test_no_plan_names_a_kernel_over_its_vmem(shape):
    """Every kernel the plan names at the shape is under the limit by the
    pure function beside it, the one ``resolve`` excludes by; and the wide
    cell's one ``tpu_*`` key changes nothing the program runs."""
    from lightgbm_tpu.ops import (VMEM_LIMIT_BYTES, hist_state_pallas,
                                  histogram_pallas, partition_pallas,
                                  split_megakernel_pallas, split_pallas)
    f = facts(**CASES[shape][0])
    p = plan.resolve(f)
    C, B = p.row_chunk, f.B
    named = {
        "lgbm_partition": (p.partition == "pallas", lambda:
                           partition_pallas.vmem_bytes(
                               p.pass_rows, C,
                               passes=-(-f.G // p.pass_rows))),
        "lgbm_histogram": (p.hist == "pallas", lambda:
                           histogram_pallas.vmem_bytes(C, B, f.G)),
        "lgbm_split_search": (p.search == "pallas", lambda:
                              split_pallas.vmem_bytes(f.F, B)),
        "lgbm_split_mega": (p.mega == "pallas", lambda:
                            split_megakernel_pallas.vmem_bytes(C, B, f.G)),
        "lgbm_hist_state": (p.hist_state == "flat", lambda:
                            hist_state_pallas.vmem_bytes(f.G, B)),
    }
    assert named["lgbm_partition"][0] and named["lgbm_histogram"][0]
    for kernel, (is_named, need) in named.items():
        assert not is_named or need() <= VMEM_LIMIT_BYTES, (kernel, need())
    off = plan.resolve(facts(**dict(CASES[shape][0], tpu_megakernel="off")))
    if shape == "wide_2000":
        assert off.kernel_plan() == p.kernel_plan()
        assert {k: v for k, v in vars(off).items() if k != "why"} \
            == {k: v for k, v in vars(p).items() if k != "why"}


GRID = [(rows, width, bins, mega)
        for rows in (1_000_000, 10_500_000, 42_000_000)
        for width in (28, 128, 2000) for bins in (63, 255)
        for mega in ("auto", "off")]


@pytest.mark.parametrize("rows, width, bins, mega", GRID)
def test_auto_is_the_one_leaf_body_on_the_chip(rows, width, bins, mega):
    """``tpu_frontier_k=auto`` on a TPU gives the plan of ``=1`` at every
    shape, field for field; only the reason given differs."""
    shape = dict(rows=rows, F=width, G=width, B=bins, tpu_megakernel=mega)
    auto = plan.resolve(facts(**shape))
    one = plan.resolve(facts(**shape, tpu_frontier_k="1"))
    assert auto.frontier_k == 1 and auto.unmet == ()
    assert auto.why.pop("frontier_k") == f"1 ({plan.AUTO_FRONTIER_K})"
    assert one.why.pop("frontier_k") == "1 (tpu_frontier_k=1)"
    assert auto == one


@pytest.mark.parametrize("spec", ["0", "-3", "bogus"])
def test_frontier_k_must_be_auto_or_positive(spec):
    with pytest.raises(ValueError, match="tpu_frontier_k"):
        plan.resolve(facts(tpu_frontier_k=spec))


def test_plan_module_stays_off_jax():
    """The plan can be read where no backend can start."""
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(plan))
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {(n.module or "").split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.level == 0}
    assert not imported & {"jax", "numpy"}, imported


# ---------------------------------------------------------------------------
# three real toy trainings against facts written by hand
# ---------------------------------------------------------------------------
TOY = dict(backend="cpu", rows=1500, F=8, G=8, B=255, num_leaves=15)
TRAININGS = {
    "serial_cpu": ({}, TOY),
    "serial_interpret": (
        {"tpu_kernel_interpret": True, "tpu_row_chunk": 256},
        dict(TOY, interpret=True, tpu_row_chunk="256")),
    # conftest.py's eight virtual devices: 188 rows a shard
    "data_parallel": (
        {"tree_learner": "data"},
        dict(TOY, rows=188, parallel_mode="data", axis_name=True,
             num_shards=8)),
}


@pytest.mark.parametrize("name", sorted(TRAININGS))
def test_kernel_plan_is_the_projection_of_resolve(name):
    import lightgbm_tpu as lgb
    params, by_hand = TRAININGS[name]
    rng = np.random.RandomState(0)
    X = rng.normal(size=(1500, 8))
    y = (2 * X[:, 0] + X[:, 1] - X[:, 2] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, **params},
                    lgb.Dataset(X, label=y), num_boost_round=1)
    g = bst._gbdt
    expect = plan.resolve(plan.PlanFacts(**{**OPTIONS, **by_hand}))
    assert g.learner.plan == expect
    assert g.kernel_plan() == {
        **expect.kernel_plan(), "fused": "on",
        "tree_learner": by_hand.get("parallel_mode", "serial")}
