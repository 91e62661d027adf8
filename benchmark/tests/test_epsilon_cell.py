"""The wide cell, rehearsed on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_epsilon_cell.py -q

`--rehearse` shrinks the rows (3000 x 2000 features) and allows the CPU;
every line says `platform: cpu` and no number of it is a device number.
(The rehearsal runs the XLA partition and histogram: the feature-tiled
kernels need a TPU or `tpu_kernel_interpret`, and tests/test_wide_kernels.py
interprets them.)
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import (BENCH, SPEC, cell_metrics, contract,  # noqa: E402
                            reference, rehearse)

CELL = "epsilon-l255-b255.rows600k"
sys.path.insert(0, os.path.join(BENCH, "readers"))


def test_the_cell_is_the_published_width_on_one_chip():
    entry = [w for w in SPEC["workloads"] if w["name"] == CELL][0]
    config = json.load(open(os.path.join(
        BENCH, "configs", entry["config"] + ".json")))
    workload = json.load(open(os.path.join(BENCH, "workloads",
                                           CELL + ".json")))
    assert entry["chips"] == workload["chips"] == 1
    assert config["features"] == 2000 and config["reduced"] == ["iterations"]
    assert config["params"]["max_bin"] == 255
    assert [k for k in config["params"] if k.startswith("tpu_")] \
        == ["tpu_megakernel"]
    assert workload["rows"] == 600000 and workload["holdout_rows"] == 100000
    # the five limits under the cells' names, each between this cell's own
    # two readings (PERF.md section 4): the widest row's gap reads 1.1e-5
    # at most in sound runs here and 0.0297 under the bf16 control, so
    # the HIGGS cells' 0.03 could not fail
    other = json.load(open(os.path.join(
        BENCH, "workloads", "higgs-l255-b255.rows42m.json")))
    assert set(workload["limits"]) == set(other["limits"])
    assert 1.1e-5 * 10 < workload["limits"]["train_score_gap"] < 0.0297 / 10
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {"partition_s_per_iter", "partition_roofline",
                      "row_pass_s_per_iter", "histogram_s_per_iter",
                      "split_search_s_per_iter", "device_unattributed_share",
                      "histogram_roofline"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_line_the_validator_accepts(trace):
    rc, last, out = rehearse(CELL, trace, extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    assert contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        cell_metrics(SPEC, CELL, trace), bool(trace)) == []
    assert line["device"]["platform"] == "cpu"
    assert ("histogram_roofline" in line["metrics"]) == bool(trace)
    if trace:
        assert 0 < line["metrics"]["histogram_roofline"]["value"]
        # 3000 rows hold 750 of summed hessian: min_sum_hessian_in_leaf
        # = 100 stops a rehearsal's trees at a handful of leaves
        assert line["metrics"]["leaves_per_tree"]["value"] > 1
    assert line["correct"] is True and line["failed"] == 0, line["compared"]


def test_the_work_of_a_tree_is_its_root_and_its_smaller_children():
    import histogram_roofline as reader
    text = "\n".join([
        "Tree=0", "num_leaves=3", "num_cat=0", "split_feature=0 1",
        "threshold=0.5 0.5", "left_child=1 -1", "right_child=-2 -3",
        "leaf_value=0 0 0", "leaf_count=60 30 10", "internal_count=100 70",
        "", "end of trees"])
    tree = reference.parse_model(text)[0]
    # the root's 100 rows, min(70, 30) at the root's split, min(60, 10)
    assert reader.rows_histogrammed(tree) == 100 + 30 + 10
    ctx = {"traced_trees": [tree], "chips": 1, "features": 2000,
           "bin_bytes": 1, "trace": None,
           "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert reader.read(ctx, ["train.fused_step"], ["histogram"], 255) is None
    by_flops = 140 * 2000 * 255 * 4 / 197e12
    assert reader.least_seconds(ctx, 255) == pytest.approx(by_flops)
    assert by_flops > 140 * 2008 / 819e9        # FLOP-bound at 255 bins
