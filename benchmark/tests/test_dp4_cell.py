"""The four-chip cell, rehearsed on four virtual CPU devices.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_dp4_cell.py -q

`--rehearse` shrinks the rows and allows the CPU; every line says
`platform: cpu` and no number of it is a device number.  (The rehearsal
runs the XLA partition and histogram: the Pallas kernels are per shard on
the chip only, and tests/test_data_parallel_kernels.py interprets them.)
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import SPEC, cell_metrics, contract, rehearse  # noqa: E402

CELL = "higgs-l255-b255-dp4.rows84m"


def test_the_cell_is_the_benchmarks_one_four_chip_cell():
    four = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    assert len(SPEC["workloads"]) // 4 <= 1


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_four_virtual_devices(trace):
    rc, last, out = rehearse(CELL, trace, devices=4, extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    assert "tree_learner=data" in out
    line = json.loads(last)
    assert contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        cell_metrics(SPEC, CELL, trace), bool(trace)) == []
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert ("hist_sync_s_per_iter" in line["metrics"]) == bool(trace)
    if trace:
        assert line["metrics"]["hist_sync_s_per_iter"]["value"] > 0
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["correct"] is True and line["failed"] == 0, line["compared"]


def test_the_cell_refuses_one_device():
    rc, last, out = rehearse(CELL, 0, extra=("--rehearse",))
    assert rc != 0 and not last.startswith("{"), out[-2000:]
