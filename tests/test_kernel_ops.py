"""Tier-1 guard on the vector work Mosaic makes of the partition kernel
(tools/kernel_ops.py), beside tests/test_hlo_guard.py's guard on the tree
loop's HLO.

The kernel is bound by what its compaction network hands the vector
units, the lane rotates first (PERF.md section 6, PR 30): an operation
more in a step of the network is paid 24 times a chunk and 85,600 chunks
an iteration at the benchmark's size, and under a timing's noise it
would land silently.  The count is taken in a
process of its own, because libtpu reads the dump flag when it is loaded.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from kernel_ops import count_bodies  # noqa: E402

# cut from a dump after apply-vector-layout: one line per vreg operation
SNIPPET = '''module attributes {stable_mosaic.version = 11 : i64} {
  func.func @main(%arg0: i32, %arg1: memref<8x256xi32, #tpu.memory_space<vmem>>) {
    %c0 = arith.constant 0 : index
    %0 = tpu.load %arg1[%c0, %c0] {sublane_mask = array<i1: true>} : memref<8x256xi32, #tpu.memory_space<vmem>>, vector<8x128xi32>
    scf.if %true {
      %9 = arith.addi %0, %0 : vector<8x128xi32>
    } {in_layout = [#tpu.vpad<"none">]}
    %1:2 = scf.for %arg2 = %c0_i32 to %n step %c1_i32 iter_args(%a = %c0_i32) -> (i32)  : i32 {
      %2 = arith.muli %arg2, %c128_i32 : i32
      %3 = tpu.dynamic_rotate %0 by %c127_i32 dim 1 : vector<8x128xi32>, i32 -> vector<8x128xi32>
      %4 = arith.cmpi ne, %3, %0 : vector<8x128xi32>
      scf.if %5 {
        %6 = arith.select %4, %3, %0 : vector<8x128xi1>, vector<8x128xi32>
        tpu.store %6, %arg1[%c0, %c0] {sublane_mask = array<i1: true>} : memref<8x256xi32, #tpu.memory_space<vmem>>, vector<8x128xi32>,
      } else {
        %7 = arith.select %4, %0, %3 : vector<8x128xi1>, vector<8x128xi32>
      }
      scf.yield %2 : i32
    }
    scf.for %arg2 = %c0_i32 to %m step %c1_i32  : i32 {
      %8 = arith.andi %0, %0 : vector<8x128xi32>
    } {in_layout = [], out_layout = []}
    return
  }
}
'''


def test_count_bodies_on_a_fixed_snippet():
    first, second = count_bodies(SNIPPET)
    # the branches nested in a body count for it, scalar operations and
    # the operations outside any loop do not
    assert first["vector_ops"] == 5
    assert first["by_kind"] == {"arith.select": 2, "tpu.dynamic_rotate": 1,
                                "arith.cmpi": 1, "tpu.store": 1}
    assert second["vector_ops"] == 1
    assert second["by_kind"] == {"arith.andi": 1}
    assert first["line"] < second["line"]


def _count(*argv):
    """The tool's JSON for one kernel, from a process of its own."""
    env = {k: v for k, v in os.environ.items() if k != "LIBTPU_INIT_ARGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kernel_ops.py"),
         *argv, "--json"],
        env=env, capture_output=True, text=True, timeout=300)
    lines = done.stdout.strip().splitlines()
    if not lines:
        pytest.skip("tools/kernel_ops.py printed nothing (libtpu held by "
                    f"another process?): {done.stderr[-300:]}")
    out = json.loads(lines[-1])
    if "error" in out:
        pytest.skip(out["error"][:300])
    return out


def test_partition_pass1_vector_ops_ceiling():
    """The cells' geometry (G32=32, C=4096, three live ghi rows, rowid in
    its own row).  Read on the installed libtpu 0.0.34: pass 1 (a chunk)
    10,758 operations, 994 of them lane rotates; pass 2 (a destination
    window) 2,256 and 128.  The parent of PR 30 read 18,826 and 1,858 in
    pass 1.  The ceilings are 5% above the readings: a step of the
    network that grows by one operation on a (1, C) row adds 768, by one
    roll of such a row 448 rotates.  The rotates are what the chip's time
    followed (PERF.md section 6, PR 30)."""
    out = _count("partition")
    pass1, pass2 = out["bodies"]
    rotates = pass1["by_kind"].get("tpu.dynamic_rotate", 0)
    assert 0 < rotates <= 1043, pass1
    assert pass1["vector_ops"] <= 11295, pass1
    assert pass2["vector_ops"] <= 2370, pass2


@pytest.mark.parametrize("bins, chunk_ops, step_ops, matmuls",
                         [(255, 1350, 14855, 224), (63, 1320, 8618, 112)],
                         ids=["b255", "b63"])
def test_histogram_step_vector_ops_ceiling(bins, chunk_ops, step_ops,
                                           matmuls):
    """``lgbm_histogram`` at the cells' geometry (28 features in 32 u8
    sublanes, C=4096).  Body 0 is what a chunk pays once (the u8 rows to
    i32, the limbs of grad and hess, their planes), body 1 one inner step
    of 2048 lanes, two a chunk: building the operands (selects, compares,
    packs to bf16) and one matmul per feature group and 128 lanes of the
    step, each streaming P*R rows through the MXU (48 at 255 bins, 64 at
    63: 21,504 and 14,336 rows a chunk).  The kernel is bound by those
    rows and by its matmul count, not by the vector work (PERF.md section
    6, PR 32), so the matmul count is exact: one more is a feature group
    more, or a narrower tile.  Read on the installed libtpu 0.0.34; the
    ceilings are 5% above.  The kernel rolls nothing: a lane rotate here
    would be a relayout Mosaic slipped in (PR 30 on what those cost)."""
    out = _count("histogram", "--bins", str(bins))
    per_chunk, per_step = out["bodies"]
    assert per_chunk["vector_ops"] <= chunk_ops * 1.05, per_chunk
    assert per_step["vector_ops"] <= step_ops * 1.05, per_step
    assert per_step["by_kind"].get("tpu.matmul", 0) == matmuls, per_step
    for body in out["bodies"]:
        assert "tpu.dynamic_rotate" not in body["by_kind"], body
