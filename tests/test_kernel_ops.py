"""Tier-1 guard on the vector work Mosaic makes of the partition kernel
(tools/kernel_ops.py), beside tests/test_hlo_guard.py's guard on the tree
loop's HLO.

The kernel is bound by what its compaction hands the vector units
(PERF.md section 6, PRs 30 and 34): an operation more per 128-lane block
is paid 64 times a chunk and 85,600 chunks an iteration at the
benchmark's size, and under a timing's noise it would land silently.
The count is taken in a process of its own, because libtpu reads the
dump flag when it is loaded.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from kernel_ops import count_bodies, count_bundles  # noqa: E402

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


# cut from libtpu's final bundles and its slot table: two bundles
# outside any loop, a loop of three (one of them in a nested loop)
BUNDLES = """= control target key start
LH: loop header
= control target key end

     0   :  { %s1 = smov 0 }
   0x1   :  { %v2 = vlaneseq }
   0x2 LB: > { %v3 = vld [vmem:[#a]]  ;;  %v4 = vadd.s32 %v2, %v2 }
   0x3 LB: > > { %v5 = vsel %vm, %v3, %v4 }
   0x4   : > { %6 = vst [vmem:[#b]] %v5 }
   0x5 PF:  { %7 = vnop }
"""
SLOTS = """== CAPACTIY:
MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU
    4     3     4     1     3     3     1     1     2
== UTILIZATION:
0 0 0 0 0 0 0 0 1
0 0 1 0 0 0 0 0 0
0 0 1 0 1 0 0 0 0
0 0 1 0 0 0 0 0 0
0 0 0 0 0 0 1 0 0
0 0 0 0 0 0 0 0 0
"""


def test_count_bundles_on_a_fixed_snippet():
    out = count_bundles(BUNDLES, SLOTS)
    assert out["capacity"]["VALU"] == 4 and out["capacity"]["VSTORE"] == 1
    (loop,) = out["loops"]
    # the nested loop's bundle counts with its outer loop, the bundles
    # before and after the loop do not
    assert loop["bundles"] == 3
    assert loop["slots"]["VALU"] == 2 and loop["slots"]["VLOAD"] == 1
    assert loop["slots"]["VSTORE"] == 1 and loop["slots"]["SALU"] == 0
    assert count_bundles("", "") == {"capacity": {}, "loops": []}


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
    """G32=32, C=4096, three live ghi rows, rowid in its own row (the
    cells carry five live rows, P = 13 for 11: the same two sublane tiles
    and, to one operation, the same counts).  Read on the installed
    libtpu 0.0.34 since PR 34: pass 1 (a chunk) 7,307 operations, 2 of
    them dynamic lane rotates, 34 matmuls (one one-hot product per
    128-lane block, and the prefix sums' two) and 64 lane gathers; pass 2
    (a destination window) 1,041, no rotate and 66 gathers.  Before PR 34
    (PR 30's network of twelve roll steps, a dynamic roll in ``stage``
    and two in pass 2): 10,758 operations and 994 rotates in pass 1,
    2,256 and 128 in pass 2; the parent of PR 30 read 18,826 and 1,858 in
    pass 1.  The ceilings are 5% above the readings; the matmuls are
    exact: one more is a block more.  Rotates were what the chip's time
    followed while there were a thousand of them (PERF.md section 6, PR
    30); one that comes back is a relayout Mosaic slipped in, paid on
    every vreg it touches."""
    out = _count("partition")
    pass1, pass2 = out["bodies"]
    assert pass1["by_kind"].get("tpu.dynamic_rotate", 0) <= 2, pass1
    assert pass1["by_kind"].get("tpu.matmul", 0) == 34, pass1
    assert pass1["by_kind"].get("tpu.dynamic_gather", 0) <= 68, pass1
    assert pass1["vector_ops"] <= 7672, pass1
    assert pass2["by_kind"].get("tpu.dynamic_rotate", 0) == 0, pass2
    assert pass2["vector_ops"] <= 1093, pass2


def test_partition_scheduled_bundles_ceiling():
    """The same geometry as scheduled for the v5e: a trip of pass 1 is
    2,099 bundles (3,909 before PR 34) with 6,075 of their 8,396 vector
    ALU slots in use, a trip of pass 2 is 442 (955).  With the lane
    rotates gone the kernel is near the ALU's slots, so what a change
    adds shows here where an operation count alone would not say which
    unit pays (PERF.md section 6, PR 34: over four forms the chip took
    one cycle a bundle at 1.5 GHz plus 1,600-1,900 cycles a chunk that
    none of them moved).  Ceilings 5% above the readings, libtpu
    0.0.34."""
    out = _count("partition", "--bundles")
    if not out.get("loops"):
        pytest.skip("libtpu wrote no bundles")
    pass1 = max(out["loops"], key=lambda loop: loop["bundles"])
    pass2 = out["loops"][-1]
    assert pass1["bundles"] <= 2203, pass1
    assert pass1["slots"]["VALU"] <= 6378, pass1
    assert pass2["bundles"] <= 464, pass2


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
