"""The control of `correct`: the program one precision step down.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s> --trace 0

The configurations state float32 histograms and prefix sums, which the
program gets by running every f32 matmul of its training path at
`Precision.HIGHEST` (`lightgbm_tpu.ops.F32_DOT_PRECISION`).  The step below
is the MXU's default: operands rounded to bfloat16 (PR 21's finding 5).
This script sets that constant to `Precision.DEFAULT` in every module of
the program that holds it, then drives run.py's own `main` unchanged, so the
last line it prints is what the benchmark says of such a program: `correct`
has to be false.  The benchmark's runs never call it; the readings that the
limits were set from are in PERF.md.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def lower_precision():
    import jax
    import lightgbm_tpu  # noqa: F401  (imports every module that holds it)
    found = [m for name, m in sys.modules.items()
             if name.startswith("lightgbm_tpu")
             and hasattr(m, "F32_DOT_PRECISION")]
    if not found:
        sys.exit("control: the program no longer has F32_DOT_PRECISION")
    for m in found:
        m.F32_DOT_PRECISION = jax.lax.Precision.DEFAULT
    print(f"[control] F32_DOT_PRECISION=DEFAULT in {len(found)} modules",
          flush=True)


if __name__ == "__main__":
    import run
    lower_precision()
    run.main(sys.argv[1:])
