"""A rehearsed run of run.py with the timed path broken underneath, for
test_benchmark.py: `python faulty_run.py <fault> <run.py's arguments>`.

Faults (planted in the program's own entry points, not in the harness):
  state_unchanged  update() returns without training once the warm-ups are done
  half_the_batch   the dataset is built from the first half of the rows
  answer_altered   the widest leaf value of the last tree is doubled where the
                   model is written out
  none             nothing is planted
"""

import os

import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def plant(fault):
    import lightgbm_tpu as lgb
    if fault == "state_unchanged":
        real, calls = lgb.Booster.update, [0]

        def update(self, *a, **kw):
            calls[0] += 1
            return real(self, *a, **kw) if calls[0] <= 3 else False
        lgb.Booster.update = update
    elif fault == "half_the_batch":
        real_init = lgb.Dataset.__init__

        def init(self, data, label=None, **kw):
            half = len(data) // 2
            real_init(self, data[:half], label=label[:half], **kw)
        lgb.Dataset.__init__ = init
    elif fault == "answer_altered":
        real_text = lgb.Booster.model_to_string

        def text(self, *a, **kw):
            out = real_text(self, *a, **kw)
            head, sep, last = out.rpartition("\nleaf_value=")
            line, nl, rest = last.partition("\n")
            values = [float(v) for v in line.split()]
            k = max(range(len(values)), key=lambda i: abs(values[i]))
            values[k] *= 2.0            # the widest leaf's value, doubled
            return (head + sep + " ".join(repr(v) for v in values)
                    + nl + rest)
        lgb.Booster.model_to_string = text
    elif fault != "none":
        sys.exit(f"unknown fault {fault}")


if __name__ == "__main__":
    import run
    plant(sys.argv[1])
    run.main(sys.argv[2:])
