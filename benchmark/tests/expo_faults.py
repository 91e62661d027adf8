"""A rehearsed or chip run of run.py with a categorical fault planted where
the model is written out, for test_expo_cell.py and for the Expo cell's
readings: `python expo_faults.py <fault> <run.py's arguments>`.

Faults:
  category_moved  in the first tree, the categorical node over the most
                  rows loses one member of its set (its lowest code): the
                  model text no longer says where the program sent that
                  category's rows
  none            nothing is planted
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def move_category(text):
    """The model text with one category moved out of one node's set."""
    lines = text.split("\n")
    first = lines.index("Tree=0")
    end = lines.index("", first)
    tree = {ln.partition("=")[0]: (i, ln.partition("=")[2])
            for i, ln in enumerate(lines[first:end], first) if "=" in ln}
    kinds = [int(v) for v in tree["decision_type"][1].split()]
    counts = [int(v) for v in tree["internal_count"][1].split()]
    node = max((a for a, k in enumerate(kinds) if k & 1),
               key=lambda a: counts[a])
    k = int(float(tree["threshold"][1].split()[node]))
    bounds = [int(v) for v in tree["cat_boundaries"][1].split()]
    words = [int(v) for v in tree["cat_threshold"][1].split()]
    for w in range(bounds[k], bounds[k + 1]):
        if words[w]:
            words[w] &= words[w] - 1          # its lowest member goes
            break
    i = tree["cat_threshold"][0]
    lines[i] = "cat_threshold=" + " ".join(str(v) for v in words)
    return "\n".join(lines)


def plant(fault):
    import lightgbm_tpu as lgb
    if fault == "category_moved":
        real_text = lgb.Booster.model_to_string

        def text(self, *a, **kw):
            return move_category(real_text(self, *a, **kw))
        lgb.Booster.model_to_string = text
    elif fault != "none":
        sys.exit(f"unknown fault {fault}")


if __name__ == "__main__":
    import run
    plant(sys.argv[1])
    run.main(sys.argv[2:])
