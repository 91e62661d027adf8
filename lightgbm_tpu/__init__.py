"""lightgbm_tpu — a TPU-native gradient-boosting framework.

A from-scratch JAX/XLA re-design with the capabilities of the reference
LightGBM fork (see SURVEY.md): leaf-wise histogram GBDT on TPU via MXU one-hot
matmul histograms, device-resident binned datasets, GOSS/EFB, the full
objective & metric matrix, DART/RF, data-/feature-/voting-parallel training
over `jax.sharding` meshes, a LightGBM-compatible model format, Python
Dataset/Booster/train/cv and sklearn APIs, and a `config=`-file CLI.
"""

import os as _os

import jax as _jax

# Persistent XLA compilation cache: the jitted tree-builder programs are
# expensive to compile (many bucket-size specializations); cache them across
# processes.  JAX_COMPILATION_CACHE_DIR (read by jax itself) places the
# cache; without it the cache lives at the fixed <checkout>/.jax_cache — the
# path is part of the cache key, so it must not move between runs.
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

from .basic import (Booster, Dataset, LightGBMError, Sequence,
                    TextFileSequence)
from .callback import (EarlyStopException, early_stopping, log_evaluation,
                       record_evaluation, reset_parameter)
from .engine import CVBooster, cv, train

__version__ = "0.1.0"

__all__ = [
    "Dataset", "Booster", "LightGBMError", "CVBooster",
    "Sequence", "TextFileSequence",
    "train", "cv",
    "early_stopping", "log_evaluation", "record_evaluation", "reset_parameter",
    "EarlyStopException", "CheckpointCallback",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
    "plot_importance", "plot_metric", "plot_tree", "create_tree_digraph",
    "plot_split_value_histogram", "register_logger",
]


def __getattr__(name):
    # lazy imports to keep base import light
    if name == "register_logger":
        from .utils.log import register_logger
        return register_logger
    if name == "CheckpointCallback":
        from .robustness.checkpoint import CheckpointCallback
        return CheckpointCallback
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in ("plot_importance", "plot_metric", "plot_tree",
                "create_tree_digraph", "plot_split_value_histogram"):
        from . import plotting as _pl
        return getattr(_pl, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute {name!r}")
