"""lightgbm_tpu: a TPU-native gradient boosting framework.

A ground-up reimplementation of LightGBM's capabilities (reference:
CharlesAuguste/LightGBM v2.2.4) designed for TPU hardware: binned features live as
dense device tensors, per-leaf gradient/hessian histograms and split-gain scans run
as JAX/XLA (and Pallas) programs, leaf-wise tree growth runs inside a single jitted
while-loop, and distributed training maps row sharding onto a jax.sharding.Mesh
with XLA collectives over ICI/DCN.

Public API mirrors the LightGBM python package: Dataset, Booster, train, cv,
sklearn-style estimators, and the callback set.
"""

from .basic import Booster, Dataset
from .callback import early_stopping, print_evaluation, record_evaluation, reset_parameter
from .config import Config
from .engine import CVBooster, cv, train
from .utils.log import LightGBMError

try:
    from .plotting import (create_tree_digraph, plot_importance, plot_metric,
                           plot_split_value_histogram, plot_tree)

    _PLOT = ["plot_importance", "plot_metric", "plot_split_value_histogram", "plot_tree", "create_tree_digraph"]
except ImportError:  # pragma: no cover - matplotlib/graphviz not installed
    _PLOT = []

try:
    from .sklearn import LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor

    _SKLEARN = ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]
except ImportError:  # pragma: no cover - sklearn not installed
    _SKLEARN = []

__version__ = "0.1.0"

__all__ = [
    "Dataset",
    "Booster",
    "Config",
    "train",
    "cv",
    "CVBooster",
    "LightGBMError",
    "early_stopping",
    "print_evaluation",
    "record_evaluation",
    "reset_parameter",
] + _SKLEARN + _PLOT
