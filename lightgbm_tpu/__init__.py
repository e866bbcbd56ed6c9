"""lightgbm_tpu: a TPU-native gradient boosting framework.

A from-scratch re-design of LightGBM (reference: vnherdeiro/LightGBM) for TPUs:
histograms, split finding, tree growth, objectives and scoring all run on
device through JAX/XLA (with Pallas kernels for the hot paths), and the
distributed tree learners use XLA collectives over the ICI mesh instead of the
reference's socket/MPI network.

Public surface mirrors the reference's Python package
(python-package/lightgbm/__init__.py): ``Dataset``, ``Booster``, ``train``,
``cv``, callbacks, and sklearn-style estimators.
"""
import time as _time

_IMPORT_T0 = _time.perf_counter()    # the `import` span's start (below)

from .basic import Booster, Dataset, Sequence  # noqa: E402
from .callback import (  # noqa: E402
    EarlyStopException,
    early_stopping,
    log_evaluation,
    record_evaluation,
    reset_parameter,
)
from .config import Config  # noqa: E402
from .engine import CVBooster, cv, train  # noqa: E402
from .obs import spans as _spans  # noqa: E402

# the `import` span, stamped and not entered: what it would be entered
# with is what it times (jax's import is most of it)
_spans.record("import", _IMPORT_T0, _time.perf_counter())

__version__ = "0.1.0"

__all__ = [
    "DaskLGBMClassifier",
    "DaskLGBMRegressor",
    "DaskLGBMRanker",
    "Dataset", "Booster", "Config", "Sequence",
    "train", "cv", "CVBooster",
    "early_stopping", "log_evaluation", "record_evaluation", "reset_parameter",
    "EarlyStopException", "TrainingInterrupted",
    "PredictionServer", "ModelRegistry", "ServingError", "ServingTimeout",
    "ServerOverloaded", "ServerClosed", "SwapFailed",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
    "plot_importance", "plot_metric", "plot_split_value_histogram",
    "plot_tree", "create_tree_digraph",
    "register_parser",
]

_PLOTTING = ("plot_importance", "plot_metric", "plot_split_value_histogram",
             "plot_tree", "create_tree_digraph")


def __getattr__(name):
    # sklearn wrappers / plotting import lazily to keep base import light
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in ("DaskLGBMClassifier", "DaskLGBMRegressor", "DaskLGBMRanker"):
        from . import dask as _dk
        return getattr(_dk, name)
    if name == "register_parser":
        from .io.loader import register_parser
        return register_parser
    if name == "TrainingInterrupted":
        from .parallel.multihost import TrainingInterrupted
        return TrainingInterrupted
    if name in ("PredictionServer", "ModelRegistry", "ServingError",
                "ServingTimeout", "ServerOverloaded", "ServerClosed",
                "SwapFailed"):
        # serving layer loads lazily: the coalescer thread machinery is
        # only wanted by processes that actually serve
        from . import serving as _serving
        return getattr(_serving, name)
    if name in _PLOTTING:
        from . import plotting as _pl
        return getattr(_pl, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute {name!r}")
