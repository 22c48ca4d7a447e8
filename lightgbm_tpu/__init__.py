"""LightGBM-TPU: a TPU-native gradient boosting framework.

A from-scratch rebuild of LightGBM v2.3.2's capabilities designed for TPU
hardware: the binned dataset lives in HBM, histogram construction / best-split
scans / partitioning run as jitted XLA+Pallas programs, the leaf-wise tree
grower is a single on-device lax.while_loop, and distributed training
(data/feature/voting parallel) is expressed as jax.sharding over a device mesh
with ICI collectives instead of socket/MPI collectives.

Public API mirrors the reference python-package (python-package/lightgbm):
Dataset, Booster, train, cv, sklearn wrappers, callbacks, plotting.
"""
import os as _os

import jax as _jax

# f64 leaf/gain math for reference parity (hist arrays stay f32; see ops/)
_jax.config.update("jax_enable_x64", True)

# what the package builds at run time (compiled programs, the native
# helpers) lives under <checkout>/.cache, gitignored; nothing in $HOME
_CACHE_ROOT = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))), ".cache")

# The one compile-cache rule. JAX reads JAX_COMPILATION_CACHE_DIR itself,
# so where it is set no directory is set in code; otherwise the cache is
# <checkout>/.cache/jax on every platform — a fixed path (the path is part
# of the cache key), bounded by LRU eviction. Clearing it is
# `rm -rf .cache/jax`.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(_CACHE_ROOT, "jax"))
    _jax.config.update("jax_compilation_cache_max_size", 128 << 20)

from .utils.log import LightGBMError, Log  # noqa: E402
from .config import Config  # noqa: E402

__version__ = "0.1.0"
__all__ = ["Config", "Log", "LightGBMError", "__version__"]


def _register_api():
    """Late-bound API surface; modules appended as they are built."""
    global __all__
    try:
        from .basic import Booster, Dataset  # noqa
        from .engine import cv, train  # noqa
        globals().update(Booster=Booster, Dataset=Dataset, train=train, cv=cv)
        __all__ += ["Booster", "Dataset", "train", "cv"]
    except ImportError:
        pass
    try:
        from .sklearn import (LGBMClassifier, LGBMModel,  # noqa
                              LGBMRanker, LGBMRegressor)
        globals().update(LGBMModel=LGBMModel, LGBMRegressor=LGBMRegressor,
                         LGBMClassifier=LGBMClassifier, LGBMRanker=LGBMRanker)
        __all__ += ["LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"]
    except ImportError:
        pass
    try:
        from .callback import (early_stopping, print_evaluation,  # noqa
                               record_evaluation, reset_parameter)
        globals().update(early_stopping=early_stopping,
                         print_evaluation=print_evaluation,
                         record_evaluation=record_evaluation,
                         reset_parameter=reset_parameter)
        __all__ += ["early_stopping", "print_evaluation",
                    "record_evaluation", "reset_parameter"]
    except ImportError:
        pass
    try:
        from .plotting import (create_tree_digraph, plot_importance,  # noqa
                               plot_metric, plot_split_value_histogram,
                               plot_tree)
        globals().update(plot_importance=plot_importance,
                         plot_split_value_histogram=plot_split_value_histogram,
                         plot_metric=plot_metric, plot_tree=plot_tree,
                         create_tree_digraph=create_tree_digraph)
        __all__ += ["plot_importance", "plot_split_value_histogram",
                    "plot_metric", "plot_tree", "create_tree_digraph"]
    except ImportError:
        pass


_register_api()
