"""lightgbm_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch rebuild of early LightGBM's capabilities (histogram-based
leaf-wise GBDT/DART, binary/regression/multiclass/LambdaRank, bagging,
feature subsampling, early stopping, model text IO, distributed training)
designed for TPUs: binned uint8 feature matrices in HBM, fused histogram /
split-search kernels under jit, and XLA collectives over a device mesh in
place of socket/MPI allreduce.
"""

__version__ = "0.1.0"


from .config import Config  # noqa: F401
from .io import BinMapper, BinnedDataset, Metadata  # noqa: F401
from .basic import Booster, Dataset, LightGBMError  # noqa: F401
from .callback import (  # noqa: F401
    EarlyStopException,
    early_stopping,
    print_evaluation,
    record_evaluation,
    reset_parameter,
)
from .engine import CVBooster, cv, train, train_many  # noqa: F401
from .sklearn import (  # noqa: F401
    LGBMClassifier,
    LGBMModel,
    LGBMRanker,
    LGBMRegressor,
)

__all__ = [
    "Config",
    "BinMapper",
    "BinnedDataset",
    "Metadata",
    "Dataset",
    "Booster",
    "LightGBMError",
    "train",
    "train_many",
    "cv",
    "CVBooster",
    "print_evaluation",
    "record_evaluation",
    "reset_parameter",
    "early_stopping",
    "EarlyStopException",
    "LGBMModel",
    "LGBMRegressor",
    "LGBMClassifier",
    "LGBMRanker",
    "__version__",
]
