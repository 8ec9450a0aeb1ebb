"""lightgbm_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch rebuild of early LightGBM's capabilities (histogram-based
leaf-wise GBDT/DART, binary/regression/multiclass/LambdaRank, bagging,
feature subsampling, early stopping, model text IO, distributed training)
designed for TPUs: binned uint8 feature matrices in HBM, fused histogram /
split-search kernels under jit, and XLA collectives over a device mesh in
place of socket/MPI allreduce.
"""

import time as _time

# the origin of every span's ``first_start_s`` (obs/telemetry.py): the
# package's import, jax's included when this is what loads it
_IMPORT_T0 = _time.perf_counter()
_IMPORT_UNIX = _time.time()

__version__ = "0.1.0"


from .config import Config  # noqa: E402,F401
from .io import BinMapper, BinnedDataset, Metadata  # noqa: E402,F401
from .basic import Booster, Dataset, LightGBMError  # noqa: E402,F401
from .callback import (  # noqa: E402,F401
    EarlyStopException,
    early_stopping,
    print_evaluation,
    record_evaluation,
    reset_parameter,
)
from .engine import CVBooster, cv, train, train_many  # noqa: E402,F401
from .sklearn import (  # noqa: E402,F401
    LGBMClassifier,
    LGBMModel,
    LGBMRanker,
    LGBMRegressor,
)
from .obs import telemetry as _telemetry  # noqa: E402

_telemetry.count_many({
    "setup.import_s": _time.perf_counter() - _IMPORT_T0,
    "setup.import_unix_s": _IMPORT_UNIX,
})

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
