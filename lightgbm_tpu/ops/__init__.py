from .histogram import histogram_feature_major
from .split import find_best_split, SplitResult

__all__ = [
    "histogram_feature_major",
    "find_best_split",
    "SplitResult",
]
