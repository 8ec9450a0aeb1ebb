"""What the data-parallel split step's second launch requires a split
(``lgbm.split_step.search``, ops/record.py split_search): read the
parent's histogram row and write the two children's, three ``[Fp, 4, Bp]``
float32 rows, on every chip alike (each searches the same summed
histograms).  ``Fp`` is the features padded to the kernels' group of 8,
``Bp`` the bins padded to a lane multiple: 256 for the 255 bins of
``max_bin`` 255, the configuration of every cell that runs it.  The
search's arithmetic is not counted: this is a floor."""

from __future__ import annotations

BINS = 256
FEATURE_GROUP = 8
ROWS = 3
STATS = 4
BYTES = 4


def required(trees: list, features: int) -> dict:
    splits = sum(len(ic) for ic, _, _, _ in trees)
    fp = -(-features // FEATURE_GROUP) * FEATURE_GROUP
    return {"flops": 0.0,
            "bytes": float(splits) * ROWS * fp * STATS * BINS * BYTES}
