"""``tools/limits.py`` for a cell whose traffic names another driver.

    python benchmarks/tools/limits_of_driver.py --workload airline-13.train
        --traffic train_steady_categorical --seeds 12 [limits.py's options]

``limits.py`` reads a cell through ``drivers/train_steady``'s set-up,
``first_trees``, ``reference`` and ``compared``.  A driver that holds its
trees to another plain reference (``train_steady_categorical``: one-vs-rest
splits) gives ``train_steady`` its own three for the length of the run
(the driver's ``bound``), and every reading, control and planted fault of
``limits.py`` is then taken against that reference.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main() -> int:
    import cells
    from tools import limits

    argv = sys.argv[1:]
    traffic = argv[argv.index("--traffic") + 1]
    driver = cells.plugin("drivers", cells.read_json(
        cells.HERE, "traffic", traffic + ".json")["driver"])
    with driver.bound():
        return limits.main()


if __name__ == "__main__":
    sys.exit(main())
