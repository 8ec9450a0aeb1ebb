"""On-chip kernel parity checks, alone (chip_smoke.py runs them too).

Runs lightgbm_tpu.analysis.kernel_parity — the Mosaic-compiled Pallas
kernels against their references, hardware-only branches included — and
exits non-zero on any mismatch or when there is no TPU.

Run through the chip tool:  python tools/tpu_parity_check.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    from lightgbm_tpu.analysis import kernel_parity

    plat = jax.devices()[0].platform
    print(f"platform: {plat}", flush=True)
    if plat != "tpu":
        sys.exit("not on a TPU: these checks validate Mosaic compilation")
    sys.exit(0 if all(kernel_parity.run_all().values()) else 1)


if __name__ == "__main__":
    main()
