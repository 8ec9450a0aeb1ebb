"""Compile a cell's grow program for a v5e that is described, not attached.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py --config malware-81
        [--rows N]   # default: the configuration's real row count

Builds the program's ``Dataset`` and ``Booster`` at the configuration's
real feature count under ``device.assume_platform("tpu")`` (the recipe of
tests/test_chip_compile.py), lowers the serial grower for
``v5e:2x2`` and compiles it: Mosaic refuses a kernel here, or XLA the HBM,
and no chip-minute is spent.  Prints ``memory_analysis()`` beside
``obs/memmodel``'s prediction.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import cells
    import program
    from lightgbm_tpu import device

    config = cells.read_json(HERE, "configs", a.config + ".json")
    gen = dict(config["generator"]["params"])
    if a.rows:
        gen["rows"] = a.rows
    t0 = time.time()
    data = cells.plugin("generators", config["generator"]["name"]).generate(
        gen, a.seed)
    n, F = data["X"].shape
    print(f"data {n} x {F} in {time.time() - t0:.1f}s", flush=True)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    with device.assume_platform("tpu"):
        t0 = time.time()
        ds = program.build_dataset(config, data)
        print(f"Dataset in {time.time() - t0:.1f}s", flush=True)
        gbdt = program.build_booster(config, ds)._gbdt
        grow = gbdt._grow
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x), sharding=chip),
            (gbdt._bins_T, jnp.zeros(n, jnp.float32),
             jnp.zeros(n, jnp.float32), gbdt._bag_mask,
             jnp.ones(gbdt._bins_T.shape[0], bool), gbdt._nbpf,
             gbdt._is_cat, gbdt._learner_params))
        t0 = time.time()
        lowered = grow.func.lower(*args, **grow.keywords)
    calls = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    out = {
        "config": a.config, "rows": n, "features": F,
        "mosaic_calls": calls, "compile_s": round(time.time() - t0, 1),
        "temp_bytes": m.temp_size_in_bytes,
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "total_gib": round((m.temp_size_in_bytes + m.argument_size_in_bytes
                            + m.output_size_in_bytes) / 2**30, 2),
        "memmodel_peak_gib": round(
            (program.memory_model({**config, "generator": {
                "params": gen}}) or 0) / 2**30, 2),
    }
    print("rehearse_compile: " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
