#!/usr/bin/env python3
"""Compile a configuration's serving steps and its reference for one chip
of a described TPU v5e (``v5e:2x2``), with no chip attached, and check
the byte reckoning: bf16 weights plus three live KV pools (no step
donates its pool) must fit the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/compile_rehearsal.py [config ...]

Prints, per configuration, the weight and pool bytes, the compiled
programs' memory analyses, and whether weights + 3 pools + the largest
program's temporaries fit 16 GB.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM = 16e9


def rehearse(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench import catalog
    from bench.families import dense
    from bench.reference import dense as ref
    from repro.kernels import ops
    from repro.models import get_model

    conf = catalog.Catalog(ROOT).config(name)
    one = SingleDeviceSharding(topo.devices[0])
    e = conf["engine"]
    model = get_model(dense.program_config(conf))
    shp = dense.shapes(conf)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                                         sharding=one),
                          shp, is_leaf=lambda x: isinstance(x, tuple))
    pool = jax.eval_shape(lambda: model.init_paged_cache(
        e["n_slots"], e["n_pages"], e["page_size"], jnp.bfloat16))
    pool = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=one), pool)
    def nbytes(t):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(t))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)

    B, maxp, C = e["n_slots"], -(-e["max_seq"] // e["page_size"]), e["prefill_chunk"]
    out = {"param_bytes": nbytes(params), "pool_bytes": nbytes(pool)}
    with ops.use_backend("pallas"):
        dec = jax.jit(model.decode_paged).lower(
            params, pool, {"tokens": i32(B, 1), "positions": i32(B),
                           "page_table": i32(B, maxp)}).compile()
        last = (e["max_seq"] // C - 1) * C
        pre = jax.jit(model.prefill_chunk, static_argnames=("offset",)).lower(
            params, pool, {"tokens": i32(1, C), "valid": i32(), "slot": i32(),
                           "page_table": i32(maxp)}, offset=last).compile()
    m = dense.dims(conf)
    n = -(-e["max_seq"] // ref.SEQ_BUCKET) * ref.SEQ_BUCKET
    with jax.default_matmul_precision("highest"):
        hid = ref._hidden.lower(params, i32(n), m=ref._M(tuple(sorted(m.items()))),
                                quant=True).compile()
    for k, c in (("decode", dec), ("prefill_last_offset", pre),
                 ("reference_hidden_fp8", hid)):
        ma = c.memory_analysis()
        out[k] = {"temp_bytes": int(ma.temp_size_in_bytes),
                  "output_bytes": int(ma.output_size_in_bytes)}
    worst = max(out[k]["temp_bytes"] for k in ("decode", "prefill_last_offset"))
    out["weights_3_pools_temp"] = out["param_bytes"] + 3 * out["pool_bytes"] + worst
    out["fits_16GB"] = out["weights_3_pools_temp"] < HBM
    return out


def main() -> None:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    names = sys.argv[1:] or ["phi4-mini-3.8b", "qwen3-8b-pp2"]
    ok = True
    for name in names:
        r = rehearse(name, topo)
        print(name, json.dumps(r))
        ok &= r["fits_16GB"]
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
