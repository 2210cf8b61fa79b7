#!/usr/bin/env python3
"""Pallas kernels against their XLA twins on a TPU, at published widths.

    python benchmarks/kernel_parity.py

Every op that ``repro.kernels.ops`` sends to a Pallas kernel on a TPU runs
once under ``ops.use_backend("pallas")`` and once under
``ops.use_backend("xla")``, on the same inputs drawn from ``SEED``, at
the width and call shape of a model that uses it: a serving prefill chunk
(one sequence, 256 tokens, and a 50-token tail) or a decode step (8
lanes). Prints, per case, the largest relative L2 error over output rows,
the largest absolute difference, and the median wall time per call of
each path (host clock, dispatch included). Exits non-zero when any case
exceeds ``REL_TOL`` or when JAX finds no TPU.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# one call at bf16 inputs: both paths accumulate in f32, and a matmul's
# bf16 passes differ by about 2^-8 per term; a wrong block, mask or
# carried state moves a row by O(1)
REL_TOL = 0.05
ITERS = 20
SEED = 0


def cases(rng: np.random.Generator):
    """(name, model, fn(ops) -> callable, args) at published widths."""
    import jax.numpy as jnp

    from repro.configs import get

    def normal(*shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def softplus_dt(*shape):
        return jnp.asarray(np.log1p(np.exp(rng.normal(-4.0, 1.0, shape))),
                           jnp.bfloat16)

    phi = get("phi4-mini-3.8b")
    H, K, Dh, d = phi.n_heads, phi.n_kv_heads, phi.d_model // phi.n_heads, \
        phi.d_model
    B, page, mp = 8, 64, 32
    lengths = jnp.asarray(rng.integers(300, mp * page + 1, B), jnp.int32)
    table = jnp.asarray(
        1 + rng.permutation(B * mp).reshape(B, mp), jnp.int32)
    pool = (B * mp + 1, page, K, Dh)
    yield ("rmsnorm", "phi4-mini-3.8b", lambda ops: ops.rmsnorm,
           (normal(1, 256, d), normal(d, dtype=jnp.float32)))
    yield ("attention q_offset=960", "phi4-mini-3.8b",
           lambda ops: lambda q, k, v: ops.attention(q, k, v, causal=True,
                                                     q_offset=960),
           (normal(1, 256, H, Dh), normal(1, 1216, K, Dh),
            normal(1, 1216, K, Dh)))
    yield ("paged_decode_attention", "phi4-mini-3.8b",
           lambda ops: ops.paged_decode_attention,
           (normal(B, H, Dh), normal(*pool), normal(*pool), table, lengths))
    yield ("decode_attention", "phi4-mini-3.8b",
           lambda ops: ops.decode_attention,
           (normal(B, H, Dh), normal(B, mp * page, K, Dh),
            normal(B, mp * page, K, Dh), lengths))

    fm = get("falcon-mamba-7b")
    di, N = fm.d_model * fm.expand, fm.ssm_state
    cd = jnp.bfloat16 if fm.ssm_dtype == "bf16" else jnp.float32
    A = -jnp.asarray(np.tile(np.arange(1, N + 1), (di, 1)), jnp.float32)
    for S in (256, 50):
        yield (f"selective_scan S={S}", "falcon-mamba-7b",
               lambda ops: lambda *a: ops.selective_scan(
                   *a, chunk=fm.ssm_chunk, compute_dtype=cd),
               (normal(1, S, di), softplus_dt(1, S, di), A,
                normal(1, S, N), normal(1, S, N),
                normal(di, dtype=jnp.float32),
                normal(1, di, N, scale=0.1, dtype=jnp.float32)))

    zb = get("zamba2-1.2b")
    hs, P, N = zb.d_model * zb.expand // zb.ssm_head_dim, zb.ssm_head_dim, \
        zb.ssm_state
    A = -jnp.asarray(np.exp(rng.uniform(0.0, np.log(16.0), hs)), jnp.float32)
    for S in (256, 50):
        yield (f"ssd S={S}", "zamba2-1.2b",
               lambda ops: lambda *a: ops.ssd(*a, chunk=zb.ssm_chunk),
               (normal(1, S, hs, P), softplus_dt(1, S, hs), A,
                normal(1, S, N), normal(1, S, N),
                normal(hs, dtype=jnp.float32),
                normal(1, hs, P, N, scale=0.1, dtype=jnp.float32)))


def run(fn, args):
    """Output leaves of one warm call, and the median seconds per call."""
    import jax

    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return jax.tree.leaves(out), float(np.median(times))


def compare(got, want) -> tuple[float, float]:
    """Largest relative L2 error over the rows (last dim) of every output,
    and the largest absolute difference."""
    rel, diff = 0.0, 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32).reshape(-1, g.shape[-1])
        w = np.asarray(w, np.float32).reshape(-1, w.shape[-1])
        if not (np.isfinite(g).all() and np.isfinite(w).all()):
            return float("inf"), float("inf")
        err = np.linalg.norm(g - w, axis=-1)
        rel = max(rel, float((err / np.maximum(
            np.linalg.norm(w, axis=-1), 1e-30)).max()))
        diff = max(diff, float(np.abs(g - w).max()))
    return rel, diff


def main() -> None:
    import jax

    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"kernel_parity: JAX found no TPU (platform {dev.platform!r})")
    from repro.launch.serve import use_compile_cache

    use_compile_cache()
    print("device:", dev.platform, dev.device_kind)
    failed = []
    for name, model, make, xs in cases(np.random.default_rng(SEED)):
        timed = {}
        for backend in ("pallas", "xla"):
            with ops.use_backend(backend):
                timed[backend] = run(jax.jit(make(ops)), xs)
        rel, diff = compare(timed["pallas"][0], timed["xla"][0])
        row = {"case": name, "model": model,
               "shapes": [list(x.shape) for x in xs],
               "max_rel_l2": rel, "max_abs": diff,
               "pallas_s": timed["pallas"][1], "xla_s": timed["xla"][1],
               "pallas_over_xla": timed["pallas"][1] / timed["xla"][1]}
        print(json.dumps(row))
        if not rel <= REL_TOL:
            failed.append(name)
    if failed:
        sys.exit(f"kernel_parity: beyond {REL_TOL}: {failed}")
    print(json.dumps({"ok": True, "device": dev.device_kind}))


if __name__ == "__main__":
    main()
