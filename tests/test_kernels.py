"""Pallas kernel sweeps: shapes × dtypes vs the pure-jnp oracles.

All kernels run in ``interpret=True`` (CPU) and must match ``ref.py``
within dtype-appropriate tolerances. The ``ops.py`` dispatch layer is
additionally swept over both CPU backends (``xla`` fallbacks and
``pallas_interpret``) in-process, so a drift in the non-default path
fails regardless of ``REPRO_KERNEL_BACKEND``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.selective_scan import selective_scan
from repro.kernels.ssd import ssd

RNG = np.random.default_rng(42)


def rand(shape, dtype, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "shape", [(2, 16, 33), (1, 7, 64), (3, 5, 960), (2, 1, 128)]
)
def test_rmsnorm(shape, dtype):
    x = rand(shape, dtype)
    w = rand(shape[-1:], jnp.float32)
    got = rmsnorm(x, w, 1e-5, block_rows=8, interpret=True)
    want = ref.rmsnorm(x, w, 1e-5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,k,d,causal,q_off",
    [
        (2, 32, 32, 4, 2, 16, True, 0),     # GQA causal square
        (1, 17, 63, 5, 1, 8, True, 46),     # ragged + offset (suffix decode)
        (2, 8, 40, 8, 8, 32, False, 0),     # MHA non-causal cross-attn
        (1, 64, 64, 2, 2, 128, True, 0),    # full head_dim tile
    ],
)
def test_flash_attention(b, sq, sk, h, k, d, causal, q_off, dtype):
    q = rand((b, sq, h, d), dtype)
    kk = rand((b, sk, k, d), dtype)
    v = rand((b, sk, k, d), dtype)
    got = flash_attention(q, kk, v, causal=causal, q_offset=q_off,
                          block_q=16, block_k=16, interpret=True)
    want = ref.attention(q, kk, v, causal=causal, q_offset=q_off)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,k,d",
    [(2, 64, 4, 2, 16), (3, 100, 8, 8, 32), (1, 48, 16, 2, 128)],
)
def test_decode_attention(b, s, h, k, d, dtype):
    q = rand((b, h, d), dtype)
    kk = rand((b, s, k, d), dtype)
    v = rand((b, s, k, d), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, b), jnp.int32)
    got = decode_attention(q, kk, v, lens, block_k=16, interpret=True)
    want = ref.decode_attention(q, kk, v, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,di,n,chunk,bc",
    [(2, 40, 24, 8, 16, 16), (1, 16, 128, 16, 8, 64), (2, 7, 8, 4, 16, 8)],
)
def test_selective_scan(b, s, di, n, chunk, bc, dtype):
    x = rand((b, s, di), dtype, 0.5)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, di))) * 0.1, dtype)
    A = jnp.asarray(-np.abs(RNG.standard_normal((di, n))) - 0.1, jnp.float32)
    Bm = rand((b, s, n), dtype, 0.5)
    C = rand((b, s, n), dtype, 0.5)
    D = rand((di,), jnp.float32)
    h0 = rand((b, di, n), jnp.float32, 0.1)
    y, hT = selective_scan(x, dt, A, Bm, C, D, h0, chunk=chunk,
                           block_channels=bc, interpret=True)
    yw, hw = ref.selective_scan(x, dt, A, Bm, C, D, h0)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yw, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hw),
                               atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hs,p,n,chunk",
    [(2, 48, 3, 16, 8, 16), (1, 16, 8, 64, 16, 8), (2, 5, 2, 8, 4, 16)],
)
def test_ssd(b, s, hs, p, n, chunk, dtype):
    x = rand((b, s, hs, p), dtype, 0.5)
    dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, hs))) * 0.1, dtype)
    A = jnp.asarray(-np.abs(RNG.standard_normal((hs,))) - 0.1, jnp.float32)
    Bm = rand((b, s, n), dtype, 0.5)
    C = rand((b, s, n), dtype, 0.5)
    D = rand((hs,), jnp.float32)
    h0 = rand((b, hs, p, n), jnp.float32, 0.1)
    y, hT = ssd(x, dt, A, Bm, C, D, h0, chunk=chunk, interpret=True)
    yw, hw = ref.ssd(x, dt, A, Bm, C, D, h0)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yw, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hw),
                               atol=5e-3, rtol=5e-3)


def _paged_case(b=3, w=4, h=4, k=2, d=16, p=8, max_pages=4, n_pages=16,
                dtype=jnp.float32):
    """A shared page pool with per-sequence page tables: distinct non-zero
    physical pages per row (page 0 is the engine's scratch page) and
    window start positions leaving room for ``w`` queries."""
    q = rand((b, w, h, d), dtype)
    kp = rand((n_pages, p, k, d), dtype)
    vp = rand((n_pages, p, k, d), dtype)
    table = np.stack([
        RNG.choice(np.arange(1, n_pages), max_pages, replace=False)
        for _ in range(b)
    ]).astype(np.int32)
    positions = jnp.asarray(
        RNG.integers(0, p * max_pages - w + 1, b), jnp.int32)
    return q, kp, vp, jnp.asarray(table), positions


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
class TestOpsMatchOracle:
    """Every dispatchable ops.py entry point must match the oracles under
    BOTH CPU backends: the XLA fallbacks are algorithmically identical
    blocked implementations, and the Pallas kernels run in interpret
    mode — so a drift in either path (not just the local default) fails
    tier-1."""

    def test_flash(self, backend):
        q = rand((2, 37, 6, 16), jnp.float32)
        k = rand((2, 37, 2, 16), jnp.float32)
        v = rand((2, 37, 2, 16), jnp.float32)
        with ops.use_backend(backend):
            got = ops.attention(q, k, v, causal=True, block_q=16, block_k=16)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_paged_decode(self, backend):
        q, kp, vp, table, positions = _paged_case(w=1)
        lengths = positions + 1
        with ops.use_backend(backend):
            got = ops.paged_decode_attention(q[:, 0], kp, vp, table, lengths)
        want = ref.paged_decode_attention(q[:, 0], kp, vp, table, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_paged_verify(self, backend, dtype):
        q, kp, vp, table, positions = _paged_case(dtype=dtype)
        with ops.use_backend(backend):
            got = ops.paged_verify_attention(q, kp, vp, table, positions)
        want = ref.paged_verify_attention(q, kp, vp, table, positions)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **tol(dtype)
        )

    def test_paged_verify_equals_sequential_decode(self, backend):
        """The verify window is W decode steps in one call: query j must
        equal a single-token paged decode at length positions + j + 1 —
        the kernel-level face of the engine's exactness guarantee."""
        q, kp, vp, table, positions = _paged_case()
        with ops.use_backend(backend):
            window = ops.paged_verify_attention(q, kp, vp, table, positions)
            for j in range(q.shape[1]):
                step = ops.paged_decode_attention(
                    q[:, j], kp, vp, table, positions + j + 1)
                np.testing.assert_allclose(
                    np.asarray(window[:, j]), np.asarray(step),
                    atol=2e-6, rtol=2e-6)

    def test_scan_chunked(self, backend):
        b, s, di, n = 2, 50, 12, 6
        x = rand((b, s, di), jnp.float32, 0.5)
        dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, di))) * 0.1,
                         jnp.float32)
        A = jnp.asarray(-np.abs(RNG.standard_normal((di, n))) - 0.1,
                        jnp.float32)
        Bm = rand((b, s, n), jnp.float32, 0.5)
        C = rand((b, s, n), jnp.float32, 0.5)
        D = rand((di,), jnp.float32)
        with ops.use_backend(backend):
            y, hT = ops.selective_scan(x, dt, A, Bm, C, D, chunk=16)
        yw, hw = ref.selective_scan(x, dt, A, Bm, C, D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hw),
                                   atol=1e-4, rtol=1e-4)

    def test_ssd_chunked(self, backend):
        b, s, hs, p, n = 1, 33, 2, 8, 4
        x = rand((b, s, hs, p), jnp.float32, 0.5)
        dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, hs))) * 0.1,
                         jnp.float32)
        A = jnp.asarray(-np.abs(RNG.standard_normal((hs,))) - 0.1, jnp.float32)
        Bm = rand((b, s, n), jnp.float32, 0.5)
        C = rand((b, s, n), jnp.float32, 0.5)
        D = rand((hs,), jnp.float32)
        with ops.use_backend(backend):
            y, hT = ops.ssd(x, dt, A, Bm, C, D, chunk=16)
        yw, hw = ref.ssd(x, dt, A, Bm, C, D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hw),
                                   atol=1e-4, rtol=1e-4)


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.kernels import ops
from repro.parallel.partition import activation_sharding

mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
r = np.random.default_rng(0)
f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
B, H, K, D, P, npg, mp = 4, 6, 2, 16, 8, 20, 4
table = jnp.asarray(r.permutation(np.arange(1, npg))[:B * mp]
                    .reshape(B, mp), jnp.int32)
args = dict(
    paged=(f(B, H, D), f(npg, P, K, D), f(npg, P, K, D), table,
           jnp.asarray([5, 17, 32, 1], jnp.int32)),
    rms=(f(B, 1, 24), f(24)),
    flash=(f(1, 16, H, D), f(1, 24, K, D), f(1, 24, K, D)),
    ssd=(f(2, 20, 4, 8), jnp.abs(f(2, 20, 4)) * 0.1, -jnp.abs(f(4)) - 0.1,
         f(2, 20, 4), f(2, 20, 4), f(4)),
    scan=(f(2, 20, 12), jnp.abs(f(2, 20, 12)) * 0.1,
          -jnp.abs(f(12, 4)) - 0.1, f(2, 20, 4), f(2, 20, 4), f(12)),
)
call = dict(
    paged=ops.paged_decode_attention,
    rms=ops.rmsnorm,
    flash=lambda q, k, v: ops.attention(q, k, v, q_offset=8),
    ssd=lambda *a: ops.ssd(*a, chunk=8)[0],
    scan=lambda *a: ops.selective_scan(*a, chunk=8)[0],
)
with ops.use_backend("pallas_interpret"):
    for name, fn in call.items():
        want = fn(*args[name])
        with activation_sharding(mesh):
            got = jax.jit(fn)(*args[name])
        err = float(jnp.abs(got - want).max())
        assert err < 1e-5, (name, err)
print("ok")
"""


def test_kernels_split_per_shard_on_a_mesh():
    """Under an activation mesh each Pallas kernel runs once per shard
    (XLA cannot partition a Mosaic kernel) and matches the plain call."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu"),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")
