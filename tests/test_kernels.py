"""Per-kernel interpret-mode validation: shape/dtype sweeps vs. the pure
jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.decode_attention import flash_decode_bkgd
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas
from repro.kernels import stressors

K = jax.random.PRNGKey


def _allclose(a, b, **kw):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **kw)


# --------------------------- flash attention -------------------------- #
@pytest.mark.parametrize("S,T,D,g,kind,dtype", [
    (128, 128, 64, 1, "causal", jnp.float32),
    (256, 256, 128, 4, "causal", jnp.bfloat16),
    (128, 384, 64, 2, "bidirectional", jnp.float32),
    (200, 200, 64, 2, "causal", jnp.float32),        # non-multiple of block
    (256, 256, 64, 1, "local", jnp.float32),
])
def test_flash_attention(S, T, D, g, kind, dtype):
    BKV = 2
    q = jax.random.normal(K(0), (BKV * g, S, D), dtype)
    k = jax.random.normal(K(1), (BKV, T, D), dtype)
    v = jax.random.normal(K(2), (BKV, T, D), dtype)
    out = flash_attention_bhsd(q, k, v, kind=kind, window=64,
                               block_q=128, block_k=128, interpret=True)
    want = ref.ref_flash_attention(q, k, v, kind=kind, window=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    _allclose(out, want, rtol=tol, atol=tol)


def test_flash_attention_model_layout():
    B, S, H, KVH, D = 2, 128, 8, 2, 64
    q = jax.random.normal(K(0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(K(1), (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(K(2), (B, S, KVH, D), jnp.float32)
    from repro.models.attention import reference_attention
    out = ops.flash_attention(q, k, v, kind="causal", interpret=True)
    want = reference_attention(q, k, v, "causal")
    _allclose(out, want, rtol=2e-5, atol=2e-5)


# ---------------------------- flash decode ---------------------------- #
@pytest.mark.parametrize("T,G,D,block_k", [(512, 4, 64, 128),
                                           (384, 1, 128, 256),
                                           (1024, 8, 64, 512)])
def test_flash_decode(T, G, D, block_k):
    BKV = 3
    q = jax.random.normal(K(0), (BKV, G, D), jnp.float32)
    k = jax.random.normal(K(1), (BKV, T, D), jnp.float32)
    v = jax.random.normal(K(2), (BKV, T, D), jnp.float32)
    lens = jnp.array([T, T // 2, 7], jnp.int32)
    out = flash_decode_bkgd(q, k, v, lens, block_k=block_k, interpret=True)
    want = ref.ref_flash_decode(q, k, v, lens)
    _allclose(out, want, rtol=2e-5, atol=2e-5)


def test_flash_decode_vs_model_decode_attention():
    from repro.models.attention import decode_attention
    B, H, KVH, D, T = 2, 8, 2, 64, 256
    q = jax.random.normal(K(0), (B, 1, H, D), jnp.float32)
    k = jax.random.normal(K(1), (B, T, KVH, D), jnp.float32)
    v = jax.random.normal(K(2), (B, T, KVH, D), jnp.float32)
    lens = jnp.array([200, 64], jnp.int32)
    out = ops.flash_decode(q, k, v, lens, interpret=True)
    want = decode_attention(q, k, v, lens)
    _allclose(out, want, rtol=2e-5, atol=2e-5)


# ------------------------------ rmsnorm ------------------------------- #
@pytest.mark.parametrize("R,d,dtype", [(64, 256, jnp.float32),
                                       (100, 512, jnp.bfloat16),
                                       (1024, 128, jnp.float32)])
def test_rmsnorm(R, d, dtype):
    x = jax.random.normal(K(0), (R, d), dtype)
    s = jax.random.normal(K(1), (d,), jnp.float32)
    out = rmsnorm_pallas(x, s, interpret=True)
    want = ref.ref_rmsnorm(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    _allclose(out, want, rtol=tol, atol=tol)


# ------------------------------ ssm scan ------------------------------ #
@pytest.mark.parametrize("S,di,N,chunk,block_d", [
    (128, 64, 8, 32, 32), (64, 128, 16, 64, 128), (96, 32, 4, 16, 32)])
def test_ssm_scan(S, di, N, chunk, block_d):
    Bb = 2
    x = jax.random.normal(K(0), (Bb, S, di), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(K(1), (Bb, S, di), jnp.float32) - 2)
    A = -jnp.exp(jax.random.normal(K(2), (di, N), jnp.float32) * 0.3)
    B = jax.random.normal(K(3), (Bb, S, N), jnp.float32) * 0.5
    C = jax.random.normal(K(4), (Bb, S, N), jnp.float32) * 0.5
    out = ssm_scan_pallas(x, dt, A, B, C, chunk=chunk, block_d=block_d,
                          interpret=True)
    want = ref.ref_ssm_scan(x, dt, A, B, C)
    _allclose(out, want, rtol=1e-4, atol=1e-4)


def test_ssm_scan_matches_model_chunked_scan():
    """Pallas kernel == the model's chunked associative scan == oracle."""
    from repro.models.ssm import mamba1_scan
    Bb, S, di, N = 1, 64, 32, 8
    x = jax.random.normal(K(0), (Bb, S, di), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(K(1), (Bb, S, di)) - 2)
    A = -jnp.exp(jax.random.normal(K(2), (di, N)) * 0.3)
    B = jax.random.normal(K(3), (Bb, S, N)) * 0.5
    C = jax.random.normal(K(4), (Bb, S, N)) * 0.5
    y_model, _ = mamba1_scan(x, dt, A, B, C, chunk=16)
    y_oracle = ref.ref_ssm_scan(x, dt, A, B, C)
    _allclose(y_model, y_oracle, rtol=1e-4, atol=1e-4)


# ------------------------------ stressors ----------------------------- #
def test_stress_mxu():
    a = jax.random.normal(K(0), (2, 128, 128), jnp.float32)
    b = jax.random.normal(K(1), (128, 128), jnp.float32) * 0.1
    out = stressors.stress_mxu(a, b, iters=4, interpret=True)
    want = ref.ref_stress_mxu(a, b, iters=4)
    _allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ilp", [1, 2, 4])
def test_stress_vpu(ilp):
    x = jax.random.normal(K(0), (256, 128), jnp.float32)
    out = stressors.stress_vpu(x, iters=16, ilp=ilp, interpret=True)
    want = ref.ref_stress_vpu(x, iters=16, ilp=ilp)
    _allclose(out, want, rtol=1e-5, atol=1e-5)


def test_stress_hbm():
    x = jax.random.normal(K(0), (2048, 128), jnp.bfloat16)
    out = stressors.stress_hbm(x, interpret=True)
    _allclose(out, x, rtol=0, atol=0)


@pytest.mark.parametrize("stride", [1, 8, 32])
def test_stress_vmem(stride):
    x = jax.random.normal(K(0), (512, 128), jnp.float32)
    out = stressors.stress_vmem(x, iters=8, stride=stride, interpret=True)
    want = ref.ref_stress_vmem(x, iters=8, stride=stride)
    _allclose(out, want, rtol=1e-5, atol=1e-5)
