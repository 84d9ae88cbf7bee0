"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU; the kernel body is identical on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import flash_attention_reference, ssd_reference
from repro.models.mamba2 import ssd_chunked

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


FLASH_CASES = [
    # (B, S, H, Hkv, hd, causal, window, dtype)
    (1, 64, 2, 2, 32, True, 0, jnp.float32),
    (2, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 256, 8, 1, 32, True, 0, jnp.float32),     # extreme GQA
    (1, 96, 4, 4, 32, True, 32, jnp.float32),     # sliding window
    (2, 128, 4, 2, 64, True, 64, jnp.float32),
    (1, 128, 2, 2, 32, False, 0, jnp.float32),    # bidirectional
    (1, 128, 4, 2, 64, True, 0, jnp.bfloat16),
    (1, 80, 2, 2, 16, True, 0, jnp.float32),      # non-128-multiple S
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_reference(case):
    B, S, H, Hkv, hd, causal, window, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    ref = flash_attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


GRAD_CASES = [
    # (B, S, H, Hkv, hd, causal, window)
    (1, 64, 2, 2, 32, True, 0),
    (2, 96, 4, 2, 16, True, 0),       # GQA group reduce in dk/dv
    (1, 128, 2, 2, 32, True, 32),     # sliding window backward
    (1, 64, 4, 1, 16, False, 0),      # bidirectional, extreme GQA
    (1, 512, 4, 2, 64, True, 0),      # S > block_k: blocked dk/dv
    (1, 256, 2, 2, 32, True, 64),     # windowed, several k blocks
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_bwd_kernel_matches_reference(case):
    """The Pallas blockwise backward (dq/dkv kernels) vs autodiff of the
    reference."""
    B, S, H, Hkv, hd, causal, window = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))

    def f_kernel(q, k, v):
        return (ops.flash_attention(q, k, v, causal=causal,
                                    window=window) ** 2).sum()

    def f_ref(q, k, v):
        o = flash_attention_reference(q.transpose(0, 2, 1, 3),
                                      k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3),
                                      causal=causal, window=window)
        return (o.transpose(0, 2, 1, 3) ** 2).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name} mismatch {case}")


def test_flash_attention_gradients_match_reference():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def f_kernel(q, k, v):
        return (ops.flash_attention(q, k, v) ** 2).sum()

    def f_ref(q, k, v):
        o = flash_attention_reference(q.transpose(0, 2, 1, 3),
                                      k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3))
        return (o.transpose(0, 2, 1, 3) ** 2).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_attention_residuals_are_linear_in_seq():
    """The custom VJP's saved residuals are O(S) — the flash signature."""
    def resid_bytes(S):
        q = jax.ShapeDtypeStruct((1, S, 2, 32), jnp.float32)
        vjp_struct = jax.eval_shape(
            lambda q_, k_, v_: jax.vjp(
                lambda a, b, c: ops.flash_attention(a, b, c), q_, k_, v_)[1],
            q, q, q)
        return sum(int(np.prod(l.shape)) * 4
                   for l in jax.tree_util.tree_leaves(vjp_struct))
    r128, r256 = resid_bytes(128), resid_bytes(256)
    assert r256 <= 2.05 * r128          # linear, not quadratic


SSD_CASES = [
    # (B, S, H, P, N, chunk, dtype)
    (1, 64, 2, 16, 8, 16, jnp.float32),
    (2, 128, 4, 32, 16, 32, jnp.float32),
    (1, 100, 2, 16, 8, 32, jnp.float32),          # padding path
    (1, 128, 1, 64, 32, 64, jnp.float32),
    (1, 64, 2, 16, 8, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_reference(case):
    B, S, H, P, N, chunk, dtype = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    y = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, _ = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-3,
                               atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


def test_ssd_chunked_jnp_matches_reference_and_state():
    """The model-internal chunked SSD (used in training) equals the naive
    recurrence including the carried state."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 96, 4, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, 32)
    y2, s2 = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1e-3)


def test_ssd_gradients_finite():
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 64, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    g = jax.grad(lambda x_: ssd_chunked(x_, dt, A, Bm, Cm, 16)[0].sum())(x)
    assert bool(jnp.all(jnp.isfinite(g)))
