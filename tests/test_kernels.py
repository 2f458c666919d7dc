"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import (chunked_attention,
                                               reference_attention)
from repro.kernels.gmm.ops import gmm
from repro.kernels.gmm.ref import gmm_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.selective_scan.ops import selective_scan
from repro.models.ssm import selective_scan_ref

RNG = np.random.default_rng(0)


def _t(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # B, Hq, Hkv, Sq, Skv, hd, causal, window, softcap, dtype
    (2, 4, 2, 64, 64, 32, True, 0, 0.0, jnp.float32),
    (1, 8, 8, 128, 128, 64, True, 0, 0.0, jnp.float32),
    (2, 4, 1, 96, 96, 32, True, 32, 0.0, jnp.float32),
    (1, 4, 2, 64, 64, 32, True, 0, 50.0, jnp.float32),
    (1, 2, 2, 80, 208, 16, False, 0, 0.0, jnp.float32),
    (2, 4, 2, 64, 64, 32, True, 0, 0.0, jnp.bfloat16),
    (1, 2, 1, 33, 65, 32, True, 0, 0.0, jnp.float32),   # ragged sizes
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case):
    B, Hq, Hkv, Sq, Skv, hd, causal, window, cap, dt = case
    q, k, v = _t(B, Sq, Hq, hd, dtype=dt), _t(B, Skv, Hkv, hd, dtype=dt), \
        _t(B, Skv, Hkv, hd, dtype=dt)
    off = Skv - Sq if causal else 0
    ref = chunked_attention(q, k, v, causal=causal, window=window,
                            softcap=cap, q_offset=off, chunk=32)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          q_offset=off, bq=32, bk=32, interpret=True)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_chunked_oracle_vs_quadratic_reference():
    q, k, v = _t(2, 40, 4, 16), _t(2, 40, 2, 16), _t(2, 40, 2, 16)
    a = chunked_attention(q, k, v, causal=True, chunk=8)
    b = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@given(sq=st.integers(8, 48), skv=st.integers(8, 48),
       hd=st.sampled_from([16, 32]), window=st.sampled_from([0, 8]))
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(sq, skv, hd, window):
    q, k, v = _t(1, sq, 2, hd), _t(1, skv, 2, hd), _t(1, skv, 2, hd)
    off = max(skv - sq, 0)
    ref = chunked_attention(q, k, v, causal=True, window=window,
                            q_offset=off, chunk=8)
    out = flash_attention(q, k, v, causal=True, window=window, q_offset=off,
                          bq=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,di,st_,bd,bs", [
    (1, 64, 32, 4, 16, 16),
    (2, 128, 64, 8, 32, 64),
    (1, 32, 16, 16, 16, 32),
])
def test_selective_scan_vs_ref(B, S, di, st_, bd, bs):
    u = _t(B, S, di)
    dt = jnp.abs(_t(B, S, di, scale=0.1)) + 0.01
    a = -jnp.abs(_t(di, st_))
    b, c = _t(B, S, st_), _t(B, S, st_)
    dk = jnp.ones((di,))
    h0 = _t(B, di, st_, scale=0.2)
    y1, h1 = selective_scan(u, dt, a, b, c, dk, h0, use_pallas=True,
                            interpret=True, bd=bd, bs=bs)
    y2, h2 = selective_scan_ref(u, dt, a, b, c, dk, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5)


# ---------------------------------------------------------------------------
# gmm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes,D,F,bt", [
    ([30, 0, 17, 40, 13], 32, 48, 16),
    ([4, 4, 4, 4], 16, 16, 4),
    ([128], 64, 32, 32),
    ([0, 0, 50], 32, 64, 8),
])
def test_gmm_vs_ragged_dot(sizes, D, F, bt):
    T = sum(sizes)
    E = len(sizes)
    x = _t(T, D)
    w = _t(E, D, F)
    gs = jnp.asarray(np.array(sizes), jnp.int32)
    out = gmm(x, w, gs, use_pallas=True, interpret=True, bt=bt)
    ref = gmm_ref(x, w, gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((4, 128), jnp.float32),
    ((3, 77, 256), jnp.bfloat16),
    ((1, 1, 64), jnp.float32),
    ((260, 512), jnp.bfloat16),
])
def test_rmsnorm_vs_ref(shape, dtype):
    x = _t(*shape, dtype=dtype)
    sc = _t(shape[-1]) + 1.0
    out = rmsnorm(x, sc, use_pallas=True, interpret=True)
    ref = rmsnorm_ref(x, sc)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
