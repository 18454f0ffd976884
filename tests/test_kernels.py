"""Per-kernel interpret-mode validation against the pure-jnp oracles,
swept over shapes and dtypes (the deliverable-(c) kernel contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg_agg import kernel as agg_k, ref as agg_r
from repro.kernels.flash_attention import kernel as fa_k, ref as fa_r
from repro.kernels.wkv6 import kernel as wkv_k, ref as wkv_r


# ---------------------------------------------------------------------------
# fedavg_agg ------------------------------------------------------------------
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 7), (3, 100), (5, 128, 33),
                                   (2, 16384), (4, 3, 5, 7),
                                   # several client tiles, ragged tail
                                   (64, 257), (70, 3, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_agg_sweep(shape, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=shape[0]), jnp.float32)
    w = w / jnp.sum(w)
    out = agg_k.weighted_aggregate(x, w, interpret=True)
    ref = agg_r.weighted_aggregate(x, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_fedavg_agg_convex_combination_bounds():
    """Property: the aggregate lies in the convex hull of the inputs."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 257)), jnp.float32)
    w = jnp.asarray([0.25, 0.25, 0.25, 0.25], jnp.float32)
    out = np.asarray(agg_k.weighted_aggregate(x, w, interpret=True))
    assert (out <= np.max(np.asarray(x), 0) + 1e-5).all()
    assert (out >= np.min(np.asarray(x), 0) - 1e-5).all()


# ---------------------------------------------------------------------------
# flash_attention --------------------------------------------------------------
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 2, 2, 128, 32),      # MHA
    (2, 4, 2, 256, 64),      # GQA 2:1
    (1, 8, 1, 128, 64),      # MQA
    (2, 4, 4, 512, 16),
])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(b, hq, hkv, s, d, window):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    out = fa_k.flash_attention(q, k, v, causal=True, window=window,
                               block_q=64, block_k=64, interpret=True)
    ref = fa_r.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    out = fa_k.flash_attention(q, k, v, block_q=64, block_k=64,
                               interpret=True)
    ref = fa_r.attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_blocked_attention_matches_exact():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 2, 4096, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 4096, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 4096, 32)), jnp.float32)
    out = fa_r.blocked_attention(q, k, v, causal=True, block=512)
    ref = fa_r.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_rows_attend_within_window_only():
    """Property: with window=1 each row attends only to itself."""
    rng = np.random.default_rng(3)
    s, d = 128, 16
    q = jnp.asarray(rng.normal(size=(1, 1, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, s, d)), jnp.float32)
    out = fa_k.flash_attention(q, k, v, causal=True, window=1,
                               block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[0, 0], np.asarray(v)[0, 0],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# wkv6 -------------------------------------------------------------------------
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,t,d,chunk", [
    (1, 1, 32, 8, 8), (2, 3, 64, 16, 16), (1, 2, 128, 64, 128),
    (2, 2, 96, 32, 32),
])
def test_wkv6_sweep(b, h, t, d, chunk):
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.7, 0.999, size=(b, h, t, d)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, d)), jnp.float32) * 0.1
    out = wkv_k.wkv(r, k, v, w, u, chunk=chunk, interpret=True)
    ref = wkv_r.wkv(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_decode_step_consistency():
    """Running T decode steps == the full-sequence recurrence."""
    rng = np.random.default_rng(1)
    b, h, t, d = 1, 2, 24, 8
    r, k, v = (jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.8, 0.99, size=(b, h, t, d)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, d)), jnp.float32) * 0.1
    ref = wkv_r.wkv(r, k, v, w, u)
    s = jnp.zeros((b, h, d, d), jnp.float32)
    outs = []
    for i in range(t):
        s, o = wkv_r.wkv_step(s, r[:, :, i], k[:, :, i], v[:, :, i],
                              w[:, :, i], u)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 2)),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_wkv6_decay_property():
    """Property: with w=0 (full decay) the state resets every step, so the
    output depends only on the current token: o_t = r_t @ (u*k_t v_t^T)."""
    rng = np.random.default_rng(2)
    b, h, t, d = 1, 1, 8, 4
    r, k, v = (jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
               for _ in range(3))
    w = jnp.zeros((b, h, t, d), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    out = np.asarray(wkv_r.wkv(r, k, v, w, u))
    for i in range(1, t):
        expected = np.asarray(r)[0, 0, i] @ (
            np.asarray(u)[0][:, None] * np.outer(np.asarray(k)[0, 0, i],
                                                 np.asarray(v)[0, 0, i])
            + np.outer(np.asarray(k)[0, 0, i - 1], np.asarray(v)[0, 0, i - 1]))
        np.testing.assert_allclose(out[0, 0, i], expected, rtol=1e-4,
                                   atol=1e-4)
