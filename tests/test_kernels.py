"""Pallas kernel validation: shape/dtype sweeps, assert_allclose against
the pure-jnp oracles (interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.percentile_norm.ops import percentile_normalize
from repro.kernels.percentile_norm.ref import percentile_normalize_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

KEY = jax.random.PRNGKey(42)


# ------------------------------------------------------------ flash attn
FLASH_CASES = [
    # B, Sq, Sk, H, Kh, hd, causal, window, bq, bk
    (2, 128, 128, 4, 2, 64, True, None, 64, 64),
    (1, 256, 256, 8, 8, 32, True, 64, 128, 64),
    (2, 100, 100, 4, 1, 64, False, None, 32, 32),
    (1, 512, 512, 4, 2, 128, True, None, 256, 256),
    (1, 64, 192, 2, 2, 16, False, None, 64, 64),   # cross-length
    (3, 80, 80, 6, 3, 48, True, 32, 16, 16),       # odd sizes + window
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, H, Kh, hd, causal, window, bq, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Kh, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Kh, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


FLASH_GRAD_CASES = [
    # B, Sq, Sk, H, Kh, hd, causal, window, bq, bk
    (2, 128, 128, 4, 2, 64, True, None, 64, 64),
    (1, 100, 100, 4, 1, 32, False, None, 32, 32),   # padding path
    (3, 80, 80, 6, 3, 48, True, 32, 16, 16),        # window + GQA
    (1, 64, 192, 2, 2, 16, False, None, 64, 64),    # cross-length
]


@pytest.mark.parametrize("case", FLASH_GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads_match_ref(case, dtype):
    """The custom-VJP backward kernels agree with autodiff through the
    jnp oracle — the contract that lets training run the Pallas path."""
    B, Sq, Sk, H, Kh, hd, causal, window, bq, bk = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Kh, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Kh, hd), dtype)
    co = jax.random.normal(ks[3], (B, Sq, H, hd), jnp.float32)

    def f(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
        return jnp.sum(out.astype(jnp.float32) * co)

    def f_ref(q, k, v):
        out = attention_ref(q, k, v, causal=causal, window=window)
        return jnp.sum(out.astype(jnp.float32) * co)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    for a, b, name in zip(g, g_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


# ------------------------------------------------------------- ssd scan
SSD_CASES = [
    # Bs, S, nh, hp, g, N, chunk
    (2, 64, 4, 16, 1, 16, 16),
    (1, 96, 8, 32, 2, 32, 32),
    (2, 130, 4, 16, 4, 8, 32),    # padding path
    (1, 128, 2, 64, 1, 64, 64),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(case, dtype):
    Bs, S, nh, hp, g, N, chunk = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bs, S, nh, hp), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, S, nh))).astype(
        jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (Bs, S, g, N), dtype)
    C = jax.random.normal(ks[4], (Bs, S, g, N), dtype)
    y = ssd_scan(x, dt, A, B, C, chunk=chunk)
    yr, _ = ssd_ref(x, dt, A, B, C)
    tol = 5e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=tol, rtol=tol)


SSD_GRAD_CASES = [
    # Bs, S, nh, hp, g, N, chunk
    (2, 64, 4, 16, 1, 16, 16),
    (2, 130, 4, 16, 4, 8, 32),    # padding path
    (1, 96, 8, 32, 2, 32, 32),
]


@pytest.mark.parametrize("case", SSD_GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_grads_match_ref(case, dtype):
    """jax.grad through the Pallas SSD op (custom VJP) agrees with
    autodiff through the sequential-recurrence oracle."""
    Bs, S, nh, hp, g, N, chunk = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (Bs, S, nh, hp), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, S, nh))).astype(
        jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (Bs, S, g, N), dtype)
    C = jax.random.normal(ks[4], (Bs, S, g, N), dtype)
    co = jax.random.normal(ks[5], (Bs, S, nh, hp), jnp.float32)

    def f(x, dt, A, B, C):
        y = ssd_scan(x, dt, A, B, C, chunk=chunk)
        return jnp.sum(y.astype(jnp.float32) * co)

    def f_ref(x, dt, A, B, C):
        y, _ = ssd_ref(x, dt, A, B, C)
        return jnp.sum(y.astype(jnp.float32) * co)

    grads = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    grads_ref = jax.grad(f_ref, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    tol = 2e-3 if dtype == jnp.float32 else 2e-1
    for a, b, name in zip(grads, grads_ref, ("dx", "ddt", "dA", "dB", "dC")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


def test_ssd_scan_return_state_matches_ref():
    """return_state=True yields the kernel's carried final state, and
    grads flow through the state output too."""
    Bs, S, nh, hp, N = 2, 64, 4, 16, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bs, S, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (Bs, S, 1, N))
    C = jax.random.normal(ks[4], (Bs, S, 1, N))
    y, h = ssd_scan(x, dt, A, B, C, chunk=16, return_state=True)
    yr, hr = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=5e-4, rtol=5e-4)
    gh = jax.grad(lambda x: jnp.sum(
        ssd_scan(x, dt, A, B, C, chunk=16, return_state=True)[1]))(x)
    gh_ref = jax.grad(lambda x: jnp.sum(ssd_ref(x, dt, A, B, C)[1]))(x)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_ref),
                               atol=5e-4, rtol=5e-4)


def test_ssd_scan_state_continuity():
    """Scanning two halves with carried state == scanning the whole."""
    from repro.models.ssm import ssd_chunked
    from repro.configs.base import SSMConfig
    cfg = SSMConfig(d_state=16, head_dim=16, n_groups=1, chunk=16)
    ks = jax.random.split(KEY, 5)
    Bs, S, nh, hp, N = 2, 64, 4, 16, 16
    x = jax.random.normal(ks[0], (Bs, S, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (Bs, S, 1, N))
    C = jax.random.normal(ks[4], (Bs, S, 1, N))
    y_full, h_full = ssd_chunked(x, dt, A, B, C, cfg)
    y1, h1 = ssd_chunked(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32], cfg)
    y2, h2 = ssd_chunked(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                         cfg, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------- percentile norm
@pytest.mark.parametrize("shape", [(64, 64, 3), (100, 37, 13), (257, 3),
                                   (31, 31, 1)])
@pytest.mark.parametrize("block_rows", [32, 128])
def test_percentile_norm_matches_ref(shape, block_rows):
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.gamma(2.0, 500.0, size=shape).astype(np.float32))
    out = percentile_normalize(img, block_rows=block_rows)
    ref = percentile_normalize_ref(img)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0


def test_percentile_norm_constant_band_safe():
    img = jnp.ones((64, 64, 2))
    out = percentile_normalize(img)
    assert bool(jnp.isfinite(out).all())


PCT_GRAD_SHAPES = [(257, 5), (64, 64, 3), (100, 37, 13)]


@pytest.mark.parametrize("shape", PCT_GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_percentile_norm_grads_match_ref(shape, dtype):
    """jax.grad through the Pallas stretch (custom VJP) agrees with
    autodiff through the pure-jnp oracle — including the percentile
    bounds' interpolation gradients, which stay outside the custom-VJP
    boundary.  Completes the per-dtype fwd+grad contract the other two
    kernels got in PR 4."""
    ks = jax.random.split(KEY, 2)
    x = (jax.random.normal(ks[0], shape) * 3.0).astype(dtype)
    co = jax.random.normal(ks[1], shape, jnp.float32)

    def f(v):
        return jnp.sum(percentile_normalize(v, block_rows=64) * co)

    def f_ref(v):
        return jnp.sum(percentile_normalize_ref(v) * co)

    g = jax.grad(f)(x)
    g_ref = jax.grad(f_ref)(x)
    assert g.shape == x.shape and g.dtype == x.dtype
    # f32 tolerance matches the SSD grad test: the percentile-neighbor
    # pixels carry the summed dlo/dhi term, where division-vs-reciprocal
    # rounding at the clip boundary costs a few 1e-4 relative
    tol = 2e-3 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_ref, np.float32),
                               atol=tol, rtol=tol)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_percentile_norm_grad_zero_outside_stretch():
    """Pixels clipped at 0 or 1 contribute zero input gradient through
    the stretch path (clip subgradient), and a constant band (hi == lo)
    stays finite instead of emitting inf/nan."""
    x = jnp.asarray(np.linspace(-100.0, 100.0, 128,
                                dtype=np.float32)).reshape(-1, 1)
    g = jax.grad(lambda v: jnp.sum(percentile_normalize(v)))(x)
    gf = np.asarray(g)
    # extremes sit outside [p1, p99]: clipped, so only the percentile
    # interpolation term (exactly zero for non-neighbor ranks) remains
    assert gf[0, 0] == 0.0 and gf[-1, 0] == 0.0
    g_const = jax.grad(lambda v: jnp.sum(percentile_normalize(v)))(
        jnp.ones((64, 2)))
    assert bool(jnp.isfinite(g_const).all())


def test_ssd_seq_parallel_matches_chunked():
    """The sequence-parallel SSD decomposition (per-segment scan + state
    combine + local correction) is exact vs the plain chunked scan."""
    from repro.configs.base import SSMConfig
    from repro.models.ssm import ssd_chunked, ssd_seq_parallel
    cfg = SSMConfig(d_state=16, head_dim=16, n_groups=2, chunk=16)
    ks = jax.random.split(KEY, 5)
    Bs, S, nh, N = 2, 128, 4, 16
    x = jax.random.normal(ks[0], (Bs, S, nh, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (Bs, S, 2, N))
    C = jax.random.normal(ks[4], (Bs, S, 2, N))
    y0, h0 = ssd_chunked(x, dt, A, B, C, cfg)
    for n_seg in (2, 4, 8):
        y1, h1 = ssd_seq_parallel(x, dt, A, B, C, cfg, n_seg)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                                   atol=2e-5, rtol=2e-5)
