"""GPTQ engine + CLAQ orchestration: compensation quality, reservation
exactness, stripe packaging, method orderings (paper Tables 1/3/4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (APConfig, CLAQConfig, ORConfig, gptq, proxy_loss,
                        quantize_matrix, rtn_quantize_matrix)

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    rows, cols = 48, 96
    W = rng.normal(size=(rows, cols)).astype(np.float32)
    W[:, :6] += rng.standard_t(df=2, size=(rows, 6)) * 4
    X = rng.normal(size=(384, cols)).astype(np.float32)
    X[:, ::7] *= 3.0  # correlated/heteroscedastic inputs
    H = (2 * X.T @ X).astype(np.float32)
    return jnp.asarray(W), jnp.asarray(H)


def test_gptq_compensation_beats_rtn(problem):
    W, H = problem
    cfg = CLAQConfig(bits=3, method="uniform", gptq_blocksize=32)
    _, Q_gptq, st = quantize_matrix(W, H, cfg)
    Q_rtn, _, _ = rtn_quantize_matrix(W, 3, "uniform")
    assert st.proxy_loss < float(proxy_loss(W, Q_rtn, H))


def test_kmeans_beats_uniform(problem):
    W, H = problem
    km = quantize_matrix(W, H, CLAQConfig(bits=3, method="kmeans",
                                          kmeans_iters=8, gptq_blocksize=32))[2]
    un = quantize_matrix(W, H, CLAQConfig(bits=3, method="uniform",
                                          gptq_blocksize=32))[2]
    assert km.proxy_loss < un.proxy_loss


def test_fusion_beats_pure_low_bit(problem):
    W, H = problem
    fusion = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
        ap=APConfig(2.2, 2, 4), orr=ORConfig(0.1)))[2]
    pure = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=6, gptq_blocksize=32))[2]
    assert fusion.proxy_loss < pure.proxy_loss


def test_or_beats_ap_at_same_budget():
    """Paper §4.3.2: at equal extra budget, reserving fp outliers beats
    spending the same bits on higher precision — the effect the paper
    attributes to *element*-granular outliers that column-granular AP
    cannot capture.  Construct exactly that regime: scattered huge
    entries, not column-aligned."""
    rng = np.random.default_rng(42)
    rows, cols = 64, 96
    W = rng.normal(size=(rows, cols)).astype(np.float32)
    mask = rng.random(W.shape) < 0.02
    W[mask] += np.sign(W[mask]) * rng.uniform(8, 20, size=mask.sum())
    X = rng.normal(size=(256, cols)).astype(np.float32)
    H = jnp.asarray(2 * X.T @ X)
    W = jnp.asarray(W)
    # budget 0.5 bits: large enough that OR's integer per-column counts
    # land within ~0.1 bit of AP's achieved budget (paper uses 4096-row
    # matrices where 0.28-bit budgets round finely; here rows=64)
    orr = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
        orr=ORConfig(0.5)))[2]
    ap = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
        ap=APConfig(2.5, 2, 4)))[2]
    assert abs(orr.effective_bits - ap.effective_bits) < 0.15
    assert orr.proxy_loss < ap.proxy_loss


def test_reserved_entries_have_zero_error(problem):
    W, H = problem
    qt, Q, _ = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=5, gptq_blocksize=32,
        orr=ORConfig(0.2)))
    deq = qt.dequantize()
    np.testing.assert_allclose(np.asarray(deq), np.asarray(Q), atol=1e-5)
    assert int(qt.out_count.sum()) > 0


def test_identity_hessian_matches_rtn_error_scale(problem):
    W, _ = problem
    _, Q, st = quantize_matrix(W, None, CLAQConfig(
        bits=4, method="uniform", gptq_blocksize=32))
    Q_rtn, _, _ = rtn_quantize_matrix(W, 4, "uniform")
    # identity Hessian => no useful compensation signal; errors comparable
    mse_rtn = float(jnp.mean((W - Q_rtn) ** 2))
    assert st.mse <= mse_rtn * 1.5


def test_frozen_codebooks_close_to_live(problem):
    W, H = problem
    live = quantize_matrix(W, H, CLAQConfig(
        bits=3, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
        codebook_mode="live"))[2]
    frozen = quantize_matrix(W, H, CLAQConfig(
        bits=3, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
        codebook_mode="frozen"))[2]
    assert frozen.proxy_loss < live.proxy_loss * 3.0


def test_effective_bits_accounting(problem):
    W, H = problem
    qt, _, st = quantize_matrix(W, H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=4, gptq_blocksize=32,
        ap=APConfig(2.5, 2, 4), orr=ORConfig(0.1)))
    assert 2.4 <= st.effective_bits <= 2.8
    assert st.effective_bits_with_codebooks > st.effective_bits
    # stripes partition the columns
    assert sum(s.n_cols for s in qt.stripes) == qt.cols
    assert sorted(s.bits for s in qt.stripes) == [2, 4]


def test_hessian_accumulation():
    st = gptq.init_hessian(8)
    x1 = jnp.ones((4, 8))
    x2 = 2 * jnp.ones((2, 8))
    st = gptq.accumulate_hessian(st, x1)
    st = gptq.accumulate_hessian(st, x2)
    H = gptq.finalize_hessian(st)
    expected = 2 * (4 * 1.0 + 2 * 4.0) / 6.0
    np.testing.assert_allclose(np.asarray(H), expected, rtol=1e-6)


def test_tap_collector_host_sums_match_device_accumulator():
    """Calibration keeps its Hessian sums on the host; they must equal the
    device accumulator's to f32 rounding, and finalizing empties the
    collector (host memory never holds the sums twice)."""
    from repro.models.modules import TapCollector
    rng = np.random.default_rng(5)
    xs = [jnp.asarray(rng.normal(size=(3, 7, 16)), jnp.float32)
          for _ in range(3)]
    col = TapCollector()
    st = gptq.init_hessian(16)
    for x in xs:
        col.record("m", x)
        st = gptq.accumulate_hessian(st, x)
    got = col.finalized()
    assert set(got) == {"m"} and col.hessians == {}
    np.testing.assert_allclose(got["m"], np.asarray(gptq.finalize_hessian(st)),
                               rtol=1e-6, atol=1e-6)


def test_prepare_hinv_cholesky_is_upper_factor():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(64, 16)).astype(np.float32)
    H = jnp.asarray(X.T @ X)
    U = gptq.prepare_hinv_cholesky(H, percdamp=0.01)
    Un = np.asarray(U)
    assert np.allclose(Un, np.triu(Un), atol=1e-6)       # upper triangular
    damp = 0.01 * float(jnp.mean(jnp.diag(H)))
    Hinv = np.linalg.inv(np.asarray(H) + damp * np.eye(16))
    np.testing.assert_allclose(Un.T @ Un, Hinv, rtol=2e-2, atol=2e-4)
