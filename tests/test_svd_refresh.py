"""The stacked randomized-SVD refresh primitives (no hypothesis needed --
this file runs on the offline CI image; the hypothesis-gated property
tests live in test_projectors.py / test_sara_sampling.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.projectors import (
    ProjectorConfig,
    refresh_projector,
    refresh_projector_stacked,
)
from repro.core.sampling import (
    gumbel_topk_indices_batched,
    inclusion_probabilities_mc,
)
from repro.core.svd import (
    clamp_sketch,
    randomized_svd,
    randomized_svd_stacked,
)

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,n,k,oversample,power_iters", [
    (4, 300, 4, 8, 2),     # kp would exceed min(m, n) without the clamp
    (300, 4, 4, 64, 2),    # huge oversample on the short side
    (8, 8, 8, 8, 4),       # square, full-rank sketch, many iterations
    (24, 48, 40, 8, 2),    # k > min(m, n): must clamp, not thin silently
    (6, 100, 2, 0, 3),     # zero oversample
])
def test_randomized_svd_degenerate_shapes_orthonormal(
    m, n, k, oversample, power_iters
):
    """Tiny ragged leaves: the sketch-width clamp (svd.clamp_sketch) must
    keep the basis orthonormal with EXACTLY min(k, m, n) columns -- the
    old code could silently return a thinner ``u[:, :k]``, and unclamped
    power iterations square the spectrum where fp32 can least afford it."""
    g = jax.random.normal(KEY, (m, n)) * 0.1
    u, s = randomized_svd(
        g, k, jax.random.PRNGKey(1),
        oversample=oversample, power_iters=power_iters,
    )
    k_eff = min(k, m, n)
    assert u.shape == (m, k_eff) and s.shape == (k_eff,)
    np.testing.assert_allclose(
        np.asarray(u.T @ u), np.eye(k_eff), atol=1e-4
    )
    assert (np.diff(np.asarray(s)) <= 1e-5).all()  # sorted spectrum
    # the clamp itself: kp never exceeds min(m, n), and a full-range
    # sketch disables the (pointless, fragile) power iterations
    k_c, kp, iters = clamp_sketch(m, n, k, oversample, power_iters)
    assert k_c == k_eff and k_c <= kp <= min(m, n)
    assert iters == (0 if kp >= min(m, n) else power_iters)


def test_randomized_svd_zero_gradient_stays_finite():
    """Step-0 zero gradients must not produce NaNs in the basis."""
    u, s = randomized_svd(jnp.zeros((16, 32)), 4, KEY)
    assert np.isfinite(np.asarray(u)).all()
    assert np.allclose(np.asarray(s), 0.0)


def _planted_stack(b, m, n, spectrum, seed=0):
    """A (b, m, n) float64 stack with random singular vectors and the given
    singular values."""
    rng = np.random.default_rng(seed)
    d = len(spectrum)
    out = []
    for _ in range(b):
        u, _ = np.linalg.qr(rng.standard_normal((m, d)))
        v, _ = np.linalg.qr(rng.standard_normal((n, d)))
        out.append((u * spectrum) @ v.T)
    return np.stack(out)


@pytest.mark.parametrize("decay", [0.5, 1.0])
def test_randomized_svd_stacked_matches_float64_svd(decay):
    """The small step (left singular pairs of the wide B = Q^T G from the
    eigendecomposition of its Gram matrix) against float64 SVD of G.  The
    planted spectrum i^-decay has rank k' = k + oversample, so the sketch
    holds G's whole range and any error left is the small step's."""
    b, m, n, k, oversample = 3, 96, 400, 40, 8
    spectrum = np.zeros(min(m, n))
    spectrum[: k + oversample] = np.arange(1.0, k + oversample + 1) ** -decay
    g = _planted_stack(b, m, n, spectrum)
    keys = jax.random.split(jax.random.fold_in(KEY, 5), b)
    u, s = randomized_svd_stacked(
        jnp.asarray(g, jnp.float32), k, keys, oversample=oversample
    )
    u, s = np.asarray(u, np.float64), np.asarray(s, np.float64)
    assert u.shape == (b, m, k) and s.shape == (b, k)
    u_ref, s_ref, _ = np.linalg.svd(g, full_matrices=False)
    for i in range(b):
        np.testing.assert_allclose(u[i].T @ u[i], np.eye(k), atol=1e-5)
        assert (np.diff(s[i]) <= 0).all()  # descending, as SARA indexes
        np.testing.assert_allclose(s[i], s_ref[i, :k], rtol=1e-3)
        overlap = np.sum((u_ref[i, :, :k].T @ u[i]) ** 2) / k
        assert overlap >= 1 - 1e-4, overlap


def test_randomized_svd_stacked_tiny_gradient_stays_finite():
    """A gradient of scale 1e-20: its Gram matrix underflows float32, and
    the basis must still come back finite and orthonormal."""
    g = jax.random.normal(KEY, (3, 96, 400)) * 1e-20
    u, s = randomized_svd_stacked(g, 40, jax.random.split(KEY, 3))
    u, s = np.asarray(u), np.asarray(s)
    assert np.isfinite(u).all() and np.isfinite(s).all()
    assert (s >= 0).all()
    for i in range(3):
        np.testing.assert_allclose(u[i].T @ u[i], np.eye(40), atol=1e-5)


def test_stacked_refresh_matches_per_slice():
    """refresh_projector_stacked == refresh_projector per slice, given the
    same per-slice keys (the batched engine's per-bucket contract)."""
    b, d, n, r = 5, 24, 40, 6
    g = jax.random.normal(KEY, (b, d, n)) * 0.1
    keys = jax.random.split(jax.random.fold_in(KEY, 7), b)
    prev = jnp.broadcast_to(jnp.eye(d, r), (b, d, r))
    for method, kw in [
        ("sara", dict(svd_backend="randomized")),
        ("dominant", dict(svd_backend="randomized")),
        ("golore", {}),
        ("grass", {}),
        ("online_pca", {}),
    ]:
        cfg = ProjectorConfig(method=method, rank=r, **kw)
        stacked = refresh_projector_stacked(g, keys, prev, cfg, rank=r)
        assert stacked.shape == (b, d, r)
        for i in range(b):
            single = refresh_projector(
                g[i], keys[i], prev[i], cfg, side="left", rank=r
            )
            np.testing.assert_array_equal(
                np.asarray(stacked[i]), np.asarray(single),
                err_msg=method,
            )


def test_stacked_refresh_rejects_exact_backend():
    """The coverage matrix is enforced, not implied: sara/dominant stacked
    refresh is randomized-only (exact stays on the per-leaf loop)."""
    g = jnp.zeros((2, 8, 12))
    keys = jax.random.split(KEY, 2)
    cfg = ProjectorConfig(method="sara", rank=4, svd_backend="exact")
    with pytest.raises(ValueError, match="randomized"):
        refresh_projector_stacked(g, keys, None, cfg, rank=4)


def test_batched_inclusion_frequencies_match_mc():
    """Empirical inclusion frequencies of the batched sampler match
    inclusion_probabilities_mc (the per-slice MC oracle) within MC noise."""
    w = jnp.array([8.0, 4.0, 2.0, 1.0, 1.0, 0.5])
    r, n_mc = 3, 8192
    keys = jax.random.split(jax.random.PRNGKey(3), n_mc)
    # one batched dispatch: n_mc rows of the same weight vector
    idx = gumbel_topk_indices_batched(
        jnp.broadcast_to(w, (n_mc, w.shape[0])), r, keys, sort_indices=False
    )
    onehot = jax.nn.one_hot(idx, w.shape[0], dtype=jnp.float32).sum(axis=1)
    freq = np.asarray(onehot.mean(axis=0))
    ref = np.asarray(
        inclusion_probabilities_mc(w, r, jax.random.PRNGKey(11), n_mc)
    )
    se = np.sqrt(ref * (1 - ref) * 2 / n_mc)
    assert np.all(np.abs(freq - ref) < 4 * se + 0.015), (freq, ref)
