"""SVD backends for projector refresh.

Two backends:
  * ``exact``      -- ``jnp.linalg.svd`` (paper-faithful; what GaLore/SARA use).
  * ``randomized`` -- Halko-Martinsson-Tropp randomized range finder with
    ``q`` subspace-iteration steps.  Matmul-dominant, so it shards over the
    mesh with only small-matrix collectives; this is the TPU-native default at
    8B+ scale where an exact SVD of every layer gradient would serialize.

The randomized chain uses the *fused* subspace-iteration form: one thin QR
per iteration followed by ``Y = G (G^T Q)``, dispatched through
``kernels/power_iter`` so the (n, k') intermediate ``Z = G^T Q`` lives in
VMEM on TPU (jnp einsums elsewhere -- identical math).  Per iteration this
squares the sketch's spectrum exactly like the classical two-QR form; the
dropped inner re-orthonormalization costs some stability for extreme
spectra, which the thin QR between iterations bounds (documented
deviation, traded for halving the QR count and fusing the GEMM pair).
The last step needs only the left singular pairs of the wide ``B = Q^T G``
(k' <= n by the clamp below), so it takes them from ``eigh`` of the
(k', k') Gram matrix ``B B^T``: an SVD of ``B`` would pay for a
Householder QR of its long side and for right vectors that are thrown
away.  The Gram product runs at the default matmul precision, like ``B``'s
own: on a TPU that rounds ``B``'s entries to bfloat16 and accumulates in
float32, a perturbation of ``B`` the size of the one its own product
already left, made before the spectrum is squared.  (At HIGHEST precision
the TPU compiler emitted about 200 MB more program code for the refresh
step, held in device memory, for no gain in the subspace.)

Degenerate shapes are clamped rather than trusted to the caller: ``k`` is
cut to ``min(m, n)`` (so the returned basis always has exactly the
promised, orthonormal columns -- never a silently thinner ``u[:, :k]``),
the sketch width ``k' = k + oversample`` is cut to ``min(m, n)``, and when
``k'`` already spans the full ``min(m, n)``-dimensional range the power
iterations are skipped outright: they cannot enlarge a full sketch, and on
tiny ragged leaves their spectrum-squaring is exactly where fp32 under- /
overflow would erode orthonormality.

Both return the left singular vectors of ``G`` (``m x k``) and the singular
values (``k,``), for ``G`` of shape ``(m, n)``.  Callers that need the *right*
side pass ``G.T``.  Leading batch dims (scanned layer stacks, expert stacks)
are handled by the ``*_batched`` wrappers via ``vmap``; the bucketed refresh
engine instead calls ``randomized_svd_stacked`` with an explicit (B, m, n)
stack and per-slice keys -- same per-slice numerics (bit-for-bit on CPU),
but ONE batched chain per bucket instead of a chain per leaf.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.power_iter import ops as power_ops


def exact_svd(g: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-``k`` left singular vectors + singular values, exactly.

    ``g``: (m, n) with any m, n.  Returns (U[:, :k], S[:k]).
    """
    # SVD in fp32 for numerical sanity even if grads arrive in bf16.
    u, s, _ = jnp.linalg.svd(g.astype(jnp.float32), full_matrices=False)
    return u[:, :k], s[:k]


def clamp_sketch(
    m: int, n: int, k: int, oversample: int, power_iters: int
) -> Tuple[int, int, int]:
    """Degenerate-shape guards shared by the per-leaf and stacked chains.

    Returns ``(k, kp, power_iters)`` with ``k <= kp <= min(m, n)`` and the
    power iterations zeroed when the sketch already spans the full range
    (tiny ragged leaves: nothing to refine, everything to lose in fp32).
    """
    d = min(m, n)
    k = max(1, min(k, d))
    kp = min(k + max(oversample, 0), d)
    if kp >= d:
        power_iters = 0
    return k, kp, power_iters


def randomized_svd(
    g: jax.Array,
    k: int,
    key: jax.Array,
    *,
    oversample: int = 8,
    power_iters: int = 2,
) -> Tuple[jax.Array, jax.Array]:
    """Randomized top-``k`` SVD (HMT 2011, fused subspace iteration).

    Cost: ~(2 + 2q) GEMMs of (m,n)-by-(n,k') + (q+1) thin QRs + the
    (k', k') Gram matrix of ``B = Q^T G`` and its ``eigh``, with
    k' = k + oversample.  All GEMMs partition cleanly under
    SPMD when ``g`` is sharded, unlike a full dense SVD.  Single-slice entry
    point of the stacked chain below -- identical per-slice numerics.
    """
    u, s = randomized_svd_stacked(
        g.astype(jnp.float32)[None],
        k,
        _as_key_stack(key),
        oversample=oversample,
        power_iters=power_iters,
    )
    return u[0], s[0]


def randomized_svd_stacked(
    g: jax.Array,
    k: int,
    keys: jax.Array,
    *,
    oversample: int = 8,
    power_iters: int = 2,
) -> Tuple[jax.Array, jax.Array]:
    """One batched randomized-SVD chain over a (B, m, n) gradient stack.

    ``keys``: (B,) per-slice PRNG keys -- the caller derives them exactly as
    the per-leaf path would (fold the global leaf index, split over leading
    batch dims), so slice ``b`` draws the SAME Gaussian sketch it would have
    drawn per-leaf and the two paths stay bit-for-bit.  The whole stack runs
    as batched GEMMs / thin QRs / one small batched ``eigh``: the
    dispatched-op count is per-chain, not per-leaf, and the power-iteration
    GEMM pair goes through ``kernels/power_iter`` (VMEM-resident
    intermediate on TPU).

    Returns ``(U (B, m, k), S (B, k))``.
    """
    g = g.astype(jnp.float32)
    _, m, n = g.shape
    k, kp, power_iters = clamp_sketch(m, n, k, oversample, power_iters)
    with jax.named_scope("sketch"):
        omega = jax.vmap(
            lambda kk: jax.random.normal(kk, (n, kp), dtype=jnp.float32)
        )(keys)
        y = jnp.einsum("bmn,bnk->bmk", g, omega)  # (B, m, kp) sketch
    for _ in range(power_iters):
        # Thin QR keeps the iteration bounded; the GEMM pair is fused.
        with jax.named_scope("qr"):
            q, _ = jnp.linalg.qr(y)
        with jax.named_scope("power_iter"):
            y = power_ops.power_iter_step(g, q)
    with jax.named_scope("qr"):
        q, _ = jnp.linalg.qr(y)  # (B, m, kp) orthonormal range basis
    with jax.named_scope("small_svd"):
        b = jnp.einsum("bmk,bmn->bkn", q, g)  # (B, kp, n), kp <= n
        # The left singular pairs of the wide b are the eigenpairs of its
        # (kp, kp) Gram matrix: no QR of the long side, no right vectors.
        c = jnp.einsum("bkn,bjn->bkj", b, b)
        with jax.default_matmul_precision("float32"):
            w, v = jnp.linalg.eigh(c)  # ascending
        ub = v[..., ::-1]
        s = jnp.sqrt(jnp.maximum(w[..., ::-1], 0.0))
        u = jnp.einsum("bmk,bkj->bmj", q, ub)  # (B, m, kp)
        return u[..., :k], s[..., :k]


def _as_key_stack(key: jax.Array) -> jax.Array:
    """A single PRNG key as a (1,)-stacked key array (old- or new-style)."""
    return key[None]


def topk_svd(
    g: jax.Array,
    k: int,
    key: jax.Array,
    *,
    backend: str = "exact",
    oversample: int = 8,
    power_iters: int = 2,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch on backend.  ``key`` is ignored by the exact backend."""
    if backend == "exact":
        return exact_svd(g, k)
    if backend == "randomized":
        return randomized_svd(
            g, k, key, oversample=oversample, power_iters=power_iters
        )
    raise ValueError(f"unknown svd backend: {backend!r}")


def topk_svd_batched(
    g: jax.Array,
    k: int,
    key: jax.Array,
    *,
    backend: str = "exact",
    oversample: int = 8,
    power_iters: int = 2,
) -> Tuple[jax.Array, jax.Array]:
    """``topk_svd`` vmapped over arbitrary leading batch dims.

    ``g``: (*batch, m, n)  ->  U: (*batch, m, k), S: (*batch, k).
    Used for scanned layer stacks (L, m, n) and expert stacks (E, m, n):
    one fused batched SVD instead of a per-layer Python loop (the torch
    implementation's pattern).
    """
    batch_shape = g.shape[:-2]
    if not batch_shape:
        return topk_svd(
            g, k, key, backend=backend, oversample=oversample,
            power_iters=power_iters,
        )
    nb = 1
    for d in batch_shape:
        nb *= d
    gf = g.reshape((nb,) + g.shape[-2:])
    keys = jax.random.split(key, nb)
    fn = functools.partial(
        topk_svd, k=k, backend=backend, oversample=oversample,
        power_iters=power_iters,
    )
    u, s = jax.vmap(lambda gg, kk: fn(gg, key=kk))(gf, keys)
    return (
        u.reshape(batch_shape + u.shape[-2:]),
        s.reshape(batch_shape + s.shape[-1:]),
    )
